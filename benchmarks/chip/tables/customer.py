"""TPC-H ``customer`` (spec 1.4.1), dbgen-shaped."""
import numpy as np

from . import _common as c

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]

COLUMNS = {
    "c_custkey": "int64", "c_name": "string", "c_address": "string",
    "c_nationkey": "int64", "c_phone": "string", "c_acctbal": "decimal",
    "c_mktsegment": "string", "c_comment": "string"}


def rows(scale: float) -> int:
    return c.counts(scale)["customer"]


def generate(scale: float, seed: int):
    import pyarrow as pa

    n_cust = rows(scale)
    rng = c.stream(seed, 1)
    c_key = np.arange(1, n_cust + 1, dtype=np.int64)
    c_nation = rng.integers(0, 25, n_cust).astype(np.int64)
    phone = np.char.add(np.char.add((10 + c_nation).astype("U2"), "-"),
                        rng.integers(1000000, 9999999, n_cust).astype("U7"))
    return c.table({
        "c_custkey": pa.array(c_key),
        "c_name": c.tagged("Customer#", c_key),
        "c_address": c.comments(rng, n_cust, 2, 4),
        "c_nationkey": pa.array(c_nation),
        "c_phone": pa.array(phone.astype(object), type=pa.string()),
        "c_acctbal": c.dec(rng.integers(-99999, 999999, n_cust)),
        "c_mktsegment": c.strings(SEGMENTS, rng.integers(0, 5, n_cust)),
        "c_comment": c.comments(rng, n_cust, 4, 9),
    })
