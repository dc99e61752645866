"""TPC-H ``orders`` (spec 1.4.1), dbgen-shaped: keys sparse as dbgen's, a
third of the customers without orders, status from the order's lines."""
import numpy as np

from . import _common as c

PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]

COLUMNS = {
    "o_orderkey": "int64", "o_custkey": "int64", "o_orderstatus": "string",
    "o_totalprice": "decimal", "o_orderdate": "date",
    "o_orderpriority": "string", "o_clerk": "string",
    "o_shippriority": "int32", "o_comment": "string"}


def rows(scale: float) -> int:
    return c.counts(scale)["orders"]


def generate(scale: float, seed: int):
    import pyarrow as pa

    n_ord, n_supp = rows(scale), c.counts(scale)["supplier"]
    sk, rng = c.order_skeleton(scale, seed, "orders")
    lines = sk["lines"]
    starts = np.concatenate([[0], np.cumsum(lines)[:-1]])
    n_open = np.add.reduceat(sk["open_line"].astype(np.int64), starts)
    status = np.where(n_open == 0, 0, np.where(n_open == lines, 1, 2))
    return c.table({
        "o_orderkey": pa.array(sk["o_key"]),
        "o_custkey": pa.array(sk["o_cust"]),
        "o_orderstatus": c.strings(["F", "O", "P"], status),
        "o_totalprice": c.dec(rng.integers(80000, 50000000, n_ord)),
        "o_orderdate": c.date(sk["o_date"]),
        "o_orderpriority": c.strings(PRIORITIES, rng.integers(0, 5, n_ord)),
        "o_clerk": c.strings(
            [f"Clerk#{k:09d}" for k in range(1, max(2, n_supp))],
            rng.integers(0, max(1, n_supp - 1), n_ord)),
        "o_shippriority": pa.array(np.zeros(n_ord, dtype=np.int32)),
        "o_comment": c.comments(rng, n_ord, 3, 8),
    })
