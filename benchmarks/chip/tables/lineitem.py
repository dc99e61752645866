"""TPC-H ``lineitem`` (spec 1.4.1), dbgen-shaped: 1 to 7 lines an order from
the stream no seed moves, prices from the part's retail price (spec 4.2.3),
return flag and line status from the dates."""
import numpy as np

from . import _common as c

INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]

COLUMNS = {
    "l_orderkey": "int64", "l_partkey": "int64", "l_suppkey": "int64",
    "l_linenumber": "int32", "l_quantity": "decimal",
    "l_extendedprice": "decimal", "l_discount": "decimal",
    "l_tax": "decimal", "l_returnflag": "string", "l_linestatus": "string",
    "l_shipdate": "date", "l_commitdate": "date", "l_receiptdate": "date",
    "l_shipinstruct": "string", "l_shipmode": "string",
    "l_comment": "string"}


def rows(scale: float) -> int:
    return int(c.lines_per_order(c.counts(scale)["orders"]).sum())


def generate(scale: float, seed: int):
    import pyarrow as pa

    n = c.counts(scale)
    n_part, n_supp = n["part"], n["supplier"]
    sk, rng = c.order_skeleton(scale, seed, "lineitem")
    lines, l_odate, l_ship = sk["lines"], sk["l_odate"], sk["l_ship"]
    n_li = len(l_ship)
    l_order = np.repeat(sk["o_key"], lines)
    ends = np.cumsum(lines)
    l_num = (np.arange(1, n_li + 1)
             - np.repeat(ends - lines, lines)).astype(np.int32)
    l_part = rng.integers(1, n_part + 1, n_li).astype(np.int64)
    which_ps = rng.integers(0, 4, n_li)
    l_supp = ((l_part + which_ps * (n_supp // 4 + 1)) % n_supp + 1) \
        .astype(np.int64)
    l_qty = rng.integers(1, 51, n_li).astype(np.int64)
    # retail price of the part in cents (spec 4.2.3), times quantity
    retail = 90000 + (l_part % 1000) * 100 + (l_part % 10) * 10
    l_commit = (l_odate + rng.integers(30, 91, n_li)).astype(np.int32)
    l_receipt = (l_ship + rng.integers(1, 31, n_li)).astype(np.int32)
    returned = l_receipt <= 9298      # 1995-06-17
    retflag = np.where(returned, rng.integers(0, 2, n_li), 2)
    return c.table({
        "l_orderkey": pa.array(l_order),
        "l_partkey": pa.array(l_part),
        "l_suppkey": pa.array(l_supp),
        "l_linenumber": pa.array(l_num),
        "l_quantity": c.dec(l_qty * 100),
        "l_extendedprice": c.dec(l_qty * retail),
        "l_discount": c.dec(rng.integers(0, 11, n_li)),
        "l_tax": c.dec(rng.integers(0, 9, n_li)),
        "l_returnflag": c.strings(["R", "A", "N"], retflag),
        "l_linestatus": c.strings(["F", "O"], sk["open_line"]),
        "l_shipdate": c.date(l_ship),
        "l_commitdate": c.date(l_commit),
        "l_receiptdate": c.date(l_receipt),
        "l_shipinstruct": c.strings(INSTRUCTS, rng.integers(0, 4, n_li)),
        "l_shipmode": c.strings(MODES, rng.integers(0, 7, n_li)),
        "l_comment": c.comments(rng, n_li, 2, 5),
    })
