"""TPC-H q4 (spec 2.4.4, validation parameter DATE = 1993-07-01) in
pandas."""
import numpy as np

from ._common import days, load


def answer(ddir: str, money: str = "int64"):
    lo, hi = days(1993, 7, 1), days(1993, 10, 1)
    orders = load(ddir, "orders", ["o_orderkey", "o_orderdate",
                                   "o_orderpriority"], money)
    orders = orders[(orders.o_orderdate >= lo) & (orders.o_orderdate < hi)]
    li = load(ddir, "lineitem", ["l_orderkey", "l_commitdate",
                                 "l_receiptdate"], money)
    late = np.unique(li.l_orderkey[li.l_commitdate < li.l_receiptdate])
    orders = orders[np.isin(orders.o_orderkey.to_numpy(), late)]
    g = orders.groupby("o_orderpriority", observed=True).size()
    rows = sorted((str(k), int(n)) for k, n in g.items())
    return rows, [(0, True)], None
