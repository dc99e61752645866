"""TPC-H q3 (spec 2.4.3, validation parameters BUILDING, 1995-03-15) in
pandas."""
from ._common import date, days, dec, load


def answer(ddir: str, money: str = "int64"):
    cutoff = days(1995, 3, 15)
    cust = load(ddir, "customer", ["c_custkey", "c_mktsegment"], money)
    cust = cust[cust.c_mktsegment == "BUILDING"]
    orders = load(ddir, "orders", ["o_orderkey", "o_custkey", "o_orderdate",
                                   "o_shippriority"], money)
    orders = orders[(orders.o_orderdate < cutoff)
                    & orders.o_custkey.isin(cust.c_custkey)]
    li = load(ddir, "lineitem", ["l_orderkey", "l_extendedprice",
                                 "l_discount", "l_shipdate"], money)
    li = li[li.l_shipdate > cutoff]
    li = li.assign(revenue=li.l_extendedprice * (100 - li.l_discount))
    j = li.merge(orders, left_on="l_orderkey", right_on="o_orderkey")
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  sort=False).revenue.sum().reset_index()
    g = g.sort_values(["revenue", "o_orderdate"], ascending=[False, True],
                      kind="mergesort")
    rows = [(int(r.l_orderkey), dec(r.revenue, 4), date(r.o_orderdate),
             int(r.o_shippriority)) for r in g.itertuples()]
    return rows, [(1, False), (2, True)], 10
