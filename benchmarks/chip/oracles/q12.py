"""TPC-H q12 (spec 2.4.12, validation parameters SHIPMODE1 = MAIL,
SHIPMODE2 = SHIP, DATE = 1994-01-01) in pandas."""
from ._common import days, load


def answer(ddir: str, money: str = "int64"):
    lo, hi = days(1994, 1, 1), days(1995, 1, 1)
    li = load(ddir, "lineitem", ["l_orderkey", "l_shipmode", "l_commitdate",
                                 "l_receiptdate", "l_shipdate"], money)
    li = li[li.l_shipmode.isin(["MAIL", "SHIP"])
            & (li.l_commitdate < li.l_receiptdate)
            & (li.l_shipdate < li.l_commitdate)
            & (li.l_receiptdate >= lo) & (li.l_receiptdate < hi)]
    orders = load(ddir, "orders", ["o_orderkey", "o_orderpriority"], money)
    j = li[["l_orderkey", "l_shipmode"]].merge(
        orders, left_on="l_orderkey", right_on="o_orderkey")
    high = j.o_orderpriority.isin(["1-URGENT", "2-HIGH"]).astype("int64")
    g = j.assign(high=high, low=1 - high).groupby(
        "l_shipmode", observed=True)[["high", "low"]].sum()
    rows = sorted((str(m), int(r.high), int(r.low))
                  for m, r in g.iterrows())
    return rows, [(0, True)], None
