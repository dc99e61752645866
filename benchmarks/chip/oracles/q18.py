"""TPC-H q18 (spec 2.4.18, validation parameter QUANTITY = 300) in pandas."""
from ._common import date, dec, load


def answer(ddir: str, money: str = "int64"):
    li = load(ddir, "lineitem", ["l_orderkey", "l_quantity"], money)
    qty = li.groupby("l_orderkey").l_quantity.sum()
    qty = qty[qty > 300 * 100].rename("sum_qty").reset_index()
    orders = load(ddir, "orders", ["o_orderkey", "o_custkey", "o_orderdate",
                                   "o_totalprice"], money)
    cust = load(ddir, "customer", ["c_custkey", "c_name"], money)
    j = qty.merge(orders, left_on="l_orderkey", right_on="o_orderkey") \
           .merge(cust, left_on="o_custkey", right_on="c_custkey")
    j = j.sort_values(["o_totalprice", "o_orderdate"],
                      ascending=[False, True], kind="mergesort")
    rows = [(r.c_name, int(r.c_custkey), int(r.o_orderkey),
             date(r.o_orderdate), dec(r.o_totalprice, 2),
             dec(r.sum_qty, 2)) for r in j.itertuples()]
    return rows, [(4, False), (3, True)], 100
