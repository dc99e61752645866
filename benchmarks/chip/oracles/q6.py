"""TPC-H q6 (spec 2.4.6, validation parameters 1994, 0.06, 24) in pandas."""
from ._common import days, dec, load


def answer(ddir: str, money: str = "int64"):
    li = load(ddir, "lineitem", ["l_quantity", "l_extendedprice",
                                 "l_discount", "l_shipdate"], money)
    li = li[(li.l_shipdate >= days(1994, 1, 1))
            & (li.l_shipdate < days(1995, 1, 1))
            & (li.l_discount >= 5) & (li.l_discount <= 7)
            & (li.l_quantity < 2400)]
    return [(dec((li.l_extendedprice * li.l_discount).sum(), 4),)], [], None
