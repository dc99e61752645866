"""TPC-H q1 (spec 2.4.1, validation parameter DELTA = 90) in pandas."""
from ._common import days, dec, load


def answer(ddir: str, money: str = "int64"):
    li = load(ddir, "lineitem", [
        "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
        "l_discount", "l_tax", "l_shipdate"], money)
    li = li[li.l_shipdate <= days(1998, 12, 1) - 90]
    disc_price = li.l_extendedprice * (100 - li.l_discount)       # scale 4
    li = li.assign(disc_price=disc_price,
                   charge=disc_price * (100 + li.l_tax))          # scale 6
    keys = ["l_returnflag", "l_linestatus"]
    g = li.groupby(keys, sort=False, observed=True).agg(
        sum_qty=("l_quantity", "sum"), sum_base=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"), sum_charge=("charge", "sum"),
        sum_disc=("l_discount", "sum"), n=("l_quantity", "size")).reset_index()
    g = g.astype({k: str for k in keys}).sort_values(keys)  # as strings sort
    rows = [(r.l_returnflag, r.l_linestatus, dec(r.sum_qty, 2),
             dec(r.sum_base, 2), dec(r.sum_disc_price, 4),
             dec(r.sum_charge, 6), float(r.sum_qty) / r.n / 100.0,
             float(r.sum_base) / r.n / 100.0, float(r.sum_disc) / r.n / 100.0,
             int(r.n))
            for r in g.itertuples()]
    return rows, [(0, True), (1, True)], None
