"""Share of the HBM roofline of the mesh join programs (both sides'
``all_to_all`` and the per-device range lookup as one program): least time
/ the programs' device seconds, over the traced window.

Least time is the bytes a join has to read at the least: for each query of
``sides``, every row that reaches the join on each side, counted by the
benchmark itself from the run's generated parquet with that side's
predicates (``where``) applied in pyarrow, times that side's ``columns``
(the join key and what the query needs of the side after the join) at the
width the device holds them; by the share of the query inside the window,
over ONE chip's peak HBM rate: the bytes are counted once for the whole
mesh.  The seconds are those of every program in ``trace["device_ops"]``
whose name starts with ``program``, which the reduction sums over the
devices' planes.  Bytes once over seconds summed, and rows no
implementation chooses: none can pass 100 %.  The bound that applies is
bytes: a join does a few integer operations a row.  Nothing to read (no
trace, no peak, no data, no query of ``sides`` in the window, the programs
not among the trace's largest) returns nothing, never 0.

A side's ``where`` is a list of ``[column, op, value]``, all of which hold:
``op`` one of ``<``, ``<=``, ``>``, ``>=``, ``=``, ``in``; ``value`` another
column of the table, an ISO date, a string, or for ``in`` a list of them.
"""
import datetime
import os

from ..work import row_bytes

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
_COMPARE = {"<": "less", "<=": "less_equal", ">": "greater",
            ">=": "greater_equal", "=": "equal"}


def _literal(value):
    import pyarrow as pa

    try:
        return pa.scalar(datetime.date.fromisoformat(value))
    except ValueError:
        return pa.scalar(value)


def side_rows(path: str, where) -> int:
    """Rows of the parquet file at ``path`` for which every condition of
    ``where`` holds."""
    import pyarrow as pa
    import pyarrow.compute as pc
    import pyarrow.parquet as pq

    if not where:
        return pq.read_metadata(path).num_rows
    table = pq.read_schema(path).names
    wanted = {c for c, _, _ in where} | {
        v for _, op, v in where if op != "in" and v in table}
    t = pq.read_table(path, columns=sorted(wanted))

    def column(name):
        col = t.column(name)
        return col.cast(pa.string()) if pa.types.is_dictionary(col.type) \
            else col

    mask = None
    for name, op, value in where:
        if op == "in":
            held = pc.is_in(column(name), value_set=pa.array(value))
        else:
            other = column(value) if value in table else _literal(value)
            held = getattr(pc, _COMPARE[op])(column(name), other)
        mask = held if mask is None else pc.and_(mask, held)
    return int(pc.sum(mask).as_py() or 0)


def join_bytes(ddir: str, sides) -> int:
    """Bytes of every side's rows that reach the join, at device width."""
    return sum(side_rows(os.path.join(ddir, f"{s['table']}.parquet"),
                         s.get("where", []))
               * row_bytes(s["table"], s["columns"]) for s in sides)


def read(evidence: dict, program: str, sides: dict):
    trace, peaks = evidence.get("trace"), evidence.get("peaks")
    if not trace or trace["simulated_device"] or not peaks:
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith(program))
    shares = {q: trace["query_shares"].get(q, 0.0) for q in sides}
    ddir = os.path.join(ROOT, ".bench_data", evidence.get("workload", ""))
    if seconds <= 0 or not any(shares.values()) or not os.path.isdir(ddir):
        return None
    least_s = sum(share * join_bytes(ddir, sides[q])
                  for q, share in shares.items() if share > 0) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
