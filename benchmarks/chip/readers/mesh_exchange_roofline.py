"""Share of the HBM roofline of the mesh exchange aggregate (partial
aggregate, ``all_to_all``, final aggregate as one program): least time / the
program's device seconds, over the traced window.  Least time is the bytes
of the aggregate's input, ``columns`` of ``table`` read once at the width
the device holds them (``input_bytes``, from shapes alone), by the share of
``query`` that lies inside the window, over ONE chip's peak HBM rate: the
bytes are counted once for the whole mesh.  The seconds are those of every
program in ``trace["device_ops"]`` whose name starts with ``program``,
which the reduction sums over the devices' planes.  Bytes once over seconds
summed: no implementation can pass 100 %.  The bound that applies is bytes:
grouping does a few integer operations a row.  Nothing to read (no trace,
no peak, the program not among the trace's largest, as in every program
that cannot run the exchange at this size) returns nothing, never 0.
"""
from ..work import row_bytes


def input_bytes(table: str, columns, cardinalities: dict) -> int:
    """Bytes of ``columns`` of every row of ``table``, each read once."""
    return cardinalities[table] * row_bytes(table, list(columns))


def read(evidence: dict, query: str, program: str, table: str, columns):
    trace, peaks = evidence.get("trace"), evidence.get("peaks")
    if not trace or trace["simulated_device"] or not peaks:
        return None
    share = trace["query_shares"].get(query, 0.0)
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith(program))
    if share <= 0 or seconds <= 0 \
            or table not in evidence.get("cardinalities", {}):
        return None
    least_s = share * input_bytes(table, columns, evidence["cardinalities"]) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
