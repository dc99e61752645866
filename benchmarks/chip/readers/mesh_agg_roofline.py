"""Share of the HBM roofline of the mesh aggregate program: least time /
the program's device seconds, over the traced window.  Least time is the
bytes of the columns ``query`` references (work.query_bytes, by the share of
the query that lies inside the window) over ONE chip's peak HBM rate: the
bytes are counted once for the whole mesh.  The seconds are those of every
program in ``trace["device_ops"]`` whose name starts with ``program``,
which the reduction sums over the devices' planes.  Bytes once over seconds
summed: no implementation can pass 100 %.  The bound that applies is bytes.
Nothing to read (no trace, no peak, the program not among the trace's
largest) returns nothing, never 0.
"""
from ..work import query_bytes


def read(evidence: dict, query: str, program: str):
    trace, peaks = evidence.get("trace"), evidence.get("peaks")
    if not trace or trace["simulated_device"] or not peaks:
        return None
    share = trace["query_shares"].get(query, 0.0)
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith(program))
    if share <= 0 or seconds <= 0 or query not in evidence["queries"]:
        return None
    least_s = share * query_bytes(evidence["queries"][query]["columns"],
                                  evidence["cardinalities"]) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
