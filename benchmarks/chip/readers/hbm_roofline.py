"""Share of the HBM roofline: least time / device-busy time, over the traced
window.  Least time is the bytes of the columns the window's queries
reference (work.query_bytes, each query by the share of it that lies inside
the window) over the chip's peak HBM rate.  The bound that applies is bytes.
Nothing to read (no trace, no peak, no busy time) returns nothing, never 0.
"""
from ..work import query_bytes


def read(evidence: dict):
    trace, peaks = evidence.get("trace"), evidence.get("peaks")
    if not trace or trace["simulated_device"] or not peaks \
            or trace["busy_s"] <= 0:
        return None
    least_bytes = sum(
        share * query_bytes(evidence["queries"][q]["columns"],
                            evidence["cardinalities"])
        for q, share in trace["query_shares"].items())
    if least_bytes <= 0:
        return None
    least_s = least_bytes / (peaks["hbm_bytes_per_s"] * trace["devices"])
    return 100.0 * least_s / trace["busy_s"]
