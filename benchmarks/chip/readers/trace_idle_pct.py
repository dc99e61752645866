"""Device idle share: 1 - (union of device-op intervals) / traced window."""


def read(evidence: dict):
    trace = evidence.get("trace")
    if not trace or trace["simulated_device"] or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
