"""A delta of ``obs.device.STATS`` counters over the window, summed over
``keys``, times ``scale``, per completed query or per window."""


def read(evidence: dict, keys, per: str = "window", scale: float = 1.0):
    counters = evidence.get("counters")
    if counters is None or any(k not in counters for k in keys):
        return None
    total = sum(counters[k] for k in keys) * scale
    if per == "window":
        return total
    done = evidence["window"]["completed"]
    return total / done if done else None
