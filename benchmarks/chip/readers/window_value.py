"""A number the traffic generator took over the window on the client's side
(``traffic.end_to_end``), by name."""


def read(evidence: dict, key: str):
    return evidence["window"].get(key)
