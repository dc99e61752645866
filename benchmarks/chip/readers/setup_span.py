"""One of the harness's own set-up spans (host clock), by name."""


def read(evidence: dict, span: str):
    return evidence.get("setup", {}).get(span)
