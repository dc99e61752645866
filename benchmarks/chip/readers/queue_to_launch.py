"""Mean of ``submitted_at - queued_at`` (ms) over the window's jobs: parse,
plan, validate and graph build on the scheduler, plus any admission wait."""


def read(evidence: dict):
    jobs = evidence.get("jobs", [])
    if not jobs:
        return None
    return sum(max(0.0, j["submitted_at_ms"] - j["queued_at_ms"])
               for j in jobs) / len(jobs)
