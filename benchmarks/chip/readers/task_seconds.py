"""Executor task seconds per query: the sum of task durations of the
window's jobs, from each job's ``graph.stats`` stage ``task_duration_s``
(count x mean), over the queries completed."""


def read(evidence: dict):
    jobs = [j["stats"] for j in evidence.get("jobs", []) if j.get("stats")]
    done = evidence["window"]["completed"]
    if not jobs or not done:
        return None
    total = 0.0
    for stats in jobs:
        for stage in stats["stages"]:
            d = stage["task_duration_s"]
            total += d.get("count", 0) * d.get("mean", 0.0)
    return total / done
