"""Where a query's time goes, from the program's own span tree.

The program keeps every finished span in a process-wide ring
(``arrow_ballista_tpu.obs.tracing.RING``), on one clock, which outlives the
deployment.  This reader takes the spans of the window's jobs
(``evidence["jobs"]``) and the client spans of the same traces, without
duplicates, and splits each query's time so that the parts close:

    client.sql + client.collect
        = client_outside_job + admission + planning + job_no_task + union(tasks)

``part`` picks what to return: ``client_outside_job_ms`` and
``job_no_task_ms`` are means over the window's queries,
``task_device_wait_s_per_query`` and ``task_host_s_per_query`` are sums over
the window's tasks per completed query.  Nothing to read (a program without
the ring, tracing off, a window job without its ``job`` span, a ring that
dropped spans of the window) returns nothing, never 0.  The sums the parts
are cut from go to standard error once a run, as ``[span_tree] {...}``.
"""
import json
import sys

WAITS = ("device_wait", "compile", "lock_wait")


def _union(intervals) -> int:
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def _descendants(span, children):
    todo = list(children.get(span.span_id, ()))
    while todo:
        s = todo.pop()
        yield s
        todo.extend(children.get(s.span_id, ()))


def tree(evidence: dict):
    """Every part, in nanoseconds summed over the window, with the counts
    to divide by; None where there is nothing to read."""
    try:
        from arrow_ballista_tpu.obs.tracing import RING
    except ImportError:             # a program from before the ring
        return None
    job_ids = {j["job_id"] for j in evidence.get("jobs", [])}
    done = evidence["window"].get("completed", 0)
    if not job_ids or not done:
        return None
    spans = {s.span_id: s for s in RING.snapshot()}
    if not spans:
        return None
    roots, by_job, children = {}, {}, {}
    for s in spans.values():
        children.setdefault(s.parent_id, []).append(s)
        job = s.attrs.get("job_id")
        if job in job_ids:
            by_job.setdefault(job, []).append(s)
            if s.kind == "scheduler" and s.name == f"job {job}":
                roots[job] = s
    if set(roots) != job_ids:
        return None
    first = min(r.start_ns for r in roots.values())
    if RING.dropped and min(s.end_ns for s in spans.values()) > first:
        return None                 # the ring lost spans of the window
    traces = {r.trace_id for r in roots.values()}
    out = dict.fromkeys(("client", "job", "admission", "planning",
                         "execution", "tasks_union", "task", *WAITS), 0)
    out.update(queries=len(traces), jobs=len(roots), completed=done,
               ring=len(spans), dropped=RING.dropped)
    for s in spans.values():
        if s.kind == "client" and s.trace_id in traces \
                and s.name in ("client.sql", "client.collect"):
            out["client"] += s.end_ns - s.start_ns
    for job, root in roots.items():
        out["job"] += root.end_ns - root.start_ns
        tasks = []
        for s in by_job[job]:
            if s.kind == "scheduler" and s.parent_id == root.span_id \
                    and s.name in ("admission", "planning", "execution"):
                out[s.name] += s.end_ns - s.start_ns
            elif s.kind == "executor":      # a task
                tasks.append(s)
        out["tasks_union"] += _union((t.start_ns, t.end_ns) for t in tasks)
        for t in tasks:
            out["task"] += t.end_ns - t.start_ns
            below = list(_descendants(t, children))
            for name in WAITS:
                out[name] += _union((s.start_ns, s.end_ns) for s in below
                                    if s.name.split(" ", 1)[0] == name)
    print("[span_tree] " + json.dumps(out), file=sys.stderr, flush=True)
    return out


def read(evidence: dict, part: str):
    if "_span_tree" not in evidence:
        evidence["_span_tree"] = tree(evidence)
    t = evidence["_span_tree"]
    if t is None:
        return None
    if part == "client_outside_job_ms":
        return (t["client"] - t["job"]) / t["queries"] / 1e6
    if part == "job_no_task_ms":
        return (t["execution"] - t["tasks_union"]) / t["jobs"] / 1e6
    if part == "task_device_wait_s_per_query":
        return t["device_wait"] / t["completed"] / 1e9
    if part == "task_host_s_per_query":
        return (t["task"] - sum(t[w] for w in WAITS)) / t["completed"] / 1e9
    raise SystemExit(f"span_tree: no part {part!r}")
