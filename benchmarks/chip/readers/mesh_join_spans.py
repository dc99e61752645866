"""Seconds of the mesh join programs' ``mesh_program`` spans under the
window's tasks, per completed query, from the program's ring of finished
spans (``arrow_ballista_tpu.obs.tracing.RING``): the spans whose
``program`` attribute starts with ``prefix`` (``mesh_join_partitioned__*``
and ``mesh_join_broadcast__*``: a join program from its dispatch, under the
dispatch lock, until its ``stats`` are on the host, a re-run included).
The attributes of each such span (its bounds, live rows, rows out and
re-runs) go to standard error once a run, as ``[mesh_join_spans] {...}``.
Nothing to read (a program without the ring, tracing off, no mesh join in
the window, a ring that dropped spans of the window) returns nothing, never
0.
"""
import json
import sys

from .span_tree import _descendants

SPAN = "mesh_program"


def spans(evidence: dict, prefix: str):
    """The join programs' ``mesh_program`` spans below the window's task
    spans, and the window's completed queries; None where there is nothing
    to read."""
    try:
        from arrow_ballista_tpu.obs.tracing import RING
    except ImportError:             # a program from before the ring
        return None
    job_ids = {j["job_id"] for j in evidence.get("jobs", [])}
    done = evidence["window"].get("completed", 0)
    if not job_ids or not done:
        return None
    every = RING.snapshot()
    children = {}
    for s in every:
        children.setdefault(s.parent_id, []).append(s)
    tasks = [s for s in every if s.kind == "executor"
             and s.attrs.get("job_id") in job_ids]
    if not tasks:
        return None
    if RING.dropped and min(s.end_ns for s in every) > min(
            t.start_ns for t in tasks):
        return None                 # the ring lost spans of the window
    mine = [s for t in tasks for s in _descendants(t, children)
            if s.name == SPAN
            and str(s.attrs.get("program", "")).startswith(prefix)]
    return (mine, done) if mine else None


def read(evidence: dict, prefix: str = "mesh_join"):
    key = "_mesh_join_spans:" + prefix
    if key not in evidence:
        evidence[key] = spans(evidence, prefix)
        if evidence[key] is not None:
            mine, done = evidence[key]
            print("[mesh_join_spans] " + json.dumps({
                "completed": done, "spans": len(mine),
                "first": {s.attrs.get("program"): dict(
                    s.attrs, seconds=(s.end_ns - s.start_ns) / 1e9)
                    for s in reversed(mine)}}, default=str),
                file=sys.stderr, flush=True)
    found = evidence[key]
    if found is None:
        return None
    mine, done = found
    return sum(s.end_ns - s.start_ns for s in mine) / done / 1e9
