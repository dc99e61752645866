"""Seconds of one named span under the window's tasks, per completed query,
from the program's ring of finished spans
(``arrow_ballista_tpu.obs.tracing.RING``); the reader ``mesh_spans`` for a
span it does not know: ``mesh_unshard`` (a mesh program's outputs brought
onto one device, until they are in place).  The sum and the spans' own
attributes (``bytes``, ``rows`` summed; the values ``via`` took) go to
standard error once a run, as ``[task_span] {...}``.  Nothing to read (a
program without the ring or without the span, as every program before the
span came; tracing off; no such span in the window; a ring that dropped
spans of the window) returns nothing, never 0.
"""
import json
import sys

from .span_tree import _descendants


def read(evidence: dict, span: str):
    try:
        from arrow_ballista_tpu.obs.tracing import RING
    except ImportError:             # a program from before the ring
        return None
    job_ids = {j["job_id"] for j in evidence.get("jobs", [])}
    done = evidence["window"].get("completed", 0)
    if not job_ids or not done:
        return None
    spans = RING.snapshot()
    children = {}
    for s in spans:
        children.setdefault(s.parent_id, []).append(s)
    tasks = [s for s in spans if s.kind == "executor"
             and s.attrs.get("job_id") in job_ids]
    if not tasks:
        return None
    if RING.dropped and min(s.end_ns for s in spans) > min(
            t.start_ns for t in tasks):
        return None                 # the ring lost spans of the window
    found = [s for t in tasks for s in _descendants(t, children)
             if s.name == span]
    if not found:
        return None
    ns = sum(s.end_ns - s.start_ns for s in found)
    print("[task_span] " + json.dumps({
        "span": span, "n": len(found), "ns": ns, "completed": done,
        "bytes": sum(int(s.attrs.get("bytes", 0)) for s in found),
        "rows": sum(int(s.attrs.get("rows", 0)) for s in found),
        "via": sorted({str(s.attrs.get("via")) for s in found})}),
        file=sys.stderr, flush=True)
    return ns / done / 1e9
