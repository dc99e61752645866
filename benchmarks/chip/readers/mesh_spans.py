"""Seconds of the mesh's own spans under the window's tasks, per completed
query, from the program's ring of finished spans
(``arrow_ballista_tpu.obs.tracing.RING``): ``mesh_program`` (a mesh
program from its dispatch, under the dispatch lock, until its outputs are
ready) and ``mesh_reshard`` (a batch placed row-sharded over the devices,
until every shard is in place).  ``span`` picks the one returned; both go to
standard error once a run, as ``[mesh_spans] {...}``.  Nothing to read (a
program without the ring or without these spans, tracing off, no mesh
operator in the window, a ring that dropped spans of the window) returns
nothing, never 0.
"""
import json
import sys

from .span_tree import _descendants

NAMES = ("mesh_program", "mesh_reshard")


def seconds(evidence: dict):
    """Nanoseconds and counts of each span of ``NAMES`` below the window's
    task spans, or None."""
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
    out = {"completed": done, "tasks": len(tasks)}
    for name in NAMES:
        out[name], out[name + "_n"] = 0, 0
    for t in tasks:
        for s in _descendants(t, children):
            if s.name in NAMES:
                out[s.name] += s.end_ns - s.start_ns
                out[s.name + "_n"] += 1
    if not any(out[name + "_n"] for name in NAMES):
        return None
    print("[mesh_spans] " + json.dumps(out), file=sys.stderr, flush=True)
    return out


def read(evidence: dict, span: str):
    if span not in NAMES:
        raise SystemExit(f"mesh_spans: no span {span!r}")
    if "_mesh_spans" not in evidence:
        evidence["_mesh_spans"] = seconds(evidence)
    t = evidence["_mesh_spans"]
    if t is None or not t[span + "_n"]:
        return None
    return t[span] / t["completed"] / 1e9
