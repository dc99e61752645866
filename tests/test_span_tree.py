"""One span tree per query, on the profiler's clock (obs/tracing.py): the
client, stage, task, operator and device-boundary spans, the process-wide
ring, program names that are the same in every interpreter, the
benchmark's ``span_tree`` reader, and the executor's profile switch."""
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pytest

from arrow_ballista_tpu.client.context import BallistaContext
from arrow_ballista_tpu.obs import tracing
from arrow_ballista_tpu.obs.tracing import RING, ROOT, Span, SpanRing, span
from arrow_ballista_tpu.utils.config import OBS_TRACING, BallistaConfig

ROOT_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SQL = "select g, count(*) c, sum(v) s from t group by g order by g"


def _table(n=4000):
    return pa.table({"g": pa.array(np.arange(n) % 7, type=pa.int64()),
                     "v": pa.array(np.arange(n), type=pa.int64())})


@pytest.fixture
def standalone():
    ctx = BallistaContext.standalone()
    ctx.register_table("t", _table())
    yield ctx
    ctx.shutdown()


@pytest.fixture
def cluster(tmp_path):
    from arrow_ballista_tpu.executor.server import ExecutorServer
    from arrow_ballista_tpu.scheduler.netservice import SchedulerNetService

    sched = SchedulerNetService("127.0.0.1", 0)
    sched.start()
    ex = ExecutorServer("127.0.0.1", sched.port, "127.0.0.1", 0,
                        work_dir=str(tmp_path), executor_id="span-exec")
    ex.start()
    ctx = BallistaContext.remote("127.0.0.1", sched.port)
    ctx.register_table("t", _table())
    yield ctx
    ctx.shutdown()
    ex.stop(notify=False)
    sched.stop()


def _query_tree(ctx):
    """Run SQL once; the spans of its trace from the ring, by name."""
    df = ctx.sql(SQL)
    assert len(df.to_arrow()) == 7
    spans = {s.span_id: s for s in RING.snapshot(df._trace["trace_id"])}
    return list(spans.values())


def _dur(spans):
    return sum(s.end_ns - s.start_ns for s in spans)


def _union(spans):
    total, end = 0, 0
    for s in sorted(spans, key=lambda s: s.start_ns):
        if s.start_ns > end:
            total, end = total + s.end_ns - s.start_ns, s.end_ns
        elif s.end_ns > end:
            total, end = total + s.end_ns - end, s.end_ns
    return total


def _parts(spans):
    named = lambda *names: [s for s in spans if s.name in names]  # noqa: E731
    client = named("client.sql", "client.collect")
    job = [s for s in spans if s.kind == "scheduler"
           and s.name.startswith("job ")]
    tasks = [s for s in spans if s.kind == "executor"]
    assert len(client) == 2 and len(job) == 1 and tasks
    return {"client": _dur(client), "job": _dur(job),
            "admission": _dur(named("admission")),
            "planning": _dur(named("planning")),
            "execution": _dur(named("execution")),
            "tasks": _union(tasks)}, job[0], tasks


@pytest.mark.parametrize("path", ["standalone", "cluster"])
def test_parts_of_a_query_close(path, request):
    """client = outside-the-job + admission + planning + no-task + tasks,
    to within 1 ms, and the tree hangs together: job under client.collect,
    tasks under execution, stage spans with their launches, waits under
    operators."""
    spans = _query_tree(request.getfixturevalue(path))
    p, job, tasks = _parts(spans)
    outside = p["client"] - p["job"]
    no_task = p["execution"] - p["tasks"]
    assert outside >= 0 and no_task >= 0
    total = outside + p["admission"] + p["planning"] + no_task + p["tasks"]
    assert abs(total - p["client"]) < 1_000_000            # 1 ms, in ns
    assert abs(p["job"] - p["admission"] - p["planning"]
               - p["execution"]) < 1_000_000
    by_id = {s.span_id: s for s in spans}
    collect = next(s for s in spans if s.name == "client.collect")
    assert job.parent_id == collect.span_id
    kids = {s.name for s in spans if s.parent_id == collect.span_id}
    assert {"submit", "wait", "fetch", "decode"} <= kids
    wait = next(s for s in spans if s.name == "wait")
    assert wait.end_ns >= job.end_ns or path == "cluster"
    if path == "cluster":
        assert wait.attrs["polls"] >= 1
    execution = next(s for s in spans if s.name == "execution")
    stages = [s for s in spans if s.name.startswith("stage ")]
    assert stages and all(s.parent_id == execution.span_id for s in stages)
    assert sum(len(s.attrs["launched_at"]) for s in stages) == len(tasks)
    for t in tasks:
        assert t.parent_id == execution.span_id
        assert t.attrs["launch_ns"] <= t.attrs["start_ns"] <= t.end_ns
    waits = [s for s in spans if s.name == "device_wait"
             and by_id[s.parent_id].kind == "operator"]
    assert waits and all(s.attrs["site"] in ("d2h", "scalar")
                         for s in waits)
    assert any(s.name == "h2d" and s.attrs["bytes"] > 0 for s in spans)


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            lines[(i, line.name)] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats)) for e in line.events]
    return lines


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def test_spans_lie_in_the_profilers_trace(standalone, tmp_path):
    """With a profiler session open the program's spans are TraceAnnotations
    in /host:CPU, on the thread that did the work, nested in time."""
    import jax

    _query_tree(standalone)                # compile outside the trace
    jax.profiler.start_trace(str(tmp_path), profiler_options=_profile_options())
    try:
        spans = _query_tree(standalone)
    finally:
        jax.profiler.stop_trace()
    job_id = next(s.attrs["job_id"] for s in spans if s.kind == "executor")
    lines = _host_events(str(tmp_path))

    def inside(inner, outer):
        return outer[1] <= inner[1] and inner[2] <= outer[2]

    seen = set()
    for events in lines.values():
        tasks = [e for e in events if e[0].startswith(f"task {job_id}/")]
        ops = [e for e in events if e[0].endswith("Exec")]
        for w in (e for e in events if e[0] == "device_wait"):
            if not tasks:
                continue                    # the client's decode fetch
            op = [o for o in ops if inside(w, o)]
            assert op and any(inside(op[0], t) for t in tasks)
            seen.add("device_wait")
        for t in tasks:
            assert t[3]["job_id"] == job_id and len(t[3]["trace_id"]) == 32
            seen.add("task")
        for c in (e for e in events if e[0] == "client.collect"):
            kids = [e for e in events if e[0] in ("wait", "fetch", "decode")]
            assert len(kids) == 3 and all(inside(k, c) for k in kids)
            seen.add("client.collect")
        if tasks and ops:
            seen.add("operator")
    assert seen == {"client.collect", "task", "operator", "device_wait"}


_NAMES_SCRIPT = r"""
import json, os, sys, tempfile
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, sys.argv[1])
from benchmarks.chip import datagen, traffic
from arrow_ballista_tpu.client.context import BallistaContext
from arrow_ballista_tpu.obs.tracing import RING
ddir = datagen.write_data(tempfile.mkdtemp(), 0.01, 7,
                          ["customer", "lineitem", "orders"])
ctx = BallistaContext.standalone()
for t in ("customer", "lineitem", "orders"):
    ctx.register_parquet(t, os.path.join(ddir, t + ".parquet"))
out = {}
for q in ("q1", "q6", "q3", "q18"):
    df = ctx.sql(traffic.load_query(q)["sql"])
    df.to_arrow()
    out[q] = sorted({s.name.split(" ", 1)[1]
                     for s in RING.snapshot(df._trace["trace_id"])
                     if s.name.startswith("compile ")})
ctx.shutdown()
print("NAMES " + json.dumps(out))
"""


def test_program_names_are_stable_and_tell_aggregates_apart():
    """Two fresh interpreters name every program of q1, q6, q3 and q18 the
    same (the name is part of the persistent compile cache's key), after
    what it computes, and q1's aggregates differ from q6's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED="random")
    procs = [subprocess.Popen([sys.executable, "-c", _NAMES_SCRIPT, ROOT_DIR],
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True, env=env, cwd=ROOT_DIR)
             for _ in range(2)]
    names = []
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-2000:]
        names.append(json.loads(next(
            line for line in out.splitlines()
            if line.startswith("NAMES "))[6:]))
    assert names[0] == names[1]
    every = {n for q in names[0].values() for n in q}
    assert not any("lambda" in n or n in ("agg_fn", "join_fn") for n in every)
    assert {"join_probe", "join_count"} <= set(names[0]["q3"])

    def aggs(q):
        return {n for n in names[0][q] if n.startswith(("agg_grouped",
                                                        "fused__"))}

    assert aggs("q1") and aggs("q6") and not aggs("q1") & aggs("q6")
    assert all("_k0" in n for n in aggs("q6"))


def test_ring_is_bounded_and_counts_what_it_drops():
    ring = SpanRing(capacity=4)
    spans = [Span(f"s{i}", "t" * 32) for i in range(6)]
    for s in spans[:5]:
        ring.add(s)
    assert ring.dropped == 1
    assert [s.name for s in ring.snapshot()] == ["s1", "s2", "s3", "s4"]
    # the collector seam adds what this process did not close, once
    spans[5]._ringed = False
    ring.export([spans[5], spans[5]])
    assert ring.dropped == 2
    assert [s.name for s in ring.snapshot("t" * 32)][-1] == "s5"
    assert len(ring.snapshot()) == 4
    from arrow_ballista_tpu.obs import make_collector

    assert make_collector("memory") is RING    # one in-memory store


def test_span_clock_and_single_close():
    """Integer nanoseconds of the realtime clock; ms values are derived;
    a span enters the ring once."""
    import time

    before = len([s for s in RING.snapshot() if s.name == "once"])
    t0 = time.time_ns()
    with span("once", "internal", ROOT, job_id="j1") as sp:
        with span("inner") as inner:        # parent: the open span
            pass
    t1 = time.time_ns()
    assert t0 <= sp.start_ns <= inner.start_ns <= inner.end_ns \
        <= sp.end_ns <= t1
    assert isinstance(sp.start_ns, int)
    assert sp.start_ms == sp.start_ns / 1e6
    assert inner.parent_id == sp.span_id and inner.trace_id == sp.trace_id
    sp.end()                                # a second close changes nothing
    assert len([s for s in RING.snapshot()
                if s.name == "once"]) == before + 1
    assert span("nothing open") is tracing._NULL_SCOPE


def test_tracing_off_leaves_no_span_and_no_annotation(tmp_path):
    """``ballista.observability.tracing`` false: the shared null context
    everywhere, nothing in the ring, nothing in a profiler trace, and the
    benchmark's reader has nothing to read."""
    import jax

    from benchmarks.chip.readers import span_tree

    ctx = BallistaContext.standalone(
        config=BallistaConfig({OBS_TRACING: False}))
    ctx.register_table("t", _table())
    try:
        ctx.sql(SQL).to_arrow()
        RING.clear()
        jax.profiler.start_trace(str(tmp_path),
                                 profiler_options=_profile_options())
        try:
            df = ctx.sql(SQL)
            assert len(df.to_arrow()) == 7
        finally:
            jax.profiler.stop_trace()
        sched = ctx._standalone.scheduler
        job_id = sched.jobs.job_ids()[-1]
    finally:
        ctx.shutdown()
    assert df._trace == {} and RING.snapshot() == []
    names = {e[0] for events in _host_events(str(tmp_path)).values()
             for e in events}
    assert not names & {"client.sql", "client.collect", "device_wait",
                        "ShuffleWriterExec", "h2d"}
    assert not any(n.startswith(("task ", "stage ")) for n in names)
    evidence = {"jobs": [{"job_id": job_id}], "window": {"completed": 1}}
    assert span_tree.read(evidence, "job_no_task_ms") is None


def test_reader_returns_nothing_for_a_job_missing_from_the_ring(standalone):
    from benchmarks.chip.readers import span_tree

    spans = _query_tree(standalone)
    job_id = next(s.attrs["job_id"] for s in spans if s.kind == "executor")
    window = {"window": {"completed": 1}}
    seen = dict(window, jobs=[{"job_id": job_id}])
    for part in ("client_outside_job_ms", "job_no_task_ms",
                 "task_device_wait_s_per_query", "task_host_s_per_query"):
        assert span_tree.read(seen, part) >= 0
    missing = dict(window, jobs=[{"job_id": job_id}, {"job_id": "nosuch"}])
    assert span_tree.read(missing, "task_host_s_per_query") is None
    assert span_tree.read(dict(window, jobs=[]), "job_no_task_ms") is None


def test_reader_parts_sum_to_query_s_on_a_rehearsal():
    """The benchmark's cluster cell rehearsed on the CPU: the four values
    with admission and planning and the task union sum to the window's
    ``query_s`` within 2 %."""
    run = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "sf1_cluster_streams", "--allow-cpu", "--scale", "0.02",
         "--seconds", "2", "--trace", "1", "--seed", "2147484999"],
        capture_output=True, text=True, timeout=600, cwd=ROOT_DIR,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert run.returncode == 0, run.stderr[-3000:]
    first, last = (json.loads(line) for line in
                   run.stdout.strip().splitlines()[-2:])
    assert last["correct"] and last["device"]["platform"] == "cpu"
    m = {k: v["value"] for k, v in last["metrics"].items()}
    t = json.loads(next(line for line in run.stderr.splitlines()
                        if line.startswith("[span_tree] "))[12:])
    assert t["dropped"] == 0 and t["jobs"] == first["window"]["completed"]
    per_query_s = (m["client_outside_job_ms"] / 1e3
                   + (t["admission"] + t["planning"]) / t["jobs"] / 1e9
                   + m["job_no_task_ms"] / 1e3
                   + t["tasks_union"] / t["jobs"] / 1e9)
    # closed loops with no think time: query_s is the mean latency
    assert per_query_s == pytest.approx(first["window"]["query_s"], rel=0.02)
    assert m["task_device_wait_s_per_query"] > 0
    assert m["task_host_s_per_query"] > 0
    assert (m["task_device_wait_s_per_query"] + m["task_host_s_per_query"]
            <= t["task"] / t["completed"] / 1e9 + 1e-9)


def test_executor_profile_switch_writes_the_task_spans(tmp_path):
    """``executor_daemon --profile-dir``: an ExecutorServer started with it
    holds a profiler session around task execution and writes it when it
    stops; the task's span is in the trace."""
    from arrow_ballista_tpu.executor.server import ExecutorServer
    from arrow_ballista_tpu.scheduler.netservice import SchedulerNetService

    prof = tmp_path / "prof"
    sched = SchedulerNetService("127.0.0.1", 0)
    sched.start()
    ex = ExecutorServer("127.0.0.1", sched.port, "127.0.0.1", 0,
                        work_dir=str(tmp_path / "work"),
                        executor_id="prof-exec", profile_dir=str(prof))
    ex.start()
    try:
        ctx = BallistaContext.remote("127.0.0.1", sched.port)
        ctx.register_table("t", _table())
        spans = _query_tree(ctx)
        ctx.shutdown()
    finally:
        ex.stop(notify=False)
        sched.stop()
    task = next(s for s in spans if s.kind == "executor")
    events = [e for line in _host_events(str(prof)).values() for e in line]
    assert any(e[0] == task.name and e[3]["job_id"] == task.attrs["job_id"]
               for e in events)
    assert any(e[0] == "device_wait" for e in events)
