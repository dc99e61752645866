"""The host half of a task where it is spent: the shuffle writer's phases as
spans, the ``scalar_waits`` counter, the garbage collector's
``gc_collections`` / ``gc_pause_time``, and the memory watermark read from
the allocator instead of a walk of every live array."""
import gc
import os
import threading

import numpy as np
import pyarrow as pa
import pytest

from arrow_ballista_tpu.client.context import BallistaContext
from arrow_ballista_tpu.models import expr as E
from arrow_ballista_tpu.models.ipc import crc32_file, read_ipc_files
from arrow_ballista_tpu.models.schema import INT64, Field, Schema
from arrow_ballista_tpu.obs import device as dev
from arrow_ballista_tpu.obs import tracing
from arrow_ballista_tpu.obs.tracing import RING, TaskSpanRecorder
from arrow_ballista_tpu.ops.physical import (MemoryScanExec, Partitioning,
                                             TaskContext)
from arrow_ballista_tpu.ops.shuffle import ShuffleWriterExec

SCHEMA = Schema([Field("k", INT64), Field("v", INT64)])
PHASES = ("shuffle.order", "shuffle.encode", "shuffle.checksum")


@pytest.fixture(autouse=True)
def _observatory_on():
    dev.set_enabled(True)
    dev.set_watermarks(True)
    yield
    dev.set_enabled(True)
    dev.set_watermarks(True)


def _writer(path: str, rows: int = 3000, partitions: int = 2):
    table = pa.table({"k": pa.array(np.arange(rows) % 11, type=pa.int64()),
                      "v": pa.array(np.arange(rows), type=pa.int64())})
    scan = MemoryScanExec(SCHEMA, table, partitions=partitions)
    part = {"hash": Partitioning.hash([E.Column("k")], 4),
            "single": Partitioning.unknown(3),
            "final": None}[path]
    return ShuffleWriterExec(scan, part, stage_id=1)


def _run_task(writer, partition, work_dir, traced=True):
    rec = TaskSpanRecorder(tracing.new_trace_id(), "",
                           name=f"task j/1/{partition}", kind="executor",
                           attrs={"job_id": "j", "stage_id": 1,
                                  "partition": partition}) if traced else None
    ctx = TaskContext(work_dir=str(work_dir), job_id="j", stage_id=1,
                      span_recorder=rec)
    with dev.task_scope():
        out = writer.execute_write(partition, ctx)
    return out, (rec.finish() if rec is not None else [])


@pytest.mark.parametrize("path", ["hash", "single", "final"])
def test_writer_phases_are_one_span_each_per_task(path, tmp_path):
    """Every writing task: one ``shuffle_write`` under ``ShuffleWriterExec``
    under the task, one span of each phase under it and none a bucket; its
    ``rows``/``bytes``/``files`` are what was written, and the operator's
    ``repart_time``/``write_time`` are the phases' own readings."""
    writer = _writer(path)
    rows_in = 0
    for partition in (0, 1):
        out, spans = _run_task(writer, partition, tmp_path)
        by_id = {s.span_id: s for s in spans}
        task = spans[0]
        assert task.kind == "executor"
        sw = [s for s in spans if s.name == "shuffle_write"]
        assert len(sw) == 1 and sw[0].kind == "operator"
        sw = sw[0]
        op = by_id[sw.parent_id]
        assert op.name == "ShuffleWriterExec" and op.parent_id == task.span_id
        for name in PHASES:
            found = [s for s in spans if s.name == name]
            assert len(found) == 1, (name, len(found))
            assert found[0].parent_id == sw.span_id
            assert sw.start_ns <= found[0].start_ns <= found[0].end_ns \
                <= sw.end_ns
        files = {"hash": 4, "single": 3, "final": 1}[path]
        assert len(out) == files and sw.attrs["files"] == files
        assert sw.attrs["rows"] == sum(p.num_rows for p in out)
        assert sw.attrs["bytes"] == sum(p.num_bytes for p in out) \
            == sum(os.path.getsize(p.path) for p in out)
        for p in out:
            assert p.checksum == crc32_file(p.path)
        rows_in += sw.attrs["rows"]
        # the timers read the spans, once each
        phase = {s.name: (s.end_ns - s.start_ns) / 1e9 for s in spans
                 if s.name in PHASES}
        assert op.attrs["repart_time"] == pytest.approx(
            phase["shuffle.order"], abs=2e-6)
        assert op.attrs["write_time"] == pytest.approx(
            phase["shuffle.encode"] + phase["shuffle.checksum"], abs=2e-6)
        if path == "final":
            assert [p.output_partition for p in out] == [partition]
        else:
            assert [p.output_partition for p in out] == list(range(files))
            if path == "single":
                assert [p.num_rows for p in out][1:] == [0, 0]
    assert rows_in == 3000
    read = read_ipc_files([p.path for p in out], SCHEMA)
    assert sum(b.num_rows for b in read) == out[0].num_rows + sum(
        p.num_rows for p in out[1:])


def test_hash_path_groups_every_row_by_its_bucket(tmp_path):
    """The order phase hands each file exactly the rows of its bucket: a key
    lands in one file only, and every row arrives once."""
    writer = _writer("hash", rows=5000, partitions=1)
    out, _ = _run_task(writer, 0, tmp_path)
    seen, total = {}, 0
    for p in out:
        for b in read_ipc_files([p.path], SCHEMA):
            cols = b.compacted_numpy()
            total += len(cols["v"])
            for k in np.unique(cols["k"]):
                assert seen.setdefault(int(k), p.output_partition) \
                    == p.output_partition
    assert total == 5000 and sorted(seen) == list(range(11))


def test_writer_timers_run_with_tracing_off(tmp_path):
    """No recorder, no span: the phases still time themselves into
    ``repart_time`` and ``write_time``, and nothing enters the ring."""
    writer = _writer("hash")
    before = len(RING.snapshot())
    _run_task(writer, 0, tmp_path, traced=False)
    m = writer.metrics().to_dict()
    assert m["repart_time"] > 0 and m["write_time"] > 0
    assert m["output_rows"] == m["input_rows"] == 1500
    assert len(RING.snapshot()) == before


def test_scalar_waits_count_the_scalar_site_only():
    class _Op:
        def __init__(self):
            from arrow_ballista_tpu.ops.physical import MetricsSet
            self._m = MetricsSet()

        def metrics(self):
            return self._m

    op = _Op()
    before = dev.STATS.snapshot()
    with dev.task_scope() as acc, dev.op_scope(op):
        for site in ("scalar", "d2h", "scalar", "ready"):
            with dev.device_wait(site):
                pass
        with dev.h2d(8):
            pass
    after = dev.STATS.snapshot()
    assert after["scalar_waits"] - before["scalar_waits"] == 2
    assert acc.values["scalar_waits"] == 2
    assert op.metrics().to_dict()["scalar_waits"] == 2
    dev.set_enabled(False)
    with dev.device_wait("scalar"):
        pass
    assert dev.STATS.snapshot()["scalar_waits"] == after["scalar_waits"]


def test_a_collection_moves_the_gc_counters(monkeypatch):
    with dev.task_scope():          # hooks the collector, once a process
        pass
    assert gc.callbacks.count(dev._on_gc) == 1
    with dev.task_scope():
        pass
    assert gc.callbacks.count(dev._on_gc) == 1
    monkeypatch.setattr(dev, "LONG_GC_NS", 0)
    before = dev.STATS.snapshot()
    t0 = tracing.now_ns()
    gc.collect()
    after = dev.STATS.snapshot()
    assert after["gc_collections"] - before["gc_collections"] >= 1
    assert after["gc_pause_time"] > before["gc_pause_time"]
    start, end, generation = dev.long_gc_pauses()[-1]
    assert t0 <= start <= end <= tracing.now_ns() and generation == 2
    # off: a collection counts nothing
    dev.set_enabled(False)
    gc.collect()
    assert dev.STATS.snapshot()["gc_collections"] == after["gc_collections"]


class _CollectingDict(dict):
    """A counter map whose lookup forces a collection, as an allocation
    inside ``STATS.add`` may."""

    def get(self, *args):
        gc.collect()
        return super().get(*args)


class _CollectingDeque(__import__("collections").deque):
    def append(self, x):
        gc.collect()
        super().append(x)


@pytest.mark.parametrize("where", ["stats", "ring"])
def test_a_collection_inside_a_held_lock_completes(where, monkeypatch):
    """The hook takes no lock: a collection that starts while the thread
    holds ``STATS``' lock, or the ring's, finishes and is counted."""
    with dev.task_scope():
        pass
    if where == "stats":
        monkeypatch.setattr(dev.STATS, "_c", _CollectingDict(dev.STATS._c))
        work = lambda: dev.STATS.add("scalar_waits", 1)  # noqa: E731
    else:
        monkeypatch.setattr(RING, "_spans", _CollectingDeque(RING._spans))
        work = lambda: tracing.Span("gc-probe").end()  # noqa: E731
    before = dev._gc_count
    th = threading.Thread(target=work, daemon=True)
    th.start()
    th.join(timeout=30)
    assert not th.is_alive(), "a collection under the lock deadlocked"
    assert dev._gc_count > before


class _StubDevice:
    def __init__(self, used):
        self.used = used

    def memory_stats(self):
        return {"bytes_in_use": self.used, "peak_bytes_in_use": self.used}


def _no_walk(monkeypatch):
    import jax

    calls = []

    def live_arrays(*a, **k):
        calls.append(1)
        raise AssertionError("the watermark walked the live arrays")

    monkeypatch.setattr(jax, "live_arrays", live_arrays)
    monkeypatch.setattr(jax, "local_devices",
                        lambda *a, **k: [_StubDevice(1000),
                                         _StubDevice(2345)])
    return calls


def test_watermark_reads_the_allocator_and_walks_nothing(monkeypatch):
    from arrow_ballista_tpu.memory.governor import _device_live_bytes

    calls = _no_walk(monkeypatch)
    with dev.task_scope() as acc:
        pass
    snap = acc.snapshot()
    assert snap["device_mem_peak"] == 3345     # summed over the devices
    assert "watermark_samples" not in snap
    assert _device_live_bytes() == 3345
    assert dev.STATS.snapshot()["device_live_peak_bytes"] >= 3345
    assert calls == []


def test_allocator_watermark_reaches_explain_analyze_and_the_fleet_gauge(
        monkeypatch):
    ctx = BallistaContext.standalone()
    try:
        ctx.register_table("t", pa.table({
            "g": pa.array(np.arange(4000) % 7, type=pa.int64()),
            "v": pa.array(np.arange(4000), type=pa.int64())}))
        sql = "select g, sum(v) s from t group by g order by g"
        ctx.sql(sql).to_arrow()                 # compile before the stub
        calls = _no_walk(monkeypatch)
        report = ctx.explain_analyze(sql)
        text = ctx._standalone.scheduler.metrics.gather()
    finally:
        ctx.shutdown()
    assert calls == []
    peaks = [(st.get("device") or {}).get("device_mem_peak")
             for st in report["stages"]]
    assert 3345 in peaks
    assert "hbm peak" in report["text"]
    assert "fleet_device_mem_peak_bytes 3345" in text.splitlines() or any(
        line.startswith("fleet_device_mem_peak_bytes ")
        and int(line.split()[-1]) >= 3345 for line in text.splitlines())


def test_layer_metrics_read_a_query_and_nothing_from_a_ring_without_it():
    """The three metrics through the runner's ``read_layer_metric``: numbers
    from a traced query's spans and the counters' delta, nothing where the
    ring holds no ``shuffle_write`` or the counters lack the keys (a program
    from before them)."""
    from benchmarks.chip.run import read_layer_metric

    ctx = BallistaContext.standalone()
    try:
        ctx.register_table("t", pa.table({
            "g": pa.array(np.arange(4000) % 7, type=pa.int64()),
            "v": pa.array(np.arange(4000), type=pa.int64())}))
        sql = "select g, count(*) c from t group by g order by g"
        ctx.sql(sql).to_arrow()
        s0 = dev.STATS.snapshot()
        df = ctx.sql(sql)
        df.to_arrow()
        gc.collect()
        s1 = dev.STATS.snapshot()
        spans = RING.snapshot(df._trace["trace_id"])
    finally:
        ctx.shutdown()
    job_id = next(s.attrs["job_id"] for s in spans if s.kind == "executor")
    evidence = {"jobs": [{"job_id": job_id}], "window": {"completed": 1},
                "counters": {k: s1[k] - s0[k] for k in s1}}
    got = {m: read_layer_metric(m, evidence) for m in (
        "shuffle_write_host_s_per_query", "scalar_waits_per_query",
        "gc_pause_s_per_query")}
    assert got["shuffle_write_host_s_per_query"] > 0
    assert got["scalar_waits_per_query"] >= 0
    assert got["gc_pause_s_per_query"] > 0
    # a ring without the span, counters without the keys: nothing, never 0
    RING.clear()
    assert read_layer_metric("shuffle_write_host_s_per_query",
                             evidence) is None
    old = dict(evidence, counters={"d2h_bytes": 0})
    assert read_layer_metric("scalar_waits_per_query", old) is None
    assert read_layer_metric("gc_pause_s_per_query", old) is None
