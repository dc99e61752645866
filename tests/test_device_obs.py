"""PR 12 device observatory: JIT compile/retrace accounting, host<->device
transfer bytes, memory watermarks, and the stage-fusion advisor.

Four layers, matching how the observatory is built:

  1. ``observed_jit`` keying semantics tested directly (compile vs retrace
     vs cache hit; scalar weak-typing; static-arg value keys resolved for
     positional call sites; disabled mode counts nothing);
  2. transfer accounting through the two sanctioned materialization sites
     in models/batch.py, checked against hand-computed byte counts from
     the padding rules (``round_capacity``);
  3. scope attribution: device events recorded inside ``op_scope`` fold
     into the operator's MetricsSet (and from there into ``_op_entry``'s
     device_ms/host_ms split); ``task_scope`` snapshots become
     ``TaskStatus.device_stats`` and survive wire serde only when
     non-empty;
  4. end-to-end through a standalone cluster: a repeated identical query
     reports 0 new compiles (the shared_program + wrapper key-set reuse
     property), shape churn retraces, watermarks appear in stage
     summaries and EXPLAIN ANALYZE, and the advisor ranks candidates
     deterministically.
"""
import json

import numpy as np
import pyarrow as pa
import pytest

from arrow_ballista_tpu import serde
from arrow_ballista_tpu.client.context import BallistaContext
from arrow_ballista_tpu.models.batch import ColumnBatch, round_capacity
from arrow_ballista_tpu.models.schema import INT64, Field, Schema
from arrow_ballista_tpu.obs import device as dev
from arrow_ballista_tpu.obs.advisor import advise_report
from arrow_ballista_tpu.obs.profile import JobObservability
from arrow_ballista_tpu.obs.stats import device_summary
from arrow_ballista_tpu.ops.physical import MetricsSet
from arrow_ballista_tpu.scheduler.types import TaskId, TaskStatus
from arrow_ballista_tpu.utils.config import BallistaConfig


@pytest.fixture(autouse=True)
def _observatory_on():
    """Every test starts from the default-on observatory; tests that flip
    the process switches get them restored."""
    dev.set_enabled(True)
    dev.set_watermarks(True)
    yield
    dev.set_enabled(True)
    dev.set_watermarks(True)


def _delta(before, after):
    return {k: after.get(k, 0) - before.get(k, 0) for k in after}


# --------------------------------------------------------------------------
# observed_jit keying
# --------------------------------------------------------------------------

def test_observed_jit_compile_retrace_hit_counts():
    import jax.numpy as jnp

    f = dev.observed_jit("test.add", lambda x: x + 1)
    before = dev.STATS.snapshot()
    f(jnp.arange(4))        # first key ever -> compile
    f(jnp.arange(4))        # repeat key -> cache hit
    f(jnp.arange(8))        # new shape -> retrace
    f(jnp.arange(8))        # repeat -> cache hit
    d = _delta(before, dev.STATS.snapshot())
    assert d["jit_compiles"] == 1
    assert d["jit_retraces"] == 1
    assert d["jit_cache_hits"] == 2
    assert d["jit_compile_time"] > 0


def test_observed_jit_scalar_weak_typing():
    """Plain Python scalars key by TYPE only — jax weak-types them, so a
    changed value does not retrace; a changed type does."""
    import jax.numpy as jnp

    f = dev.observed_jit("test.scale", lambda x, s: x * s)
    before = dev.STATS.snapshot()
    f(jnp.arange(4), 2)
    f(jnp.arange(4), 3)      # int again: same key -> hit, not retrace
    f(jnp.arange(4), 2.5)    # float: new key -> retrace
    d = _delta(before, dev.STATS.snapshot())
    assert d["jit_compiles"] == 1
    assert d["jit_retraces"] == 1
    assert d["jit_cache_hits"] == 1


def test_observed_jit_static_args_key_by_value_positionally():
    """static_argnames resolve to positions (via the signature) so the
    positional call sites in kernels.py key statics by VALUE."""
    import jax.numpy as jnp

    def take(x, n):
        return x[:n]

    f = dev.observed_jit("test.take", take, static_argnames=("n",))
    before = dev.STATS.snapshot()
    assert f(jnp.arange(8), 2).shape == (2,)   # compile
    assert f(jnp.arange(8), 3).shape == (3,)   # new static value -> retrace
    assert f(jnp.arange(8), 2).shape == (2,)   # repeat -> hit
    d = _delta(before, dev.STATS.snapshot())
    assert d["jit_compiles"] == 1
    assert d["jit_retraces"] == 1
    assert d["jit_cache_hits"] == 1


def test_alias_churn_flagged_statically_and_counted_at_runtime(tmp_path):
    """Static/runtime agreement: the alias-churn scenario the
    trace-key-stability lint predicts (batch-varying column names flowing
    into a static arg) is the same one the observatory counts as
    retraces — one per distinct alias set, under the same signature."""
    import textwrap

    import jax.numpy as jnp

    from arrow_ballista_tpu.analysis import run_lints

    # static half: the lint flags the churning tuple(b.columns) static
    fixture = tmp_path / "arrow_ballista_tpu" / "ops"
    fixture.mkdir(parents=True)
    (fixture / "packer.py").write_text(textwrap.dedent("""\
        from ..obs.device import observed_jit

        def pack_fn(cols, names):
            return tuple(cols[n] for n in names)

        pack = observed_jit("churn.pack", pack_fn,
                            static_argnames=("names",))

        def run(batches):
            out = []
            for b in batches:
                names = tuple(b.columns)
                out.append(pack(b.columns, names))
            return out
        """))
    found = run_lints(str(tmp_path), rule_names=["trace-key-stability"])
    assert len(found) == 1
    assert "'churn.pack'" in found[0].message

    # runtime half: the identical wrapper shape, driven with churning
    # name tuples — the observatory books a retrace per new alias set
    def pack_fn(cols, names):
        return tuple(cols[n] for n in names)

    pack = dev.observed_jit("churn.pack", pack_fn,
                            static_argnames=("names",))
    arr = jnp.arange(8)
    before = dev.STATS.snapshot()
    for names in (("a",), ("b",), ("c",)):
        pack({names[0]: arr}, names)
    d = _delta(before, dev.STATS.snapshot())
    assert d["jit_compiles"] == 1
    assert d["jit_retraces"] == 2  # one per churned alias set
    assert d["jit_cache_hits"] == 0


def test_observed_jit_decorator_form_and_disabled_mode():
    import jax.numpy as jnp

    @dev.observed_jit("test.deco")
    def g(x):
        return x - 1

    dev.set_enabled(False)
    before = dev.STATS.snapshot()
    assert int(g(jnp.arange(4))[1]) == 0       # still computes
    assert int(g(jnp.arange(16))[1]) == 0      # new shape, still no count
    d = _delta(before, dev.STATS.snapshot())
    assert all(v == 0 for v in d.values()), f"disabled mode counted: {d}"


# --------------------------------------------------------------------------
# transfer accounting (hand-computed against the padding rules)
# --------------------------------------------------------------------------

SCHEMA2 = Schema([Field("a", INT64), Field("b", INT64)])


def test_transfer_bytes_match_padded_layout():
    n = 1000
    cap = round_capacity(n)
    assert cap == 1024  # the fixture's arithmetic below assumes this
    data = {"a": np.arange(n, dtype=np.int64),
            "b": np.arange(n, dtype=np.int64)}
    with dev.task_scope() as acc:
        cb = ColumnBatch.from_numpy(SCHEMA2, data)
        cols, rows = cb.packed_numpy()
    assert rows == n
    v = acc.values
    # h2d: one transfer of (2 int64 columns + bool mask) at capacity
    assert v["h2d_transfers"] == 1
    assert v["h2d_bytes"] == 2 * cap * 8 + cap
    # d2h: one packed int64 buffer of (count word + 2 columns at capacity)
    assert v["d2h_transfers"] == 1
    assert v["d2h_bytes"] == (1 + 2 * cap) * 8
    np.testing.assert_array_equal(cols["a"], data["a"])


def test_task_scope_snapshot_and_watermarks():
    import jax.numpy as jnp

    # a zero device watermark is left out of the snapshot: hold one buffer,
    # so the test does not lean on what earlier tests left alive
    held = jnp.arange(8).block_until_ready()
    with dev.task_scope() as acc:
        dev.record_transfer("h2d", 64, 0.001)
    snap = acc.snapshot()
    assert snap["h2d_bytes"] == 64
    assert snap["host_mem_peak"] > 0  # ru_maxrss is always nonzero on Linux
    assert snap["device_mem_peak"] >= held.nbytes
    json.dumps(snap)  # wire-framing safe

    dev.set_enabled(False)
    with dev.task_scope() as acc2:
        pass
    assert acc2 is None, "disabled task_scope yields None (no serde keys)"


# --------------------------------------------------------------------------
# scope attribution into operator metrics
# --------------------------------------------------------------------------

class _Op:
    def __init__(self):
        self._m = MetricsSet()

    def metrics(self):
        return self._m


def test_op_scope_attributes_events_to_operator_metrics():
    import jax.numpy as jnp

    op = _Op()
    f = dev.observed_jit("test.attr", lambda x: x * 2)
    with dev.op_scope(op):
        f(jnp.arange(4))     # compile
        f(jnp.arange(16))    # retrace — attributed to THIS operator
        dev.record_transfer("h2d", 100, 0.25)
    mm = op.metrics().to_dict()
    assert mm["jit_compiles"] == 1
    assert mm["jit_retraces"] == 1
    assert mm["h2d_bytes"] == 100
    assert mm["h2d_time"] == 0.25

    # the _op_entry fold: *_time keys -> time_ms, transfer/compile time
    # -> host_ms, h2d/d2h bytes -> transfer_bytes
    from arrow_ballista_tpu.obs.stats import _op_entry

    entry = _op_entry("0", 0, op, mm)
    assert entry["compiles"] == 1 and entry["retraces"] == 1
    assert entry["transfer_bytes"] == 100
    assert entry["host_ms"] >= 250.0   # the recorded h2d_time alone
    assert entry["host_ms"] <= entry["time_ms"] + 1e-6
    assert entry["device_ms"] == pytest.approx(
        entry["time_ms"] - entry["host_ms"], abs=0.01)


def test_op_scope_disabled_is_shared_null_context():
    dev.set_enabled(False)
    op = _Op()
    assert dev.op_scope(op) is dev.op_scope(op), \
        "disabled op_scope must not allocate per call"


# --------------------------------------------------------------------------
# TaskStatus.device_stats: wire serde + stage folding
# --------------------------------------------------------------------------

def test_device_stats_serde_only_when_present():
    bare = TaskStatus(TaskId("job-1", 1, 0), "exec-1", "success")
    o = serde.status_to_obj(bare)
    assert "device_stats" not in o, \
        "disabled mode must add no TaskStatus wire keys"
    assert serde.status_from_obj(o).device_stats == {}

    full = TaskStatus(TaskId("job-1", 1, 1), "exec-1", "success",
                      device_stats={"jit_compiles": 3, "h2d_bytes": 17408,
                                    "device_mem_peak": 4096})
    o2 = serde.status_to_obj(full)
    assert o2["device_stats"]["h2d_bytes"] == 17408
    rt = serde.status_from_obj(json.loads(json.dumps(o2)))
    assert rt.device_stats == full.device_stats
    assert serde.status_to_obj(rt) == o2  # canonical round-trip stability


def test_device_summary_sums_counters_maxes_peaks_and_guards_attempts():
    class _Info:
        def __init__(self, ds, attempt=0, st_attempt=0):
            self.attempt = attempt
            self.status = TaskStatus(
                TaskId("j", 1, 0, task_attempt=st_attempt), "e", "success",
                device_stats=ds)

    class _Stage:
        task_infos = [
            _Info({"jit_compiles": 2, "device_mem_peak": 100}),
            _Info({"jit_compiles": 3, "device_mem_peak": 70}),
            # speculative loser: status attempt != info attempt -> excluded
            _Info({"jit_compiles": 99, "device_mem_peak": 999},
                  attempt=1, st_attempt=0),
        ]

    out = device_summary(_Stage())
    assert out["jit_compiles"] == 5
    assert out["device_mem_peak"] == 100


# --------------------------------------------------------------------------
# advisor (pure, synthetic report)
# --------------------------------------------------------------------------

def _tree_op(path, op, host_ms=0.0, device_ms=5.0, compiles=0, retraces=0,
             compile_time=0.0, transfer=0):
    return {
        "path": path, "depth": path.count("."), "op": op, "label": op,
        "rows": 10, "time_ms": host_ms + device_ms, "bytes": 0,
        "device_ms": device_ms, "host_ms": host_ms,
        "transfer_bytes": transfer, "compiles": compiles,
        "retraces": retraces,
        "metrics": {"jit_compile_time": compile_time},
    }


def _synthetic_report():
    return {
        "job_id": "job-syn", "state": "successful", "wall_time_ms": 500.0,
        "stages": [
            {"stage_id": 1, "operator_tree": [
                _tree_op("0", "ShuffleWriterExec", host_ms=1.0),
                _tree_op("0.0", "ProjectionExec", host_ms=2.0,
                         compiles=1, retraces=3, compile_time=0.4),
                _tree_op("0.0.0", "FilterExec", host_ms=40.0, transfer=512),
                _tree_op("0.0.0.0", "ScanExec", host_ms=10.0),
            ]},
            {"stage_id": 2, "operator_tree": [
                _tree_op("0", "HashAggregateExec", host_ms=1.0),
                _tree_op("0.0", "ShuffleReaderExec", host_ms=50.0),
            ]},
        ],
    }


def test_advisor_chains_rank_and_schema():
    advice = advise_report(_synthetic_report())
    assert advice["job_id"] == "job-syn"
    assert advice["generated_from"] == "explain_analyze"
    cands = advice["candidates"]
    # stage 2's only chain head is unfusable-adjacent: HashAggregate ->
    # ShuffleReader never fuses, so only stage 1's chain survives
    assert len(cands) == 1
    c = cands[0]
    assert c["operators"] == ["ProjectionExec", "FilterExec", "ScanExec"]
    # est savings = downstream host_ms (40+10) + head retrace share
    # (400 ms compile time * 3/(1+3))
    assert c["est_savings_ms"] == pytest.approx(50.0 + 300.0)
    assert c["transfer_bytes"] == 512
    assert c["retraces"] == 3
    assert c["reasons"]
    assert advice["total_est_savings_ms"] == c["est_savings_ms"]
    assert "FUSION ADVISOR" in advice["text"]
    json.dumps(advice)


def test_advisor_deterministic_and_min_savings_filter():
    r = _synthetic_report()
    a1, a2 = advise_report(r), advise_report(r)
    assert a1 == a2, "equal inputs must produce identical advice"
    filtered = advise_report(r, min_savings_ms=10_000.0)
    assert filtered["candidates"] == []
    assert "no operator chain" in filtered["text"]


# --------------------------------------------------------------------------
# failover trace continuity (obs/profile.py adoption hooks)
# --------------------------------------------------------------------------

def test_adoption_continues_original_trace():
    obs = JobObservability()
    obs.on_submitted("job-f")
    parent = obs.task_parent("job-f")
    orig_trace = parent["trace_id"]

    # the adopting shard receives the checkpointed graph.trace and must
    # keep the SAME trace_id so both shards land on one Chrome timeline
    obs2 = JobObservability()
    obs2.on_adopted("job-f", epoch=7, prev_owner="shard-0",
                    scheduler_id="shard-1", trace=parent)
    adopted_parent = obs2.task_parent("job-f")
    assert adopted_parent["trace_id"] == orig_trace
    profile = obs2.get_profile("job-f")
    assert profile["trace_id"] == orig_trace
    assert "adoption@7" in profile["phases"], \
        "the adoption marker must annotate the fencing epoch"
    # without the checkpointed context, adoption starts a fresh trace
    obs3 = JobObservability()
    obs3.on_adopted("job-g", epoch=1)
    assert obs3.task_parent("job-g")["trace_id"] != orig_trace


def test_stand_down_closes_spans_and_keeps_profile():
    obs = JobObservability()
    obs.on_submitted("job-s")
    obs.on_stand_down("job-s", "lease lost to shard-9")
    prof = obs.profiles.get("job-s")
    assert prof is not None
    assert prof["state"] == "stood-down"
    assert prof["stand_down_reason"] == "lease lost to shard-9"
    spans = obs.profiles.get_spans("job-s")
    assert any(s.name == "lease stand-down" for s in spans)
    assert all(s.end_ms for s in spans), "stand-down must close every span"


# --------------------------------------------------------------------------
# end-to-end (standalone cluster)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ctx():
    c = BallistaContext.standalone(
        BallistaConfig({"ballista.shuffle.partitions": "4"}),
        concurrent_tasks=2, num_executors=2)
    rng = np.random.default_rng(11)
    n = 2000
    c.register_table("lineitem", pa.table({
        "okey": pa.array(rng.integers(0, 200, n), type=pa.int64()),
        "flag": pa.array(rng.integers(0, 3, n), type=pa.int64()),
        "qty": pa.array(rng.integers(1, 50, n), type=pa.int64()),
        "price": pa.array(rng.random(n) * 1000, type=pa.float64()),
    }))
    c.register_table("orders", pa.table({
        "okey": pa.array(np.arange(200), type=pa.int64()),
        "cust": pa.array(np.arange(200) % 17, type=pa.int64()),
    }))
    yield c
    c.shutdown()


Q1 = ("select flag, sum(qty) as sq, sum(price) as sp, count(*) as c "
      "from lineitem where qty < 45 group by flag order by flag")


def test_repeated_query_reports_zero_new_compiles(ctx):
    ctx.sql(Q1).to_pandas()            # warm: compiles + retraces happen here
    before = dev.STATS.snapshot()
    ctx.sql(Q1).to_pandas()            # identical plan + identical shapes
    d = _delta(before, dev.STATS.snapshot())
    assert d["jit_compiles"] == 0 and d["jit_retraces"] == 0, (
        f"identical re-run must be all cache hits, got {d}")
    assert d["jit_cache_hits"] > 0
    assert d["program_cache_hits"] > 0, \
        "second run must reuse the shared_program closures"


def test_shape_churn_is_counted_as_retraces(ctx):
    ctx.sql(Q1).to_pandas()
    before = dev.STATS.snapshot()
    # a changed output alias changes the packed-column static key through
    # the ONE module-level pack_for_host wrapper — a retrace, not a fresh
    # compile, because that wrapper already traced q1's layouts
    ctx.sql("select flag, sum(qty) as churn_total from lineitem "
            "group by flag order by flag").to_pandas()
    d = _delta(before, dev.STATS.snapshot())
    assert d["jit_retraces"] > 0, \
        f"key churn through shared wrappers must count retraces: {d}"


def test_explain_analyze_carries_device_evidence(ctx):
    report = ctx.explain_analyze(Q1)
    assert report["state"] == "successful"
    saw_device_stage = saw_op_fields = saw_watermark = False
    for st in report["stages"]:
        devd = st.get("device") or {}
        if devd.get("h2d_bytes") or devd.get("d2h_bytes"):
            saw_device_stage = True
        if devd.get("device_mem_peak", 0) > 0:
            saw_watermark = True
        for op in st["operator_tree"]:
            assert {"device_ms", "host_ms", "transfer_bytes",
                    "compiles", "retraces"} <= set(op)
            if op["compiles"] or op["transfer_bytes"]:
                saw_op_fields = True
    assert saw_device_stage, "some stage must record transfer bytes"
    assert saw_op_fields, "some operator must attribute compiles/transfers"
    assert saw_watermark, "watermarks must fold into stage device summaries"
    json.dumps(report)


def test_advisor_end_to_end_ranks_a_candidate(ctx):
    # a COLD q18-shaped join+aggregate: first execution pays real compile
    # time, so fusion candidates clear the configured min-savings threshold
    advice = ctx.advise(
        "select o.cust, sum(l.qty) as total, count(*) as c "
        "from lineitem l join orders o on l.okey = o.okey "
        "where l.qty < 48 group by o.cust order by total desc")
    assert advice["candidates"], \
        "a cold join+aggregate must rank at least one fusion candidate"
    top = advice["candidates"][0]
    assert len(top["operators"]) >= 2
    assert top["est_savings_ms"] >= advice["candidates"][-1]["est_savings_ms"]
    assert top["est_savings_ms"] >= advice["min_savings_ms"]
    assert advice["text"].count("fuse") >= 1
    # the warm path stays schema-stable and deterministic even when the
    # threshold filters everything out
    a1, a2 = ctx.advise(Q1), ctx.advise(Q1)
    assert [c["operators"] for c in a1["candidates"]] \
        == [c["operators"] for c in a2["candidates"]]
