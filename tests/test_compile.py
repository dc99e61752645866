"""Whole-stage compilation: chain detection, policy verdicts, fused
execution, serde, observability convergence, and interplay with the
adaptive-execution machinery.

Layers, matching how the subsystem is built:

  1. chain detection (compile/chains.py): the ONE candidate finder the
     advisor and the compiler share — plan-walk and operator_tree views
     must agree, and the structural fingerprint must be stable across
     equal chains and sensitive to real differences;
  2. policy + verdicts (compile/fuse.py): config parsing, the
     conservative per-instance allowlist (host mode, scalar subqueries,
     non-partial aggregates, clustered annotations), and the
     agg-heads-only run splitting;
  3. fused execution (compile/fused.py): a FusedStageExec's output is
     bit-identical to the interpreted chain it replaced, for row-only
     and aggregate-headed chains, with the runtime fallback latch;
  4. serde: fused plan nodes round-trip the wire; graph checkpoints
     carry fusion records;
  5. e2e (standalone): fusion on vs off produces identical results, the
     stage records the rewrite, EXPLAIN ANALYZE shows the fused kernel,
     the advisor marks chains fused vs merely advised, and the doctor's
     fusion-missed rule fires only above its savings threshold;
  6. interplay: lineage rollback re-resolves and re-fuses (without
     double-wrapping), speculative duplicates ship the same fused plan,
     and AQE rewrites validate against fused stages.
"""
import json

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from arrow_ballista_tpu import Field, INT64, Schema, serde
from arrow_ballista_tpu.client.context import BallistaContext
from arrow_ballista_tpu.compile.chains import (
    STATIC_REASONS,
    UNFUSABLE,
    chain_fingerprint,
    dict_chains,
    plan_chains,
    walk_plan_paths,
)
from arrow_ballista_tpu.compile.fuse import (
    CompilePolicy,
    _op_verdict,
    _split_runs,
    fuse_stage,
)
from arrow_ballista_tpu.compile.fused import FusedStageExec
from arrow_ballista_tpu.models import expr as E
from arrow_ballista_tpu.ops import operators as O
from arrow_ballista_tpu.ops.physical import (
    MemoryScanExec,
    MetricsSet,
    TaskContext,
    schema_sig,
)
from arrow_ballista_tpu.utils.config import BallistaConfig
from arrow_ballista_tpu.utils.errors import InternalError

from .test_scheduler import drain, physical_plan


# --------------------------------------------------------------------------
# plumbing
# --------------------------------------------------------------------------

def _scan(n=100, partitions=2):
    t = pa.table({"x": pa.array(np.arange(n, dtype=np.int64)),
                  "y": pa.array((np.arange(n, dtype=np.int64) * 3) % 7)})
    schema = Schema([Field("x", INT64), Field("y", INT64)])
    return MemoryScanExec(schema, t, partitions, [])


def _chain(n=100, partitions=2):
    """scan -> filter -> projection, returned head-first."""
    scan = _scan(n, partitions)
    filt = O.FilterExec(scan, E.BinOp(">", E.Column("x"), E.Lit(10)))
    proj = O.ProjectionExec(
        filt, [(E.BinOp("*", E.Column("x"), E.Lit(2)), "xx"),
               (E.Column("y"), "y")])
    return proj, filt, scan


def _ctx():
    return TaskContext(config=BallistaConfig(), job_id="test-compile")


def _rows(batches):
    """Sorted materialized rows, null-masked, for exact comparison."""
    out = []
    for b in batches:
        tbl = b.to_arrow()
        out.extend(sorted(map(str, tbl.to_pylist())))
    return sorted(out)


def _graph(sql=None, partitions=4, enabled=True, min_ops=2):
    from arrow_ballista_tpu.compile.fuse import fuse_resolved_stages
    from arrow_ballista_tpu.scheduler.execution_graph import ExecutionGraph

    graph = ExecutionGraph.build("job-fuse", physical_plan(sql, partitions))
    graph.compiler = CompilePolicy(enabled=enabled, min_ops=min_ops)
    fuse_resolved_stages(graph)
    return graph


def _fused_nodes(plan):
    out = []

    def walk(p):
        if isinstance(p, FusedStageExec):
            out.append(p)
        for c in p.children():
            walk(c)

    walk(plan)
    return out


# --------------------------------------------------------------------------
# 1. chain detection
# --------------------------------------------------------------------------

def test_plan_walk_paths_match_metric_convention():
    proj, filt, scan = _chain()
    writer_free = proj  # stage plans root at a writer; walk any subtree
    paths = walk_plan_paths(writer_free)
    assert [(p, type(n).__name__) for p, n in paths] == [
        ("0", "ProjectionExec"), ("0.0", "FilterExec"),
        ("0.0.0", "MemoryScanExec")]


def test_plan_and_dict_chains_agree():
    proj, filt, scan = _chain()
    pc = plan_chains(proj)
    tree = [{"path": p, "op": type(n).__name__}
            for p, n in walk_plan_paths(proj)]
    dc = dict_chains(tree)
    assert [[type(n).__name__ for _p, n in c] for c in pc] \
        == [[op["op"] for op in c] for c in dc]
    # the chain covers the whole single-child pipeline
    assert [[type(n).__name__ for _p, n in c] for c in pc] == [
        ["ProjectionExec", "FilterExec", "MemoryScanExec"]]


def test_chains_break_at_unfusable_and_multi_child():
    assert "ShuffleReaderExec" in UNFUSABLE
    tree = [
        {"path": "0", "op": "ShuffleWriterExec"},
        {"path": "0.0", "op": "ProjectionExec"},
        {"path": "0.0.0", "op": "JoinExec"},
        {"path": "0.0.0.0", "op": "FilterExec"},
        {"path": "0.0.0.0.0", "op": "ShuffleReaderExec"},
        {"path": "0.0.0.1", "op": "ShuffleReaderExec"},
    ]
    chains = dict_chains(tree)
    # writer is unfusable; join has two children so the proj->join chain
    # stops there; the filter's only child is a reader -> run of 1 -> no
    # chain below the join
    assert [[op["op"] for op in c] for c in chains] == [
        ["ProjectionExec", "JoinExec"]]


def test_chain_fingerprint_stable_and_sensitive():
    proj1, filt1, _ = _chain()
    proj2, filt2, _ = _chain()
    sig = schema_sig(filt1.input.schema)
    assert chain_fingerprint([proj1, filt1], sig) \
        == chain_fingerprint([proj2, filt2], sig), \
        "equal chains must share a fingerprint (shared program cache)"
    filt2.predicate = E.BinOp(">", E.Column("x"), E.Lit(99))
    assert chain_fingerprint([proj1, filt1], sig) \
        != chain_fingerprint([proj2, filt2], sig), \
        "a different predicate must change the fingerprint"


# --------------------------------------------------------------------------
# 2. policy + verdicts
# --------------------------------------------------------------------------

def test_policy_from_config_and_defaults():
    assert CompilePolicy.from_config(None).enabled is True
    cfg = BallistaConfig({
        "ballista.compile.enabled": "false",
        "ballista.compile.min.ops": "3",
        "ballista.compile.operators": "FilterExec, ProjectionExec",
        "ballista.compile.donate": "false",
    })
    p = CompilePolicy.from_config(cfg)
    assert p.enabled is False
    assert p.min_ops == 3
    assert p.operators == {"FilterExec", "ProjectionExec"}
    assert p.donate is False
    assert CompilePolicy(min_ops=0).min_ops == 2, \
        "min_ops clamps to 2 (a fused run needs at least 2 operators)"


def test_verdicts_reject_every_doubt():
    pol = CompilePolicy()
    proj, filt, scan = _chain()
    assert _op_verdict(pol, filt) == (True, None)
    assert _op_verdict(pol, proj) == (True, None)

    host_filt = O.FilterExec(scan, E.BinOp(">", E.Column("x"), E.Lit(10)),
                             host_mode=True)
    ok, why = _op_verdict(pol, host_filt)
    assert not ok and "host-mode" in why

    ok, why = _op_verdict(pol, scan)
    assert not ok and why == STATIC_REASONS["MemoryScanExec"]

    agg = O.HashAggregateExec(
        scan, [(E.Column("y"), "y")],
        [O.AggSpec("sum", E.Column("x"), "s")], "partial")
    assert _op_verdict(pol, agg) == (True, None)
    final = O.HashAggregateExec(
        agg, [(E.Column("y"), "y")],
        [O.AggSpec("sum", E.Column("s"), "s")], "final")
    ok, why = _op_verdict(pol, final)
    assert not ok and "final" in why
    glob = O.HashAggregateExec(
        scan, [], [O.AggSpec("sum", E.Column("x"), "s")], "partial")
    ok, why = _op_verdict(pol, glob)
    assert not ok and "global" in why
    clustered = O.HashAggregateExec(
        scan, [(E.Column("y"), "y")],
        [O.AggSpec("sum", E.Column("x"), "s")], "partial")
    clustered.clustered = (E.Lit(True), [], None)
    ok, why = _op_verdict(pol, clustered)
    assert not ok and "clustered" in why


def test_split_runs_agg_heads_only():
    pol = CompilePolicy()
    scan = _scan()
    filt = O.FilterExec(scan, E.BinOp(">", E.Column("x"), E.Lit(1)))
    agg = O.HashAggregateExec(
        filt, [(E.Column("y"), "y")],
        [O.AggSpec("sum", E.Column("x"), "s")], "partial")
    proj = O.ProjectionExec(agg, [(E.Column("y"), "y"), (E.Column("s"), "s")])
    chain = [("0.0", proj), ("0.0.0", agg), ("0.0.0.0", filt),
             ("0.0.0.0.0", scan)]
    runs, rejected = _split_runs(pol, chain)
    # the aggregate may only HEAD a fused program: proj's run closes, the
    # aggregate opens its own with the filter inside it
    assert [[type(n).__name__ for _p, n in r] for r in runs] == [
        ["ProjectionExec"],
        ["HashAggregateExec", "FilterExec"]]
    assert [r["op"] for r in rejected] == ["MemoryScanExec"]


def test_fused_ctor_validates_linkage():
    proj, filt, _scan_ = _chain()
    with pytest.raises(InternalError):
        FusedStageExec([proj])  # needs >= 2 ops
    other = O.FilterExec(_scan(), E.BinOp(">", E.Column("x"), E.Lit(5)))
    with pytest.raises(InternalError):
        FusedStageExec([proj, other])  # not input-linked


# --------------------------------------------------------------------------
# 3. fused execution == interpreted execution
# --------------------------------------------------------------------------

def test_row_chain_fused_matches_interpreted():
    proj, filt, scan = _chain(n=500, partitions=2)
    ctx = _ctx()
    interpreted = [proj.execute(p, ctx) for p in range(2)]
    proj2, filt2, _ = _chain(n=500, partitions=2)
    fused = FusedStageExec([proj2, filt2])
    got = [fused.execute(p, ctx) for p in range(2)]
    for p in range(2):
        assert _rows(got[p]) == _rows(interpreted[p])
    assert fused.schema.names() == proj.schema.names()


def test_row_chain_donates_columns_and_mask(monkeypatch):
    """ROADMAP #2 via the donation-safety analyzer: the mask (arg 1) is
    provably dead after the fused row call — same freshness proof as the
    columns — so row-only chains donate BOTH buffers.  CPU gates donation
    off, so force the gate and capture what _build hands observed_jit."""
    import jax

    from arrow_ballista_tpu.compile import fused as fused_mod

    captured = {}
    real = fused_mod.observed_jit

    def spy(sig, fn=None, **kw):
        captured[sig] = dict(kw)
        return real(sig, fn, **kw)

    monkeypatch.setattr(fused_mod, "observed_jit", spy)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    proj, filt, _ = _chain(n=100, partitions=1)
    fused = FusedStageExec([proj, filt], donate=True)
    fused._build(_ctx())
    assert captured[fused.fused_sig()]["donate_argnums"] == (0, 1)

    # agg-headed chains donate too since plan-ahead capacity: out_cap is
    # sized before the single jfn call, so there is no retry ladder
    # re-reading donated buffers — inputs are provably dead after call
    scan = _scan(n=100, partitions=1)
    filt_a = O.FilterExec(scan, E.BinOp(">", E.Column("x"), E.Lit(5)))
    agg = O.HashAggregateExec(
        filt_a, [(E.Column("y"), "y")],
        [O.AggSpec("sum", E.Column("x"), "sx")], "partial")
    fused_a = FusedStageExec([agg, filt_a], donate=True)
    captured.clear()
    fused_a._build(_ctx())
    assert captured[fused_a.fused_sig()]["donate_argnums"] == (0, 1)


def test_agg_chain_fused_matches_interpreted():
    ctx = _ctx()

    def build():
        scan = _scan(n=1000, partitions=2)
        filt = O.FilterExec(scan, E.BinOp(">", E.Column("x"), E.Lit(100)))
        agg = O.HashAggregateExec(
            filt, [(E.Column("y"), "y")],
            [O.AggSpec("sum", E.Column("x"), "sx"),
             O.AggSpec("count", E.Column("x"), "n")], "partial")
        return agg, filt

    agg_i, _ = build()
    interpreted = [agg_i.execute(p, ctx) for p in range(2)]
    agg_f, filt_f = build()
    fused = FusedStageExec([agg_f, filt_f])
    got = [fused.execute(p, ctx) for p in range(2)]
    for p in range(2):
        assert _rows(got[p]) == _rows(interpreted[p])


def test_fused_agg_counts_its_contractions(tpu_branches):
    """A fused stage headed by an aggregate counts ``mxu_grouped_sums`` as
    the aggregate alone does: one a kernel call on the TPU branch."""
    ctx = _ctx()
    scan = _scan(n=1000, partitions=2)
    filt = O.FilterExec(scan, E.BinOp(">", E.Column("x"), E.Lit(101)))
    agg = O.HashAggregateExec(     # a bool key: a dense domain of two
        filt, [(E.BinOp(">", E.Column("y"), E.Lit(3)), "big")],
        [O.AggSpec("sum", E.Column("x"), "sx"),
         O.AggSpec("count", E.Column("x"), "n")], "partial")
    fused = FusedStageExec([agg, filt])
    for p in range(2):
        fused.execute(p, ctx)
    assert fused.metrics().to_dict()["mxu_grouped_sums"] == 2


def test_runtime_fallback_latches_to_interpreted():
    # unique literals: a fresh fingerprint so the shared-program cache
    # cannot satisfy this chain (the broken _build below must be reached)
    scan = _scan(n=200, partitions=1)
    filt = O.FilterExec(scan, E.BinOp(">", E.Column("x"), E.Lit(173)))
    proj = O.ProjectionExec(
        filt, [(E.BinOp("*", E.Column("x"), E.Lit(757)), "xx")])
    fused = FusedStageExec([proj, filt])
    ctx = _ctx()
    baseline = _rows(proj.execute(0, ctx))

    def boom(ctx_):
        raise RuntimeError("injected kernel-build failure")

    fused._build = boom  # first fused attempt dies inside the safety valve
    got = _rows(fused.execute(0, ctx))
    assert got == baseline, "fallback must produce the interpreted answer"
    assert fused._fallback, "the interpreted path must be latched"
    assert fused.metrics().to_dict().get("fused_fallbacks") == 1


def test_metrics_deferred_resolver_may_reenter_add():
    # Regression: the fused aggregate's deferred output_rows resolver
    # records fused_passthrough_fallbacks on the SAME metrics set when the
    # poor-reduction probe fires.  to_dict resolves deferred fns under the
    # lock, so add must be reentrant — a plain Lock deadlocked q20 at SF1
    # (the only query whose partial agg is big and poor enough to latch).
    import threading

    m = MetricsSet()

    def resolver():
        m.add("reentrant_latch", 1)
        return 7

    m.add_deferred("output_rows", resolver)
    got = {}
    t = threading.Thread(target=lambda: got.update(m.to_dict()))
    t.start()
    t.join(timeout=10)
    assert not t.is_alive(), "to_dict deadlocked on a deferred re-entrant add"
    assert got["output_rows"] == 7
    assert got["reentrant_latch"] == 1


# --------------------------------------------------------------------------
# 4. serde
# --------------------------------------------------------------------------

def test_fused_plan_serde_roundtrip():
    proj, filt, scan = _chain()
    fused = FusedStageExec([proj, filt], donate=True)
    obj = json.loads(json.dumps(serde.plan_to_obj(fused)))
    back = serde.plan_from_obj(obj)
    assert isinstance(back, FusedStageExec)
    assert back.donate is True
    assert [type(o).__name__ for o in back.ops] == \
        ["ProjectionExec", "FilterExec"]
    assert back.ops[0].input is back.ops[1], "chain links must survive"
    assert type(back.input).__name__ == "MemoryScanExec"


def test_graph_checkpoint_carries_fusion_records():
    graph = _graph()
    fused_stages = [s for s in graph.stages.values()
                    if s.resolved_plan is not None
                    and _fused_nodes(s.resolved_plan)]
    assert fused_stages, "the leaf group-by stage must fuse"
    assert graph.compile_log, "fusion decisions must land in compile_log"
    obj = json.loads(json.dumps(serde.graph_to_obj(graph)))
    back = serde.graph_from_obj(obj)
    assert [r["kind"] for r in back.compile_log] \
        == [r["kind"] for r in graph.compile_log]
    for sid, stage in graph.stages.items():
        assert [r.get("fused") for r in back.stages[sid].fusion_rewrites] \
            == [r.get("fused") for r in stage.fusion_rewrites]
    # recovered graphs have no policy installed: conservative default
    assert back.compiler is None


# --------------------------------------------------------------------------
# 5. scheduler integration + interplay
# --------------------------------------------------------------------------

def test_leaf_stage_fuses_and_disabled_policy_does_not():
    on = _graph(enabled=True)
    assert any(_fused_nodes(s.resolved_plan) for s in on.stages.values()
               if s.resolved_plan is not None)
    off = _graph(enabled=False)
    assert not any(_fused_nodes(s.resolved_plan)
                   for s in off.stages.values()
                   if s.resolved_plan is not None)
    assert not off.compile_log


def test_fuse_stage_idempotent_per_attempt():
    graph = _graph()
    stage = next(s for s in graph.stages.values()
                 if s.resolved_plan is not None
                 and _fused_nodes(s.resolved_plan))
    before = len(stage.fusion_rewrites)
    assert fuse_stage(graph, stage) == 0, \
        "same attempt must not re-fuse (or re-record)"
    assert len(stage.fusion_rewrites) == before
    assert len(_fused_nodes(stage.resolved_plan)) == 1


def test_task_ships_fused_plan_and_speculative_duplicate_shares_it():
    graph = _graph()
    stage = next(s for s in graph.stages.values()
                 if s.resolved_plan is not None
                 and _fused_nodes(s.resolved_plan))
    t = graph.pop_next_task("exec-0")
    assert t is not None and t.task.stage_id == stage.stage_id
    assert _fused_nodes(t.plan), "launched tasks must carry the fused plan"
    # a speculative duplicate launches from the same resolved plan object,
    # so it executes the SAME fused kernel as the primary
    spec = graph.launch_speculative(stage.stage_id, t.task.partition,
                                    "exec-1")
    assert spec is not None
    assert spec.task.speculative
    assert _fused_nodes(spec.plan), \
        "the duplicate attempt must run the fused kernel too"
    assert spec.plan is t.plan


def test_rollback_re_resolves_and_keeps_single_fusion():
    graph = _graph()
    stage = next(s for s in graph.stages.values()
                 if s.fusion_rewrites
                 and any(r["fused"] for r in s.fusion_rewrites))
    attempt = stage.stage_attempt
    stage.rollback()
    assert stage.resolved_plan is None
    graph.revive()
    assert stage.stage_attempt == attempt + 1
    assert stage.resolved_plan is not None
    # the re-resolved attempt re-decided fusion under the new epoch and
    # never double-wrapped: exactly one fused node in the live plan
    assert stage._fused_attempt == stage.stage_attempt, \
        "revive must re-run the fusion decision for the new attempt"
    assert len(_fused_nodes(stage.resolved_plan)) == 1
    drain(graph)
    assert graph.status == "successful"


def test_aqe_coalesce_validates_against_fused_producer():
    """AQE's dynamic coalescing rewrites the CONSUMER of the fused
    stage's output; both rewrites must coexist on one graph and the job
    must still complete (validate_rewrite re-checks the mutated stage)."""
    from arrow_ballista_tpu.scheduler.aqe import AqePolicy

    graph = _graph(partitions=8)
    graph.aqe = AqePolicy(enabled=True)
    drain(graph)
    assert graph.status == "successful"
    assert any(r["fused"] for r in graph.compile_log)


# --------------------------------------------------------------------------
# 6. e2e (standalone) + observability convergence
# --------------------------------------------------------------------------

def _frame(rng, n=2000, groups=9):
    return pd.DataFrame({
        "g": rng.integers(0, groups, n).astype(np.int64),
        "v": rng.integers(0, 100, n).astype(np.int64),
    })


def _run_standalone(sql, df, enabled, tables=("t",)):
    cfg = BallistaConfig({
        "ballista.shuffle.partitions": "4",
        "ballista.compile.enabled": str(enabled).lower(),
        # tiny test data: don't let the advisor's savings floor hide chains
        "ballista.observability.device.advisor.min_savings_ms": "0",
    })
    c = BallistaContext.standalone(cfg)
    try:
        for name in tables:
            c.register_table(name, df)
        out = c.sql(sql).to_pandas()
        logs = []
        jobs = c._standalone.scheduler.jobs
        for jid in list(getattr(jobs, "_graphs", {}) or {}):
            logs.extend(getattr(jobs.get_graph(jid), "compile_log", []))
        return out, logs, c
    except BaseException:
        c.shutdown()
        raise


def test_standalone_fusion_ab_identical_and_observable():
    rng = np.random.default_rng(42)
    df = _frame(rng)
    sql = ("select g, sum(v) as s, count(*) as n from t "
           "where v > 10 group by g order by g")
    on, logs_on, c_on = _run_standalone(sql, df, True)
    try:
        fused_recs = [r for r in logs_on if r["fused"]]
        assert fused_recs, "the partial-agg stage must fuse"
        assert any("HashAggregateExec" in run
                   for r in fused_recs for run in r["fused_ops"]), \
            "the fused run must include the partial aggregate"
        rep = c_on.explain_analyze(sql)
        assert "FusedStageExec" in rep["text"], \
            "EXPLAIN ANALYZE must show the fused kernel"
        assert any("fused " in _hdr for _hdr in rep["text"].splitlines()), \
            "the stage header must carry the fusion annotation"
        # advisor convergence: the fused chain is marked fused=True
        adv = c_on.advise(sql)
        assert any(cand["fused"] for cand in adv["candidates"])
        assert "[FUSED]" in adv["text"]
    finally:
        c_on.shutdown()
    off, logs_off, c_off = _run_standalone(sql, df, False)
    c_off.shutdown()
    assert not logs_off
    # bit-identical: fused output must equal the interpreted output
    pd.testing.assert_frame_equal(on, off)


def test_advisor_reports_rejection_reason():
    rng = np.random.default_rng(3)
    # float64 arithmetic plans host-mode operators: allowlist rejects
    df = pd.DataFrame({
        "g": rng.integers(0, 5, 800).astype(np.int64),
        "v": rng.normal(size=800),
    })
    sql = ("select g, sum(v) as s from t where v > 0.1 "
           "group by g order by g")
    out, logs, c = _run_standalone(sql, df, True)
    try:
        adv = c.advise(sql)
        rejected = [cand for cand in adv["candidates"]
                    if not cand["fused"] and cand["reason"]]
        assert rejected, "rejected chains must carry a reason"
    finally:
        c.shutdown()


def test_doctor_fusion_missed_threshold():
    from arrow_ballista_tpu.obs.doctor import (
        FUSION_MISSED_MIN_SAVINGS_MS,
        diagnose,
    )

    def bundle(retraces, compile_s):
        stage = {
            "stage_id": 1, "state": "successful", "stage_attempt": 0,
            "partitions": 2, "planned_partitions": 2, "tasks_completed": 2,
            "task_launches": 2, "speculative_launches": 0,
            "output_rows": 10, "output_bytes": 100,
            "partition_rows": {}, "partition_bytes": {}, "skew": 1.0,
            "row_histogram": {"edges": [], "counts": []},
            "task_duration_s": {"count": 2, "p50": 0.1, "p75": 0.1,
                                "p95": 0.1, "max": 0.1, "mean": 0.1},
            "operators": {
                "0.0:HashAggregateExec": {"output_rows": 10},
                "0.0.0:FilterExec": {
                    "jit_compile_time": compile_s,
                    "jit_compiles": 1, "jit_retraces": retraces,
                },
            },
            "device": {}, "aqe": [],
            "fusion": [{
                "kind": "fusion", "stage_id": 1, "stage_attempt": 0,
                "operators": ["HashAggregateExec", "FilterExec"],
                "paths": ["0.0", "0.0.0"],
                "fused": False, "fused_ops": [],
                "rejected": [{"op": "HashAggregateExec", "path": "0.0",
                              "reason": "aggregate mode 'final'"}],
                "donate": False,
            }],
        }
        return {"schema": "ballista.forensics/v1", "job_id": "j",
                "generated_ts_ms": 0, "status": {"state": "successful"},
                "journal": [], "stages": [stage], "aqe_log": [],
                "metrics": {}, "cluster_history": {}}

    # pure first-compile cost never fires the rule (a fused kernel
    # compiles once too)
    cold = diagnose(bundle(retraces=0, compile_s=1.0))
    assert "fusion-missed" not in [f["rule"] for f in cold["findings"]]
    # heavy RETRACE share above the threshold does
    hot = diagnose(bundle(retraces=9, compile_s=1.0))
    missed = [f for f in hot["findings"] if f["rule"] == "fusion-missed"]
    assert missed, "retrace-dominated rejected chain must be diagnosed"
    f = missed[0]
    assert f["evidence"]["est_savings_ms"] >= FUSION_MISSED_MIN_SAVINGS_MS
    assert any("final" in r for r in f["evidence"]["rejected"])
    assert "ballista.compile" in f["remedy"]
    assert "fusion-missed" in hot["rules_evaluated"]


def test_repeated_template_reports_zero_new_compiles():
    """Plan-cache repeat contract: the second run of the same statement
    reuses the shared fused program — 0 new jit compiles."""
    rng = np.random.default_rng(11)
    df = _frame(rng)
    sql = ("select g, sum(v) as s from t where v > 25 "
           "group by g order by g")
    cfg = BallistaConfig({
        "ballista.shuffle.partitions": "2",
        "ballista.compile.enabled": "true",
    })
    c = BallistaContext.standalone(cfg)
    try:
        c.register_table("t", df)
        first = c.sql(sql).to_pandas()
        rep1 = c.explain_analyze(sql)
        again = c.sql(sql).to_pandas()
        pd.testing.assert_frame_equal(first, again)
        # sum fused-kernel compiles across the LAST run's stages: the
        # shared_program cache means the fused signature never recompiles
        last = c.explain_analyze(sql)
        fused_ops = [op
                     for st in last["stages"]
                     for op in st["operator_tree"]
                     if op["op"] == "FusedStageExec"]
        assert fused_ops, "repeat run must still show the fused kernel"
        assert sum(op["compiles"] for op in fused_ops) == 0, \
            "a repeated statement must report 0 new fused compiles"
    finally:
        c.shutdown()


# --------------------------------------------------------------------------
# 7. chaos: executor killed mid-fused-task
# --------------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_executor_killed_mid_fused_task(tmp_path):
    """Fault-recovery interplay: kill an executor right before it runs a
    task whose stage plan carries a FusedStageExec.  The scheduler's
    lineage machinery must re-run the work and the final answer must
    equal the fusion-OFF oracle — the fused kernel adds no new failure
    mode."""
    from arrow_ballista_tpu import faults

    from .test_chaos import (
        SQL,
        _client,
        _frames_equal,
        _make_cluster,
        _teardown,
    )

    sched, executors = _make_cluster(tmp_path)
    try:
        c_off = _client(sched.port)
        c_off.config.set("ballista.compile.enabled", "false")
        oracle = c_off.sql(SQL).to_pandas()
        c_off.shutdown()

        c = _client(sched.port)  # compiler on by default
        victim = executors[1]
        plan = faults.FaultPlan.from_obj({"seed": 7, "rules": [{
            "site": "executor.task.before_run", "action": "kill",
            "match": {"executor_id": victim.metadata.executor_id},
            "on_hit": 1, "times": 1}]})
        with faults.use_plan(plan):
            got = c.sql(SQL).to_pandas()

        assert victim._killed, "the kill must reach the registered target"
        _frames_equal(got, oracle)
        # the surviving run really did fuse: some graph on the scheduler
        # recorded an installed kernel
        jobs = sched.server.jobs
        logs = []
        for jid in list(getattr(jobs, "_graphs", {}) or {}):
            logs.extend(getattr(jobs.get_graph(jid), "compile_log", []))
        assert any(r.get("fused") for r in logs), \
            "the killed run's stages must have carried fused kernels"
        c.shutdown()
    finally:
        _teardown(sched, executors)


# --------------------------------------------------------------------------
# 8. SF1 oracle sweep (slow: needs the generated TPC-H dataset)
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_sf1_all_queries_bit_identical_fusion_on_vs_off():
    """The whole TPC-H suite at SF1, fusion on vs off, through the
    standalone scheduler: every query's result frame must be EXACTLY
    equal — the compiler is a pure performance rewrite."""
    import os

    from benchmarks.queries import QUERIES
    from benchmarks.tpch import register_tables

    data = os.path.join(os.path.dirname(__file__), "..",
                        ".bench_data", "tpch-sf1")
    if not os.path.exists(os.path.join(data, "lineitem.parquet")):
        pytest.skip("TPC-H SF1 dataset not generated "
                    "(python -m benchmarks.tpch convert --scale 1 "
                    "--output .bench_data/tpch-sf1)")

    def run(enabled):
        cfg = BallistaConfig({
            "ballista.shuffle.partitions": "4",
            "ballista.compile.enabled": str(enabled).lower(),
        })
        c = BallistaContext.standalone(cfg, concurrent_tasks=4)
        out, logs = {}, []
        try:
            register_tables(c, data)
            for q in sorted(QUERIES):
                out[q] = c.sql(QUERIES[q]).to_pandas()
            jobs = c._standalone.scheduler.jobs
            for jid in list(getattr(jobs, "_graphs", {}) or {}):
                logs.extend(getattr(jobs.get_graph(jid), "compile_log", []))
        finally:
            c.shutdown()
        return out, logs

    on, logs_on = run(True)
    off, logs_off = run(False)
    assert not logs_off
    assert any(r.get("fused") for r in logs_on), \
        "the fusion-on sweep must have installed at least one kernel"
    mismatched = []
    for q in sorted(on):
        try:
            pd.testing.assert_frame_equal(on[q], off[q])
        except AssertionError as exc:
            mismatched.append((q, str(exc).splitlines()[0]))
    assert not mismatched, \
        f"{len(mismatched)}/22 queries differ fusion-on vs off: {mismatched}"
