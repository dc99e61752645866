"""Mesh-fused aggregation: bit-identical to the file-shuffle stage pair.

The fused program (partial agg -> ICI all_to_all -> final agg as one XLA
program, ops/mesh_exec.py) must return exactly what the two-stage shuffle
path returns — the scheduler may pick either transport per stage boundary.
"""
from decimal import Decimal

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from arrow_ballista_tpu.client.context import BallistaContext
from arrow_ballista_tpu.ops.mesh_exec import MeshAggregateExec
from arrow_ballista_tpu.utils.config import BallistaConfig


@pytest.fixture(scope="module")
def table():
    rng = np.random.default_rng(11)
    n = 50_000
    return pa.table({
        "g": pa.array(rng.integers(0, 100, n).astype(np.int64)),
        "s": pa.array(rng.choice(["aa", "bb", "cc"], n)),
        "v": pa.array(rng.integers(-50, 100, n).astype(np.int64)),
        "w": pa.array(rng.integers(0, 10, n).astype(np.int32)),
    })


def contexts(table):
    base = {"ballista.shuffle.partitions": "4"}
    mesh_ctx = BallistaContext.local(BallistaConfig({**base, "ballista.shuffle.mesh": "true",
        "ballista.shuffle.mesh.min_rows": "0"}))
    file_ctx = BallistaContext.local(BallistaConfig(base))
    for c in (mesh_ctx, file_ctx):
        c.register_table("t", table)
    return mesh_ctx, file_ctx


QUERIES = [
    "select g, sum(v) as sv, count(*) as n, min(v) as lo, max(v) as hi "
    "from t group by g order by g",
    "select s, g, sum(w) as sw from t where v > 0 group by s, g order by s, g",
    "select s, avg(v) as a from t group by s order by s",
]


@pytest.mark.parametrize("q", range(len(QUERIES)))
def test_mesh_matches_file_shuffle(table, q):
    mesh_ctx, file_ctx = contexts(table)
    sql = QUERIES[q]
    mesh_df = mesh_ctx.sql(sql)
    # the fused operator must actually be in the mesh plan
    from arrow_ballista_tpu.scheduler.physical_planner import PhysicalPlanner
    from arrow_ballista_tpu.scheduler.planner import collect_nodes
    from arrow_ballista_tpu.sql.optimizer import optimize

    planned = PhysicalPlanner(mesh_ctx.catalog, mesh_ctx.config).plan_query(
        optimize(mesh_df.logical))
    assert collect_nodes(planned.plan, MeshAggregateExec), \
        f"mesh plan missing fused operator:\n{planned.plan.display()}"

    got = mesh_df.to_pandas()
    want = file_ctx.sql(sql).to_pandas()
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


def test_mesh_dense_aggregate_counts_its_contraction(table, tpu_branches):
    """A dense domain (one dictionary key) on the TPU branch: the mesh
    program's sums and counts are one contraction a device, counted once a
    dispatch; answers as over files."""
    mesh_ctx, file_ctx = contexts(table)
    sql = "select s, sum(v) as sv, count(*) as n from t group by s order by s"
    report = mesh_ctx.explain_analyze(sql)
    counted = [op["metrics"].get("mxu_grouped_sums", 0)
               for stage in report["stages"]
               for op in stage["operator_tree"]
               if op["op"] == "MeshAggregateExec"]
    assert counted == [1], report["text"]
    pd.testing.assert_frame_equal(mesh_ctx.sql(sql).to_pandas(),
                                  file_ctx.sql(sql).to_pandas(),
                                  check_dtype=False)


def test_mesh_standalone_cluster(table):
    config = BallistaConfig({"ballista.shuffle.partitions": "4",
                             "ballista.shuffle.mesh": "true",
        "ballista.shuffle.mesh.min_rows": "0"})
    ctx = BallistaContext.standalone(config, concurrent_tasks=4)
    ctx.register_table("t", table)
    got = ctx.sql("select g, sum(v) as sv from t group by g order by g").to_pandas()
    pdf = table.to_pandas()
    want = pdf.groupby("g").agg(sv=("v", "sum")).reset_index()
    pd.testing.assert_frame_equal(got, want, check_dtype=False)
    ctx.shutdown()


def test_mesh_nullable_operands_match_file_shuffle():
    """NULL-bearing measures stay ON the mesh path (derive neutralizes NULL
    rows per aggregate; hidden valid counts ride the exchange) and produce
    the same answers as the file path, including all-NULL groups -> NULL."""
    rng = np.random.default_rng(7)
    n = 20_000
    v = rng.integers(-50, 100, n).astype(np.float64)
    # group 0: every row NULL (exercises the sentinel restore)
    g = rng.integers(0, 20, n)
    null_at = (rng.random(n) < 0.3) | (g == 0)
    table = pa.table({
        "g": pa.array(g.astype(np.int64)),
        "v": pa.array([None if m else int(x) for m, x in zip(null_at, v)],
                      type=pa.int64()),
        "d": pa.array([None if m else Decimal(int(x)) / 4
                       for m, x in zip(null_at, v)],
                      type=pa.decimal128(12, 2)),
    })
    mesh_ctx, file_ctx = contexts(table)
    sql = ("select g, sum(v) as sv, count(v) as cv, min(v) as lo, "
           "max(v) as hi, sum(d) as sd, count(*) as n "
           "from t group by g order by g")
    from arrow_ballista_tpu.ops.mesh_exec import MeshAggregateExec
    from arrow_ballista_tpu.scheduler.physical_planner import PhysicalPlanner
    from arrow_ballista_tpu.scheduler.planner import collect_nodes
    from arrow_ballista_tpu.sql.optimizer import optimize

    mesh_df = mesh_ctx.sql(sql)
    planned = PhysicalPlanner(mesh_ctx.catalog, mesh_ctx.config).plan_query(
        optimize(mesh_df.logical))
    assert collect_nodes(planned.plan, MeshAggregateExec), \
        f"nullable operands fell off the mesh path:\n{planned.plan.display()}"
    got = mesh_df.to_pandas()
    want = file_ctx.sql(sql).to_pandas()
    pd.testing.assert_frame_equal(got, want, check_dtype=False)
    # group 0 is all-NULL: sum/min/max NULL, count(v) 0
    row0 = got[got.g == 0].iloc[0]
    assert pd.isna(row0.sv) and pd.isna(row0.lo) and pd.isna(row0.hi)
    assert row0.cv == 0 and row0.n > 0


# --------------------------------------------------------------------------
# mesh-fused partitioned join
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def join_tables():
    rng = np.random.default_rng(23)
    n_fact, n_dim = 30_000, 2_000
    fact = pa.table({
        "fk": pa.array(rng.integers(0, n_dim * 2, n_fact).astype(np.int64)),
        "val": pa.array(rng.integers(0, 1000, n_fact).astype(np.int64)),
        "tag": pa.array(rng.choice(["x", "y", "z"], n_fact)),
    })
    dim = pa.table({
        "dk": pa.array(np.arange(n_dim, dtype=np.int64)),
        "name": pa.array(rng.choice(["aa", "bb", "cc", "dd"], n_dim)),
        "weight": pa.array(rng.integers(1, 5, n_dim).astype(np.int64)),
    })
    return fact, dim


def join_contexts(join_tables, strategy="broadcast"):
    fact, dim = join_tables
    # broadcast threshold 0 forces the partitioned path on both contexts
    base = {"ballista.shuffle.partitions": "4",
            "ballista.join.broadcast_threshold": "0"}
    mesh_extra = {"ballista.shuffle.mesh": "true",
        "ballista.shuffle.mesh.min_rows": "0"}
    if strategy == "partitioned":
        # force both sides through the all_to_all exchange (the 2k-row dim
        # side would otherwise take the all_gather broadcast path)
        mesh_extra["ballista.shuffle.mesh.broadcast_rows"] = "0"
    mesh_ctx = BallistaContext.local(BallistaConfig({**base, **mesh_extra}))
    file_ctx = BallistaContext.local(BallistaConfig(base))
    for c in (mesh_ctx, file_ctx):
        c.register_table("fact", fact)
        c.register_table("dim", dim)
    return mesh_ctx, file_ctx


JOIN_QUERIES = [
    # inner equi-join + aggregate (the TPC-H q3 shape)
    "select name, sum(val) as sv, count(*) as n from fact "
    "join dim on fk = dk group by name order by name",
    # plain inner join, row-level output
    "select fk, val, name, weight from fact join dim on fk = dk "
    "order by fk, val, name, weight limit 500",
    # string keys
    "select tag, name, count(*) as n from fact join dim on tag = name "
    "group by tag, name order by tag, name",
]


@pytest.mark.parametrize("strategy", ["partitioned", "broadcast"])
@pytest.mark.parametrize("q", range(len(JOIN_QUERIES)))
def test_mesh_join_matches_file_shuffle(join_tables, q, strategy):
    from arrow_ballista_tpu.ops.mesh_exec import MeshJoinExec
    from arrow_ballista_tpu.scheduler.physical_planner import PhysicalPlanner
    from arrow_ballista_tpu.scheduler.planner import collect_nodes
    from arrow_ballista_tpu.sql.optimizer import optimize

    mesh_ctx, file_ctx = join_contexts(join_tables, strategy)
    sql = JOIN_QUERIES[q]
    mesh_df = mesh_ctx.sql(sql)
    planned = PhysicalPlanner(mesh_ctx.catalog, mesh_ctx.config).plan_query(
        optimize(mesh_df.logical))
    assert collect_nodes(planned.plan, MeshJoinExec), \
        f"mesh plan missing fused join:\n{planned.plan.display()}"

    got = mesh_df.to_pandas()
    want = file_ctx.sql(sql).to_pandas()
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


@pytest.mark.parametrize("strategy", ["partitioned", "broadcast"])
def test_mesh_semi_join_matches(join_tables, strategy):
    mesh_ctx, file_ctx = join_contexts(join_tables, strategy)
    sql = ("select count(*) as n from fact where fk in (select dk from dim)")
    got = mesh_ctx.sql(sql).to_pandas()
    want = file_ctx.sql(sql).to_pandas()
    pd.testing.assert_frame_equal(got, want, check_dtype=False)

def test_mesh_broadcast_join_metric(join_tables):
    """The size gate actually routes small build sides through the
    all_gather broadcast variant (and the forced-partitioned config does
    not)."""
    from arrow_ballista_tpu.ops.mesh_exec import MeshJoinExec
    from arrow_ballista_tpu.scheduler.physical_planner import PhysicalPlanner
    from arrow_ballista_tpu.scheduler.planner import collect_nodes
    from arrow_ballista_tpu.sql.optimizer import optimize
    from arrow_ballista_tpu.ops.physical import TaskContext

    for strategy, want_broadcast in (("broadcast", 1), ("partitioned", 0)):
        mesh_ctx, _ = join_contexts(join_tables, strategy)
        df = mesh_ctx.sql(JOIN_QUERIES[0])
        planned = PhysicalPlanner(mesh_ctx.catalog, mesh_ctx.config).plan_query(
            optimize(df.logical))
        joins = collect_nodes(planned.plan, MeshJoinExec)
        assert joins
        for p in range(planned.plan.output_partition_count()):
            planned.plan.execute(p, TaskContext(mesh_ctx.config))
        got = joins[0].metrics().values.get("broadcast_joins", 0)
        assert got == want_broadcast, (strategy, got)


# --------------------------------------------------------------------------
# hybrid composition: mesh WITHIN a host, file shuffle ACROSS hosts
# --------------------------------------------------------------------------


def test_mesh_hybrid_plan_shape(table):
    """Hybrid mode keeps the stage pair (file exchange) and meshes only the
    partial: MeshPartialAggregateExec under a hash Repartition under a
    final HashAggregateExec."""
    from arrow_ballista_tpu.ops.mesh_exec import MeshPartialAggregateExec
    from arrow_ballista_tpu.ops.operators import HashAggregateExec
    from arrow_ballista_tpu.scheduler.physical_planner import PhysicalPlanner
    from arrow_ballista_tpu.scheduler.planner import collect_nodes
    from arrow_ballista_tpu.sql.optimizer import optimize

    cfg = BallistaConfig({"ballista.shuffle.mesh": "true",
        "ballista.shuffle.mesh.min_rows": "0",
                          "ballista.shuffle.mesh.hybrid": "true",
                          "ballista.shuffle.partitions": "4"})
    ctx = BallistaContext.local(cfg)
    try:
        ctx.register_table("t", table)
        df = ctx.sql(QUERIES[0])
        planned = PhysicalPlanner(ctx.catalog, ctx.config).plan_query(
            optimize(df.logical))
        partials = collect_nodes(planned.plan, MeshPartialAggregateExec)
        finals = [n for n in collect_nodes(planned.plan, HashAggregateExec)
                  if n.mode == "final"]
        assert partials and finals, planned.plan.display()
        # the partial keeps the input's partitioning (one task per partition)
        assert partials[0].output_partition_count() > 1
    finally:
        ctx.shutdown()


def test_mesh_hybrid_matches_file_shuffle(table):
    """Hybrid path results are identical to the plain file-shuffle path."""
    hybrid_cfg = BallistaConfig({"ballista.shuffle.mesh": "true",
        "ballista.shuffle.mesh.min_rows": "0",
                                 "ballista.shuffle.mesh.hybrid": "true",
                                 "ballista.shuffle.partitions": "4"})
    plain_cfg = BallistaConfig({"ballista.shuffle.partitions": "4"})
    for sql in QUERIES:
        hctx = BallistaContext.local(hybrid_cfg)
        fctx = BallistaContext.local(plain_cfg)
        try:
            hctx.register_table("t", table)
            fctx.register_table("t", table)
            got = hctx.sql(sql).to_pandas()
            want = fctx.sql(sql).to_pandas()
        finally:
            hctx.shutdown()
            fctx.shutdown()
        pd.testing.assert_frame_equal(got, want, check_dtype=False)


def test_mesh_hybrid_nullable_operands():
    """The hybrid partial restores all-NULL groups to sentinels so the
    downstream (cross-host) final aggregate's value-based null check skips
    them — same answers as the file path."""
    rng = np.random.default_rng(5)
    n = 30_000
    g = rng.integers(0, 15, n)
    null_at = (rng.random(n) < 0.4) | (g == 3)
    table = pa.table({
        "g": pa.array(g.astype(np.int64)),
        "v": pa.array([None if m else int(x)
                       for m, x in zip(null_at, rng.integers(-9, 99, n))],
                      type=pa.int64()),
    })
    hybrid_cfg = BallistaConfig({"ballista.shuffle.mesh": "true",
        "ballista.shuffle.mesh.min_rows": "0",
                                 "ballista.shuffle.mesh.hybrid": "true",
                                 "ballista.shuffle.partitions": "4"})
    plain_cfg = BallistaConfig({"ballista.shuffle.partitions": "4"})
    sql = ("select g, sum(v) sv, count(v) cv, min(v) lo, max(v) hi "
           "from t group by g order by g")
    hctx = BallistaContext.local(hybrid_cfg)
    fctx = BallistaContext.local(plain_cfg)
    try:
        hctx.register_table("t", table)
        fctx.register_table("t", table)
        from arrow_ballista_tpu.ops.mesh_exec import MeshPartialAggregateExec
        from arrow_ballista_tpu.scheduler.physical_planner import PhysicalPlanner
        from arrow_ballista_tpu.scheduler.planner import collect_nodes
        from arrow_ballista_tpu.sql.optimizer import optimize

        hdf = hctx.sql(sql)
        planned = PhysicalPlanner(hctx.catalog, hctx.config).plan_query(
            optimize(hdf.logical))
        assert collect_nodes(planned.plan, MeshPartialAggregateExec), \
            f"nullable operands fell off the hybrid path:\n{planned.plan.display()}"
        got = hdf.to_pandas()
        want = fctx.sql(sql).to_pandas()
    finally:
        hctx.shutdown()
        fctx.shutdown()
    pd.testing.assert_frame_equal(got, want, check_dtype=False)
    assert pd.isna(got[got.g == 3].sv.iloc[0]) and got[got.g == 3].cv.iloc[0] == 0


def test_mesh_hybrid_through_network_scheduler(tmp_path, table):
    """The hybrid exchange runs through SchedulerNetService with TWO
    executors: mesh-fused partial tasks execute on different executors and
    their states cross hosts via the file/data-plane shuffle (north star:
    ICI within a host, Flight fallback across hosts)."""
    from arrow_ballista_tpu.executor.server import ExecutorServer
    from arrow_ballista_tpu.scheduler.netservice import SchedulerNetService

    sched = SchedulerNetService("127.0.0.1", 0, rest_port=0)
    sched.start()
    exes = [ExecutorServer("127.0.0.1", sched.port, "127.0.0.1", 0,
                           work_dir=str(tmp_path / f"w{i}"),
                           executor_id=f"hyb-exec-{i}", concurrent_tasks=2)
            for i in range(2)]
    for ex in exes:
        ex.start()
    try:
        cfg = BallistaConfig({"ballista.shuffle.mesh": "true",
        "ballista.shuffle.mesh.min_rows": "0",
                              "ballista.shuffle.mesh.hybrid": "true",
                              "ballista.shuffle.partitions": "4"})
        ctx = BallistaContext.remote("127.0.0.1", sched.port, cfg)
        ctx.register_table("t", table)
        got = ctx.sql(QUERIES[0]).to_pandas()
        ctx.shutdown()

        plain = BallistaContext.local(BallistaConfig())
        plain.register_table("t", table)
        want = plain.sql(QUERIES[0]).to_pandas()
        plain.shutdown()
        pd.testing.assert_frame_equal(got, want, check_dtype=False)
    finally:
        for ex in exes:
            ex.stop(notify=False)
        sched.stop()


def test_mesh_hybrid_join_matches_file_shuffle(join_tables):
    """Hybrid mode: joins keep the partitioned stage structure but each
    task's join fuses over the local mesh (MeshTaskJoinExec) — identical
    results to the plain file path."""
    from arrow_ballista_tpu.ops.mesh_exec import MeshTaskJoinExec
    from arrow_ballista_tpu.scheduler.physical_planner import PhysicalPlanner
    from arrow_ballista_tpu.scheduler.planner import collect_nodes
    from arrow_ballista_tpu.sql.optimizer import optimize

    fact, dim = join_tables
    base = {"ballista.shuffle.partitions": "4",
            "ballista.join.broadcast_threshold": "0"}
    hctx = BallistaContext.local(BallistaConfig({
        **base, "ballista.shuffle.mesh": "true",
        "ballista.shuffle.mesh.min_rows": "0",
        "ballista.shuffle.mesh.hybrid": "true"}))
    fctx = BallistaContext.local(BallistaConfig(base))
    for c in (hctx, fctx):
        c.register_table("fact", fact)
        c.register_table("dim", dim)
    for sql in JOIN_QUERIES:
        df = hctx.sql(sql)
        planned = PhysicalPlanner(hctx.catalog, hctx.config).plan_query(
            optimize(df.logical))
        joins = collect_nodes(planned.plan, MeshTaskJoinExec)
        assert joins, f"hybrid plan missing task-mesh join:\n{planned.plan.display()}"
        got = df.to_pandas()
        want = fctx.sql(sql).to_pandas()
        pd.testing.assert_frame_equal(got, want, check_dtype=False)


def test_mesh_hybrid_join_through_standalone_cluster(join_tables):
    """The task-mesh join ships over the wire (serde) and runs as N
    partition tasks through the real scheduler."""
    fact, dim = join_tables
    cfg = BallistaConfig({"ballista.shuffle.partitions": "4",
                          "ballista.join.broadcast_threshold": "0",
                          "ballista.shuffle.mesh": "true",
        "ballista.shuffle.mesh.min_rows": "0",
                          "ballista.shuffle.mesh.hybrid": "true"})
    ctx = BallistaContext.standalone(cfg, concurrent_tasks=4)
    try:
        ctx.register_table("fact", fact)
        ctx.register_table("dim", dim)
        got = ctx.sql(JOIN_QUERIES[0]).to_pandas()
    finally:
        ctx.shutdown()
    pdf = fact.to_pandas().merge(dim.to_pandas(), left_on="fk", right_on="dk")
    want = pdf.groupby("name").agg(sv=("val", "sum"), n=("val", "size")).reset_index()
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


def test_mesh_task_join_serde_roundtrip(join_tables):
    """MeshTaskJoinExec survives the wire encoding."""
    from arrow_ballista_tpu import serde
    from arrow_ballista_tpu.ops.mesh_exec import MeshTaskJoinExec
    from arrow_ballista_tpu.scheduler.physical_planner import PhysicalPlanner
    from arrow_ballista_tpu.scheduler.planner import collect_nodes
    from arrow_ballista_tpu.sql.optimizer import optimize

    fact, dim = join_tables
    ctx = BallistaContext.local(BallistaConfig({
        "ballista.shuffle.partitions": "4",
        "ballista.join.broadcast_threshold": "0",
        "ballista.shuffle.mesh": "true",
        "ballista.shuffle.mesh.min_rows": "0",
        "ballista.shuffle.mesh.hybrid": "true"}))
    ctx.register_table("fact", fact)
    ctx.register_table("dim", dim)
    planned = PhysicalPlanner(ctx.catalog, ctx.config).plan_query(
        optimize(ctx.sql(JOIN_QUERIES[0]).logical))
    obj = serde.plan_to_obj(planned.plan)
    back = serde.plan_from_obj(obj)
    assert collect_nodes(back, MeshTaskJoinExec)
    assert back.display() == planned.plan.display()


def test_adaptive_transport_gate(tmp_path):
    """VERDICT r4 #5: mesh vs file is chosen per exchange from row
    estimates — small exchanges stay on the materialized file path even
    with mesh enabled; min_rows=0 forces mesh (operator/test override)."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from arrow_ballista_tpu.client.context import BallistaContext
    from arrow_ballista_tpu.utils.config import BallistaConfig

    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({
        "k": np.arange(4000, dtype=np.int64) % 50,
        "v": np.arange(4000, dtype=np.int64),
    }), path, row_group_size=1000)  # 4 row groups -> multi-partition scan

    def physical_plan(cfg):
        ctx = BallistaContext.local(BallistaConfig(cfg))
        ctx.register_parquet("t", path)
        df = ctx.sql("explain select k, sum(v) from t group by k").to_pandas()
        return df[df.plan_type == "physical_plan"].plan.iloc[0]

    gated = physical_plan({"ballista.shuffle.mesh": "true",
                           "ballista.shuffle.partitions": "4",
                           "ballista.shuffle.mesh.min_rows": "1000000"})
    assert "MeshAggregate" not in gated  # 4000-row table: file path
    forced = physical_plan({"ballista.shuffle.mesh": "true",
                            "ballista.shuffle.partitions": "4",
                            "ballista.shuffle.mesh.min_rows": "0"})
    assert "MeshAggregate" in forced
    small_floor = physical_plan({"ballista.shuffle.mesh": "true",
                                 "ballista.shuffle.partitions": "4",
                                 "ballista.shuffle.mesh.min_rows": "100"})
    assert "MeshAggregate" in small_floor  # estimate clears the gate
