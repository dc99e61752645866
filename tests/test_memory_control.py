"""Memory control: bounded-footprint execution under a per-task budget.

The reference's answer to memory pressure is reactive spill
(reference ballista/core/src/utils.rs:176-212 write_stream_to_disk);
a static-shape TPU engine cannot realloc or spill mid-kernel, so the
budget (``ballista.memory.task.budget.bytes``) is enforced BEFORE
allocation: joins run their probe loop in bounded windows, and 'auto'
shuffle partition counts scale so planned task inputs fit.  Disk-tier
state remains the shuffle's IPC files (the same role the reference's
shuffle files play as data checkpoints).
"""
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from arrow_ballista_tpu import BallistaConfig, Field, INT64, Schema
from arrow_ballista_tpu.models import expr as E
from arrow_ballista_tpu.ops.operators import JoinExec
from arrow_ballista_tpu.ops.physical import MemoryScanExec, TaskContext
from arrow_ballista_tpu.utils.config import (
    MEM_TASK_BUDGET,
    resolve_task_budget,
)

SCHEMA_F = Schema([Field("k", INT64), Field("v", INT64)])
SCHEMA_D = Schema([Field("dk", INT64), Field("w", INT64)])


def _tables(n_fact=30_000, n_dim=500, dup=3, seed=11):
    rng = np.random.default_rng(seed)
    fact = pa.table({
        "k": rng.integers(0, n_dim * 2, n_fact).astype(np.int64),
        "v": rng.integers(0, 1000, n_fact).astype(np.int64),
    })
    # duplicate dim keys -> fan-out > 1 so expansion buffers matter
    dk = np.repeat(np.arange(n_dim, dtype=np.int64), dup)
    dim = pa.table({
        "dk": dk,
        "w": np.arange(len(dk), dtype=np.int64),
    })
    return fact, dim


def _join(join_type, budget=None):
    fact, dim = _tables()
    left = MemoryScanExec(SCHEMA_F, fact, 1)
    right = MemoryScanExec(SCHEMA_D, dim, 1)
    dist = "partitioned" if join_type == "full" else "broadcast"
    j = JoinExec(left, right, [(E.Column("k"), E.Column("dk"))],
                 join_type=join_type, dist=dist)
    cfg = {} if budget is None else {MEM_TASK_BUDGET: str(budget)}
    ctx = TaskContext(config=BallistaConfig(cfg), job_id="jmem")
    batches = j.execute(0, ctx)
    frames = [b.to_pandas() for b in batches if b.num_rows]
    df = pd.concat(frames, ignore_index=True) if frames else pd.DataFrame()
    return j, df


@pytest.mark.parametrize("join_type", ["inner", "semi", "anti"])
def test_chunked_join_matches_single_pass(join_type):
    _, unlimited = _join(join_type)
    j, budgeted = _join(join_type, budget=200_000)  # ~0.2 MB forces windows
    chunks = j.metrics().to_dict().get("join_probe_chunks", 0)
    assert chunks > 1, "budget did not engage the windowed probe loop"
    sort_cols = list(unlimited.columns)
    a = unlimited.sort_values(sort_cols).reset_index(drop=True)
    b = budgeted.sort_values(sort_cols).reset_index(drop=True)
    pd.testing.assert_frame_equal(a, b)


@pytest.mark.parametrize("join_type", ["full", "left"])
def test_outer_joins_keep_single_pass(join_type):
    """full: unmatched-build needs all-probe hit accumulation; left: the
    miss-append block is probe-capacity-sized per window, so windowing
    would multiply memory instead of bounding it."""
    _, unlimited = _join(join_type)
    j, budgeted = _join(join_type, budget=200_000)
    assert j.metrics().to_dict().get("join_probe_chunks", 0) == 0
    sort_cols = list(unlimited.columns)
    pd.testing.assert_frame_equal(
        unlimited.sort_values(sort_cols).reset_index(drop=True),
        budgeted.sort_values(sort_cols).reset_index(drop=True))


def _lookups(j):
    m = j.metrics().to_dict()
    return m.get("range_lookups", 0), m.get("probe_rows_searched", 0)


@pytest.mark.parametrize("join_type",
                         ["inner", "left", "full", "semi", "anti"])
def test_one_range_lookup_per_probe_batch(join_type):
    """The build's hashes are searched once per probe batch joined, over
    the probe's capacity: 30 000 rows in one batch of 32 768 slots."""
    j, _ = _join(join_type)
    assert _lookups(j) == (1, 32768)


@pytest.mark.parametrize("join_type", ["inner", "semi", "anti"])
def test_chunked_join_searches_the_build_once(join_type):
    """Every window takes its rows' ranges from the one lookup made for the
    whole probe; none searches the build again."""
    j, _ = _join(join_type, budget=200_000)
    assert j.metrics().to_dict()["join_probe_chunks"] > 1
    assert _lookups(j) == (1, 32768)


@pytest.mark.parametrize("join_type", ["inner", "semi", "anti"])
def test_spilled_join_searches_once_per_rehydrated_partition(join_type,
                                                             tmp_path):
    from arrow_ballista_tpu.memory.governor import MemoryGovernor

    fact, dim = _tables()
    j = JoinExec(MemoryScanExec(SCHEMA_F, fact, 1),
                 MemoryScanExec(SCHEMA_D, dim, 1),
                 [(E.Column("k"), E.Column("dk"))],
                 join_type=join_type, dist="partitioned")
    ctx = TaskContext(config=BallistaConfig(), job_id="jspill",
                      work_dir=str(tmp_path),
                      governor=MemoryGovernor(host_budget=64))
    j.execute(0, ctx)
    # one build batch, so one spill run per non-empty hash-range partition
    parts = j.metrics().to_dict()["spill_runs"]
    assert 1 < parts <= JoinExec._SPILL_PARTS
    assert _lookups(j) == (parts, parts * 32768)


@pytest.mark.parametrize("join_type",
                         ["inner", "left", "full", "semi", "anti"])
def test_join_probe_takes_no_sorted_hash_operand(join_type):
    """``join.probe`` starts from the lookup's lo/counts: no uint64 array
    (the sorted hashes are the join's only ones) is among its operands."""
    import jax

    fact, dim = _tables()
    left = MemoryScanExec(SCHEMA_F, fact, 1)
    right = MemoryScanExec(SCHEMA_D, dim, 1)
    dist = "partitioned" if join_type == "full" else "broadcast"
    j = JoinExec(left, right, [(E.Column("k"), E.Column("dk"))],
                 join_type=join_type, dist=dist)
    ctx = TaskContext(config=BallistaConfig(), job_id="jops")
    j._ensure_compiled(ctx, SCHEMA_F, SCHEMA_D)
    compiled = list(j._compiled)
    probe_program, seen = compiled[3], []
    assert probe_program.name == "join_probe"

    def spy(*args):
        seen.append([str(leaf.dtype) for leaf in jax.tree.leaves(args[:-1])])
        return probe_program(*args)

    compiled[3] = spy
    j._compiled = tuple(compiled)
    j.execute(0, ctx)
    assert len(seen) == 1 and "uint64" not in seen[0]
    assert seen[0].count("int32") == 2  # lo, counts


def test_budget_resolution():
    assert resolve_task_budget(BallistaConfig({MEM_TASK_BUDGET: "0"})) == 0
    assert resolve_task_budget(BallistaConfig({MEM_TASK_BUDGET: "1048576"})) == 1 << 20
    # auto on the CPU test backend: unlimited
    assert resolve_task_budget(BallistaConfig()) == 0


def test_auto_partitions_scale_with_budget():
    """A 100M-row x 17-byte table under a 64 MB task budget needs ~27
    partitions more than the 64-cap would ever grant at batch=16M."""
    from arrow_ballista_tpu.catalog import SchemaCatalog, TableProvider
    from arrow_ballista_tpu.models import logical as L
    from arrow_ballista_tpu.scheduler.physical_planner import PhysicalPlanner

    class BigTable(TableProvider):
        name = "big"
        schema = SCHEMA_F

        def scan(self, projection, filters, target_partitions):
            raise NotImplementedError

        def row_count(self):
            return 100_000_000

    cat = SchemaCatalog()
    cat.register(BigTable())
    scan = L.TableScan("big", SCHEMA_F)
    base_cfg = BallistaConfig({"ballista.shuffle.partitions": "auto",
                               "ballista.batch.size": str(1 << 24)})
    p = PhysicalPlanner(cat, base_cfg)
    p._resolve_auto_partitions(scan)
    unbounded = p.partitions
    assert unbounded <= 64
    cfg = BallistaConfig({"ballista.shuffle.partitions": "auto",
                          "ballista.batch.size": str(1 << 24),
                          MEM_TASK_BUDGET: str(64 << 20)})
    p2 = PhysicalPlanner(cat, cfg)
    p2._resolve_auto_partitions(scan)
    assert p2.partitions > unbounded
    assert p2.partitions <= 256
    # a task's planned input now fits the budget
    assert 100_000_000 * 17 / p2.partitions <= (64 << 20)


def test_q9_class_query_under_capped_budget(tmp_path):
    """VERDICT r4 #6 done-criterion (scaled): a multi-join + group-by
    (q9-shaped) completes under an artificially capped memory budget and
    matches the unlimited run."""
    import pyarrow.parquet as pq

    from arrow_ballista_tpu.client.context import BallistaContext

    rng = np.random.default_rng(23)
    n = 60_000
    pq.write_table(pa.table({
        "pk": rng.integers(0, 2000, n).astype(np.int64),
        "sk": rng.integers(0, 100, n).astype(np.int64),
        "qty": rng.integers(1, 50, n).astype(np.int64),
    }), str(tmp_path / "li.parquet"), row_group_size=10_000)
    pq.write_table(pa.table({
        "pk": np.arange(2000, dtype=np.int64),
        "grp": np.array(["g%d" % (i % 12) for i in range(2000)]),
    }), str(tmp_path / "part.parquet"))
    pq.write_table(pa.table({
        "sk": np.arange(100, dtype=np.int64),
        "nat": np.array(["n%d" % (i % 7) for i in range(100)]),
    }), str(tmp_path / "supp.parquet"))

    q = ("select p.grp, s.nat, count(*) as n, sum(l.qty) as q "
         "from li l join part p on l.pk = p.pk "
         "join supp s on l.sk = s.sk "
         "group by p.grp, s.nat order by p.grp, s.nat")

    def run(budget):
        cfg = {"ballista.shuffle.partitions": "4",
               "ballista.join.broadcast_threshold": "10"}  # force partitioned
        if budget:
            cfg[MEM_TASK_BUDGET] = str(budget)
        ctx = BallistaContext.standalone(BallistaConfig(cfg),
                                         concurrent_tasks=2)
        ctx.register_parquet("li", str(tmp_path / "li.parquet"))
        ctx.register_parquet("part", str(tmp_path / "part.parquet"))
        ctx.register_parquet("supp", str(tmp_path / "supp.parquet"))
        out = ctx.sql(q).to_pandas()
        ctx.shutdown()
        return out

    unlimited = run(None)
    capped = run(300_000)
    pd.testing.assert_frame_equal(unlimited, capped)
