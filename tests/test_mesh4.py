"""The deployment ``tpch_sf10_mesh4`` (benchmarks/chip/configs), small, on
the CPU's virtual devices: what the planner chooses at the configuration's
own settings, q1 and q6 over the device mesh against the benchmark's plain
reference and against the one-chip path, the shards' states adding up to the
whole, and what a mesh program leaves in the names, counters and spans."""
import json
import os
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import jax
import jax.numpy as jnp

from arrow_ballista_tpu.catalog import ParquetTable
from arrow_ballista_tpu.client.context import BallistaContext
from arrow_ballista_tpu.obs import device as device_obs
from arrow_ballista_tpu.obs.tracing import RING
from arrow_ballista_tpu.ops import kernels as K
from arrow_ballista_tpu.ops import mesh_exec
from arrow_ballista_tpu.ops.operators import HashAggregateExec
from arrow_ballista_tpu.parallel import distributed
from arrow_ballista_tpu.parallel.mesh import make_mesh
from arrow_ballista_tpu.scheduler.physical_planner import PhysicalPlanner
from arrow_ballista_tpu.scheduler.planner import collect_nodes
from arrow_ballista_tpu.sql.optimizer import optimize
from arrow_ballista_tpu.utils.config import BallistaConfig
from benchmarks.chip import compare, datagen
from benchmarks.chip.oracles import q1 as oracle_q1, q6 as oracle_q6

ROOT_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT_DIR, "benchmarks", "chip")
MESH_OPS = (mesh_exec.MeshAggregateExec, mesh_exec.MeshPartialAggregateExec,
            mesh_exec.MeshJoinExec)
SEED = 2147493650


def _settings(**over) -> dict:
    """The configuration's own settings, and what a test changes of them."""
    with open(os.path.join(CHIP, "configs", "tpch_sf10_mesh4.json")) as fh:
        return {**json.load(fh)["settings"], **over}


def _sql(q: str) -> str:
    with open(os.path.join(CHIP, "queries", f"{q}.sql")) as fh:
        return fh.read()


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    """Seeded lineitem at SF0.01 in seven row groups, so that a scan has
    several partitions; cut to 60 051 rows, a multiple of no device
    count."""
    ddir = str(tmp_path_factory.mktemp("mesh4"))
    table = datagen.generate_tables(0.01, SEED, ["lineitem"])["lineitem"]
    table = table.slice(0, 60_051)
    pq.write_table(table, os.path.join(ddir, "lineitem.parquet"),
                   compression="zstd", row_group_size=9000)
    return ddir


def _devices(monkeypatch, n: int) -> None:
    """The process sees ``n`` of the eight virtual devices: the mesh
    operators take every device jax reports."""
    every = jax.devices()
    monkeypatch.setattr(jax, "devices", lambda *a, **k: every[:n])


# --- the planner's choice at defaults -------------------------------------

@pytest.mark.parametrize("reported, on_mesh", [(60_004_710, True),
                                               (6_000_000, False)])
def test_planner_choice_at_the_configurations_settings(data, reported,
                                                       on_mesh):
    """A lineitem that reports SF10's rows passes the gate (a quarter of
    them, 15.0M, over 8M) and q1 becomes a MeshAggregateExec; q6 has no
    keys and stays the plain keyless aggregate; SF1's rows pass nothing."""
    ctx = BallistaContext.local(BallistaConfig(_settings()))
    table = ParquetTable("lineitem", os.path.join(data, "lineitem.parquet"))
    table._rows = reported          # what row_count() reports
    ctx.catalog.register(table)
    plans = {}
    for q in ("q1", "q6"):
        planned = PhysicalPlanner(ctx.catalog, ctx.config).plan_query(
            optimize(ctx.sql(_sql(q)).logical))
        plans[q] = planned.plan
    mesh_q1 = collect_nodes(plans["q1"], mesh_exec.MeshAggregateExec)
    assert bool(mesh_q1) == on_mesh, plans["q1"].display()
    if on_mesh:
        assert mesh_q1[0].input.output_partition_count() == 4
        assert mesh_q1[0].output_partition_count() == 1
    assert not any(collect_nodes(plans["q6"], op) for op in MESH_OPS), \
        plans["q6"].display()
    aggs = collect_nodes(plans["q6"], HashAggregateExec)
    assert aggs and all(not a.group_exprs for a in aggs)


# --- answers ----------------------------------------------------------------

def _answers(data, settings: dict) -> dict:
    ctx = BallistaContext.standalone(BallistaConfig(settings),
                                     concurrent_tasks=4, num_executors=1)
    try:
        ctx.register_parquet("lineitem",
                             os.path.join(data, "lineitem.parquet"))
        return {q: compare.table_rows(ctx.sql(_sql(q)).to_arrow())
                for q in ("q1", "q6")}
    finally:
        ctx.shutdown()


@pytest.fixture(scope="module")
def one_chip_answers(data):
    return _answers(data, _settings(**{"ballista.shuffle.mesh": "false"}))


@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_mesh_answers_equal_the_reference_and_the_one_chip_path(
        data, one_chip_answers, monkeypatch, n_dev):
    """The configuration's settings with only ``min_rows`` lowered, so that
    small data engages the mesh: exact against the plain reference (the
    three ``avg`` to 1e-9) and row for row what the one-chip path gives."""
    _devices(monkeypatch, n_dev)
    s0 = device_obs.STATS.snapshot()
    got = _answers(data, _settings(
        **{"ballista.shuffle.mesh.min_rows": "0"}))
    s1 = device_obs.STATS.snapshot()
    assert s1["mesh_programs"] - s0["mesh_programs"] == 1      # q1, not q6
    for q, oracle in (("q1", oracle_q1), ("q6", oracle_q6)):
        fault, gap = compare.compare(got[q], oracle.answer(data))
        assert fault is None, fault
        assert gap is None or gap <= 1e-9
        assert got[q] == one_chip_answers[q]
    devices = {s.attrs["devices"] for s in RING.snapshot()
               if s.name == "mesh_reshard"}
    assert n_dev in devices


# --- the parts add up -------------------------------------------------------

@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_shards_dense_states_add_up_to_the_whole(n_dev):
    """Over a row count no device count divides: the dense states of each
    device's shard (padded rows masked off), merged on the host as the
    collective merges them, are the one-device states of the whole batch,
    and the mesh program's answer is their compaction."""
    rng = np.random.default_rng(n_dev)
    rows = 10_007
    cols = {"k0": rng.integers(-1, 3, rows).astype(np.int32),
            "k1": rng.integers(0, 2, rows).astype(np.int32),
            "v": rng.integers(-10**9, 10**9, rows).astype(np.int64),
            "w": rng.integers(1, 50, rows).astype(np.int64)}
    mask = rng.random(rows) < 0.9
    key_ranges = ((-1, 2), (0, 1))
    domain = K.dense_domain(key_ranges)
    aggs = [("v", "sum"), ("w", "count"), ("v", "min"), ("w", "max")]

    def states(c, m):
        vals, exists, bad = K.dense_group_states(
            [jnp.asarray(c["k0"]), jnp.asarray(c["k1"])],
            [(jnp.asarray(c[name]), how) for name, how in aggs],
            jnp.asarray(m), key_ranges, domain)
        assert not bool(bad)
        return [np.asarray(v) for v in vals], np.asarray(exists)

    whole, whole_exists = states(cols, mask)
    mesh = make_mesh(n_dev)
    dcols, dmask, padded = mesh_exec._shard_rows(
        {k: jnp.asarray(v) for k, v in cols.items()}, jnp.asarray(mask),
        mesh, n_dev)
    assert padded % n_dev == 0 and padded - rows < n_dev
    per = padded // n_dev
    parts = [states({k: np.asarray(v)[d * per:(d + 1) * per]
                     for k, v in dcols.items()},
                    np.asarray(dmask)[d * per:(d + 1) * per])
             for d in range(n_dev)]
    merge = {"sum": np.sum, "count": np.sum, "min": np.min, "max": np.max}
    for i, (_, how) in enumerate(aggs):
        merged = merge[how](np.stack([p[0][i] for p in parts]), axis=0)
        np.testing.assert_array_equal(merged, whole[i])
    np.testing.assert_array_equal(
        np.sum(np.stack([p[1] for p in parts]), axis=0), whole_exists)

    prog = distributed.distributed_dense_aggregate(
        mesh, lambda c, m: (c, m), ["k0", "k1"], aggs, key_ranges, domain)
    fk, fv, fmask, overflow = prog(dcols, dmask)
    assert not bool(overflow)
    live = np.asarray(fmask)
    order = np.flatnonzero(whole_exists > 0)
    for i in range(len(aggs)):
        np.testing.assert_array_equal(np.asarray(fv[i])[live],
                                      whole[i][order])
    assert prog.collective == "dense_reduce"
    widths = sum(v.dtype.itemsize for v in whole) + whole_exists.itemsize
    assert prog.collective_bytes(dcols, dmask) == n_dev * domain * widths


# --- names, counters, spans ---------------------------------------------------

_NAMES_SCRIPT = """
import json, sys
sys.path.insert(0, sys.argv[1])
from arrow_ballista_tpu.parallel import distributed as D
from arrow_ballista_tpu.parallel.mesh import make_mesh
mesh = make_mesh(2)
ident = lambda c, m, *aux: (c, m)
print("NAMES " + json.dumps([
    D.distributed_dense_aggregate(mesh, ident, ["a", "b"], [("v", "sum")],
                                  ((0, 1), (0, 2)), 6).name,
    D.distributed_filter_aggregate(mesh, ident, ["a"], [("v", "sum")],
                                   64, 64).name,
    D.distributed_partial_aggregate(mesh, ident, ["a", "b", "c"],
                                    [("v", "sum")], 64).name,
    D.distributed_hash_join(mesh, 1, ["x"], ["y"], "inner", 64, 64, 64,
                            {}).name,
    D.distributed_broadcast_join(mesh, 1, ["x"], ["y"], "semi", 64,
                                 {}).name]))
"""


def test_mesh_program_names_are_program_names_in_every_interpreter():
    want = [device_obs.program_name("mesh.agg_dense", "k2"),
            device_obs.program_name("mesh.agg_exchange", "k1"),
            device_obs.program_name("mesh.agg_partial", "k3"),
            device_obs.program_name("mesh.join_partitioned", "inner"),
            device_obs.program_name("mesh.join_broadcast", "semi")]
    assert want[0] == "mesh_agg_dense__k2"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED="random")
    run = subprocess.run([sys.executable, "-c", _NAMES_SCRIPT, ROOT_DIR],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=ROOT_DIR)
    assert run.returncode == 0, run.stderr[-2000:]
    names = json.loads(next(line for line in run.stdout.splitlines()
                            if line.startswith("NAMES "))[6:])
    assert names == want


def _ancestors(span, by_id):
    while span.parent_id in by_id:
        span = by_id[span.parent_id]
        yield span


def test_a_mesh_query_counts_its_compile_once_and_leaves_spans():
    """A cold run of a plan shape no other test has counts the mesh
    program's compile and opens its ``compile`` span; the warm run, a new
    job, counts none.  ``mesh_reshard_bytes`` is the bytes of the sharded
    columns and mask; both spans lie under the task's span, with their
    attributes."""
    n = 30_011
    rng = np.random.default_rng(28)
    table = pa.table({
        "flag": pa.array(rng.choice(["A", "N", "R"], n)),
        "cents_28": pa.array(rng.integers(0, 10**7, n).astype(np.int64)),
        "qty_28": pa.array(rng.integers(1, 50, n).astype(np.int32))})
    ctx = BallistaContext.standalone(BallistaConfig(_settings(
        **{"ballista.shuffle.mesh.min_rows": "0"})), concurrent_tasks=4)
    ctx.register_table("t28", table)
    sql = ("select flag, sum(cents_28) as s, max(qty_28) as hi, count(*) as n "
           "from t28 group by flag order by flag")
    name = device_obs.program_name("mesh.agg_dense", "k1")
    try:
        deltas = []
        for _ in range(2):
            RING.clear()
            s0 = device_obs.STATS.snapshot()
            got = ctx.sql(sql).to_pandas()
            s1 = device_obs.STATS.snapshot()
            deltas.append(({k: s1[k] - s0[k] for k in s0},
                           RING.snapshot()))
    finally:
        ctx.shutdown()
    pdf = table.to_pandas()
    want = pdf.groupby("flag").agg(s=("cents_28", "sum"),
                                   hi=("qty_28", "max"),
                                   n=("flag", "size")).reset_index()
    assert got["flag"].tolist() == want["flag"].tolist()
    assert got["s"].astype(np.int64).tolist() == want["s"].tolist()
    assert got["n"].tolist() == want["n"].tolist()

    (cold, cold_spans), (warm, warm_spans) = deltas
    compiles = [s for s in cold_spans if s.name == f"compile {name}"]
    assert len(compiles) == 1 and cold["jit_compiles"] >= 1
    assert not [s for s in warm_spans if s.name.startswith("compile")]
    assert warm["jit_compiles"] == 0 and warm["jit_retraces"] == 0
    for counters, spans in deltas:
        assert counters["mesh_programs"] == 1
        by_id = {s.span_id: s for s in spans}
        reshard = [s for s in spans if s.name == "mesh_reshard"]
        program = [s for s in spans if s.name == "mesh_program"]
        assert len(reshard) == 1 and len(program) == 1
        # flag codes int32, cents int64, qty int32, and the mask
        assert reshard[0].attrs["rows"] % 8 == 0
        assert reshard[0].attrs["bytes"] == reshard[0].attrs["rows"] * 17
        assert reshard[0].attrs["devices"] == 8
        assert counters["mesh_reshard_bytes"] == reshard[0].attrs["bytes"]
        assert program[0].attrs == {**program[0].attrs, "program": name,
                                    "collective": "dense_reduce"}
        # 3 + 1 slots of two int64 sums... whatever the width, 8 devices'
        assert counters["mesh_collective_bytes"] > 0
        assert counters["mesh_collective_bytes"] % 8 == 0
        for s in (reshard[0], program[0]):
            up = list(_ancestors(s, by_id))
            assert up[0].name == "MeshAggregateExec"
            assert any(a.kind == "executor" for a in up)
        waits = [s for s in spans if s.name == "device_wait"
                 and s.parent_id == program[0].span_id]
        assert len(waits) == 1 and waits[0].attrs["site"] == "scalar"
        assert reshard[0].end_ns <= program[0].start_ns
    cold_compile = compiles[0]
    assert cold_compile.parent_id in {
        s.span_id for s in cold_spans if s.name == "mesh_program"}


# --- the limb recombination the cell's first runs found at fault --------------

def _exact(parts: np.ndarray) -> np.ndarray:
    """Python integers: sum over chunks of limb sums << 16 i, mod 2^64."""
    out = np.zeros(parts.shape[2:], np.int64).reshape(-1)
    flat = parts.reshape(parts.shape[0], 4, -1)
    for k in range(flat.shape[2]):
        v = sum(int(flat[:, i, k].astype(np.int64).sum()) << (16 * i)
                for i in range(4)) % (1 << 64)
        out[k] = v - (1 << 64) if v >= 1 << 63 else v
    return out.reshape(parts.shape[2:])


@pytest.mark.parametrize("case", ["pr28", "bounds", "random"])
def test_limb_sums_recombine_exactly_in_32_bit_arithmetic(case):
    """``kernels._recombine_chunk_limbs``: the limb sums the v5e compiler
    recombined wrongly in int64 (PR 28, q1's sum_disc_price of group N/O:
    0xc130694c, 0x1e8effec, 0, 0), the largest chunk sums over the most
    chunks a call may make, and random ones whose total wraps past 2^63."""
    rng = np.random.default_rng(7)
    if case == "pr28":
        parts = np.zeros((2, 4, 3), np.int32)
        parts[:, 0, 1] = [0x6130694C, 0x60000000]
        parts[:, 1, 1] = [0x1E8EFFEC, 0]
    elif case == "bounds":
        parts = np.full((K._MAX_CHUNKS, 4, 2), 2 ** 31 - 1, np.int32)
        parts[:, :, 1] = 65535 * K._SEG_CHUNK
    else:
        parts = rng.integers(0, 2 ** 31, (458, 4, 9, 13)).astype(np.int32)
    got = np.asarray(jax.jit(K._recombine_chunk_limbs)(jnp.asarray(parts)))
    np.testing.assert_array_equal(got, _exact(parts))
    if case == "pr28":
        assert int(got[1]) == 0x1E8FC11C694C


@pytest.mark.parametrize("segments", [13, 4 * K._MATMUL_SEG_LIMIT])
def test_grouped_sums_on_the_chips_branch_equal_plain_segment_sums(
        monkeypatch, segments):
    """Both formulations of the chip's branch (one-hot matmul, chunk-offset
    segment sums), steered onto it as tests/test_kernels.py does, over rows
    that fill no whole chunk and values of either sign up to 2^62."""
    K._tpu_backend.cache_clear()
    monkeypatch.setattr(K, "_tpu_backend", lambda: True)
    rng = np.random.default_rng(segments)
    n = 3 * K._SEG_CHUNK + 1234
    seg = rng.integers(0, segments, n).astype(np.int32)
    vals = [rng.integers(-2 ** 62, 2 ** 62, n).astype(np.int64),
            rng.integers(0, 10 ** 9, n).astype(np.int64),
            np.full(n, -1, np.int64)]
    got = K.grouped_sums_i64([jnp.asarray(v) for v in vals],
                             jnp.asarray(seg), segments)
    for v, g in zip(vals, got):
        want = np.zeros(segments, np.int64)
        np.add.at(want, seg, v)
        np.testing.assert_array_equal(np.asarray(g), want)
    monkeypatch.undo()
    K._tpu_backend.cache_clear()
