"""The deployment ``tpch_sf10_mesh4_joins`` (benchmarks/chip/configs),
small, on four of the CPU's virtual devices: q4 and q12 planned as
``MeshJoinExec`` at SF10's row counts, both through the partitioned program
(both sides' ``all_to_all``) against the benchmark's plain references, the
join's bounds taken from each side's live rows and not its slots, keys that
all hash to one device (a send bucket overflows: one re-run at the need the
program reports), the programs' ``stats`` against numpy, the deployment's
own check, the two new readers, and the comparison catching a wrong answer
in the new mix."""
import json
import os
import time

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp

from arrow_ballista_tpu.catalog import ParquetTable
from arrow_ballista_tpu.client.context import BallistaContext
from arrow_ballista_tpu.models import expr as E
from arrow_ballista_tpu.models.batch import ColumnBatch
from arrow_ballista_tpu.models.schema import INT64, Field, Schema
from arrow_ballista_tpu.obs import device as device_obs
from arrow_ballista_tpu.obs.tracing import RING, ROOT, span
from arrow_ballista_tpu.ops import kernels as K
from arrow_ballista_tpu.ops import mesh_exec
from arrow_ballista_tpu.ops.physical import MemoryScanExec, TaskContext
from arrow_ballista_tpu.parallel import distributed
from arrow_ballista_tpu.parallel.mesh import make_mesh, row_sharding
from arrow_ballista_tpu.scheduler.physical_planner import PhysicalPlanner
from arrow_ballista_tpu.scheduler.planner import collect_nodes
from arrow_ballista_tpu.sql.optimizer import optimize
from arrow_ballista_tpu.utils.config import BallistaConfig
from benchmarks.chip import compare, datagen, run
from benchmarks.chip.deployments import mesh4, mesh4_join
from benchmarks.chip.oracles import _common
from benchmarks.chip.oracles import q4 as oracle_q4, q12 as oracle_q12
from benchmarks.chip.readers import mesh_join_roofline, mesh_join_spans

ROOT_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT_DIR, "benchmarks", "chip")
CELL = "sf10_mesh4_joins"
SEED = 2147494012
SCALE = 0.05        # 75 000 orders, 300 889 lineitem rows
ORACLES = {"q4": oracle_q4, "q12": oracle_q12}


def _settings(**over) -> dict:
    """The configuration's own settings, and what a test changes of them."""
    with open(os.path.join(CHIP, "configs",
                           "tpch_sf10_mesh4_joins.json")) as fh:
        return {**json.load(fh)["settings"], **over}


# what makes small data take the partitioned program: the mesh's gate, the
# plain broadcast join's threshold and the mesh's all_gather choice at 0
PARTITIONED = {"ballista.shuffle.mesh.min_rows": "0",
               "ballista.join.broadcast_threshold": "0",
               "ballista.shuffle.mesh.broadcast_rows": "0"}


def _sql(q: str) -> str:
    with open(os.path.join(CHIP, "queries", f"{q}.sql")) as fh:
        return fh.read()


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    ddir = str(tmp_path_factory.mktemp("mesh_joins"))
    return datagen.write_data(ddir, SCALE, SEED, ["lineitem", "orders"])


@pytest.fixture
def four_devices(monkeypatch):
    """The process sees four of the eight virtual devices: the mesh
    operators take every device jax reports."""
    every = jax.devices()
    monkeypatch.setattr(jax, "devices", lambda *a, **k: every[:4])


def _standalone(settings: dict, tables: dict):
    ctx = BallistaContext.standalone(BallistaConfig(settings),
                                     concurrent_tasks=4, num_executors=1)
    for name, source in tables.items():
        if isinstance(source, str):
            ctx.register_parquet(name, source)
        else:
            ctx.register_table(name, source)
    return ctx


def _join_metrics(report: dict) -> list:
    return [op["metrics"] for stage in report["stages"]
            for op in stage["operator_tree"] if op["op"] == "MeshJoinExec"]


def _join_spans(since: int) -> list:
    return [s for s in RING.snapshot()
            if s.name == "mesh_program" and s.start_ns >= since
            and str(s.attrs.get("program", "")).startswith("mesh_join")]


# --- (a) the planner at SF10's rows ---------------------------------------

@pytest.mark.parametrize("q, join_type", [("q4", "semi"), ("q12", "inner")])
def test_both_statements_plan_the_mesh_join_at_sf10_rows(data, q, join_type):
    """At the configuration's own settings, tables that report SF10's rows
    put both sides of each join over the 4M-row broadcast threshold and the
    exchange over the 8M-row gate: the join is a MeshJoinExec, orders its
    probe side and lineitem its build side."""
    ctx = BallistaContext.local(BallistaConfig(_settings()))
    for table, rows in (("lineitem", 60_004_710), ("orders", 15_000_000)):
        t = ParquetTable(table, os.path.join(data, f"{table}.parquet"))
        t._rows = rows              # what row_count() reports
        ctx.catalog.register(t)
    plan = PhysicalPlanner(ctx.catalog, ctx.config).plan_query(
        optimize(ctx.sql(_sql(q)).logical)).plan
    (join,) = collect_nodes(plan, mesh_exec.MeshJoinExec)
    assert join.join_type == join_type
    assert join.left.schema.names()[0] == "orders.o_orderkey"
    assert join.right.schema.names()[0] == "lineitem.l_orderkey"


# --- (b) both statements through the partitioned program ------------------

def _live_rows(ddir: str, q: str):
    """Each side's rows that reach the join, and the rows it gives, by
    pandas."""
    o = _common.load(ddir, "orders", ["o_orderkey", "o_orderdate"])
    li = _common.load(ddir, "lineitem", [
        "l_orderkey", "l_shipmode", "l_commitdate", "l_receiptdate",
        "l_shipdate"])
    late = li.l_commitdate < li.l_receiptdate
    if q == "q4":
        probe = o[(o.o_orderdate >= _common.days(1993, 7, 1))
                  & (o.o_orderdate < _common.days(1993, 10, 1))]
        out = probe.o_orderkey.isin(li.l_orderkey[late]).sum()
        return len(probe), int(late.sum()), int(out)
    build = li[li.l_shipmode.isin(["MAIL", "SHIP"]) & late
               & (li.l_shipdate < li.l_commitdate)
               & (li.l_receiptdate >= _common.days(1994, 1, 1))
               & (li.l_receiptdate < _common.days(1995, 1, 1))]
    return len(o), len(build), len(build)


@pytest.mark.parametrize("q", ["q4", "q12"])
def test_partitioned_mesh_join_equals_the_reference(data, four_devices, q):
    """Exact against the plain reference; the operator ran the partitioned
    program once, its span carries each side's live rows and the send
    capacities taken from them, the rows it gave, and the collective bytes
    those capacities make."""
    ctx = _standalone(_settings(**PARTITIONED), {
        t: os.path.join(data, f"{t}.parquet") for t in ("lineitem", "orders")})
    try:
        s0, t0 = device_obs.STATS.snapshot(), time.time_ns()
        report = ctx.explain_analyze(_sql(q))
        s1 = device_obs.STATS.snapshot()
        got = compare.table_rows(ctx.sql(_sql(q)).to_arrow())
    finally:
        ctx.shutdown()
    want = ORACLES[q].answer(data)
    assert len(want[0]) > 0
    fault, _gap = compare.compare(got, want)
    assert fault is None, fault

    (metrics,) = _join_metrics(report)
    assert metrics["partitioned_joins"] == 1
    assert "broadcast_joins" not in metrics
    assert "exchange_retries" not in metrics
    assert metrics["mesh_devices"] == 4
    span = _join_spans(t0)[0]
    p_live, b_live, rows_out = _live_rows(data, q)
    join_type = {"q4": "semi", "q12": "inner"}[q]
    assert span.attrs["program"] == f"mesh_join_partitioned__{join_type}"
    assert span.attrs["collective"] == "all_to_all"
    assert (span.attrs["probe_rows"], span.attrs["build_rows"]) \
        == (p_live, b_live)
    assert span.attrs["rows_out"] == rows_out == metrics["output_rows"]
    assert span.attrs["retries"] == 0
    assert "side_overflowed" not in span.attrs

    def send(live):     # twice a bucket's even share, a power of two
        return mesh_exec._pow2(distributed.shuffle_capacity_of(
            -(-live // 4), 4))

    p_cap, b_cap = span.attrs["probe_capacity"], span.attrs["build_capacity"]
    assert (p_cap, b_cap) == (send(p_live), send(b_live))
    if join_type == "semi":
        # the key sort answers it: no pair, so no pair bound
        assert "out_capacity" not in span.attrs
        assert span.attrs["membership"] == 1
        assert metrics["membership_joins"] == 1
    else:
        assert span.attrs["out_capacity"] == mesh_exec._pow2(
            2 * -(-min(p_live, b_live) // 4))
        assert "membership" not in span.attrs
        assert "membership_joins" not in metrics
    # four devices send four buckets a side: the probe's columns and key,
    # and of the build side only its key in the semi join, a mask byte a row
    probe_row, build_row = {"q4": (8 + 4 + 4 + 8, 8),
                            "q12": (8 + 4 + 8, 8 + 4 * 4 + 8)}[q]
    sent = 16 * (p_cap * (probe_row + 1) + b_cap * (build_row + 1))
    assert s1["mesh_join_collective_bytes"] \
        - s0["mesh_join_collective_bytes"] == sent
    # which mesh_collective_bytes keeps counting, beside any aggregate's
    assert s1["mesh_collective_bytes"] - s0["mesh_collective_bytes"] >= sent
    assert s1["mesh_join_retries"] == s0["mesh_join_retries"]


# --- (c) bounds from live rows, not slots -----------------------------------

KEY = Schema([Field("k", INT64), Field("v", INT64)])
BKEY = Schema([Field("bk", INT64), Field("w", INT64)])


def _batch(schema, cols: dict, live: np.ndarray) -> ColumnBatch:
    """``cols`` (one value a slot) with ``live`` as the mask, the count
    unknown as on a chip."""
    return ColumnBatch(schema, {k: jnp.asarray(v) for k, v in cols.items()},
                       jnp.asarray(live))


def _spread(values: dict, slots: int, rng) -> tuple:
    """The same rows in ``slots`` slots, dead slots interleaved."""
    n = len(next(iter(values.values())))
    where = np.sort(rng.choice(slots, n, replace=False))
    live = np.zeros(slots, bool)
    live[where] = True
    cols = {}
    for k, v in values.items():
        full = np.full(slots, -7, np.int64)
        full[where] = v
        cols[k] = full
    return cols, live


@pytest.mark.parametrize("join_type", ["semi", "anti", "inner"])
def test_a_membership_join_has_no_output_bound(four_devices, monkeypatch,
                                               join_type):
    """``MeshJoinExec._run`` builds a semi or anti join's program with no
    pair bound (None), so ``ballista.join.output_factor`` enters neither its
    program key nor its remembered bounds: two factors, one program, no
    ``out_capacity`` on its spans, ``membership=1`` and one
    ``membership_joins`` a join.  An inner join's bound follows the
    factor."""
    keys = []
    real = mesh_exec._program

    def spy(op, key, build):
        keys.append(key)
        return real(op, key, build)

    monkeypatch.setattr(mesh_exec, "_program", spy)
    mesh_exec._EXCHANGE_NEED.clear()
    rng = np.random.default_rng(41)
    probe = {"k": rng.integers(0, 6000, 5000), "v": rng.integers(0, 99, 5000)}
    build = {"bk": np.arange(3000) * 2, "w": rng.integers(0, 99, 3000)}
    op = mesh_exec.MeshJoinExec(
        MemoryScanExec(KEY, pa.table({"k": [0], "v": [0]})),
        MemoryScanExec(BKEY, pa.table({"bk": [0], "w": [0]})),
        [(E.Column("k"), E.Column("bk"))], join_type)
    found = set(probe["k"].tolist()) & set(build["bk"].tolist())
    hit = np.isin(probe["k"], list(found))
    for factor in (2, 8):
        ctx = TaskContext(BallistaConfig(
            {**PARTITIONED, "ballista.join.output_factor": str(factor)}))
        t0 = time.time_ns()
        with span("task t/1/0", "executor", ROOT):
            (out,) = op._join_batches(_batch(KEY, probe, np.ones(5000, bool)),
                                      _batch(BKEY, build, np.ones(3000, bool)),
                                      ctx)
        (program,) = _join_spans(t0)
        want = {"semi": int(hit.sum()), "anti": int((~hit).sum()),
                "inner": int(hit.sum())}[join_type]
        assert out.num_rows == program.attrs["rows_out"] == want
        if join_type == "inner":
            assert program.attrs["out_capacity"] == mesh_exec._pow2(
                factor * -(-3000 // 4))
            assert "membership" not in program.attrs
        else:
            assert "out_capacity" not in program.attrs
            assert program.attrs["membership"] == 1
    # (name, probe send bucket, build send bucket[, pairs]) + what the
    # program shares across jobs by, beginning with the devices
    send = ("mesh_join_partitioned", 1024, 512)
    if join_type == "inner":
        assert [k[:5] for k in keys] == [send + (2048, 4), send + (8192, 4)]
        assert "membership_joins" not in op.metrics().values
    else:
        assert [k[:4] for k in keys] == [send + (4,)] * 2
        assert keys[0] == keys[1]   # one program for both factors, shared
        assert None not in keys[0]
        assert op.metrics().values["membership_joins"] == 2
    assert op.metrics().values["partitioned_joins"] == 2


def test_send_capacities_follow_live_rows_not_slots(four_devices):
    """5 000 probe and 3 000 build rows, dense and then spread over 7 000
    and 4 000 slots (no power of two to free, so neither is compacted):
    the same send capacities, from the live rows, and the same answer."""
    rng = np.random.default_rng(40)
    probe = {"k": rng.integers(0, 6000, 5000), "v": rng.integers(0, 99, 5000)}
    build = {"bk": np.arange(3000) * 2, "w": rng.integers(0, 99, 3000)}
    op = mesh_exec.MeshJoinExec(
        MemoryScanExec(KEY, pa.table({"k": [0], "v": [0]})),
        MemoryScanExec(BKEY, pa.table({"bk": [0], "w": [0]})),
        [(E.Column("k"), E.Column("bk"))], "inner")
    ctx = TaskContext(BallistaConfig(PARTITIONED))
    answers, bounds = [], []
    for p_slots, b_slots in ((5000, 3000), (7000, 4000)):
        pcols, plive = _spread(probe, p_slots, rng)
        bcols, blive = _spread(build, b_slots, rng)
        t0 = time.time_ns()
        with span("task t/1/0", "executor", ROOT):
            (out,) = op._join_batches(_batch(KEY, pcols, plive),
                                      _batch(BKEY, bcols, blive), ctx)
        live = np.asarray(out.mask)
        answers.append(sorted(zip(*(np.asarray(out.columns[c])[live].tolist()
                                    for c in ("k", "v", "bk", "w")))))
        (program,) = _join_spans(t0)
        bounds.append((program.attrs["probe_capacity"],
                       program.attrs["build_capacity"]))
        assert out.num_rows == len(answers[-1]) == program.attrs["rows_out"]
    assert bounds[0] == bounds[1] == (
        mesh_exec._join_send_capacity(5000, 1250, 4),
        mesh_exec._join_send_capacity(3000, 750, 4)) == (1024, 512)
    assert answers[0] == answers[1]
    frame = pd.DataFrame(probe).merge(pd.DataFrame(build), left_on="k",
                                      right_on="bk")
    assert answers[0] == sorted(map(tuple, frame[["k", "v", "bk", "w"]]
                                    .itertuples(index=False)))


# --- (d) every probe key on one device ----------------------------------------

def _keys_of_bucket(bucket: int, n_dev: int, count: int) -> np.ndarray:
    """The first ``count`` non-negative int64 keys the exchange sends to
    device ``bucket`` of ``n_dev``."""
    cand = np.arange(16 * count * n_dev, dtype=np.int64)
    dest = np.asarray(K.bucket_of([jnp.asarray(cand)], n_dev))
    keys = cand[dest == bucket][:count]
    assert len(keys) == count
    return keys


def test_keys_that_all_hash_to_one_device_rerun_once(four_devices):
    """8 192 probe keys, every one owned by device 2, against 16 of them:
    each device's probe bucket for device 2 holds twice what its send
    capacity does, the program says so and what it needed, and one re-run
    at that need is exact.  A second execution starts at the learned
    need."""
    keys = _keys_of_bucket(2, 4, 8192)
    rng = np.random.default_rng(12)
    v = rng.integers(1, 1000, len(keys)).astype(np.int64)
    t = pa.table({"g": pa.array(rng.permutation(keys)), "v": pa.array(v)})
    d = pa.table({"dk": pa.array(keys[::512]),
                  "w": pa.array(np.arange(16, dtype=np.int64))})
    sql = "select count(*) as n, sum(v) as sv, sum(w) as sw from t " \
          "join d on g = dk"
    mesh_exec._EXCHANGE_NEED.clear()
    ctx = _standalone(_settings(**PARTITIONED), {"t": t, "d": d})
    try:
        s0, t0 = device_obs.STATS.snapshot(), time.time_ns()
        report = ctx.explain_analyze(sql)
        s1 = device_obs.STATS.snapshot()
        got = ctx.sql(sql).to_pandas()
        s2 = device_obs.STATS.snapshot()
    finally:
        ctx.shutdown()
    j = t.to_pandas().merge(d.to_pandas(), left_on="g", right_on="dk")
    assert len(j) == 16
    assert got.values.tolist() == [[16, int(j.v.sum()), int(j.w.sum())]]
    (metrics,) = _join_metrics(report)
    assert metrics["partitioned_joins"] == 1
    assert metrics["exchange_retries"] == 1
    assert s1["mesh_join_retries"] - s0["mesh_join_retries"] == 1
    assert s1["mesh_exchange_retries"] == s0["mesh_exchange_retries"]
    first, second = _join_spans(t0)[:2]
    assert (first.attrs["retries"], second.attrs["retries"]) == (0, 1)
    assert second.attrs["side_overflowed"] == "probe"
    assert (first.attrs["probe_capacity"], second.attrs["probe_capacity"]) \
        == (1024, 2048)
    assert second.attrs["build_capacity"] == first.attrs["build_capacity"]
    assert second.attrs["rows_out"] == 16
    # the next execution of the statement starts at the learned need
    assert s2["mesh_join_retries"] == s1["mesh_join_retries"]
    assert _join_spans(t0)[-1].attrs["probe_capacity"] == 2048


def test_a_join_that_stays_flagged_raises(four_devices, monkeypatch):
    """A program that still flags an overflow with nothing past its bounds
    is an error, never a short answer."""
    from arrow_ballista_tpu.utils.errors import CapacityError

    real = mesh_exec._dispatch

    def flagged(prog, *args, **kw):
        *out, last = real(prog, *args, **kw)
        if prog.name.startswith("mesh_join"):
            last = last.copy()
            last[0] = 1
        return (*out, last)

    monkeypatch.setattr(mesh_exec, "_dispatch", flagged)
    t = pa.table({"g": pa.array(np.arange(4096, dtype=np.int64) * 7919)})
    ctx = BallistaContext.local(BallistaConfig(_settings(**PARTITIONED)))
    ctx.register_table("t", t)
    ctx.register_table("d", t.rename_columns(["dk"]))
    with pytest.raises(CapacityError, match="passed a bound"):
        ctx.sql("select count(*) as n from t join d on g = dk").to_pandas()


# --- (e) the programs' stats ------------------------------------------------

@pytest.mark.parametrize("variant", ["partitioned", "broadcast"])
@pytest.mark.parametrize("join_type", ["inner", "semi", "left", "anti"])
def test_join_programs_report_their_needs(join_type, variant):
    """``stats`` of both join programs against numpy: no overflow at ample
    bounds, the fullest send bucket of each side (none in a broadcast), the
    most candidate pairs a device found, and the rows out; and the bytes
    handed to the collective, from shapes."""
    n_dev, rows, n_build = 4, 4096, 1024
    rng = np.random.default_rng(len(join_type))
    pk = rng.integers(0, 2 * n_build, rows).astype(np.int64)
    bk = rng.integers(0, n_build, n_build).astype(np.int64)   # duplicates
    pmask, bmask = rng.random(rows) < 0.9, rng.random(n_build) < 0.8
    mesh = make_mesh(n_dev)
    place = lambda a: jax.device_put(jnp.asarray(a), row_sharding(mesh))
    pcols = {"__jk0": place(pk), "x": place(pk * 3)}
    bcols = {"__jk0": place(bk)}
    if join_type in ("inner", "left"):
        bcols["y"] = place(bk * 5)
    build_names = [n for n in bcols if n != "__jk0"]
    # a semi or anti join expands no pair: it takes no pair bound
    out_cap = 1 << 14 if join_type in ("inner", "left") else None
    if variant == "partitioned":
        prog = distributed.distributed_hash_join(
            mesh, 1, ["x"], build_names, join_type, rows, n_build, out_cap,
            {"y": 0})
    else:
        prog = distributed.distributed_broadcast_join(
            mesh, 1, ["x"], build_names, join_type, out_cap, {"y": 0})
    args = (pcols, place(pmask), bcols, place(bmask))
    out_cols, out_mask, stats = prog(*args)
    overflow, p_need, b_need, pairs, rows_out = (int(v) for v in stats)
    assert overflow == 0
    assert rows_out == int(np.asarray(out_mask).sum())

    lp, lb = pk[pmask], bk[bmask]
    matches = {k: int((lb == k).sum()) for k in np.unique(lb)}
    n_pairs = sum(matches.get(k, 0) for k in lp)
    hit = np.array([k in matches for k in lp])
    want_rows = {"inner": n_pairs, "semi": int(hit.sum()),
                 "anti": int((~hit).sum()),
                 "left": n_pairs + int((~hit).sum())}[join_type]
    assert rows_out == want_rows
    # a semi or anti join asks only whether a key is present: no pair
    membership = join_type in ("semi", "anti")
    if variant == "broadcast":
        assert (p_need, b_need) == (0, 0)
        per = rows // n_dev
        dev_pairs = [sum(matches.get(k, 0) for k in
                         pk[d * per:(d + 1) * per][pmask[d * per:(d + 1) * per]])
                     for d in range(n_dev)]
        assert pairs == (0 if membership else max(dev_pairs))
        assert prog.collective_bytes(*args) == n_dev * n_build * (
            8 * len(bcols) + 1)
        return
    dest_p = np.asarray(K.bucket_of([jnp.asarray(pk)], n_dev))
    dest_b = np.asarray(K.bucket_of([jnp.asarray(bk)], n_dev))

    def fullest(dest, mask, per):
        return max(int(((dest[d * per:(d + 1) * per] == b)
                        & mask[d * per:(d + 1) * per]).sum())
                   for d in range(n_dev) for b in range(n_dev))

    assert p_need == fullest(dest_p, pmask, rows // n_dev)
    assert b_need == fullest(dest_b, bmask, n_build // n_dev)
    dev_pairs = [sum(matches.get(k, 0) for k in lp[dest_p[pmask] == d])
                 for d in range(n_dev)]
    assert pairs == (0 if membership else max(dev_pairs))
    assert prog.collective_bytes(*args) == n_dev * n_dev * (
        rows * (16 + 1) + n_build * (8 * len(bcols) + 1))


# --- (f) the deployment's own check ------------------------------------------

def _stats(**operators):
    return {"stages": [{"operators": operators}]}


PART_JOB = _stats(**{"0.0:MeshJoinExec": {"mesh_devices": 4.0,
                                          "partitioned_joins": 1.0},
                     "0:HashAggregateExec": {"output_rows": 5.0}})
BCAST_JOB = _stats(**{"0.0:MeshJoinExec": {"mesh_devices": 4.0,
                                           "broadcast_joins": 1.0}})
OLD_JOB = _stats(**{"0.0:MeshJoinExec": {"mesh_devices": 4.0,
                                         "capacity_recompiles": 1.0}})
FILE_JOB = _stats(**{"0:JoinExec": {"output_rows": 5.0}})


def test_the_check_refuses_a_join_that_broadcast_or_left_the_mesh():
    assert mesh4_join.joins_of(PART_JOB) == (4, 1, 0)
    assert mesh4_join.joins_of(None) == (0, 0, 0)
    ok, found = mesh4_join.check_joins(2, [PART_JOB, PART_JOB], 4, True)
    assert ok and "2 of them ran MeshJoinExec's partitioned" in found
    for other in (BCAST_JOB, FILE_JOB, OLD_JOB):
        ok, found = mesh4_join.check_joins(2, [PART_JOB, other], 4, True)
        assert not ok and "1 of them" in found
    # a mesh of two devices is not the configuration's
    two = _stats(**{"0.0:MeshJoinExec": {"mesh_devices": 2.0,
                                         "partitioned_joins": 1.0}})
    assert not mesh4_join.check_joins(1, [two], 4, True)[0]
    assert not mesh4_join.check_joins(0, [], 4, True)[0]
    # a program that counts no partitioned_joins: a mesh join that did not
    # broadcast stands for one
    ok, found = mesh4_join.check_joins(1, [OLD_JOB], 4, False)
    assert ok and "no broadcast_joins" in found
    assert not mesh4_join.check_joins(1, [BCAST_JOB], 4, False)[0]
    assert mesh4_join.counts_partitioned()


class _Scheduler:
    class metrics:
        @staticmethod
        def record_submitted(*a):
            pass


def _deployment(monkeypatch, platform: str, stats):
    """A mesh4_join deployment that served one grouped statement in one job
    with ``stats``, on a device of ``platform``, built without a context;
    the program counts one mesh program where the job ran a mesh join."""
    class Stub(mesh4_join.Deployment):
        def build(self):
            self.scheduler = _Scheduler()
            self.ctx = type("Ctx", (), {"shutdown": lambda s: closed.append(1),
                                        "sql": lambda s, text: text})()

        def job_stats(self, job_id):
            return stats

    closed = []
    dev = type("Dev", (), {"platform": platform})()
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    d = Stub({"settings": {}, "task_slots": 4, "executors": 1,
              "mesh": {"axis": "part", "devices": 4}}, "", [])
    d.session().sql("select a, count(*) from t group by a")
    assert (d.statements, d.grouped_statements) == (1, 1)
    d.submitted.append({"job_id": "j1"})
    if mesh4.mesh_devices_of(stats):
        device_obs.record_mesh_program(0, join=True)
    return d, closed


def test_close_fails_the_run_on_a_tpu_and_only_reports_on_a_cpu(
        monkeypatch, capsys):
    d, closed = _deployment(monkeypatch, "tpu", BCAST_JOB)
    with pytest.raises(SystemExit, match="partitioned program"):
        d.close()
    assert closed == [1]            # the context was shut down all the same
    d, closed = _deployment(monkeypatch, "cpu", FILE_JOB)
    d.close()
    assert "0 of them ran MeshJoinExec" in capsys.readouterr().err
    d, closed = _deployment(monkeypatch, "tpu", PART_JOB)
    d.close()
    assert closed == [1]


# --- (g) the readers ------------------------------------------------------------

def _trace(**over):
    base = {"simulated_device": False, "query_shares": {"q4": 2.0, "q12": 1.5},
            "devices": 4, "busy_s": 1.0, "window_s": 2.0,
            "device_ops": [["jit_mesh_join_partitioned__semi", 1.5],
                           ["jit_mesh_join_partitioned__inner", 0.5],
                           ["jit_concatenate", 0.1]]}
    return {**base, **over}


def test_mesh_join_roofline_counts_the_rows_that_reach_the_join(
        data, monkeypatch, tmp_path):
    """Each side's rows after its predicates, counted from the parquet of
    the run, times its columns at device width, by each query's share of
    the window, over one chip's rate, against the join programs' seconds
    summed over the devices."""
    os.makedirs(tmp_path / ".bench_data")
    os.symlink(data, tmp_path / ".bench_data" / CELL)
    monkeypatch.setattr(mesh_join_roofline, "ROOT", str(tmp_path))
    with open(os.path.join(CHIP, "layer_metrics",
                           "mesh_join_hbm_roofline.json")) as fh:
        args = json.load(fh)["args"]
    evidence = {"workload": CELL, "trace": _trace(),
                "peaks": {"hbm_bytes_per_s": 819e9}}
    value = mesh_join_roofline.read(evidence, **args)
    q4_probe, q4_build, _ = _live_rows(data, "q4")
    q12_probe, q12_build, _ = _live_rows(data, "q12")
    want = (2.0 * (q4_probe * 12 + q4_build * 8)
            + 1.5 * (q12_probe * 12 + q12_build * 12)) / 819e9 / 2.0
    assert value == pytest.approx(100 * want)
    assert 0 < value < 100
    for nothing in ({"trace": None}, {"peaks": None}, {"workload": "none"},
                    {"trace": _trace(simulated_device=True)},
                    {"trace": _trace(device_ops=[["jit_concatenate", 1.0]])},
                    {"trace": _trace(query_shares={"q1": 4.0})}):
        assert mesh_join_roofline.read({**evidence, **nothing}, **args) \
            is None


def test_mesh_join_spans_sums_the_join_programs_under_the_windows_tasks():
    RING.clear()
    made = {}
    for job in ("in-window", "warm-up"):
        with span(f"task {job}/1/0", "executor", ROOT, job_id=job):
            with span("MeshJoinExec", "operator"):
                with span("mesh_program", "device",
                          program="mesh_join_partitioned__semi",
                          collective="all_to_all", probe_capacity=64) as join:
                    pass
                with span("mesh_program", "device",
                          program="mesh_agg_exchange__k1",
                          collective="all_to_all"):
                    pass
        made[job] = join
    join = made["in-window"]
    evidence = {"jobs": [{"job_id": "in-window"}], "window": {"completed": 2}}
    assert mesh_join_spans.read(dict(evidence)) == \
        (join.end_ns - join.start_ns) / 2 / 1e9
    # a window whose tasks ran no mesh join reports nothing, never 0
    with span("task file/1/0", "executor", ROOT, job_id="file"):
        pass
    assert mesh_join_spans.read({"jobs": [{"job_id": "file"}],
                                 "window": {"completed": 1}}) is None
    RING.clear()


# --- (h) the comparison over the new mix ---------------------------------------

def _run_cell(monkeypatch, capsys, alter=None) -> dict:
    """One rehearsal run of the cell on the CPU at SF0.02, every fifth
    answer altered by ``alter`` where it is produced."""
    if alter is not None:
        class Altering:
            n = 0

            def __init__(self, inner):
                self.inner = inner

            def sql(self, text):
                self._df = self.inner.sql(text)
                return self

            def to_arrow(self):
                table = self._df.to_arrow()
                Altering.n += 1
                return alter(table) if Altering.n % 5 == 0 else table

        session = mesh4_join.Deployment.session
        monkeypatch.setattr(mesh4_join.Deployment, "session",
                            lambda self: Altering(session(self)))
    assert run.main(["--workload", CELL, "--seed", str(2 ** 31 + 4012),
                     "--seconds", "0.5", "--trace", "0", "--allow-cpu",
                     "--scale", "0.02"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _count_off_by_one(table):
    col = table.column(table.num_columns - 1)
    values = col.to_pylist()
    values[0] += 1
    return table.set_column(table.num_columns - 1,
                            table.schema[table.num_columns - 1],
                            pa.array(values, type=col.type))


@pytest.mark.parametrize("alter", [None, _count_off_by_one,
                                   lambda table: table.slice(1)],
                         ids=["sound", "count_off_by_one", "row_dropped"])
def test_the_new_mix_is_correct_and_a_wrong_answer_is_not(
        alter, monkeypatch, capsys):
    out = _run_cell(monkeypatch, capsys, alter)
    assert out["device"]["platform"] == "cpu"       # never read as a chip
    assert out["failed"] == 0
    if alter is None:
        assert out["correct"]
        assert out["compared"]["answers_wrong"]["value"] == 0
    else:
        assert not out["correct"]
        assert out["compared"]["answers_wrong"]["value"] >= 1
