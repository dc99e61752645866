"""The deployment ``tpch_sf10_mesh4_q18`` (benchmarks/chip/configs), small,
on four of the CPU's virtual devices: q18's inner aggregate through the
exchange (partial aggregate, ``all_to_all``, final aggregate as one mesh
program) with more groups than any configuration key bounds, the whole q18
against the benchmark's plain reference, keys that all hash to one device
(the send bucket overflows: one re-run at the observed need, never fewer
groups), the devices' shares adding up to the one-device aggregate, and
sums past 2^31."""
import json
import os
import time

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp

from arrow_ballista_tpu.client.context import BallistaContext
from arrow_ballista_tpu.obs import device as device_obs
from arrow_ballista_tpu.obs.tracing import RING
from arrow_ballista_tpu.ops import kernels as K
from arrow_ballista_tpu.ops import mesh_exec
from arrow_ballista_tpu.parallel import distributed
from arrow_ballista_tpu.parallel.mesh import make_mesh, row_sharding
from arrow_ballista_tpu.utils.config import BallistaConfig
from arrow_ballista_tpu.utils.errors import CapacityError
from benchmarks.chip import compare, datagen
from benchmarks.chip.oracles import _common, q18 as oracle_q18

ROOT_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT_DIR, "benchmarks", "chip")
SEED = 2147493707
SCALE = 0.2        # 300 000 orders: 75 000 groups a device, over 2^16
INNER = ("select l_orderkey, sum(l_quantity) as q from lineitem "
         "group by l_orderkey having sum(l_quantity) > {limit}")


def _settings(**over) -> dict:
    """The configuration's own settings, ``min_rows`` lowered so that small
    data passes the planner's gate, and what a test changes of them."""
    with open(os.path.join(CHIP, "configs",
                           "tpch_sf10_mesh4_q18.json")) as fh:
        return {**json.load(fh)["settings"],
                "ballista.shuffle.mesh.min_rows": "0", **over}


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    ddir = str(tmp_path_factory.mktemp("mesh_q18"))
    return datagen.write_data(ddir, SCALE, SEED,
                              ["customer", "lineitem", "orders"])


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


def _mesh_aggregates(report: dict) -> list:
    return [op["metrics"] for stage in report["stages"]
            for op in stage["operator_tree"]
            if op["op"] == "MeshAggregateExec"]


def _exchange_spans(since: int) -> list:
    return [s for s in RING.snapshot()
            if s.name == "mesh_program" and s.start_ns >= since
            and s.attrs.get("collective") == "all_to_all"]


# --- (a) the inner aggregate, more groups than 2^16 a device ---------------

def test_inner_aggregate_with_more_groups_than_agg_capacity(
        data, four_devices):
    """300 000 groups over four devices at every key's default: exact
    against pandas.  The parent bounded a device's final groups by
    ``ballista.agg.capacity`` (65 536) and raised CapacityError here."""
    ctx = _standalone(_settings(), {
        "lineitem": os.path.join(data, "lineitem.parquet")})
    try:
        s0, t0 = device_obs.STATS.snapshot(), time.time_ns()
        report = ctx.explain_analyze(INNER.format(limit=-1))
        got = ctx.sql(INNER.format(limit=250)).to_pandas()
        every = ctx.sql(INNER.format(limit=-1)).to_pandas()
    finally:
        ctx.shutdown()
    li = _common.load(data, "lineitem", ["l_orderkey", "l_quantity"])
    want = li.groupby("l_orderkey").l_quantity.sum()
    assert len(want) == 300_000
    assert dict(zip(every.l_orderkey, (every.q * 100).astype("int64"))) \
        == want.to_dict()
    big = want[want > 250 * 100]
    assert len(big) and dict(
        zip(got.l_orderkey, (got.q * 100).astype("int64"))) == big.to_dict()

    (metrics,) = _mesh_aggregates(report)
    assert metrics["exchange_collective"] == 1
    # the exchange's partial and final aggregates, by run scans both
    assert metrics["run_scan_aggregates"] == 2
    assert "mxu_grouped_sums" not in metrics
    assert "exchange_retries" not in metrics
    assert metrics["mesh_devices"] == 4
    s1 = device_obs.STATS.snapshot()
    assert s1["mesh_programs"] - s0["mesh_programs"] == 3
    assert s1["mesh_exchange_retries"] == s0["mesh_exchange_retries"]
    assert s1["mesh_unshard_bytes"] > s0["mesh_unshard_bytes"]
    # the bounds come from the shard: a quarter of the scanned batches'
    # slots (their rows padded to capacities)
    span = _exchange_spans(t0)[0]
    rows = span.attrs["partial_capacity"]
    assert len(li) <= 4 * rows < 2 * len(li)
    assert span.attrs["shuffle_capacity"] == -(-rows // 2)
    assert span.attrs["final_capacity"] == 4 * span.attrs["shuffle_capacity"]
    assert span.attrs["groups_out"] == 300_000
    assert span.attrs["retries"] == 0
    # what the all_to_all is handed: devices x buckets x capacity state
    # rows of a key, a sum and a mask byte
    assert s1["mesh_collective_bytes"] - s0["mesh_collective_bytes"] \
        == 3 * 4 * 4 * span.attrs["shuffle_capacity"] * 17
    unshard = [s for s in RING.snapshot()
               if s.name == "mesh_unshard" and s.start_ns >= t0]
    assert unshard and all(s.attrs["via"] == "device" for s in unshard)
    # 75 000 groups a device leave as 2^17 slots a device, not as the
    # final capacity
    assert unshard[0].attrs["rows"] == 4 * (1 << 17)


# --- (b) the whole q18 -------------------------------------------------------

def test_q18_through_the_mesh_equals_the_reference(data, four_devices):
    with open(os.path.join(CHIP, "queries", "q18.sql")) as fh:
        sql = fh.read()
    ctx = _standalone(_settings(), {
        t: os.path.join(data, f"{t}.parquet")
        for t in ("customer", "lineitem", "orders")})
    try:
        report = ctx.explain_analyze(sql)
        got = compare.table_rows(ctx.sql(sql).to_arrow())
    finally:
        ctx.shutdown()
    assert _mesh_aggregates(report), report["text"]
    want = oracle_q18.answer(data)
    assert len(want[0]) > 0
    fault, _gap = compare.compare(got, want)
    assert fault is None, fault
    # rows in ORDER BY order: o_totalprice desc, o_orderdate
    assert got == sorted(got, key=lambda r: (-r[4], r[3]))


# --- (c) every group on one device -------------------------------------------

def _keys_of_bucket(bucket: int, n_dev: int, count: int) -> np.ndarray:
    """The first ``count`` non-negative int64 keys the exchange sends to
    device ``bucket`` of ``n_dev``."""
    cand = np.arange(16 * count * n_dev, dtype=np.int64)
    dest = np.asarray(K.bucket_of([jnp.asarray(cand)], n_dev))
    keys = cand[dest == bucket][:count]
    assert len(keys) == count
    return keys


def test_keys_that_all_hash_to_one_device_are_all_there(four_devices):
    """8 192 distinct keys, every one owned by device 2: each device's send
    bucket for it overflows at twice its even share, the program says so
    and what it needed, and one re-run at that need is exact.  A second
    execution starts at the learned need."""
    keys = _keys_of_bucket(2, 4, 8192)
    rng = np.random.default_rng(5)
    g = rng.permutation(np.repeat(keys, 2))
    v = rng.integers(1, 1000, len(g)).astype(np.int64)
    table = pa.table({"g": pa.array(g), "v": pa.array(v)})
    sql = "select g, sum(v) as sv, count(*) as n from t group by g"
    mesh_exec._EXCHANGE_NEED.clear()
    ctx = _standalone(_settings(), {"t": table})
    try:
        s0, t0 = device_obs.STATS.snapshot(), time.time_ns()
        report = ctx.explain_analyze(sql)
        s1 = device_obs.STATS.snapshot()
        got = ctx.sql(sql).to_pandas()
        s2 = device_obs.STATS.snapshot()
    finally:
        ctx.shutdown()
    want = pd.DataFrame({"g": g, "v": v}).groupby("g").v.agg(["sum", "count"])
    assert len(got) == 8192
    assert dict(zip(got.g, zip(got.sv, got.n))) \
        == dict(zip(want.index, zip(want["sum"], want["count"])))
    (metrics,) = _mesh_aggregates(report)
    assert metrics["exchange_collective"] == 2
    assert metrics["exchange_retries"] == 1
    assert s1["mesh_exchange_retries"] - s0["mesh_exchange_retries"] == 1
    first, second = _exchange_spans(t0)[:2]
    assert (first.attrs["retries"], second.attrs["retries"]) == (0, 1)
    assert second.attrs["shuffle_capacity"] > first.attrs["shuffle_capacity"]
    assert second.attrs["groups_out"] == 8192
    # the next execution of the statement does not re-run
    assert s2["mesh_programs"] - s1["mesh_programs"] == 1
    assert s2["mesh_exchange_retries"] == s1["mesh_exchange_retries"]


def test_an_exchange_that_stays_flagged_raises(four_devices, monkeypatch):
    """A program that still flags an overflow at the need it reported is
    an error, never a short answer."""
    real = mesh_exec._dispatch

    def flagged(prog, *args, **kw):
        *out, last = real(prog, *args, **kw)
        if prog.collective == "all_to_all":
            last = last.copy()
            last[0] = 1
        return (*out, last)

    monkeypatch.setattr(mesh_exec, "_dispatch", flagged)
    table = pa.table({"g": pa.array(np.arange(4096, dtype=np.int64) * 7919),
                      "v": pa.array(np.ones(4096, dtype=np.int64))})
    ctx = BallistaContext.local(BallistaConfig(_settings()))
    ctx.register_table("t", table)
    with pytest.raises(CapacityError, match="passed a bound"):
        ctx.sql("select g, sum(v) as sv from t group by g").to_pandas()


# --- (d) the shares add up ---------------------------------------------------

@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_devices_shares_add_up_to_the_one_device_aggregate(n_dev):
    """Each device's share of the exchange's final states holds only keys
    that hash to it, no key is in two shares, and the shares together are
    the one-device ``grouped_aggregate`` of the same rows."""
    rng = np.random.default_rng(n_dev)
    rows = 4096 * n_dev
    g = rng.integers(0, 3000, rows).astype(np.int64) * 104_729
    v = rng.integers(-(1 << 40), 1 << 40, rows).astype(np.int64)
    mask = rng.random(rows) < 0.9
    aggs = [("v", "sum"), ("v", "count"), ("v", "min"), ("v", "max")]
    mesh = make_mesh(n_dev)
    partial, shuffle, final = mesh_exec._exchange_bounds(rows // n_dev, n_dev)
    prog = distributed.distributed_grouped_aggregate(
        mesh, ["g"], aggs, partial, final, shuffle_capacity=shuffle)
    place = lambda a: jax.device_put(jnp.asarray(a), row_sharding(mesh))
    fk, fv, fmask, stats = prog({"g": place(g), "v": place(v)}, place(mask))
    overflow, _need, groups_max, groups_out = (int(x) for x in stats)
    assert not overflow

    wk, wv, wmask, _ = K.grouped_aggregate(
        [jnp.asarray(g)], [(jnp.asarray(v), how) for _, how in aggs],
        jnp.asarray(mask), rows)
    wlive = np.asarray(wmask)
    whole = {int(k): tuple(int(np.asarray(x)[wlive][i]) for x in wv)
             for i, k in enumerate(np.asarray(wk[0])[wlive])}
    assert groups_out == len(whole)

    shares = {}
    live = np.asarray(fmask).reshape(n_dev, final)
    keys = np.asarray(fk[0]).reshape(n_dev, final)
    vals = [np.asarray(x).reshape(n_dev, final) for x in fv]
    for d in range(n_dev):
        mine = keys[d][live[d]]
        assert live[d][:len(mine)].all()        # compacted to the front
        assert len(mine) <= groups_max
        dest = np.asarray(K.bucket_of([jnp.asarray(mine)], n_dev))
        assert (dest == d).all()
        for i, k in enumerate(mine):
            assert int(k) not in shares
            shares[int(k)] = tuple(int(x[d][live[d]][i]) for x in vals)
    assert shares == whole


# --- (e) sums past 2^31 ------------------------------------------------------

def test_sums_past_32_bits_merge_exactly(four_devices):
    """Unscaled values near 2^40 of both signs, a few hundred rows a group:
    the partial sums, the merge after the exchange and the unshard keep
    every bit of an int64 sum."""
    rng = np.random.default_rng(18)
    n = 40_000
    g = rng.integers(0, 150, n).astype(np.int64) * 1_000_003 + 17
    v = rng.integers(-(1 << 40), 1 << 40, n).astype(np.int64)
    v[::7] = (1 << 62) // 512          # sums of these alone pass 2^53
    table = pa.table({"g": pa.array(g), "v": pa.array(v)})
    ctx = _standalone(_settings(), {"t": table})
    try:
        got = ctx.sql("select g, sum(v) as sv, min(v) as lo, max(v) as hi "
                      "from t group by g").to_pandas()
    finally:
        ctx.shutdown()
    want = pd.DataFrame({"g": g, "v": v}).groupby("g").v.agg(
        ["sum", "min", "max"])
    assert want["sum"].abs().max() > 1 << 53
    assert dict(zip(got.g, zip(got.sv, got.lo, got.hi))) == dict(
        zip(want.index, zip(want["sum"], want["min"], want["max"])))
