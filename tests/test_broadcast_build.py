"""A broadcast join's build is compacted once a job, by a sort.

The build is materialized once a job and read by every probe task, so
``JoinExec`` learns its live rows (one scalar wait) and drops it to the
capacity they need, where ``shrink()`` elsewhere skips an unknown count on
an accelerator (``BALLISTA_REMOTE_DEVICE=1`` stands in for one here).  The
compaction is one sort (``models/batch.py`` ``_shrink_device``) and puts the
rows where ``kernels.compaction_order`` puts them."""
import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

from arrow_ballista_tpu import BallistaConfig, Field, INT64, Schema
from arrow_ballista_tpu.models import batch as B
from arrow_ballista_tpu.models import expr as E
from arrow_ballista_tpu.obs import device as device_obs
from arrow_ballista_tpu.ops import kernels as K
from arrow_ballista_tpu.ops.operators import (AggSpec, FilterExec,
                                              HashAggregateExec, JoinExec)
from arrow_ballista_tpu.ops.physical import MemoryScanExec, TaskContext
from benchmarks.chip import datagen

SEED = 2147493707
SCALE = 0.02
# q18's HAVING at QUANTITY 250: at SF0.02 the spec's 300 leaves no order
QUANTITY = 250
PROBES = 4

LINEITEM = Schema([Field("l_orderkey", INT64), Field("l_quantity", INT64)])
ORDERS = Schema([Field("o_orderkey", INT64), Field("o_custkey", INT64),
                 Field("o_totalprice", INT64)])


@pytest.fixture(scope="module")
def tpch():
    t = datagen.generate_tables(SCALE, SEED, ["lineitem", "orders"])
    return (t["lineitem"].select(LINEITEM.names()),
            t["orders"].select(ORDERS.names()))


@pytest.fixture
def remote(monkeypatch):
    monkeypatch.setenv("BALLISTA_REMOTE_DEVICE", "1")


def _having(lineitem) -> FilterExec:
    """``select l_orderkey, sum(l_quantity) from lineitem group by
    l_orderkey having sum(l_quantity) > QUANTITY``, quantities unscaled."""
    agg = HashAggregateExec(
        MemoryScanExec(LINEITEM, lineitem, 1),
        [(E.Column("l_orderkey"), "l_orderkey")],
        [AggSpec("sum", E.Column("l_quantity"), "sum_qty")], "single")
    return FilterExec(agg, E.BinOp(">", E.Column("sum_qty"),
                                   E.Lit(QUANTITY * 100)))


def _run(tpch, join_type, job_id):
    """Every probe partition of one job: (operator, frame, scalar waits)."""
    lineitem, orders = tpch
    j = JoinExec(MemoryScanExec(ORDERS, orders, PROBES), _having(lineitem),
                 [(E.Column("o_orderkey"), E.Column("l_orderkey"))],
                 join_type=join_type, dist="broadcast")
    ctx = TaskContext(config=BallistaConfig(), job_id=job_id)
    s0 = device_obs.STATS.snapshot()["scalar_waits"]
    frames = [b.to_pandas() for p in range(PROBES)
              for b in j.execute(p, ctx)]
    waits = device_obs.STATS.snapshot()["scalar_waits"] - s0
    df = pd.concat(frames, ignore_index=True)
    return j, df.sort_values(list(df.columns)).reset_index(drop=True), waits


def _oracle(tpch, join_type):
    lineitem, orders = tpch
    li = lineitem.to_pandas()
    qty = li.groupby("l_orderkey").l_quantity.sum().rename("sum_qty")
    qty = qty[qty > QUANTITY * 100].reset_index()
    o = orders.to_pandas()
    if join_type == "semi":
        want = o[o.o_orderkey.isin(qty.l_orderkey)]
    else:
        want = o.merge(qty, left_on="o_orderkey", right_on="l_orderkey")
    return len(qty), want.sort_values(list(want.columns)).reset_index(
        drop=True)


@pytest.mark.parametrize("join_type", ["semi", "inner"])
def test_broadcast_build_is_compacted_once_a_job(tpch, remote, monkeypatch,
                                                 join_type):
    live, want = _oracle(tpch, join_type)
    ctx = TaskContext(config=BallistaConfig(), job_id="having")
    wide = B.concat_batches(ORDERS, _having(tpch[0]).execute(0, ctx)).capacity
    assert B.round_capacity(live) < wide

    j, got, waits = _run(tpch, join_type, "shrunk")
    pd.testing.assert_frame_equal(got, want, check_dtype=False)
    cached = j._build_cache[1]
    assert cached.capacity == B.round_capacity(live)
    assert cached.num_rows == live
    m = j.metrics().to_dict()
    assert m["build_shrinks"] == 1
    assert m["build_slots_dropped"] == wide - B.round_capacity(live)
    assert m["range_lookups"] == PROBES

    # the same job with the build left as the aggregate hands it over
    monkeypatch.setattr(JoinExec, "_compact_build", lambda self, b: b)
    j_wide, got_wide, waits_wide = _run(tpch, join_type, "wide")
    pd.testing.assert_frame_equal(got_wide, want, check_dtype=False)
    assert j_wide._build_cache[1].capacity == wide
    assert "build_shrinks" not in j_wide.metrics().to_dict()
    assert waits == waits_wide + 1


def test_probe_side_keeps_its_capacity_where_the_count_is_unknown(remote):
    """Every other ``shrink()`` keeps its gate: a batch whose count is not
    on the host stays as it is, unless the caller asks for the fetch."""
    n = 5000
    cols = {"k": jnp.arange(n, dtype=jnp.int64)}
    b = B.ColumnBatch(Schema([Field("k", INT64)]), cols,
                      jnp.arange(n) % 7 == 0)
    s0 = device_obs.STATS.snapshot()["scalar_waits"]
    assert b.shrink() is b
    assert device_obs.STATS.snapshot()["scalar_waits"] == s0
    small = b.shrink(fetch_count=True)
    assert device_obs.STATS.snapshot()["scalar_waits"] == s0 + 1
    assert (small.capacity, small.num_rows) == (1024, -(-n // 7))
    np.testing.assert_array_equal(small.compacted_numpy()["k"],
                                  np.arange(0, n, 7))


def _columns(cap, rng):
    """An int64 key, a nullable int64 (NULL sentinel), dictionary codes of
    a string column with NULLs (-1), a nullable float64 (NaN), an int32 date
    and a bool."""
    v = rng.integers(-(1 << 62), 1 << 62, cap)
    v[rng.random(cap) < 0.1] = np.iinfo(np.int64).min
    f = rng.standard_normal(cap)
    f[rng.random(cap) < 0.1] = np.nan
    codes = rng.integers(-1, 40, cap).astype(np.int32)
    return {"key": np.arange(cap, dtype=np.int64) * 3 + 1, "v": v,
            "s": codes, "f": f,
            "d": rng.integers(0, 20000, cap).astype(np.int32),
            "b": rng.random(cap) < 0.5}


def _mask(kind, cap, rng):
    if kind == "all_dead":
        return np.zeros(cap, bool)
    if kind == "all_live":
        return np.ones(cap, bool)
    share = {"sparse": 0.01, "half": 0.5, "dense": 0.97}[kind]
    return rng.random(cap) < share


@pytest.mark.parametrize("cap,target,kind", [
    (4096, 1024, "sparse"),
    (8192, 4096, "half"),
    (1 << 14, 1024, "all_dead"),
    (4096, 4096, "all_live"),
    (4096, 4096, "dense"),
    (8192, 8192, "half"),
    (1 << 15, 2048, "sparse"),
    (2048, 1024, "half"),
])
def test_shrink_program_puts_rows_where_compaction_order_does(cap, target,
                                                             kind):
    """Exact on every column and the mask, against the first ``target``
    slots of ``compaction_order``; where more rows live than ``target``
    holds, both keep the first of them."""
    rng = np.random.default_rng(cap + target)
    cols = jax.device_put(_columns(cap, rng))
    mask = jnp.asarray(_mask(kind, cap, rng))
    got_cols, got_mask = jax.device_get(B._shrink_device(cols, mask, target))
    order = K.compaction_order(mask)[:target]
    want_cols, want_mask = jax.device_get(
        ({k: v[order] for k, v in cols.items()}, mask[order]))
    np.testing.assert_array_equal(got_mask, want_mask)
    for k in cols:
        assert got_cols[k].dtype == want_cols[k].dtype
        np.testing.assert_array_equal(got_cols[k], want_cols[k])
    live = int(np.asarray(mask).sum())
    assert int(got_mask.sum()) == min(live, target)
