"""ICI-mesh shuffle + distributed aggregate on the virtual 8-device mesh.

Multi-chip coverage without a pod, mirroring how the reference tests
multi-node scheduling without a cluster (SURVEY.md §4).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from arrow_ballista_tpu.parallel import (
    PART_AXIS,
    dispatch_to_buckets,
    distributed_filter_aggregate,
    distributed_grouped_aggregate,
    make_mesh,
    row_sharding,
    shuffle_rows,
)
from arrow_ballista_tpu.ops import kernels as K


N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= N_DEV
    return make_mesh(N_DEV)


def _place(mesh, arr):
    return jax.device_put(arr, row_sharding(mesh))


def _dispatch_case(case, rng):
    """(dest, mask, num_dest, capacity) of one ``dispatch_to_buckets``
    case, 96 rows in."""
    rows, num_dest = 96, 4
    dest = rng.integers(0, num_dest, rows).astype(np.int32)
    mask = np.ones(rows, dtype=bool)
    capacity = 64
    if case == "dead_rows_interleaved":
        mask = rng.random(rows) < 0.6
    elif case == "empty_bucket":
        dest[dest == 2] = 3
        mask = rng.random(rows) < 0.8
    elif case == "one_bucket_overflows":
        dest[:] = 1
        capacity = 40
    elif case == "capacity_is_row_count":
        dest[:] = num_dest - 1      # the last start is as late as it can be
        capacity = rows
    elif case == "capacity_past_row_count":
        dest[dest == 0] = num_dest - 1
        capacity = rows + 32
    else:
        raise ValueError(case)
    return dest, mask, num_dest, capacity


@pytest.mark.parametrize("n_cols", [2, 6])
@pytest.mark.parametrize("case", [
    "dead_rows_interleaved", "empty_bucket", "one_bucket_overflows",
    "capacity_is_row_count", "capacity_past_row_count"])
def test_dispatch_to_buckets_matches_numpy(rng, case, n_cols):
    """The send buffer against numpy: a bucket holds, under its mask, the
    multiset of the live rows bound for it (on overflow ``capacity`` of them,
    each once), ``need`` is the fullest bucket's live rows and ``overflow``
    whether that passed ``capacity``; columns keep their dtypes."""
    dest, mask, num_dest, capacity = _dispatch_case(case, rng)
    rows = dest.shape[0]
    # `id` is unique, so a row is identified; dead rows hold values too
    cols = {"id": rng.permutation(rows).astype(np.int64),
            "i32": rng.integers(-9, 9, rows).astype(np.int32),
            "flag": rng.random(rows) < 0.5,
            "big": rng.integers(-(1 << 62), 1 << 62, rows).astype(np.int64),
            "flag2": rng.random(rows) < 0.5,
            "small": rng.integers(0, 1 << 30, rows).astype(np.int32)}
    cols = dict(list(cols.items())[:n_cols])

    send, send_mask, overflow, need = jax.jit(
        dispatch_to_buckets, static_argnums=(3, 4))(
        {m: jnp.asarray(c) for m, c in cols.items()}, jnp.asarray(dest),
        jnp.asarray(mask), num_dest, capacity)

    counts = [int((mask & (dest == b)).sum()) for b in range(num_dest)]
    assert int(need) == max(counts) and need.dtype == jnp.int32
    assert bool(overflow) == (max(counts) > capacity)
    assert bool(overflow) == (case == "one_bucket_overflows")
    send_mask = np.asarray(send_mask)
    assert send_mask.shape == (num_dest, capacity)
    assert set(send) == set(cols)
    for m, c in cols.items():
        assert send[m].shape == (num_dest, capacity)
        assert send[m].dtype == c.dtype
    for b in range(num_dest):
        sent = sorted(zip(*(np.asarray(send[m])[b][send_mask[b]].tolist()
                            for m in cols)))
        bound = sorted(zip(*(c[mask & (dest == b)].tolist()
                             for c in cols.values())))
        if counts[b] <= capacity:
            assert sent == bound
        else:
            assert len(sent) == capacity == len(set(sent))
            assert set(sent) <= set(bound)


def test_shuffle_rows_preserves_multiset(mesh, rng):
    rows = 128 * N_DEV
    vals = rng.permutation(rows).astype(np.int64)  # unique, so routing is checkable
    dest = rng.integers(0, N_DEV, rows).astype(np.int32)
    mask = rng.random(rows) < 0.8

    cap = 128  # generous: per-device per-dest load ~16
    def per_shard(cols, d, m):
        rc, rm, ovf, _need = shuffle_rows(cols, d, m, PART_AXIS, N_DEV, cap)
        return rc, rm, ovf

    from jax.sharding import PartitionSpec as P
    fn = jax.jit(jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=({"v": P(PART_AXIS)}, P(PART_AXIS), P(PART_AXIS)),
        out_specs=({"v": P(PART_AXIS)}, P(PART_AXIS), P(PART_AXIS))))
    rc, rm, ovf = fn({"v": _place(mesh, vals)}, _place(mesh, dest),
                     _place(mesh, mask))
    assert not np.any(np.asarray(ovf))
    got = np.sort(np.asarray(rc["v"])[np.asarray(rm)])
    want = np.sort(vals[mask])
    np.testing.assert_array_equal(got, want)

    # routing: rows for destination d actually land on shard d
    rm_np = np.asarray(rm).reshape(N_DEV, -1)
    rv_np = np.asarray(rc["v"]).reshape(N_DEV, -1)
    val_to_dest = {int(v): int(d) for v, d, m in zip(vals, dest, mask) if m}
    for shard in range(N_DEV):
        for v in rv_np[shard][rm_np[shard]]:
            assert val_to_dest[int(v)] == shard


def test_shuffle_overflow_flag(mesh):
    rows = 64 * N_DEV
    vals = np.arange(rows, dtype=np.int64)
    dest = np.zeros(rows, dtype=np.int32)  # all rows to device 0
    mask = np.ones(rows, dtype=bool)

    from jax.sharding import PartitionSpec as P
    def per_shard(cols, d, m):
        return shuffle_rows(cols, d, m, PART_AXIS, N_DEV, 8)

    fn = jax.jit(jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=({"v": P(PART_AXIS)}, P(PART_AXIS), P(PART_AXIS)),
        out_specs=({"v": P(PART_AXIS)}, P(PART_AXIS), P(PART_AXIS),
                   P(PART_AXIS))))
    rc, rm, ovf, need = fn({"v": _place(mesh, vals)}, _place(mesh, dest),
                           _place(mesh, mask))
    assert np.all(np.asarray(ovf))
    # every device's fullest bucket held its whole shard: what a re-run
    # needs, and 8 rows of each shard did fit (which 8 is the sort's choice)
    np.testing.assert_array_equal(np.asarray(need), np.full(N_DEV, 64))
    assert int(np.asarray(rm).sum()) == 8 * N_DEV


def test_distributed_aggregate_matches_single_device(mesh, rng):
    rows = 512 * N_DEV
    g = rng.integers(0, 23, rows).astype(np.int64)
    x = rng.integers(1, 100, rows).astype(np.int64)
    mask = rng.random(rows) < 0.9

    run = distributed_grouped_aggregate(
        mesh, ["g"], [("x", "sum"), ("x", "count"), ("x", "min")],
        partial_capacity=64, final_capacity=16)
    fk, fv, fm, ovf = run({"g": _place(mesh, g), "x": _place(mesh, x)},
                          _place(mesh, mask))
    assert not int(np.asarray(ovf)[0])
    fm = np.asarray(fm)
    keys = np.asarray(fk[0])[fm]
    sums = np.asarray(fv[0])[fm]
    counts = np.asarray(fv[1])[fm]
    mins = np.asarray(fv[2])[fm]

    assert len(keys) == len(np.unique(g[mask]))
    for k in np.unique(g[mask]):
        sel = (g == k) & mask
        i = np.where(keys == k)[0]
        assert len(i) == 1, f"group {k} appears {len(i)} times"
        assert sums[i[0]] == x[sel].sum()
        assert counts[i[0]] == sel.sum()
        assert mins[i[0]] == x[sel].min()


def test_distributed_filter_aggregate_q1_shape(mesh, rng):
    """A q1-shaped fused step: filter + derived column + 2-key group-by."""
    rows = 256 * N_DEV
    flag = rng.integers(0, 3, rows).astype(np.int64)
    status = rng.integers(0, 2, rows).astype(np.int64)
    qty = rng.integers(1, 50, rows).astype(np.float64)
    price = rng.random(rows).astype(np.float64) * 1000
    ship = rng.integers(0, 2500, rows).astype(np.int32)
    mask = np.ones(rows, dtype=bool)

    cutoff = 2000

    def filt(cols, m):
        keep = m & (cols["ship"] <= cutoff)
        cols = dict(cols)
        cols["disc_price"] = cols["price"] * 0.95
        return cols, keep

    run = distributed_filter_aggregate(
        mesh, filt, ["flag", "status"],
        [("qty", "sum"), ("disc_price", "sum"), ("qty", "count")],
        partial_capacity=16, final_capacity=8)
    fk, fv, fm, ovf = run(
        {"flag": _place(mesh, flag), "status": _place(mesh, status),
         "qty": _place(mesh, qty), "price": _place(mesh, price),
         "ship": _place(mesh, ship)},
        _place(mesh, mask))
    assert not int(np.asarray(ovf)[0])
    fm = np.asarray(fm)
    kf, ks = np.asarray(fk[0])[fm], np.asarray(fk[1])[fm]
    sq = np.asarray(fv[0])[fm]

    keep = ship <= cutoff
    seen = set()
    for f, s in zip(kf, ks):
        seen.add((int(f), int(s)))
        sel = keep & (flag == f) & (status == s)
        i = np.where((kf == f) & (ks == s))[0]
        np.testing.assert_allclose(sq[i[0]], qty[sel].sum())
    want = {(int(f), int(s)) for f, s in zip(flag[keep], status[keep])}
    assert seen == want


def test_distributed_aggregate_at_scale_with_skew(mesh, rng):
    """VERDICT r4 #9: the mesh step at >=100k rows/device, at a distinct-key
    volume where the capacity-factor state exchange overflows at a tight
    factor and the retry ladder (bigger factor) succeeds — the same
    host-retry mechanism ops/mesh_exec.py / parallel/ici_shuffle.py run."""
    rows_per_dev = 131_072
    rows = rows_per_dev * N_DEV
    n_groups = 60_000
    g = rng.integers(0, n_groups, rows).astype(np.int64)
    # size skew on top: ~25% of rows pile into group 0
    g = np.where(rng.random(rows) < 0.25, 0, g)
    x = rng.integers(1, 50, rows).astype(np.int64)
    mask = rng.random(rows) < 0.95

    # tight capacity factor: each device emits up to ~60k/8 distinct-key
    # states per bucket, far above a cap of half the even share
    tight = distributed_grouped_aggregate(
        mesh, ["g"], [("x", "sum"), ("x", "count")],
        partial_capacity=1 << 16, final_capacity=1 << 14,
        shuffle_capacity=(1 << 16) // N_DEV // 2)
    _, _, _, ovf = tight({"g": _place(mesh, g), "x": _place(mesh, x)},
                         _place(mesh, mask))
    assert int(np.asarray(ovf)[0]), "tight factor did not overflow"

    run = distributed_grouped_aggregate(
        mesh, ["g"], [("x", "sum"), ("x", "count")],
        partial_capacity=1 << 16, final_capacity=1 << 14)
    fk, fv, fm, ovf = run({"g": _place(mesh, g), "x": _place(mesh, x)},
                          _place(mesh, mask))
    assert not int(np.asarray(ovf)[0])
    fm_np = np.asarray(fm)
    keys = np.asarray(fk[0])[fm_np]
    sums = np.asarray(fv[0])[fm_np]
    counts = np.asarray(fv[1])[fm_np]
    assert len(keys) == len(np.unique(g[mask]))
    # exact check on the skewed group and two tail groups
    uniq = np.unique(g[mask])
    for k in (0, int(uniq[1]), int(uniq[-1])):
        sel = (g == k) & mask
        i = np.where(keys == k)[0]
        assert len(i) == 1
        assert sums[i[0]] == x[sel].sum()
        assert counts[i[0]] == sel.sum()
