"""Device kernel tests against numpy/pandas oracles."""
import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr

from arrow_ballista_tpu.ops import kernels as K


def test_grouped_aggregate_matches_pandas(rng):
    n, cap = 1000, 1024
    keys = rng.integers(0, 37, n).astype(np.int64)
    keys2 = rng.integers(0, 5, n).astype(np.int32)
    vals = rng.integers(-100, 100, n).astype(np.int64)
    mask = np.zeros(cap, dtype=bool)
    mask[:n] = rng.random(n) < 0.9

    kd = np.zeros(cap, np.int64); kd[:n] = keys
    k2d = np.zeros(cap, np.int32); k2d[:n] = keys2
    vd = np.zeros(cap, np.int64); vd[:n] = vals

    out_keys, out_vals, out_mask, overflow = K.grouped_aggregate(
        [jnp.asarray(kd), jnp.asarray(k2d)],
        [(jnp.asarray(vd), K.AGG_SUM), (jnp.asarray(vd), K.AGG_COUNT),
         (jnp.asarray(vd), K.AGG_MIN), (jnp.asarray(vd), K.AGG_MAX)],
        jnp.asarray(mask), out_capacity=256,
    )
    assert not bool(overflow)
    m = np.asarray(out_mask)
    got = pd.DataFrame({
        "k": np.asarray(out_keys[0])[m], "k2": np.asarray(out_keys[1])[m],
        "s": np.asarray(out_vals[0])[m], "c": np.asarray(out_vals[1])[m],
        "mn": np.asarray(out_vals[2])[m], "mx": np.asarray(out_vals[3])[m],
    }).sort_values(["k", "k2"]).reset_index(drop=True)

    live = mask[:n]
    exp = (pd.DataFrame({"k": keys[live], "k2": keys2[live], "v": vals[live]})
           .groupby(["k", "k2"], as_index=False)
           .agg(s=("v", "sum"), c=("v", "count"), mn=("v", "min"), mx=("v", "max"))
           .sort_values(["k", "k2"]).reset_index(drop=True))
    pd.testing.assert_frame_equal(got.astype(np.int64), exp.astype(np.int64))


def test_grouped_aggregate_global():
    vals = jnp.asarray(np.array([5, 7, 9, 0], dtype=np.int64))
    mask = jnp.asarray(np.array([True, True, True, False]))
    out_keys, out_vals, out_mask, overflow = K.grouped_aggregate(
        [], [(vals, K.AGG_SUM), (vals, K.AGG_COUNT)], mask, out_capacity=4)
    assert np.asarray(out_mask).tolist() == [True, False, False, False]
    assert int(out_vals[0][0]) == 21 and int(out_vals[1][0]) == 3


def test_grouped_aggregate_overflow_flag():
    n = 64
    keys = jnp.asarray(np.arange(n, dtype=np.int64))
    mask = jnp.ones(n, dtype=bool)
    _, _, _, overflow = K.grouped_aggregate([keys], [(keys, K.AGG_SUM)], mask, out_capacity=8)
    assert bool(overflow)


def _two_sided(bh_sorted, ph, pmask, out_cap):
    """The join's lookup and expansion as they stood before probe_ranges
    and expand_pairs: a two-sided search of the sorted hashes."""
    lo = jnp.searchsorted(bh_sorted, ph, side="left")
    hi = jnp.searchsorted(bh_sorted, ph, side="right")
    counts = jnp.where(pmask, hi - lo, 0)
    offsets = jnp.cumsum(counts)
    total = offsets[-1]
    starts = offsets - counts
    j = jnp.arange(out_cap)
    probe_idx = jnp.clip(jnp.searchsorted(offsets, j, side="right"),
                         0, ph.shape[0] - 1)
    k = j - starts[probe_idx]
    pair_valid = (j < total) & (k >= 0) & (k < counts[probe_idx])
    build_pos = jnp.clip(lo[probe_idx] + k, 0, bh_sorted.shape[0] - 1)
    return lo, counts, (probe_idx, build_pos, pair_valid, total)


def _range_case(name, rng):
    """(build hashes, build liveness, probe hashes, probe mask)."""
    dead = np.uint64(K.DEAD_HASH)
    u64 = lambda n: rng.integers(0, 2**63, n, dtype=np.int64).astype(np.uint64)
    if name == "runs_of_1_to_1000":
        distinct = u64(40)
        bh = np.repeat(distinct, rng.integers(1, 1001, 40))
        rng.shuffle(bh)
        live = rng.random(bh.size) < 0.9
        ph = np.concatenate([rng.choice(distinct, 300), u64(300)])
    elif name == "every_hash_equal":  # force_hash_collisions
        bh, live = np.zeros(512, np.uint64), np.ones(512, bool)
        ph = np.zeros(256, np.uint64)
    elif name == "below_above_and_dead_sentinel":
        bh = np.sort(u64(200)) + np.uint64(5)
        live = np.ones(200, bool); live[::7] = False  # dead build rows
        ph = np.array([0, bh.min() - np.uint64(1), bh.max() + np.uint64(1),
                       dead - np.uint64(1), dead, bh[3], bh[199]], np.uint64)
    elif name == "capacity_one":
        bh, live = np.array([77], np.uint64), np.ones(1, bool)
        ph = np.array([76, 77, 78, dead], np.uint64)
    elif name == "all_dead_build":
        bh, live = u64(64), np.zeros(64, bool)
        ph = np.concatenate([bh[:8], np.array([0, dead], np.uint64)])
    else:
        raise AssertionError(name)
    pmask = rng.random(ph.size) < 0.8  # masked probe rows in every case
    pmask[-1] = True
    return bh, live, ph, pmask


@pytest.mark.parametrize("ties", ["as_sorted", "reversed", "shuffled"])
@pytest.mark.parametrize("case", [
    "runs_of_1_to_1000", "every_hash_equal", "below_above_and_dead_sentinel",
    "capacity_one", "all_dead_build"])
def test_probe_ranges_equals_two_sided_search(case, ties, rng, monkeypatch):
    bh, live, ph, pmask = _range_case(case, rng)
    # the case's hashes are the keys themselves; the lookup's one sort of
    # both sides may leave equal hashes in any order: here in the CPU
    # sort's, with every tie reversed (build rows first), and with the ties
    # in a random order
    monkeypatch.setattr(K, "hash64", lambda arrays: arrays[0])
    if ties != "as_sorted":
        sort = jax.lax.sort
        rank = np.arange(ph.size + bh.size, dtype=np.int32)[::-1]
        if ties == "shuffled":
            rank = rng.permutation(rank)

        def ties_broken_by_rank(operands, num_keys, is_stable):
            keys, src = operands
            keys, _, src = sort((keys, jnp.asarray(rank)[src], src),
                                num_keys=2)
            return keys, src

        monkeypatch.setattr(jax.lax, "sort", ties_broken_by_rank)
    bh_sorted, _, _ = K.build_side_sort([jnp.asarray(bh)], jnp.asarray(live))
    want_sorted = np.sort(np.where(live, bh, np.uint64(K.DEAD_HASH)))
    assert np.array_equal(np.asarray(bh_sorted), want_sorted)
    ph_j, pmask_j = jnp.asarray(ph), jnp.asarray(pmask)
    out_cap = 1 << 17
    want_lo, want_counts, want_pairs = _two_sided(bh_sorted, ph_j, pmask_j,
                                                  out_cap)
    lo, counts, total = K.probe_ranges(ph_j, pmask_j, bh_sorted)
    assert lo.dtype == jnp.int32 and counts.dtype == jnp.int32
    assert np.array_equal(np.asarray(lo), np.asarray(want_lo))
    assert np.array_equal(np.asarray(counts), np.asarray(want_counts))
    assert int(total) == int(want_pairs[3]) <= out_cap
    got = K.expand_pairs(lo, counts, bh_sorted.shape[0], out_cap)
    valid = np.asarray(want_pairs[2])
    assert np.array_equal(np.asarray(got[2]), valid)
    for g, w in zip(got[:2], want_pairs[:2]):
        assert np.array_equal(np.asarray(g)[valid], np.asarray(w)[valid])
    assert int(got[3]) == int(want_pairs[3])


def test_probe_join_expansion(rng):
    build_n, probe_n, cap = 40, 60, 64
    build_keys = rng.integers(0, 20, build_n).astype(np.int64)
    probe_keys = rng.integers(0, 25, probe_n).astype(np.int64)
    bmask = np.zeros(cap, bool); bmask[:build_n] = True
    pmask = np.zeros(cap, bool); pmask[:probe_n] = True
    bk = np.zeros(cap, np.int64); bk[:build_n] = build_keys
    pk = np.zeros(cap, np.int64); pk[:probe_n] = probe_keys

    bh_sorted, order, _ = K.build_side_sort([jnp.asarray(bk)], jnp.asarray(bmask))
    ph = K.hash64([jnp.asarray(pk)])
    out_cap = 4 * cap
    lo, counts, _ = K.probe_ranges(ph, jnp.asarray(pmask), bh_sorted)
    pi, bp, valid, total = K.expand_pairs(lo, counts, cap, out_cap)

    # verify real equality after hash match
    build_key_sorted = jnp.asarray(bk)[order]
    pairs_ok = np.asarray(valid & (jnp.asarray(pk)[pi] == build_key_sorted[bp]))
    got = sorted(
        (int(pk[p]), int(np.asarray(build_key_sorted)[b]))
        for p, b, v in zip(np.asarray(pi), np.asarray(bp), pairs_ok) if v
    )
    exp = sorted(
        (int(p), int(b)) for p in probe_keys for b in build_keys if p == b
    )
    assert got == exp


def test_civil_from_days():
    dates = pd.to_datetime(["1970-01-01", "1992-02-29", "1998-12-01", "2049-07-04", "1901-03-01"])
    days = (dates - pd.Timestamp("1970-01-01")).days.to_numpy().astype(np.int32)
    y, m, d = K.civil_from_days(jnp.asarray(days))
    assert np.asarray(y).tolist() == [1970, 1992, 1998, 2049, 1901]
    assert np.asarray(m).tolist() == [1, 2, 12, 7, 3]
    assert np.asarray(d).tolist() == [1, 29, 1, 4, 1]


def test_sort_order_multi_key_desc():
    k1 = jnp.asarray(np.array([2, 1, 2, 1, 0], dtype=np.int64))
    k2 = jnp.asarray(np.array([5, 9, 3, 9, 1], dtype=np.int32))
    mask = jnp.asarray(np.array([True, True, True, True, False]))
    order = np.asarray(K.sort_order([(k1, True), (k2, False)], mask))
    # expect: k1 asc, k2 desc among live rows; dead row last
    assert order.tolist()[:4] == [1, 3, 0, 2]
    assert order.tolist()[4] == 4


def test_bucket_of_deterministic():
    k = jnp.asarray(np.arange(100, dtype=np.int64))
    b1 = np.asarray(K.bucket_of([k], 8))
    b2 = np.asarray(K.bucket_of([k], 8))
    assert (b1 == b2).all() and b1.min() >= 0 and b1.max() < 8


def test_i64_limb_reductions_match_plain_paths(monkeypatch):
    """The TPU-fast int64 reductions (limb matmul / chunk-offset limb
    segment_sums / two-pass min-max) must be bit-identical to the plain
    segment-op paths on every input class: negatives, full-range
    magnitudes, empty groups, dump slots."""
    import numpy as np
    import jax.numpy as jnp

    from arrow_ballista_tpu.ops import kernels as K

    rng = np.random.default_rng(5)
    n, S = 4096, 37
    seg_np = rng.integers(0, S, n).astype(np.int32)
    vals_np = [
        rng.integers(-2**40, 2**40, n).astype(np.int64),
        rng.integers(-5, 5, n).astype(np.int64) * (2**52),
        np.ones(n, dtype=np.int64),
    ]
    seg = jnp.asarray(seg_np)
    vals = [jnp.asarray(v) for v in vals_np]

    def with_fast(flag, fn):
        K._tpu_backend.cache_clear()
        monkeypatch.setattr(K, "_tpu_backend", lambda: flag)
        try:
            return fn()
        finally:
            monkeypatch.undo()
            K._tpu_backend.cache_clear()

    # small-S: one-hot limb matmul branch
    fast = with_fast(True, lambda: [np.asarray(x) for x in
                                    K.grouped_sums_i64(vals, seg, S)])
    slow = with_fast(False, lambda: [np.asarray(x) for x in
                                     K.grouped_sums_i64(vals, seg, S)])
    for f, s in zip(fast, slow):
        assert np.array_equal(f, s)
        assert f.dtype == np.int64
    # large-S: chunk-offset int32 segment_sum branch
    Sbig = K._MATMUL_SEG_LIMIT + 3
    segb = jnp.asarray(rng.integers(0, Sbig, n).astype(np.int32))
    fast_b = with_fast(True, lambda: [np.asarray(x) for x in
                                      K.grouped_sums_i64(vals, segb, Sbig)])
    slow_b = with_fast(False, lambda: [np.asarray(x) for x in
                                       K.grouped_sums_i64(vals, segb, Sbig)])
    for f, s in zip(fast_b, slow_b):
        assert np.array_equal(f, s)

    # min/max: two-pass int32 vs int64 segment ops, incl. empty-slot idents
    ok = jnp.asarray(rng.random(n) < 0.8)
    Sgap = S + 4  # slots S..S+3 stay empty -> ident values must match
    for is_min in (True, False):
        f = with_fast(True, lambda: np.asarray(
            K.grouped_minmax_i64(vals[0], ok, seg, Sgap, is_min)))
        s = with_fast(False, lambda: np.asarray(
            K.grouped_minmax_i64(vals[0], ok, seg, Sgap, is_min)))
        assert np.array_equal(f, s)

    # the sort path (run scans: one program on every backend)
    keys = [jnp.asarray(rng.integers(0, 50, n).astype(np.int64))]
    mask = jnp.asarray(rng.random(n) < 0.9)
    vcols = [(vals[0], K.AGG_SUM), (vals[1], K.AGG_SUM),
             (jnp.zeros(n, jnp.int64), K.AGG_COUNT),
             (vals[0], K.AGG_MIN), (vals[0], K.AGG_MAX)]
    out_f = with_fast(True, lambda: K.grouped_aggregate(keys, vcols, mask, 64))
    out_s = with_fast(False, lambda: K.grouped_aggregate(keys, vcols, mask, 64))
    for f, s in zip(out_f[0] + out_f[1], out_s[0] + out_s[1]):
        assert np.array_equal(np.asarray(f), np.asarray(s))
    assert np.array_equal(np.asarray(out_f[2]), np.asarray(out_s[2]))

    # DENSE path (key_ranges -> dense_group_states i64 routing): fast vs
    # plain must agree through the public API too, including min/max and a
    # mixed agg list that exercises the position bookkeeping
    dkeys = [jnp.asarray(rng.integers(0, 3, n).astype(np.int32)),
             jnp.asarray(rng.integers(0, 2, n).astype(np.int32))]
    dranges = ((0, 2), (0, 1))
    dv = [(vals[0], K.AGG_SUM), (jnp.zeros(n, jnp.int64), K.AGG_COUNT),
          (vals[0], K.AGG_MIN), (vals[1], K.AGG_SUM), (vals[0], K.AGG_MAX)]
    dout_f = with_fast(True, lambda: K.grouped_aggregate(
        dkeys, dv, mask, 8, key_ranges=dranges))
    dout_s = with_fast(False, lambda: K.grouped_aggregate(
        dkeys, dv, mask, 8, key_ranges=dranges))
    for f, s in zip(dout_f[0] + dout_f[1], dout_s[0] + dout_s[1]):
        assert np.array_equal(np.asarray(f), np.asarray(s))
    assert np.array_equal(np.asarray(dout_f[2]), np.asarray(dout_s[2]))


# --------------------------------------------------------------------------
# int64 sums into a small domain: one contraction on the matrix unit
# --------------------------------------------------------------------------

def _plain_sums(vals, seg, S):
    """numpy int64, wrapping mod 2^64, one row at a time."""
    out = []
    with np.errstate(over="ignore"):
        for v in vals:
            acc = np.zeros(S, np.int64)
            np.add.at(acc, seg, v)
            out.append(acc)
    return out


def _value_classes(rng, n, live):
    """name -> int64[n], dead rows already 0 as the kernel's callers give
    them."""
    i64 = np.iinfo(np.int64)
    full = rng.integers(i64.min, i64.max, n, dtype=np.int64, endpoint=True)
    full[:3] = [i64.min, i64.max, i64.min][:n]      # sums wrap mod 2^64
    out = {
        "full_range": full,
        "all_negative": -rng.integers(1, 2**62, n, dtype=np.int64),
        "all_zero": np.zeros(n, np.int64),
        "count_0_1": np.ones(n, np.int64),
        "q1_magnitudes": rng.integers(0, 10**11, n, dtype=np.int64),
    }
    return {k: np.where(live, v, 0) for k, v in out.items()}


@pytest.mark.parametrize("n", [1, 289, (1 << 15) - 1, (1 << 15) + 7, 1 << 18])
@pytest.mark.parametrize("S", [1, 2, 26, 290, 1024])
def test_contracted_sums_match_numpy(tpu_branches, S, n):
    """Every value class through ONE contraction (a vector given twice is
    contracted once), dead rows in the dump slot S-1: sums bit-identical to
    numpy's wrapping int64, rows per slot equal to the plain count."""
    assert K.i64_sum_path(S, n) == "contraction"
    rng = np.random.default_rng(1000 * S + n % 997)
    live = rng.random(n) < 0.9
    seg = np.where(live, rng.integers(0, max(S - 1, 1), n), S - 1) \
        .astype(np.int32)
    classes = _value_classes(rng, n, live)
    vals_np = list(classes.values())
    vals = [jnp.asarray(v) for v in vals_np]
    vals.append(vals[0])                    # the same array again
    sums, rows = K.grouped_sums_and_rows_i64(vals, jnp.asarray(seg), S)
    want = _plain_sums(vals_np + [vals_np[0]], seg, S)
    for name, got, ref in zip(list(classes) + ["again"], sums, want):
        assert got.dtype == jnp.int64 and got.shape == (S,)
        assert np.array_equal(np.asarray(got), ref), name
    assert rows.dtype == jnp.int32
    assert np.array_equal(np.asarray(rows), np.bincount(seg, minlength=S))


@pytest.mark.parametrize("case", ["pr28_limb_sums", "max_chunks_of_max_parts",
                                  "one_chunk_of_zeros"])
def test_recombine_8bit_limb_sums_in_32bit_arithmetic(case):
    """The recombination alone, at the contraction's limb width."""
    if case == "pr28_limb_sums":
        # PR 28's 16-bit limb sums 0xc130694c, 0x1e8effec, 0, 0 as sums of
        # 8-bit limbs: limb 2i + (limb 2i+1 << 8) is 16-bit limb i
        l0, l1 = 0xC130694C, 0x1E8EFFEC
        total8 = [l0 & 0xFF, l0 >> 8, l1 & 0xFF, l1 >> 8, 0, 0, 0, 0]
        chunks = 4                  # every part under 2^23
        parts = np.zeros((chunks, 8, 1), np.int64)
        for i, t in enumerate(total8):
            parts[:, i, 0] = t // chunks
            parts[0, i, 0] += t % chunks
        want = 0x1E8FC11C694C
    elif case == "max_chunks_of_max_parts":
        # as many chunks as a call may make, every limb sum at its ceiling
        # (2^15 rows of 255), two columns that differ
        top = 255 * K._SEG_CHUNK
        parts = np.full((K._MAX_CHUNKS, 8, 2), top, np.int64)
        parts[:, :, 1] = top - np.arange(8)[None, :]
        want = None
    else:
        parts = np.zeros((1, 8, 1), np.int64)
        want = 0
    assert parts.max() < 1 << 23
    got = np.asarray(K._recombine_chunk_limbs8(jnp.asarray(parts, jnp.int32)))
    for col in range(parts.shape[2]):
        exact = sum(int(parts[:, i, col].sum()) << (8 * i) for i in range(8))
        exact &= (1 << 64) - 1
        if want is not None:
            assert exact == want
        assert int(got[col]) & ((1 << 64) - 1) == exact


@pytest.mark.parametrize("aggs", [
    pytest.param("sums_and_counts", id="q1-int64-sums-and-counts"),
    pytest.param("counts_only", id="counts-only"),
    pytest.param("with_minmax_and_float", id="minmax-and-float-sum"),
])
def test_dense_group_states_rows_from_the_contraction(tpu_branches, aggs):
    """``exists_cnt`` and every count equal the plain count on the same
    inputs; with int64 sums and counts alone (q1) the traced program holds
    no scatter at all."""
    rng = np.random.default_rng(17)
    n = (1 << 15) + 100
    key_ranges = ((-1, 15), (-1, 15))            # q1's 17 x 17 slots
    domain = K.dense_domain(key_ranges)
    assert domain == 289
    k0 = rng.integers(-1, 3, n).astype(np.int32)
    k1 = rng.integers(-1, 2, n).astype(np.int32)
    mask = rng.random(n) < 0.8
    v = rng.integers(-2**62, 2**62, n, dtype=np.int64)
    w = rng.integers(0, 10**11, n, dtype=np.int64)
    f = rng.random(n)
    val_cols = {
        "sums_and_counts": [(v, K.AGG_SUM), (w, K.AGG_COUNT), (w, K.AGG_SUM),
                            (v, K.AGG_COUNT), (v, K.AGG_SUM)],
        "counts_only": [(v, K.AGG_COUNT)],
        "with_minmax_and_float": [(v, K.AGG_MIN), (f, K.AGG_SUM),
                                  (v, K.AGG_SUM), (w, K.AGG_COUNT),
                                  (w, K.AGG_MAX)],
    }[aggs]

    def run(k0, k1, mask, *arrs):
        return K.dense_group_states(
            [k0, k1], [(a, how) for a, (_, how) in zip(arrs, val_cols)],
            mask, key_ranges, domain)

    args = (k0, k1, mask) + tuple(a for a, _ in val_cols)
    dense_vals, exists_cnt, bad = jax.jit(run)(*args)
    slot = (k0 + 1) * 17 + (k1 + 1)
    plain = np.bincount(slot[mask], minlength=domain)
    assert exists_cnt.dtype == jnp.int32 and not bool(bad)
    assert np.array_equal(np.asarray(exists_cnt), plain)
    for (a, how), got in zip(val_cols, dense_vals):
        if how == K.AGG_COUNT:
            assert got.dtype == jnp.int64
            assert np.array_equal(np.asarray(got), plain)
        elif how == K.AGG_SUM and a.dtype == np.int64:
            ref = _plain_sums([np.where(mask, a, 0)],
                              np.where(mask, slot, domain), domain + 1)[0]
            assert np.array_equal(np.asarray(got), ref[:domain])
    prims = set(_primitives(jax.make_jaxpr(run)(*args).jaxpr))
    if aggs != "with_minmax_and_float":
        assert not {p for p in prims if "scatter" in p}, prims
    assert "dot_general" in prims


@pytest.mark.parametrize("backend,S,n,path", [
    ("cpu", 290, 1 << 20, "scatter"),
    ("tpu", 1, 1, "contraction"),
    ("tpu", 290, 1 << 23, "contraction"),
    ("tpu", 1024, 1 << 20, "contraction"),
    ("tpu", 1025, 1 << 20, "chunk_offset"),
    ("tpu", 1 << 22, 1 << 20, "scatter"),       # chunk-offset ids would wrap
    ("tpu", 290, (1 << 30) + 1, "scatter"),     # past the recombination
])
def test_i64_sum_path_is_the_one_authority(request, backend, S, n, path):
    if backend == "tpu":
        request.getfixturevalue("tpu_branches")
    assert K.i64_sum_path(S, n) == path


# --------------------------------------------------------------------------
# keyless (global) aggregate: one masked reduction into one row
# --------------------------------------------------------------------------

_I64 = np.iinfo(np.int64)


def _global_case(name):
    """(value columns as numpy, hows, mask, expected slot-0 values)."""
    rng = np.random.default_rng(11)
    n = 4096
    mask = rng.random(n) < 0.7
    if name == "sum_wraps_mod_2_64":
        # every partial sum is past 2^53 and the total wraps int64
        v = rng.integers(2**61, 2**62, n).astype(np.int64)
        assert int(v[mask].astype(object).sum()) > _I64.max
        return [v], [K.AGG_SUM], mask, [v[mask].sum(dtype=np.int64)]
    if name == "negative_values":
        v = rng.integers(-2**40, 2**40, n).astype(np.int64)
        w = -rng.integers(1, 2**52, n).astype(np.int64)
        return [v, w], [K.AGG_SUM, K.AGG_SUM], mask, \
            [v[mask].sum(dtype=np.int64), w[mask].sum(dtype=np.int64)]
    if name == "count_star":
        return [np.zeros(n, np.int64)], [K.AGG_COUNT], mask, \
            [np.int64(mask.sum())]
    if name == "minmax_at_extremes":
        v = rng.integers(-2**62, 2**62, n).astype(np.int64)
        live = np.flatnonzero(mask)
        v[live[0]], v[live[1]] = _I64.min, _I64.max
        dead = np.flatnonzero(~mask)
        v[dead[:2]] = [_I64.min, _I64.max]     # masked rows must not win
        small = rng.integers(-9, 9, n).astype(np.int64)
        return [v, v, small, small], \
            [K.AGG_MIN, K.AGG_MAX, K.AGG_MIN, K.AGG_MAX], mask, \
            [_I64.min, _I64.max, small[mask].min(), small[mask].max()]
    if name == "float64_sum":
        v = rng.random(n) * 1e6
        return [v, v, v], [K.AGG_SUM, K.AGG_MIN, K.AGG_MAX], mask, \
            [v[mask].sum(), v[mask].min(), v[mask].max()]
    if name == "n_not_a_multiple_of_2_15":
        n = 2 * (1 << 15) + 77
        mask = rng.random(n) < 0.5
        v = rng.integers(-2**62, 2**62, n).astype(np.int64)
        return [v, np.zeros(n, np.int64)], [K.AGG_SUM, K.AGG_COUNT], mask, \
            [v[mask].sum(dtype=np.int64), np.int64(mask.sum())]
    if name == "every_row_masked":
        v = rng.integers(-2**40, 2**40, n).astype(np.int64)
        f = rng.random(n)
        return [v, v, v, v, f, f], \
            [K.AGG_SUM, K.AGG_COUNT, K.AGG_MIN, K.AGG_MAX, K.AGG_SUM,
             K.AGG_MIN], np.zeros(n, bool), \
            [np.int64(0), np.int64(0), _I64.max, _I64.min, 0.0, np.inf]
    raise AssertionError(name)


@pytest.mark.parametrize("out_capacity", [1, 4])
@pytest.mark.parametrize("case", [
    "sum_wraps_mod_2_64", "negative_values", "count_star",
    "minmax_at_extremes", "float64_sum", "n_not_a_multiple_of_2_15",
    "every_row_masked"])
def test_global_aggregate_matches_numpy(case, out_capacity):
    """No keys: slot 0 holds each aggregate over the live rows, exactly
    (int64 mod 2^64, identities on no live row), the rest is padding.  One
    formulation on every backend (a plain int64 reduction read 0.73 ms
    against 1.5 ms for 16-bit limbs at 2^23 rows on the v5e, PR 27), so
    there is no backend to patch here."""
    cols, hows, mask, want = _global_case(case)
    keys, vals, out_mask, overflow = K.grouped_aggregate(
        [], [(jnp.asarray(c), h) for c, h in zip(cols, hows)],
        jnp.asarray(mask), out_capacity)
    assert keys == [] and overflow is None
    assert np.asarray(out_mask).tolist() == \
        [bool(mask.any())] + [False] * (out_capacity - 1)
    for got, col, how, exp in zip(vals, cols, hows, want):
        got = np.asarray(got)
        assert got.shape == (out_capacity,)
        assert got.dtype == (np.int64 if how == K.AGG_COUNT else col.dtype)
        if got.dtype.kind == "f" and how == K.AGG_SUM:
            np.testing.assert_allclose(got[0], exp, rtol=1e-12)
        else:
            assert got[0] == exp, (how, got[0], exp)


def _equations(jaxpr):
    """Every equation, through the sub-jaxprs that eqn params hold (pjit,
    cond branches, loop bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                if isinstance(sub, ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, Jaxpr):
                    yield from _equations(sub)


def _primitives(jaxpr):
    """Every primitive's name."""
    return (eqn.primitive.name for eqn in _equations(jaxpr))


def test_keyless_program_is_reductions_into_one_row():
    """q6's ``agg_grouped__partial_k0`` at a partition's real shape: no
    scatter, gather, sort or scan anywhere in the program the operator
    compiles, and every output one row."""
    from arrow_ballista_tpu import BallistaConfig, Field, INT64, Schema, decimal
    from arrow_ballista_tpu.models import expr as E
    from arrow_ballista_tpu.ops.operators import (
        AggSpec, HashAggregateExec, _SchemaSource)
    from arrow_ballista_tpu.ops.physical import TaskContext

    schema = Schema([Field("price", decimal(2)), Field("disc", decimal(2)),
                     Field("qty", INT64, nullable=True)])
    op = HashAggregateExec(
        _SchemaSource(schema), [],
        [AggSpec("sum", E.BinOp("*", E.Column("price"), E.Column("disc")),
                 "revenue"),
         AggSpec("count", None, "c"), AggSpec("min", E.Column("qty"), "mn"),
         AggSpec("max", E.Column("qty"), "mx")], "partial")
    assert op.program_variant() == "partial_k0"
    op._ensure_compiled(TaskContext(config=BallistaConfig()), schema)
    comp, _, _, _, jfn = op._compiled
    n = 1 << 20
    cols = {f.name: jax.ShapeDtypeStruct((n,), f.dtype.np_dtype)
            for f in schema}
    closed = jfn.jaxpr(cols, jax.ShapeDtypeStruct((n,), np.bool_),
                       comp.aux_arrays({}), 1, ())
    bad = {p for p in _primitives(closed.jaxpr)
           if any(w in p for w in ("scatter", "gather", "sort", "cum"))}
    assert not bad, bad
    assert closed.out_avals and all(a.shape == (1,) for a in closed.out_avals)


# --------------------------------------------------------------------------
# the sort path and the presorted path: runs reduced by segmented scans
# --------------------------------------------------------------------------

def _run_scan_case(name, rng):
    """-> (keys: list of int arrays, vals: int64[n], mask, out_capacity)."""
    i64 = _I64
    n = 1000
    keys = [rng.integers(-20, 20, n).astype(np.int64)]
    vals = rng.integers(-2**40, 2**40, n).astype(np.int64)
    mask = rng.random(n) < 0.7
    cap = 64
    if name == "three_keys":
        keys = [rng.integers(-3, 3, n).astype(np.int64),
                rng.integers(0, 4, n).astype(np.int32),
                rng.integers(-2, 2, n).astype(np.int64)]
        cap = 128
    elif name == "extreme_keys_live":
        keys = [rng.choice(np.array([i64.min, i64.min + 1, -1, 0, 1,
                                     i64.max - 1, i64.max]), n)]
    elif name == "all_dead":
        mask = np.zeros(n, bool)
    elif name == "one_group":
        keys = [np.full(n, -7, np.int64)]
    elif name == "one_live_row":
        mask = np.zeros(n, bool)
        mask[n // 3] = True
    elif name == "every_row_its_own_group":
        keys = [rng.permutation(n).astype(np.int64) - n // 2]
        mask = np.ones(n, bool)
        cap = n
    elif name == "capacity_past_the_rows":
        cap = n + 24
    elif name == "capacity_under_the_groups":
        cap = 7
    elif name == "sums_past_2_32":
        vals = rng.integers(2**31, 2**33, n).astype(np.int64)
    elif name == "sums_wrap_mod_2_64":
        keys = [rng.integers(0, 3, n).astype(np.int64)]
        vals = rng.choice(np.array([i64.max, i64.min, i64.max - 5, -1, 1]),
                          n)
    elif name == "rows_not_a_power_of_two":
        n = 777
        keys = [keys[0][:n]]
        vals, mask = vals[:n], mask[:n]
    elif name == "two_rows":
        n = 2
        keys, vals = [np.array([5, 5], np.int64)], vals[:n]
        mask = np.array([True, True])
        cap = 2
    elif name != "dead_rows_interleaved":
        raise AssertionError(name)
    return keys, vals, mask, cap


_RUN_SCAN_CASES = [
    "dead_rows_interleaved", "three_keys", "extreme_keys_live", "all_dead",
    "one_group", "one_live_row", "every_row_its_own_group",
    "capacity_past_the_rows", "capacity_under_the_groups", "sums_past_2_32",
    "sums_wrap_mod_2_64", "rows_not_a_power_of_two", "two_rows"]


def _expected_groups(keys, vals, mask):
    """numpy/pandas: groups in ascending key order, int64 sums mod 2^64."""
    df = pd.DataFrame({f"k{i}": k[mask] for i, k in enumerate(keys)})
    df["v"] = vals[mask]
    names = [f"k{i}" for i in range(len(keys))]
    rows = []
    for key, g in df.groupby(names, sort=True):
        v = g["v"].to_numpy()
        with np.errstate(over="ignore"):
            s = np.add.reduce(v, dtype=np.int64)
        rows.append((key if isinstance(key, tuple) else (key,),
                     int(s), len(v), int(v.min()), int(v.max())))
    return rows


def _check_groups(out, keys, vals, mask, cap):
    out_keys, out_vals, out_mask, overflow = out
    exp = _expected_groups(keys, vals, mask)
    n = len(mask)
    if cap >= n:
        assert overflow is None
    else:
        assert bool(overflow) == (len(exp) > cap)
    kept = exp[:cap]
    m = np.asarray(out_mask)
    assert m.shape == (cap,) and m.tolist() == \
        [True] * len(kept) + [False] * (cap - len(kept))
    for i, k in enumerate(out_keys):
        assert k.shape == (cap,) and k.dtype == keys[i].dtype
        assert np.asarray(k)[m].tolist() == [r[0][i] for r in kept]
    s, c, mn, mx = (np.asarray(v) for v in out_vals)
    for got, col in ((s, 1), (c, 2), (mn, 3), (mx, 4)):
        assert got.dtype == np.int64 and got.shape == (cap,)
        assert got[m].tolist() == [r[col] for r in kept]
    # an empty slot holds what a merge can take: the identities
    assert (s[~m] == 0).all() and (c[~m] == 0).all()
    assert (mn[~m] == _I64.max).all() and (mx[~m] == _I64.min).all()


@pytest.mark.parametrize("backend", ["cpu", "tpu_branches"])
@pytest.mark.parametrize("case", _RUN_SCAN_CASES)
def test_sort_path_groups_match_pandas(request, rng, case, backend):
    """``grouped_aggregate`` with keys and no dense domain: int64 sum,
    count, min and max of every group, keys in ascending order at the front
    of ``out_capacity`` slots, the overflow contract."""
    if backend == "tpu_branches":
        request.getfixturevalue("tpu_branches")
    keys, vals, mask, cap = _run_scan_case(case, rng)
    v = jnp.asarray(vals)
    out = jax.jit(
        lambda ks, v, m: K.grouped_aggregate(
            ks, [(v, K.AGG_SUM), (v, K.AGG_COUNT), (v, K.AGG_MIN),
                 (v, K.AGG_MAX)], m, cap))(
        [jnp.asarray(k) for k in keys], v, jnp.asarray(mask))
    _check_groups(out, keys, vals, mask, cap)


@pytest.mark.parametrize("backend", ["cpu", "tpu_branches"])
@pytest.mark.parametrize("case", [
    c for c in _RUN_SCAN_CASES if c != "three_keys"] + ["disorder"])
def test_presorted_groups_match_pandas(request, rng, case, backend):
    """``grouped_aggregate_presorted`` on rows whose live keys are in
    order, dead rows lying between them: the same groups, and ``disorder``
    False; on rows out of order the flag and nothing else is promised."""
    if backend == "tpu_branches":
        request.getfixturevalue("tpu_branches")
    keys, vals, mask, cap = _run_scan_case(
        "dead_rows_interleaved" if case == "disorder" else case, rng)
    k = keys[0].copy()
    live = np.flatnonzero(mask)
    if case == "disorder":
        k[live[0]] = k[live].max() + 1    # the first live key is the largest
    else:
        k[live] = np.sort(k[live])        # dead rows keep whatever they hold
    v = jnp.asarray(vals)
    *out, disorder = jax.jit(
        lambda k, v, m: K.grouped_aggregate_presorted(
            [k], [(v, K.AGG_SUM), (v, K.AGG_COUNT), (v, K.AGG_MIN),
                  (v, K.AGG_MAX)], m, cap))(jnp.asarray(k), v,
                                            jnp.asarray(mask))
    assert bool(disorder) == (case == "disorder")
    if case != "disorder":
        _check_groups(out, [k], vals, mask, cap)


def _moved_by_index(jaxpr, n):
    """(primitive, operand shapes) of every scatter, and of every gather
    whose operand has ``n`` or more elements."""
    out = []
    for eqn in _equations(jaxpr):
        name = eqn.primitive.name
        shapes = [v.aval.shape for v in eqn.invars]
        if "scatter" in name or (
                "gather" in name and int(np.prod(shapes[0])) >= n):
            out.append((name, shapes))
    return out


@pytest.mark.parametrize("entry", ["sort_path", "sort_path_three_keys",
                                   "presorted"])
def test_run_scan_program_moves_no_row_by_index(tpu_branches, entry):
    """int64 sum, count, min and max over a batch of 2^20 slots: one sort
    (none presorted) that carries the columns, scans, one sort that brings
    the run ends to the front — no scatter, no gather of a column, no
    ``cumsum`` (which compiles for 5-25 s a shape for the TPU)."""
    n = 1 << 20
    hows = [K.AGG_SUM, K.AGG_COUNT, K.AGG_MIN, K.AGG_MAX]
    i64 = jax.ShapeDtypeStruct((n,), np.int64)
    keys = [i64, jax.ShapeDtypeStruct((n,), np.int32), i64][
        :3 if entry == "sort_path_three_keys" else 1]
    fn = K.grouped_aggregate_presorted if entry == "presorted" \
        else K.grouped_aggregate
    closed = jax.make_jaxpr(
        lambda ks, v, m: fn(ks, [(v, h) for h in hows], m, n))(
        keys, i64, jax.ShapeDtypeStruct((n,), np.bool_))
    assert not _moved_by_index(closed.jaxpr, n)
    prims = list(_primitives(closed.jaxpr))
    assert prims.count("sort") == (1 if entry == "presorted" else 2)
    assert not [p for p in prims if p.startswith("cum")]


def test_exchange_program_moves_no_row_by_index(tpu_branches):
    """``distributed_filter_aggregate`` (q18's inner aggregate: one int64
    key, one int64 sum) over four devices scatters nothing, gathers nothing
    as long as a shard and holds no ``cumsum``: five sorts, two a
    ``grouped_aggregate`` (partial and final: the one that carries the
    columns, the one that brings the run ends to the front) and one
    ``dispatch_to_buckets``' (rows onto their send bucket), whose buckets are
    four ``dynamic_slice``s a column of the sorted shard."""
    from jax.sharding import Mesh

    from arrow_ballista_tpu.ops.mesh_exec import _exchange_bounds
    from arrow_ballista_tpu.parallel import distributed

    per = 1 << 12
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("part",))
    partial, shuffle, final = _exchange_bounds(per, 4)
    run = distributed.distributed_grouped_aggregate(
        mesh, ["k"], [("v", "sum")], partial_capacity=partial,
        final_capacity=final, axis="part", shuffle_capacity=shuffle)
    cols = {c: jax.ShapeDtypeStruct((4 * per,), np.int64) for c in "kv"}
    closed = jax.make_jaxpr(run.jit.__wrapped__)(
        cols, jax.ShapeDtypeStruct((4 * per,), np.bool_))
    assert not _moved_by_index(closed.jaxpr, per)
    prims = list(_primitives(closed.jaxpr))
    assert not [p for p in prims if p.startswith("cum")]
    # the aggregates' four sorts order by the group key (int64) or the group
    # index and carry more; the dispatch's one has the int32 bucket as its
    # key and the key and the state as its payload
    sorts = [[v.aval for v in eqn.invars] for eqn in _equations(closed.jaxpr)
             if eqn.primitive.name == "sort"]
    assert len(sorts) == 5, sorts
    dispatch = [avals for avals in sorts
                if [(a.dtype, a.shape) for a in avals]
                == [(np.int32, (partial,))] + [(np.int64, (partial,))] * 2]
    assert len(dispatch) == 1, sorts
    # two columns into four buckets of `shuffle` slots each
    slices = [eqn for eqn in _equations(closed.jaxpr)
              if eqn.primitive.name == "dynamic_slice"
              and eqn.outvars[0].aval.shape == (shuffle,)]
    assert len(slices) == 8, slices
