"""Core device kernels: pure jit-compatible functions over padded arrays.

These are the TPU replacements for the DataFusion operator internals the
reference leans on (hash aggregate / hash join / sort inside the
ShuffleWriter hot loop, reference
ballista/core/src/execution_plans/shuffle_writer.rs:214-252).  Every kernel
keeps **static shapes**: data-dependent cardinalities (group counts, join
fan-out) go to fixed capacities with liveness masks, which is what lets XLA
compile one fused program per stage.

Key techniques:
- grouping is sort-based (one sort that carries the columns -> boundary
  flags -> a segmented scan per aggregate -> a second sort that brings the
  run ends to the front), exact for any key combination, no hash tables in
  HBM required and no row moved by an index;
- joins sort the build side by a 64-bit mixed key; a probe batch finds every
  row's run of equal hashes in it once (``probe_ranges``: one sort of
  probe and build together, no search), and
  everything after it (``expand_pairs``: variable fan-out through a
  cumulative-offset inversion) takes that range as an operand; *real* key
  equality is then verified so hash collisions never corrupt results;
- calendar decomposition (EXTRACT) uses the civil-from-days algorithm in
  pure integer arithmetic.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..obs.device import observed_jit


# --------------------------------------------------------------------------
# hashing
# --------------------------------------------------------------------------


def force_hash_collisions() -> bool:
    """Collision-stress mode (the reference ships this as the
    ``force_hash_collisions`` cargo feature, reference
    ballista/core/Cargo.toml:40-41): every hash64 becomes a constant, so
    all rows collide into one shuffle bucket / join probe range.  Join and
    aggregate correctness must survive because both re-verify real key
    equality after hashing.  Process-level env flag — set
    ``BALLISTA_FORCE_HASH_COLLISIONS=1`` before any program traces — the
    first read is cached for the process lifetime, so already-traced and
    newly-traced programs can never disagree about hashing (a mid-process
    flip would silently split keys across transports)."""
    global _FORCE_COLLISIONS
    if _FORCE_COLLISIONS is None:
        from ..utils.config import env_flag

        _FORCE_COLLISIONS = bool(env_flag("BALLISTA_FORCE_HASH_COLLISIONS"))
    return _FORCE_COLLISIONS


_FORCE_COLLISIONS: Optional[bool] = None


def hash64(arrays: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Combine columns into a 64-bit mixed hash (splitmix64-style)."""
    if force_hash_collisions():
        return jnp.zeros(arrays[0].shape, dtype=jnp.uint64)
    h = jnp.zeros(arrays[0].shape, dtype=jnp.uint64)
    for a in arrays:
        x = a.astype(jnp.uint64)
        x = (x ^ (x >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> 27)) * jnp.uint64(0x94D049BB133111EB)
        x = x ^ (x >> 31)
        h = h * jnp.uint64(0x9E3779B97F4A7C15) + x
        h = h ^ (h >> 29)
    return h


def bucket_of(key_arrays: Sequence[jnp.ndarray], num_buckets: int) -> jnp.ndarray:
    """Shuffle partition id per row (same role as the reference's
    BatchPartitioner hash path, shuffle_writer.rs:201-252)."""
    return (hash64(key_arrays) % jnp.uint64(num_buckets)).astype(jnp.int32)


# --------------------------------------------------------------------------
# compaction
# --------------------------------------------------------------------------


def compaction_order(mask: jnp.ndarray) -> jnp.ndarray:
    """Stable permutation moving live rows to the front.

    Sort-free: destinations come from two cumsums and the permutation from
    one scatter — O(n) work, and (unlike jnp.argsort on this backend) the
    XLA program compiles in seconds, not minutes."""
    n = mask.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    live_pos = jnp.cumsum(mask) - 1
    dead_pos = jnp.sum(mask) + jnp.cumsum(~mask) - 1
    dest = jnp.where(mask, live_pos, dead_pos).astype(jnp.int32)
    return jnp.zeros(n, dtype=jnp.int32).at[dest].set(idx)


def compact_columns(cols: Dict[str, jnp.ndarray], mask: jnp.ndarray):
    order = compaction_order(mask)
    return {k: v[order] for k, v in cols.items()}, mask[order]


# --------------------------------------------------------------------------
# wire packing: ONE device->host transfer per materialization boundary
# --------------------------------------------------------------------------
#
# A device->host transfer has a FIXED latency — even for a scalar — on top
# of its streaming time.  A boundary that fetches per-column padded arrays
# (or syncs num_rows separately) pays that fixed cost many times over.
# pack_for_host compacts live rows, bit-packs every column AND
# the row count into one int64 buffer on device, so a boundary costs exactly
# one fetch of (live rows x columns) bytes.  The reference has no analog —
# its operators live host-side (shuffle_writer.rs streams host batches);
# this is the TPU-native replacement for that hot loop's memory traffic.


@observed_jit("kernels.pack_for_host",
              static_argnames=("target", "namesi64", "namesf64", "names32"))
def pack_for_host(cols, mask, target: int, namesi64, namesf64, names32):
    """Compact live rows to the front and pack columns + live-row count for
    a minimal device->host transfer.

    Returns ``(buf, fbuf)``: ``buf`` is one flat int64 buffer laid out as
    [count:1][each int64 column:target][all 32-bit columns, bit-paired into
    int64: len(names32)*target/2]; ``fbuf`` stacks float64 columns
    separately (or None) because the TPU X64-emulation pass implements
    s32<->s64 bitcasts but not f64 ones — f64 columns only occur in small
    late-stage outputs (averages), so the extra transfer leaf rides the
    same device_get.  float32 bitcasts to int32 (exact); bool widens to
    int32.  ``target`` caps the packed row count — the host checks
    count<=target and refetches at a larger target otherwise (count rides
    in the same buffer, so the common case is one transfer with no separate
    num_rows sync)."""
    order = compaction_order(mask)[:target]
    parts = [jnp.sum(mask).astype(jnp.int64)[None]]
    for k in namesi64:
        parts.append(cols[k][order])
    if names32:
        w32 = []
        for k in names32:
            v = cols[k]
            if v.dtype == jnp.float32:
                v = jax.lax.bitcast_convert_type(v, jnp.int32)
            else:
                v = v.astype(jnp.int32)
            w32.append(v[order])
        w = jnp.concatenate(w32)
        if w.shape[0] % 2:
            w = jnp.concatenate([w, jnp.zeros(1, jnp.int32)])
        parts.append(jax.lax.bitcast_convert_type(w.reshape(-1, 2), jnp.int64))
    buf = jnp.concatenate(parts)
    fbuf = (jnp.stack([cols[k][order] for k in namesf64])
            if namesf64 else None)
    return buf, fbuf


def unpack_from_host(buf, fbuf, target: int, fieldsi64, fieldsf64, fields32):
    """Host half of pack_for_host: slice the fetched buffers back into
    per-column numpy arrays (views where possible).  ``fields*`` are
    [(name, np_dtype)] in pack order.  Returns (cols, n) or (None, n) when
    the packed target was too small and the caller must refetch."""
    n = int(buf[0])
    if n > target:
        return None, n
    out = {}
    off = 1
    for name, _dt in fieldsi64:
        out[name] = buf[off:off + target][:n]
        off += target
    if fields32:
        w = buf[off:].view(np.int32)[: len(fields32) * target]
        for i, (name, dt) in enumerate(fields32):
            seg = w[i * target:i * target + target][:n]
            if dt.kind == "f":
                out[name] = seg.view(dt)
            elif dt == np.bool_:
                out[name] = seg.astype(np.bool_)
            else:
                out[name] = seg.astype(dt, copy=False)
    for i, (name, _dt) in enumerate(fieldsf64):
        out[name] = fbuf[i][:n]
    return out, n


# --------------------------------------------------------------------------
# sorting
# --------------------------------------------------------------------------


def sort_order(keys: Sequence[Tuple[jnp.ndarray, bool]], mask: jnp.ndarray) -> jnp.ndarray:
    """Permutation sorting live rows by (k1, k2, ...) with per-key
    ascending flags; dead rows sort to the end."""
    seq = []
    for arr, asc in reversed(list(keys)):
        a = arr
        if not asc:
            if a.dtype == jnp.bool_:
                a = ~a
            else:
                a = -a.astype(jnp.int64) if a.dtype.kind == "i" else -a
        seq.append(a)
    seq.append(~mask)  # primary: live rows first
    return jnp.lexsort(seq)


# --------------------------------------------------------------------------
# grouped aggregation (sort-based, static output capacity)
# --------------------------------------------------------------------------

AGG_SUM = "sum"
AGG_COUNT = "count"
AGG_MIN = "min"
AGG_MAX = "max"


DENSE_DOMAIN_LIMIT = 1 << 16  # max enumerable key-combination count


def dense_domain(key_ranges) -> Optional[int]:
    """Enumerable key-combination count when EVERY key has static (lo, hi)
    bounds and the product is within DENSE_DOMAIN_LIMIT; else None.  The
    single authority for 'does the dense path apply' — callers use it to
    clamp output capacities to what the kernel will actually produce."""
    if not key_ranges or any(r is None for r in key_ranges):
        return None
    domain = 1
    for lo, hi in key_ranges:
        domain *= max(0, hi - lo + 1)
    return domain if 0 < domain <= DENSE_DOMAIN_LIMIT else None


def grouped_aggregate_presorted(
    key_cols: List[jnp.ndarray],
    val_cols: List[Tuple[jnp.ndarray, str]],
    mask: jnp.ndarray,
    out_capacity: int,
):
    """Sort-FREE grouping for inputs already ordered by the single group
    key (clustered scans: physical_planner._clustered_having_pushdown):
    ``grouped_aggregate``'s reduction without its first sort.  The rows are
    reduced where they lie — the boundaries and the scans skip dead rows in
    place (``_live_runs``), nothing is compacted and no column is gathered.

    Returns (out_keys, out_vals, out_mask, overflow, disorder): ``disorder``
    is True when live keys were NOT non-decreasing — the caller must then
    discard the result and re-run the sorted path (split runs of one key
    would otherwise emit duplicate partial states, which merge fine at a
    final aggregate but break early-HAVING filters)."""
    assert len(key_cols) == 1, "presorted grouping is single-key"
    keys_f, boundary, (prev_live, prev_keys) = _live_runs(key_cols, mask)
    disorder = jnp.any(mask & prev_live & (key_cols[0] < prev_keys[0]))
    out_keys, out_vals, out_mask, overflow = _reduce_runs(
        keys_f, boundary, val_cols, mask, out_capacity)
    return out_keys, out_vals, out_mask, overflow, disorder


def grouped_aggregate(
    key_cols: List[jnp.ndarray],
    val_cols: List[Tuple[jnp.ndarray, str]],
    mask: jnp.ndarray,
    out_capacity: int,
    key_ranges: Optional[Tuple[Optional[Tuple[int, int]], ...]] = None,
):
    """Group by ``key_cols`` and reduce ``val_cols`` (list of (array, how)).

    Returns (out_keys: list, out_vals: list, out_mask, overflow: bool scalar).
    Exact for arbitrary keys.  ``out_capacity`` bounds distinct groups;
    ``overflow`` flags truncation (host raises CapacityError).

    ``key_ranges``: optional static (lo, hi) bounds per key (inclusive), e.g.
    dictionary-code ranges for string keys.  When every key is bounded and
    the enumerable domain is small, grouping takes the **dense path**: the
    fused key IS the segment id — no sort at all.  This matters enormously
    on TPU, where the sort-based program's XLA compile takes minutes while
    the dense program compiles in seconds (measured: 163 s vs 3.8 s for the
    q1 shape on v5e) and runs ~2.5x faster.  Otherwise grouping is
    sort-based and moves no row by an index (the comment above
    ``i64_sum_path``): ONE unstable sort on the group keys that carries
    the mask and the value vectors along, a segmented scan per aggregate
    over the runs of equal keys (``_reduce_runs``), and a second sort that
    brings the run ends, in key order, to the front of the output.  Float
    aggregates keep their ``jax.ops.segment_*`` lowering (and, for their
    summation order, a stable first sort): no cell runs one on this path.

    CONTRACT: ``key_ranges`` bounds are a caller-guaranteed invariant — every
    live row's key must lie inside its declared range.  On the dense path,
    when the domain fits ``out_capacity`` the overflow flag is statically
    None and out-of-range rows are **silently folded into the scratch slot**
    (dropped); only when the domain exceeds ``out_capacity`` does the
    returned flag also surface bad rows.  Engine callers build ranges
    structurally (dictionary code ranges, bool {0,1}) so violation is
    impossible there; external callers passing literal ranges own the
    guarantee.
    """
    if not key_cols:
        return _global_aggregate(val_cols, mask, out_capacity)
    domain = dense_domain(key_ranges)
    if domain is not None:
        return _grouped_aggregate_dense(key_cols, val_cols, mask,
                                        out_capacity, key_ranges, domain)
    # the comparator reads the key words and nothing else: liveness is not
    # a sort key (dead rows land anywhere and the reduction skips them), and
    # nothing needs stability — equal keys are one run and every integer
    # reduction commutes — but a float sum's order of additions.  The mask
    # and the value vectors ride along; a count reads the mask alone.
    carried = [a for a, how in val_cols if how != AGG_COUNT]
    nk = len(key_cols)
    out = jax.lax.sort(
        (*key_cols, mask, *carried), num_keys=nk,
        is_stable=any(a.dtype.kind == "f" for a in carried))
    keys_s, mask_s, carried_s = list(out[:nk]), out[nk], iter(out[nk + 1:])
    vals_s = [(mask_s if how == AGG_COUNT else next(carried_s), how)
              for _, how in val_cols]
    keys_f, boundary, _ = _live_runs(keys_s, mask_s)
    return _reduce_runs(keys_f, boundary, vals_s, mask_s, out_capacity)


def _global_aggregate(
    val_cols: List[Tuple[jnp.ndarray, str]],
    mask: jnp.ndarray,
    out_capacity: int,
):
    """No group keys: one group, so each aggregate is ONE masked reduction
    over the rows as they lie — no compaction, no gather, no segment ids, no
    scatter.  Slot 0 holds the state and is live iff any row is (a partial
    over no live rows emits no row); a caller's ``out_capacity`` > 1 is
    honoured by padding.  int64 sums and counts are exact mod 2^64, int64
    min/max over no live row give INT64_MAX / INT64_MIN (the merge identities
    ``grouped_minmax_i64`` gives an empty slot), float sums stay in the
    column's dtype.  One group cannot overflow: the flag is statically
    None."""
    out_vals = []
    for a, how in val_cols:
        if how == AGG_COUNT:
            v = jnp.sum(mask, dtype=jnp.int64)
        elif how == AGG_SUM:
            v = jnp.sum(jnp.where(mask, a, jnp.zeros((), a.dtype)))
        elif how == AGG_MIN:
            v = jnp.min(jnp.where(mask, a, _max_ident(a.dtype)))
        elif how == AGG_MAX:
            v = jnp.max(jnp.where(mask, a, _min_ident(a.dtype)))
        else:
            raise ValueError(f"unknown agg {how}")
        out_vals.append(_in_slot0(v, out_capacity))
    return [], out_vals, _in_slot0(jnp.any(mask), out_capacity), None


def _in_slot0(v: jnp.ndarray, capacity: int) -> jnp.ndarray:
    """Scalar -> [capacity] with ``v`` in slot 0 and zeros (False) after."""
    return jnp.pad(v[None], (0, capacity - 1))


def _live_runs(keys: List[jnp.ndarray], mask: jnp.ndarray):
    """The runs of rows whose LIVE rows are in group order (equal keys
    adjacent among the live rows; dead rows anywhere between them).

    Returns (keys_f, boundary, (prev_live, prev_keys)): ``keys_f`` per row
    the keys of the last live row at or before it (so a run's last row holds
    the run's key even where that row is dead), ``boundary`` the live rows
    that start a run, ``prev_live`` / ``prev_keys`` the same carried keys
    one row earlier (whether a live row lies before the row at all, and its
    keys).  The carry is a scan by doubling shifts in which the nearer live
    row wins: no compaction, no gather."""
    n = mask.shape[0]
    has, keys_f = mask, list(keys)
    shift = 1
    while shift < n:
        keys_f = [jnp.where(has, k, jnp.concatenate([k[:shift], k[:-shift]]))
                  for k in keys_f]
        has = has | jnp.concatenate([jnp.zeros(shift, bool), has[:-shift]])
        shift *= 2
    prev_live = jnp.concatenate([jnp.zeros(1, bool), has[:-1]])
    prev_keys = [jnp.concatenate([k[:1], k[:-1]]) for k in keys_f]
    differs = ~prev_live
    for k, pk in zip(keys, prev_keys):
        differs = differs | (k != pk)
    return keys_f, mask & differs, (prev_live, prev_keys)


def _run_scan(states: Tuple[jnp.ndarray, ...], gid: jnp.ndarray, combine):
    """Inclusive scan that restarts wherever ``gid`` (non-decreasing)
    changes, by doubling shifts: ``combine(states, earlier)`` merges each
    row's state with the state ``shift`` rows earlier, taken only while
    both lie in one run, so that a run's last row ends up holding the whole
    run's state.  (Doubling shifts, not ``jnp.cumsum`` / ``lax.cummin``:
    ``_shift_scan``.)"""
    n = gid.shape[0]
    shift = 1
    while shift < n:
        same = jnp.concatenate([jnp.zeros(shift, bool),
                                gid[shift:] == gid[:-shift]])
        earlier = tuple(jnp.concatenate([x[:shift], x[:-shift]])
                        for x in states)
        states = tuple(jnp.where(same, m, x)
                       for m, x in zip(combine(states, earlier), states))
        shift *= 2
    return states


def _i64_words(a: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """int64 -> (high word int32, low word uint32) of its two's
    complement."""
    return (a >> 32).astype(jnp.int32), a.astype(jnp.uint32)


def _i64_of_words(hi: jnp.ndarray, lo: jnp.ndarray) -> jnp.ndarray:
    """The int64 of two 32-bit words, assembled by a bitcast (no emulated
    64-bit arithmetic: ``_recombine_chunk_limbs``)."""
    return jax.lax.bitcast_convert_type(
        jnp.stack([lo.astype(jnp.uint32), hi.astype(jnp.uint32)], axis=-1),
        jnp.int64)


def _add_words(cur, earlier):
    """(hi, lo) + (hi, lo) mod 2^64 in uint32 words with the carry taken
    out by hand: the chip's emulated 64-bit addition is not to be trusted
    (``_recombine_chunk_limbs``)."""
    (hi, lo), (ehi, elo) = cur, earlier
    low = lo + elo
    return hi + ehi + (low < elo).astype(jnp.uint32), low


def _words_less(a, b):
    """a < b for int64s given as (hi int32, lo uint32)."""
    (ahi, alo), (bhi, blo) = a, b
    return (ahi < bhi) | ((ahi == bhi) & (alo < blo))


def _min_words(cur, earlier):
    take = _words_less(earlier, cur)
    return tuple(jnp.where(take, e, c) for c, e in zip(cur, earlier))


def _max_words(cur, earlier):
    take = _words_less(cur, earlier)
    return tuple(jnp.where(take, e, c) for c, e in zip(cur, earlier))


def _front(x: jnp.ndarray, capacity: int) -> jnp.ndarray:
    """The first ``capacity`` slots of ``x``, zero-padded if it is
    shorter."""
    n = x.shape[0]
    return x[:capacity] if capacity <= n else jnp.pad(x, (0, capacity - n))


_SEGMENT_OPS = {AGG_SUM: jax.ops.segment_sum, AGG_MIN: jax.ops.segment_min,
                AGG_MAX: jax.ops.segment_max}


def _identity(how: str, dtype):
    """What a dead row holds under ``how``: the reduction's identity, which
    is also what an empty output slot holds (0, and the INT64_MAX /
    INT64_MIN ``grouped_minmax_i64`` gives an empty slot)."""
    if how == AGG_MIN:
        return _max_ident(dtype)
    if how == AGG_MAX:
        return _min_ident(dtype)
    return jnp.zeros((), dtype)


def _scanned_states(a: jnp.ndarray, how: str, mask: jnp.ndarray,
                    gid: jnp.ndarray):
    """One aggregate's segmented scan over the runs of ``gid``: (words,
    identities, finish) — the state per row as 32-bit words (a run's last
    row holds the group's), what an empty slot's words hold, and how the
    words make the output column.  A count is an int32 sum of the mask, an
    integer of up to 32 bits is reduced in its own type, an int64 as two
    words: sums exactly mod 2^64 with the carry taken by hand, min/max by
    comparing (high, low).  None for any other type (floats): the caller
    keeps their ``jax.ops.segment_*`` lowering."""
    if how == AGG_COUNT:
        words = _run_scan((mask.astype(jnp.int32),), gid,
                          lambda s, e: (s[0] + e[0],))
        return words, (0,), lambda w: w[0].astype(jnp.int64)
    ident = _identity(how, a.dtype)
    pre = jnp.where(mask, a, ident)
    if a.dtype == jnp.int64:
        hi, lo = _i64_words(pre)
        if how == AGG_SUM:
            hi = hi.astype(jnp.uint32)
        combine = {AGG_SUM: _add_words, AGG_MIN: _min_words,
                   AGG_MAX: _max_words}[how]
        return (_run_scan((hi, lo), gid, combine), _i64_words(ident),
                lambda w: _i64_of_words(*w))
    if a.dtype.kind in "iu" and a.dtype.itemsize <= 4:
        op = {AGG_SUM: jnp.add, AGG_MIN: jnp.minimum,
              AGG_MAX: jnp.maximum}[how]
        return (_run_scan((pre,), gid, lambda s, e: (op(s[0], e[0]),)),
                (ident,), lambda w: w[0])
    return None


def _reduce_runs(
    keys_f: List[jnp.ndarray],
    boundary: jnp.ndarray,
    val_cols: List[Tuple[jnp.ndarray, str]],
    mask: jnp.ndarray,
    out_capacity: int,
):
    """Reduce the runs ``_live_runs`` found: shared by the sort path
    (grouped_aggregate) and the clustered presorted path
    (grouped_aggregate_presorted); ``val_cols`` as those get them (a
    count's vector is not read).

    Per aggregate one segmented scan (``_scanned_states``) over the values
    with dead rows at the reduction's identity, so that a run's last row
    holds the group's state.  Then ONE sort keyed on the group index at a
    run's last row, and on a value past every index elsewhere, carries the
    keys and the states to the front, in ascending key order: the only data
    movement.  Slots past the groups hold zero keys and the reductions'
    identities, groups past ``out_capacity`` are dropped and flagged.
    Nothing as long as the input is scattered or gathered, except by a
    float aggregate, which keeps its ``jax.ops.segment_*`` lowering into
    the group's slot."""
    n = mask.shape[0]
    # group index per row: -1 before the first live row
    gid = _shift_scan(boundary.astype(jnp.int32), jnp.add, 0) - 1
    num_groups = gid[n - 1] + 1
    run_end = jnp.concatenate([gid[1:] != gid[:-1], jnp.ones(1, bool)]) \
        & (gid >= 0)

    # per aggregate its scan, or (a float) its segment reduction straight
    # into out_vals
    scans = []
    out_vals: List[Optional[jnp.ndarray]] = []
    for a, how in val_cols:
        if how not in (AGG_SUM, AGG_COUNT, AGG_MIN, AGG_MAX):
            raise ValueError(f"unknown agg {how}")
        scans.append(_scanned_states(a, how, mask, gid))
        if scans[-1] is not None:
            out_vals.append(None)
            continue
        seg_ids = jnp.where(mask & (gid < out_capacity), gid, out_capacity)
        out_vals.append(_SEGMENT_OPS[how](
            jnp.where(mask, a, _identity(how, a.dtype)), seg_ids,
            num_segments=out_capacity + 1)[:out_capacity])

    words = [w for st in scans if st is not None for w in st[0]]
    moved = jax.lax.sort(
        (jnp.where(run_end, gid, _I32_MAX), *keys_f, *words),
        num_keys=1, is_stable=False)[1:]
    out_mask = jnp.arange(out_capacity) < jnp.minimum(num_groups,
                                                      out_capacity)

    def slots(x, empty):
        return jnp.where(out_mask, _front(x, out_capacity),
                         jnp.asarray(empty).astype(x.dtype))

    at = len(keys_f)
    out_keys = [slots(k, 0) for k in moved[:at]]
    for pos, st in enumerate(scans):
        if st is not None:
            _, idents, finish = st
            out_vals[pos] = finish(
                [slots(w, e) for w, e in zip(moved[at:], idents)])
            at += len(idents)

    # out_capacity >= n makes overflow statically impossible: report None so
    # the host skips the flag check — a scalar device->host sync costs its
    # fixed latency once per task
    overflow = (num_groups > out_capacity) if out_capacity < n else None
    return out_keys, out_vals, out_mask, overflow


# --------------------------------------------------------------------------
# int64 grouped reductions without 64-bit scatters
# --------------------------------------------------------------------------
#
# XLA's TPU scatter-add is the segment_sum lowering, and with x64 emulation
# an int64 segment_sum measured 18M rows/s — and the realistic multi-
# aggregate shape (8 int64 sums over one segment id vector, TPC-H q1's
# stage) collapsed to 1M rows/s.  Two families of kernels avoid it.
#
# GROUPS FOUND BY SORTING (grouped_aggregate with keys and no dense domain,
# grouped_aggregate_presorted): no segment ids at all.  The chip sorts a
# slot some twenty times faster than it scatters or gathers one, so the
# sort carries the columns, every reduction is a segmented scan by doubling
# shifts that leaves a group's state on its run's last row (_run_scan; an
# int64 as two 32-bit words, sums with the carry taken by hand), and a
# second sort brings the run ends to the front (_reduce_runs).  On one v5e
# (PR 33's micro, PERF.md section 6; one int64 key, one int64 sum), at
# 15.0M slots: the first sort 68 ms, the carry of the live keys 19 ms, the
# group index 5 ms, the sum 20 ms, the second sort 72 ms, the whole call
# 170 ms (11 ns a slot), and 411 ms at 30.0M slots; presorted at 2^20
# slots 3.4 ms.  The form before it (lexsort, a gather of every column by
# the permutation, then segment ids into the int64 paths below and an int64
# .at[].set of the keys) took 4.4 s and 10.5 s at those sizes — the three
# gathers 0.66 and 2.9 s, the int64 segment_sum 1.9 and 3.9 s (125 ns a
# slot), the key scatter 1.8 and 3.5 s, its lexsort 0.08 and 0.19 s — and
# 243 ms presorted at 2^20.  Float aggregates alone keep jax.ops.segment_*.
#
# GROUPS THAT ARE SLOTS (the dense path: dense_group_states, whose fused key
# IS the segment id) decompose int64 reductions into exact limbs, and
# i64_sum_path says which way a call's sums go:
#
# - sums into at most _MATMUL_SEG_LIMIT slots ("contraction"): per chunk of
#   rows ONE contraction on the matrix unit, in a type the unit has — the
#   eight 8-bit limbs of every distinct value vector and one row of ones
#   (the rows per slot: every count, and dense_group_states' exists_cnt) as
#   bfloat16 against the chunk's one-hot(segment) as bfloat16, accumulated
#   in float32.  Products of integers under 2^8 and partial sums under 2^23
#   are exact in float32 in any order.  The per-chunk sums are recombined in
#   32-bit arithmetic alone (_recombine_chunk_limbs).  On one v5e, five
#   sums and four counts of 2^23 rows into 290 slots (q1's shape): 8.6 ms,
#   about 1 ns a row and some twenty times the HBM bound; the cost is the
#   making of the limb rows, not the multiply-adds (290 slots or 13, bf16
#   or int8 alike).  The form before it — 16-bit limbs in int32 through an
#   int32 dot, counts as four more value vectors, rows per slot by an int32
#   segment_sum — took 85-97 ms, 73 of them the segment_sum (PR 31's
#   micro, PERF.md section 6).
# - sums into more slots, up to the dense domain's 2^16 ("chunk_offset"):
#   chunk-offset int32 segment_sums of 16-bit limbs, recombined the same
#   way; past the sizes that holds (2^27 chunk-slots: 2^26 rows into 2^16
#   slots), the plain int64 segment_sum ("scatter").
# - min/max (grouped_minmax_i64): lexicographic two-pass over (hi32,
#   lo32-with-flipped-sign) int32 segment_min/max; identity values
#   recombine to exactly the int64 idents, so empty slots stay mergeable
#   (mesh pmin/pmax).
#
# Callers of grouped_sums_i64 since PR 33: dense_group_states (through
# grouped_sums_and_rows_i64) alone, every branch of it.  The CPU backend
# keeps plain segment ops there (its scatters are fast and the matmul would
# cost O(n*segments) scalar FLOPs on a host core); the sorting family is
# the same program on every backend.


@lru_cache(maxsize=1)
def _tpu_backend() -> bool:
    return jax.default_backend() == "tpu"


_MATMUL_SEG_LIMIT = 1024  # one-hot matmul while chunk x segments tiles fit
# max rows/chunk.  Chunk-offset path: 2^15 rows x 16-bit limbs < 2^31.
# Contraction: 2^15 rows x 8-bit limbs < 2^23, exact in float32, and a pair
# of limb sums put together, lo + (hi << 8) < 2^23 + 2^31, fits uint32
_SEG_CHUNK = 1 << 15
# chunk-offset path ceiling on C*(S+1): keeps the per-limb scratch buffer
# <= 512 MB int32 AND far from the int32 id wrap at 2^31 (advisor r4:
# wrapped ids silently dropped rows -> wrong aggregates with no error)
_CHUNK_OFFSET_LIMIT = 1 << 27
# chunks the contraction's scan takes an iteration: 11.3 ms unrolled by 1,
# 9.2 by 2, 8.7 by 4, 8.4 by 8 at q1's shape (PR 31's micro)
_SCAN_UNROLL = 4
# chunks a call's rows may make: the 16-bit halves of 2^15 chunk sums add
# up to less than 2^31 and every digit of the carry chain stays under 2^32
_MAX_CHUNKS = 1 << 15


def i64_sum_path(num_segments: int, n: int) -> str:
    """The way the int64 sums (and counts) of ``n`` rows into
    ``num_segments`` slots go where the slot IS the segment id (the dense
    path; groups found by sorting have no segment ids and never ask), from
    what is static: ``"contraction"`` (the matrix unit), ``"chunk_offset"``
    (int32 limb segment_sums) or ``"scatter"`` (plain segment ops: the CPU
    backend, and sizes past what the 32-bit recombination or the
    chunk-offset ids hold).  The single authority: ``grouped_sums_i64``
    branches on it and the operators count ``mxu_grouped_sums`` by it."""
    if not _tpu_backend():
        return "scatter"
    n_chunks = -(-n // _SEG_CHUNK)
    if n_chunks > _MAX_CHUNKS:
        # 2^30 rows in one call: past what the 32-bit recombination holds
        return "scatter"
    if num_segments <= _MATMUL_SEG_LIMIT:
        return "contraction"
    if n_chunks * (num_segments + 1) > _CHUNK_OFFSET_LIMIT:
        # ids = seg + chunk_index*S1 wraps int32 past 2^31 — XLA would then
        # silently DROP the wrapped rows — and the C*S1 scratch buffer per
        # limb reaches multiple GB well before the wrap point: the plain
        # int64 segment_sum (a slow 64-bit scatter, but exact) rather than
        # ever risking silent wrong aggregates
        return "scatter"
    return "chunk_offset"


def _i64_limbs(v: jnp.ndarray) -> List[jnp.ndarray]:
    """Four 16-bit limbs (int32, non-negative) of an int64 array's two's
    complement; limb-wise sums recombine exactly mod 2^64."""
    u = v.astype(jnp.uint64)
    return [((u >> (16 * i)) & jnp.uint64(0xFFFF)).astype(jnp.int32)
            for i in range(4)]


def _i64_limb_rows(vals: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """int64[k] per value -> bfloat16[8 x values, k]: each value's eight
    8-bit limbs (integers in [0, 255]: exact in bfloat16), low limb first —
    the bytes of its two's complement, read off by a bitcast: no 64-bit
    shift is left to the chip's emulation, and one op makes every row."""
    b = jax.lax.bitcast_convert_type(jnp.stack(vals), jnp.uint8)  # [V, k, 8]
    # by way of int32: 4 % quicker on the v5e than uint8 straight to bfloat16
    rows = jnp.moveaxis(b, -1, 1).astype(jnp.int32).astype(jnp.bfloat16)
    return rows.reshape(-1, rows.shape[-1])


def _recombine_chunk_limbs(parts: jnp.ndarray) -> jnp.ndarray:
    """parts: int32[C, 4, ...], per chunk the sums (each in [0, 2^31 +
    2^23)) of the four 16-bit limbs -> int64[...], the sum over the chunks
    recombined exactly mod 2^64.

    In 32-bit arithmetic alone, and the 64-bit value only assembled from
    its two words by a bitcast: the chip's emulated 64-bit addition is not
    to be trusted with it.  As ``sum(limb_sum[i] << 16 * i)`` over int64
    limb sums the v5e compiler gave 0x1e8f40f0694c for limb sums 0xc130694c,
    0x1e8effec, 0, 0 (right: 0x1e8fc11c694c) in q1's sum_disc_price, inside
    this kernel and in some fusions of the recombination alone, not in
    others (PR 28: one group of one sum wrong on one of two seeds).

    The chunk sums are split into 16-bit halves so that their sums over up
    to 2^15 chunks stay under 2^31, then the carries are taken out digit by
    digit (base 2^16; every intermediate under 2^32)."""
    p = parts.astype(jnp.uint32)
    lo16 = jnp.sum(p & jnp.uint32(0xFFFF), axis=0, dtype=jnp.uint32)
    hi16 = jnp.sum(p >> jnp.uint32(16), axis=0, dtype=jnp.uint32)
    digits = []
    carry = jnp.zeros_like(lo16[0])
    for i in range(4):
        t = lo16[i] + carry
        if i:
            t = t + hi16[i - 1]
        digits.append(t & jnp.uint32(0xFFFF))
        carry = t >> jnp.uint32(16)
    words = jnp.stack([digits[0] | (digits[1] << jnp.uint32(16)),
                       digits[2] | (digits[3] << jnp.uint32(16))], axis=-1)
    return jax.lax.bitcast_convert_type(words, jnp.int64)


def _recombine_chunk_limbs8(parts: jnp.ndarray) -> jnp.ndarray:
    """parts: int32[C, 8, ...], per chunk the sums (each in [0, 2^23]: at
    most _SEG_CHUNK rows of an 8-bit limb) of the eight 8-bit limbs ->
    int64[...], as _recombine_chunk_limbs.  Each chunk's pair of limb sums
    makes one 16-bit limb's sum, lo + (hi << 8) < 2^23 + 2^31 in uint32."""
    p = parts.astype(jnp.uint32)
    return _recombine_chunk_limbs(p[:, 0::2] + (p[:, 1::2] << jnp.uint32(8)))


def _contracted_sums_i64(vals: List[jnp.ndarray], seg: jnp.ndarray,
                         num_segments: int):
    """The "contraction" of i64_sum_path: (sums: int64[num_segments] per
    value, rows: int32[num_segments], the rows whose ``seg`` is the slot).
    A value vector given twice (the same array) is contracted once."""
    n = seg.shape[0]
    S = num_segments
    chunk = min(_SEG_CHUNK, n)
    pad = (-n) % chunk
    slot_of, distinct = {}, []
    for v in vals:
        if id(v) not in slot_of:
            slot_of[id(v)] = len(distinct)
            distinct.append(v)
    if pad:
        # padded rows: seg == S matches no one-hot column -> contribute 0
        seg = jnp.concatenate([seg, jnp.full(pad, S, seg.dtype)])
        distinct = [jnp.concatenate([v, jnp.zeros(pad, v.dtype)])
                    for v in distinct]
    iota_s = jnp.arange(S, dtype=jnp.int32)

    # carry-free scan (stacked per-chunk partials, summed after): a
    # zeros-initialized carry has no varying manual axes and trips
    # shard_map's vma check when this runs inside a mesh program
    def body(_, xs):
        vc, sc = xs
        rows = jnp.ones_like(sc, dtype=jnp.bfloat16)[None]
        if vc:
            rows = jnp.concatenate([_i64_limb_rows(vc), rows])
        oh = (sc[:, None] == iota_s[None, :]).astype(jnp.bfloat16)
        return None, jax.lax.dot_general(
            rows, oh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _, parts = jax.lax.scan(
        body, None,
        (tuple(v.reshape(-1, chunk) for v in distinct),
         seg.reshape(-1, chunk)), unroll=_SCAN_UNROLL)
    parts = parts.astype(jnp.int32)   # [chunks, 8 x values + 1, S]: exact
    C, V = parts.shape[0], len(distinct)
    rows = jnp.sum(parts[:, 8 * V], axis=0, dtype=jnp.int32)
    # [chunks, values, limbs, S] -> [chunks, limbs, values, S]
    sums = _recombine_chunk_limbs8(
        parts[:, :8 * V].reshape(C, V, 8, S).transpose(0, 2, 1, 3))
    return [sums[slot_of[id(v)]] for v in vals], rows


def grouped_sums_i64(vals: List[jnp.ndarray], seg: jnp.ndarray,
                     num_segments: int) -> List[jnp.ndarray]:
    """Exact int64 grouped sums of pre-masked values (dead rows must
    already be 0).  ``seg`` is int32 in [0, num_segments); rows may also
    carry seg == num_segments-1 as a dump slot — this computes all slots
    and the caller slices."""
    n = seg.shape[0]
    S = num_segments
    path = i64_sum_path(S, n)
    if path == "scatter":
        return [jax.ops.segment_sum(v, seg, num_segments=S) for v in vals]
    if path == "contraction":
        return _contracted_sums_i64(vals, seg, S)[0]
    # large segment count: chunk-offset int32 segment_sums per limb (per
    # chunk x segment a limb sum stays < 2^31), recombined as above
    chunk = min(_SEG_CHUNK, n)
    S1 = S + 1  # one scratch slot for padded rows
    pad = (-n) % chunk
    if pad:
        seg = jnp.concatenate([seg, jnp.full(pad, S, seg.dtype)])
    C = seg.shape[0] // chunk
    ids = (seg.reshape(C, chunk)
           + (jnp.arange(C, dtype=jnp.int32) * S1)[:, None]).reshape(-1)
    out = []
    for v in vals:
        if pad:
            v = jnp.concatenate([v, jnp.zeros(pad, v.dtype)])
        parts = [jax.ops.segment_sum(limb, ids, num_segments=C * S1)
                 .reshape(C, S1) for limb in _i64_limbs(v)]
        out.append(_recombine_chunk_limbs(jnp.stack(parts, axis=1))[:S])
    return out


def grouped_sums_and_rows_i64(vals: List[jnp.ndarray], seg: jnp.ndarray,
                              num_segments: int):
    """``grouped_sums_i64`` and the rows per slot (int32[num_segments],
    rows in the dump slot counted there): on the contraction path both come
    out of the one contraction and the program holds no scatter."""
    if i64_sum_path(num_segments, seg.shape[0]) == "contraction":
        return _contracted_sums_i64(vals, seg, num_segments)
    rows = jax.ops.segment_sum(jnp.ones(seg.shape, jnp.int32), seg,
                               num_segments=num_segments)
    return grouped_sums_i64(vals, seg, num_segments), rows


# numpy scalars: a jnp constant here would start a backend at import, in
# processes (scheduler, remote client) that must not hold a device
_I32_MAX = np.int32(2**31 - 1)
_I32_MIN = np.int32(-2**31)


def grouped_minmax_i64(v: jnp.ndarray, ok: jnp.ndarray, seg: jnp.ndarray,
                       num_segments: int, is_min: bool) -> jnp.ndarray:
    """Exact int64 grouped min/max via two int32 passes: first the high
    word, then the (unsigned-ordered) low word among rows matching the
    winning high word.  Empty slots recombine to exactly INT64_MAX /
    INT64_MIN — the same merge identities the int64 segment ops produce."""
    if not _tpu_backend():
        ident = _max_ident(v.dtype) if is_min else _min_ident(v.dtype)
        masked = jnp.where(ok, v, ident)
        op = jax.ops.segment_min if is_min else jax.ops.segment_max
        return op(masked, seg, num_segments=num_segments)
    hi = (v >> 32).astype(jnp.int32)
    # low word compared as unsigned: subtract 2^31 so int32 order matches
    lo = ((v & jnp.int64(0xFFFFFFFF)) - jnp.int64(1 << 31)).astype(jnp.int32)
    op = jax.ops.segment_min if is_min else jax.ops.segment_max
    ident = _I32_MAX if is_min else _I32_MIN
    hi_best = op(jnp.where(ok, hi, ident), seg, num_segments=num_segments)
    sel = ok & (hi == hi_best[seg])
    lo_best = op(jnp.where(sel, lo, ident), seg, num_segments=num_segments)
    lo_u = (lo_best.astype(jnp.int64) + jnp.int64(1 << 31)) \
        & jnp.int64(0xFFFFFFFF)
    return (hi_best.astype(jnp.int64) << 32) | lo_u


def _dense_strides(key_ranges):
    """Row-major packing of a dense key domain: per-key sizes and strides.
    The single owner of the packing convention — dense_group_states encodes
    fused keys with it and compact_dense_states decodes them."""
    sizes = [hi - lo + 1 for lo, hi in key_ranges]
    strides = [1] * len(sizes)
    for i in range(len(sizes) - 2, -1, -1):
        strides[i] = strides[i + 1] * sizes[i + 1]
    return sizes, strides


def dense_group_states(
    key_cols: List[jnp.ndarray],
    val_cols: List[Tuple[jnp.ndarray, str]],
    mask: jnp.ndarray,
    key_ranges: Tuple[Tuple[int, int], ...],
    domain: int,
):
    """Slot-aligned dense accumulators: slot d holds key combination d
    (row-major packing over ``key_ranges``), for EVERY d in the domain.

    Returns (dense_vals: list, exists_cnt: int32[domain], bad_rows: bool).
    Because slots are positionally aligned, states from different shards
    merge by pure elementwise reduction (psum/pmin/pmax) — the basis of the
    mesh reduce-collective aggregate (parallel/distributed.py)."""
    sizes, strides = _dense_strides(key_ranges)

    fused = jnp.zeros(mask.shape, dtype=jnp.int32)
    in_range = mask
    for k, (lo, hi), stride in zip(key_cols, key_ranges, strides):
        ki = k.astype(jnp.int32)
        in_range = in_range & (ki >= lo) & (ki <= hi)
        fused = fused + (ki - lo) * jnp.int32(stride)
    # rows outside the declared ranges (impossible for dict codes; would
    # indicate a batch/range mismatch) raise the overflow flag: capacity
    # retries won't help, but surfacing a CapacityError beats silently
    # dropping rows
    bad_rows = jnp.any(mask & ~in_range)
    seg = jnp.where(in_range, fused, domain).astype(jnp.int32)

    # int64 sums batch through the limb path, and the rows per slot — every
    # count and exists_cnt — come with them (one program for every
    # aggregate: the TPU-fast formulation, see grouped_sums_and_rows_i64)
    i64_sums: List[Tuple[int, jnp.ndarray]] = []
    counts: List[int] = []
    masked: Dict[int, jnp.ndarray] = {}
    dense_vals: List[Optional[jnp.ndarray]] = []
    for arr, how in val_cols:
        if how == AGG_COUNT:
            counts.append(len(dense_vals))
            dense_vals.append(None)
        elif how == AGG_SUM and arr.dtype == jnp.int64:
            # one column summed twice is one masked vector, contracted once
            if id(arr) not in masked:
                masked[id(arr)] = jnp.where(in_range, arr,
                                            jnp.zeros((), arr.dtype))
            i64_sums.append((len(dense_vals), masked[id(arr)]))
            dense_vals.append(None)
        elif how == AGG_SUM:
            v = jax.ops.segment_sum(
                jnp.where(in_range, arr, jnp.zeros((), arr.dtype)), seg,
                num_segments=domain + 1)[:domain]
            dense_vals.append(v)
        elif how in (AGG_MIN, AGG_MAX):
            if arr.dtype == jnp.int64:
                v = grouped_minmax_i64(arr, in_range, seg, domain + 1,
                                       is_min=(how == AGG_MIN))[:domain]
            elif how == AGG_MIN:
                v = jax.ops.segment_min(
                    jnp.where(in_range, arr, _max_ident(arr.dtype)), seg,
                    num_segments=domain + 1)[:domain]
            else:
                v = jax.ops.segment_max(
                    jnp.where(in_range, arr, _min_ident(arr.dtype)), seg,
                    num_segments=domain + 1)[:domain]
            dense_vals.append(v)
        else:
            raise ValueError(f"unknown agg {how}")
    # rows outside the ranges sit in slot ``domain`` and are sliced away
    sums, rows = grouped_sums_and_rows_i64([v for _, v in i64_sums], seg,
                                           domain + 1)
    exists_cnt = rows[:domain]
    for (pos, _), s in zip(i64_sums, sums):
        dense_vals[pos] = s[:domain]
    for pos in counts:
        dense_vals[pos] = exists_cnt.astype(jnp.int64)
    return dense_vals, exists_cnt, bad_rows


def compact_dense_states(
    key_cols_dtypes,
    dense_vals: List[jnp.ndarray],
    exists: jnp.ndarray,
    out_capacity: int,
    key_ranges: Tuple[Tuple[int, int], ...],
    domain: int,
):
    """Compact slot-aligned dense states into the (keys, vals, mask,
    overflow) shape the sort path produces: non-empty groups first, in
    ascending fused-key order, padded/truncated to ``out_capacity``.
    ``key_cols_dtypes``: output dtype per key column."""
    sizes, strides = _dense_strides(key_ranges)

    # compact non-empty groups to the front (stable: keeps ascending key
    # order); domain is small, so this sort is trivial
    order = jnp.argsort(~exists, stable=True)
    if domain > out_capacity:
        order = order[:out_capacity]
    num_groups = jnp.sum(exists)
    out_mask_full = exists[order]
    out_vals = [v[order] for v in dense_vals]
    out_keys = []
    for i, ((lo, hi), stride, dt) in enumerate(
            zip(key_ranges, strides, key_cols_dtypes)):
        dk = lo + (order.astype(jnp.int32) // jnp.int32(stride)) % jnp.int32(sizes[i])
        out_keys.append(dk.astype(dt))

    # pad up to out_capacity if the domain is smaller
    if domain < out_capacity:
        pad = out_capacity - domain
        out_mask_full = jnp.concatenate([out_mask_full, jnp.zeros(pad, dtype=bool)])
        out_vals = [jnp.concatenate([v, jnp.zeros(pad, dtype=v.dtype)]) for v in out_vals]
        out_keys = [jnp.concatenate([k, jnp.zeros(pad, dtype=k.dtype)]) for k in out_keys]

    overflow = num_groups > out_capacity
    return out_keys, out_vals, out_mask_full, overflow


def _grouped_aggregate_dense(
    key_cols: List[jnp.ndarray],
    val_cols: List[Tuple[jnp.ndarray, str]],
    mask: jnp.ndarray,
    out_capacity: int,
    key_ranges: Tuple[Tuple[int, int], ...],
    domain: int,
):
    """Dense-domain grouping: every key combination is enumerable, so the
    fused (row-major packed) key is the segment id directly.  Output groups
    come out in ascending fused-key order — the same ascending key order the
    sort path produces."""
    dense_vals, exists_cnt, bad_rows = dense_group_states(
        key_cols, val_cols, mask, key_ranges, domain)
    out_keys, out_vals, out_mask, overflow = compact_dense_states(
        [k.dtype for k in key_cols], dense_vals, exists_cnt > 0,
        out_capacity, key_ranges, domain)
    if domain <= out_capacity:
        # overflow is statically impossible (num_groups <= domain) and the
        # bad_rows guard is structurally excluded for caller-built ranges
        # (dict codes < len(dict) <= rounded range; bool in {0,1}): return
        # None so the host skips the per-task flag sync where
        # remote_device() holds
        return out_keys, out_vals, out_mask, None
    return out_keys, out_vals, out_mask, overflow | bad_rows


def overflow_flag(x):
    """Normalize a grouped_aggregate overflow result for jit-traced
    combinators: None (statically impossible) becomes a constant False
    scalar so flags can be |'d and psum'd uniformly."""
    return jnp.zeros((), bool) if x is None else x


def _max_ident(dtype):
    if dtype.kind == "f":
        return jnp.array(jnp.inf, dtype=dtype)
    return jnp.array(jnp.iinfo(dtype).max, dtype=dtype)


def _min_ident(dtype):
    if dtype.kind == "f":
        return jnp.array(-jnp.inf, dtype=dtype)
    return jnp.array(jnp.iinfo(dtype).min, dtype=dtype)


# --------------------------------------------------------------------------
# join (sorted build + one range lookup a probe batch + offset-inversion
# expansion)
# --------------------------------------------------------------------------

#: hash given to dead build rows: they sort behind every live row
DEAD_HASH = 0xFFFFFFFFFFFFFFFF


def build_side_sort(build_keys: List[jnp.ndarray], build_mask: jnp.ndarray):
    """Sort the build side by mixed 64-bit key; dead rows get DEAD_HASH.

    Returns (hash_sorted: uint64, order: permutation, n_build).
    """
    h = hash64(build_keys)
    h = jnp.where(build_mask, h, jnp.uint64(DEAD_HASH))
    order = jnp.argsort(h)
    return h[order], order, jnp.sum(build_mask)


def probe_ranges(
    probe_hash: jnp.ndarray,
    probe_mask: jnp.ndarray,
    build_hash_sorted: jnp.ndarray,
):
    """The join's one range lookup: the run ``[lo, lo + counts)`` of equal
    hashes in the sorted build, per probe row.

    Returns (lo: int32, counts: int32 masked by ``probe_mask``, total): the
    first index with a hash >= the row's, and how many are equal to it.  A
    hash the build lacks has an empty run; a probe hash equal to DEAD_HASH
    meets the dead rows, whose liveness the caller checks pair by pair.

    Read off one sort of probe and build hashes together, with no search
    and no gather, on every backend: the TPU sorts far faster than it
    gathers.  A binary search is log2(n) dependent gathers of an emulated
    64-bit word per probe row (two of them read 841 ns a row against 2^18
    build slots), this 15 ns a row (PERF.md section 6, PR 29).

    Equal hashes end up in one run, in no particular order inside it (the
    unstable sort compares two words, not three, and compiles in a third of
    the time).  With the running count of build rows along the sorted
    order, a run's probe rows have ``lo`` = the build rows before the run
    and ``counts`` = the build rows inside it; both are carried along the
    run from its first and last place and scattered back to row order.
    """
    n_p = probe_hash.shape[0]
    merged = jnp.concatenate([probe_hash, build_hash_sorted])
    hashes, src = jax.lax.sort(
        (merged, jnp.arange(merged.shape[0], dtype=jnp.int32)),
        num_keys=1, is_stable=False)
    is_build = src >= n_p
    upto = _shift_scan(is_build.astype(jnp.int32), jnp.add, 0)
    first = jnp.concatenate([jnp.ones(1, bool), hashes[1:] != hashes[:-1]])
    last = jnp.concatenate([first[1:], jnp.ones(1, bool)])
    before = _shift_scan(jnp.where(first, upto - is_build, -1),
                         jnp.maximum, -1)
    through = _shift_scan(jnp.where(last, upto, _I32_MAX),
                          jnp.minimum, _I32_MAX, reverse=True)
    dest = jnp.where(is_build, n_p, src)  # build rows fall off the end
    lo = jnp.zeros(n_p, jnp.int32).at[dest].set(before, mode="drop")
    counts = jnp.zeros(n_p, jnp.int32).at[dest].set(through - before,
                                                     mode="drop")
    counts = jnp.where(probe_mask, counts, 0)
    return lo, counts, jnp.sum(counts)


def _shift_scan(x: jnp.ndarray, op, identity, reverse: bool = False):
    """Inclusive scan of ``op`` along ``x`` by doubling shifts: log2(n)
    elementwise passes.  Over a whole batch ``jnp.cumsum`` compiles for
    5-25 s a shape for the TPU and ``lax.cummin`` for a minute; this
    compiles in a second and the passes cost microseconds each (PERF.md
    section 6, PR 29)."""
    n = x.shape[0]
    shift = 1
    while shift < n:
        pad = jnp.full(shift, identity, x.dtype)
        x = op(x, jnp.concatenate([x[shift:], pad]) if reverse
               else jnp.concatenate([pad, x[:-shift]]))
        shift *= 2
    return x


def expand_pairs(lo: jnp.ndarray, counts: jnp.ndarray, n_build: int,
                 out_capacity: int):
    """Expand the ranges of ``probe_ranges`` into candidate pairs.

    Returns (probe_idx, build_pos, pair_valid, total_pairs):
    - ``probe_idx[j]``: which probe row pair j belongs to,
    - ``build_pos[j]``: position in the *sorted* build array,
    - ``pair_valid[j]``: pair j is within the real match set,
    - ``total_pairs``: dynamic count (<= out_capacity or overflow).
    Callers MUST verify real key equality afterwards (hash collisions).
    """
    offsets = _shift_scan(counts, jnp.add, 0)  # inclusive
    total = offsets[-1]
    starts = offsets - counts

    j = jnp.arange(out_capacity)
    # probe row for output slot j: first i with offsets[i] > j
    probe_idx = jnp.searchsorted(offsets, j, side="right")
    probe_idx = jnp.clip(probe_idx, 0, lo.shape[0] - 1)
    k = j - starts[probe_idx]
    build_pos = lo[probe_idx] + k
    pair_valid = (j < total) & (k >= 0) & (k < counts[probe_idx])
    build_pos = jnp.clip(build_pos, 0, n_build - 1)
    return probe_idx, build_pos, pair_valid, total


def segment_any(values: jnp.ndarray, seg_ids: jnp.ndarray, num_segments: int) -> jnp.ndarray:
    """Per-segment logical OR (used for semi/anti reduction)."""
    return jax.ops.segment_max(values.astype(jnp.int32), seg_ids, num_segments=num_segments) > 0


# --------------------------------------------------------------------------
# calendar (EXTRACT) — civil-from-days, pure integer ops
# --------------------------------------------------------------------------


def civil_from_days(days, xp=jnp):
    """Epoch days -> (year, month, day), vectorized (Howard Hinnant's algo).

    ``xp`` is jnp (device) or numpy (host-finalize expression mode).
    """
    z = days.astype("int64") + 719468
    era = xp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + xp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y.astype("int32"), m.astype("int32"), d.astype("int32")


def extract_field(days, field: str, xp=jnp):
    y, m, d = civil_from_days(days, xp)
    if field == "year":
        return y
    if field == "month":
        return m
    if field == "day":
        return d
    raise ValueError(f"unsupported EXTRACT field {field}")


# --------------------------------------------------------------------------
# top-k (sort + limit fusion)
# --------------------------------------------------------------------------


def topk_order(keys, mask, k: int) -> jnp.ndarray:
    """First k positions of the sort order (full sort; XLA's sort is fast)."""
    return sort_order(keys, mask)[:k]
