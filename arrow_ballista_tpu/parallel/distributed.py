"""Distributed operators over the ICI mesh: whole stages as one XLA program.

Where the reference runs partial-agg tasks, materializes shuffle files,
then runs final-agg tasks as a separate stage (stage DAG built by
DistributedPlanner, reference ballista/scheduler/src/planner.rs:80-165),
the on-pod TPU path fuses partial agg → all_to_all → final agg into ONE
compiled program per stage pair: XLA overlaps the collective with compute
and nothing touches the host.  This is the "fuse co-located stages" row of
SURVEY.md §2.5's parallelism table.

The same two-phase plan shape is kept (partial by every device over its
rows, exchange by key hash, final by the bucket owner), so results are
bit-identical to the file-shuffle path — the scheduler can pick either
transport per stage boundary.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..obs.device import observed_jit
from ..ops import kernels as K
from .ici_shuffle import shuffle_rows
from .mesh import PART_AXIS, mesh_axis_size

# aggregate merge rule: partial counts merge by summation, rest by themselves
_MERGE = {"sum": "sum", "count": "sum", "min": "min", "max": "max"}


def shuffle_capacity_of(states: int, n: int) -> int:
    """State rows a destination bucket of a device's send buffer holds
    unless told otherwise: twice the even share of ``states`` over ``n``
    buckets, and never more than ``states`` (all of them in one bucket)."""
    return max(1, min(states, -(-2 * states // n)))


def _identity_filter(cols, mask, *aux):
    return cols, mask


def _row_bytes(arrays) -> int:
    """Bytes of one row across ``arrays`` (columns of equal length)."""
    return sum(a.dtype.itemsize for a in arrays)


class MeshProgram:
    """One program over the device mesh: an ``observed_jit`` around a
    ``shard_map`` (so it is named, counted and spanned like every other
    program), the collective it is built around (``dense_reduce`` |
    ``all_to_all`` | ``all_gather`` | ``none``) and the bytes one call
    hands to that collective, summed over the devices, from shapes."""

    __slots__ = ("jit", "collective", "_bytes")

    def __init__(self, jit, collective: str, bytes_of: Callable[..., int]):
        self.jit, self.collective, self._bytes = jit, collective, bytes_of

    @property
    def name(self) -> str:
        return self.jit.name

    def collective_bytes(self, *args) -> int:
        return int(self._bytes(*args))

    def __call__(self, *args):
        return self.jit(*args)


def distributed_filter_aggregate(
    mesh: Mesh,
    filter_fn,
    key_names: Sequence[str],
    agg_specs: Sequence[Tuple[str, str]],
    partial_capacity: int,
    final_capacity: int,
    axis: str = PART_AXIS,
    key_ranges=None,
    shuffle_capacity: int = None,
):
    """Fused scan-filter → partial agg → ICI shuffle → final agg step.

    ``filter_fn(cols, mask) -> (cols, mask)`` runs per shard first (the
    stage's projection/filter pipeline).  ``agg_specs``: (value_column,
    how) with how in sum/count/min/max — AVG is decomposed into sum+count
    by the planner, the same two-phase split the reference inherits from
    DataFusion.  ``key_ranges`` (static per-key (lo, hi) bounds or None)
    selects the dense sort-free grouping path on both sides of the
    exchange — see kernels.grouped_aggregate.

    The three static bounds, per device: ``partial_capacity`` group states
    out of the shard's rows (a shard of that many rows cannot overflow it),
    ``shuffle_capacity`` states a destination bucket of the send buffer
    (``shuffle_capacity_of(partial_capacity, n)`` unless given),
    ``final_capacity`` groups owned after the exchange (``n *
    shuffle_capacity``, all a device can receive, cannot overflow it).

    Returns ``run(cols, mask) -> (out_keys, out_vals, out_mask, stats)``
    with outputs sharded over the mesh (device d owns the groups whose
    key-hash bucket is d, compacted to the front of its ``final_capacity``
    slots), each of shape ``[n * final_capacity]``, and ``stats``, a
    replicated int32 ``[overflow, bucket_need, groups_max, groups_out]``:
    whether any bound was passed (the result is then short of rows and must
    not be used), the fullest send bucket's states on any device (the
    ``shuffle_capacity`` a re-run needs), the most groups a device owns and
    the groups in all.
    """
    n = mesh_axis_size(mesh, axis)
    cap = shuffle_capacity if shuffle_capacity is not None \
        else shuffle_capacity_of(partial_capacity, n)
    sent = {}       # bytes a device hands to the all_to_all, set as it traces

    def per_shard(cols: Dict[str, jnp.ndarray], mask: jnp.ndarray, *aux):
        cols, mask = filter_fn(cols, mask, *aux)
        keys = [cols[k] for k in key_names]
        vals = [(cols[v], how) for v, how in agg_specs]
        pk, pv, pmask, ovf1 = K.grouped_aggregate(keys, vals, mask,
                                                  partial_capacity,
                                                  key_ranges=key_ranges)
        shuffled = {f"k{i}": a for i, a in enumerate(pk)}
        shuffled.update({f"v{i}": a for i, a in enumerate(pv)})
        # n buckets of cap state rows, each with its mask byte
        sent["bytes"] = n * cap * (_row_bytes(shuffled.values()) + 1)
        dest = K.bucket_of(pk, n)
        recv, rmask, ovf2, need = shuffle_rows(shuffled, dest, pmask, axis,
                                               n, cap)
        rk = [recv[f"k{i}"] for i in range(len(pk))]
        rv = [(recv[f"v{i}"], _MERGE[agg_specs[i][1]]) for i in range(len(pv))]
        fk, fv, fmask, ovf3 = K.grouped_aggregate(rk, rv, rmask,
                                                  final_capacity,
                                                  key_ranges=key_ranges)
        flags = K.overflow_flag(ovf1) | ovf2[0] | K.overflow_flag(ovf3)
        groups = jnp.sum(fmask, dtype=jnp.int32)
        stats = jnp.stack([
            lax.psum(flags.astype(jnp.int32), axis),
            lax.pmax(need[0], axis),
            lax.pmax(groups, axis),
            lax.psum(groups, axis)])
        return fk, fv, fmask, stats

    return _make_runner(
        "mesh.agg_exchange", f"k{len(key_names)}", per_shard, mesh,
        _agg_specs_of(axis, len(key_names), len(agg_specs), P(axis)),
        "all_to_all", lambda *args: n * sent.get("bytes", 0))


def distributed_dense_aggregate(
    mesh: Mesh,
    filter_fn,
    key_names: Sequence[str],
    agg_specs: Sequence[Tuple[str, str]],
    key_ranges,
    domain: int,
    axis: str = PART_AXIS,
):
    """Reduce-collective aggregate for dense key domains: every device
    reduces its row shard into slot-aligned dense states
    (kernels.dense_group_states — slot d IS key combination d), then the
    cross-device merge is ONE elementwise ``psum``/``pmin``/``pmax`` per
    aggregate over ``[domain]``-element arrays.  No all_to_all, no shuffle
    capacity, no skew sensitivity; the exchanged payload for TPC-H q1 is
    6 slots x a few aggregates.

    This is the reduce-collective counterpart of the all_to_all exchange in
    ``distributed_filter_aggregate`` — where the reference's final-agg stage
    always consumes hash-partitioned shuffle files
    (ballista/scheduler/src/planner.rs:80-165), a dense domain lets the TPU
    path replace the exchange with the collective that actually matches the
    dataflow (an elementwise reduction over aligned accumulators).

    Returns ``run(cols, mask) -> (keys, vals, mask, overflow)`` with
    REPLICATED outputs of shape ``[domain]`` (groups compacted to the
    front in ascending fused-key order, matching the sort path's order).
    """

    n = mesh_axis_size(mesh, axis)
    sent = {}       # bytes a device hands to the reduce, set as it traces

    def per_shard(cols: Dict[str, jnp.ndarray], mask: jnp.ndarray, *aux):
        cols, mask = filter_fn(cols, mask, *aux)
        keys = [cols[k] for k in key_names]
        vals = [(cols[v], how) for v, how in agg_specs]
        dense_vals, exists_cnt, bad = K.dense_group_states(
            keys, vals, mask, key_ranges, domain)
        # [domain] slots of every aggregate and of the exists count
        sent["bytes"] = sum(v.size * v.dtype.itemsize
                            for v in (*dense_vals, exists_cnt))
        merged = []
        for v, (_, how) in zip(dense_vals, agg_specs):
            if how in ("sum", "count"):
                merged.append(lax.psum(v, axis))
            elif how == "min":
                merged.append(lax.pmin(v, axis))
            else:
                merged.append(lax.pmax(v, axis))
        exists = lax.psum(exists_cnt, axis) > 0
        bad = lax.psum(bad.astype(jnp.int32), axis) > 0
        fk, fv, fmask, ovf = K.compact_dense_states(
            [k.dtype for k in keys], merged, exists, domain, key_ranges,
            domain)
        return fk, fv, fmask, ovf | bad

    return _make_runner(
        "mesh.agg_dense", f"k{len(key_names)}", per_shard, mesh,
        _agg_specs_of(axis, len(key_names), len(agg_specs), P()),
        "dense_reduce", lambda *args: n * sent.get("bytes", 0))


def distributed_partial_aggregate(
    mesh: Mesh,
    derive_fn,
    key_names: Sequence[str],
    agg_specs: Sequence[Tuple[str, str]],
    capacity: int,
    axis: str = PART_AXIS,
    key_ranges=None,
):
    """Mesh-local HALF of the hybrid exchange: derive -> per-device grouped
    aggregate, NO collective.  Each device reduces its row shard to group
    states; the cross-HOST merge happens via the ordinary file shuffle +
    final aggregate (SURVEY §2.5 north star: "ICI shuffle for co-located
    executors, Flight fallback across hosts" — this is the ICI-side piece
    that composes with the file side).

    Returns ``run(cols, mask) -> (keys, vals, mask, overflow)`` where each
    output is the concatenation of every device's ``capacity`` state rows.
    """
    def per_shard(cols: Dict[str, jnp.ndarray], mask: jnp.ndarray, *aux):
        cols, mask = derive_fn(cols, mask, *aux)
        keys = [cols[k] for k in key_names]
        vals = [(cols[v], how) for v, how in agg_specs]
        pk, pv, pmask, ovf = K.grouped_aggregate(keys, vals, mask, capacity,
                                                 key_ranges=key_ranges)
        overflow = lax.psum(K.overflow_flag(ovf).astype(jnp.int32), axis) > 0
        return pk, pv, pmask, overflow

    # the states leave through the file shuffle: no collective but the flag
    return _make_runner(
        "mesh.agg_partial", f"k{len(key_names)}", per_shard, mesh,
        _agg_specs_of(axis, len(key_names), len(agg_specs), P(axis)),
        "none", lambda *args: 0)


def _agg_specs_of(axis: str, n_keys: int, n_aggs: int, out):
    """``make_specs`` of the aggregate programs: columns and mask sharded by
    rows, whatever follows them (the expressions' lookup tables) replicated;
    keys, states and mask leave as ``out``, the overflow flag replicated."""
    row = P(axis)

    def make_specs(cols, mask, *aux):
        return ({name: row for name in cols}, row, *(P(),) * len(aux)), \
               ([out] * n_keys, [out] * n_aggs, out, P())

    return make_specs


def _make_runner(sig: str, variant: str, per_shard, mesh, make_specs,
                 collective: str, bytes_of) -> MeshProgram:
    """``per_shard`` over ``mesh`` as one ``observed_jit`` program named
    ``program_name(sig, variant)``.  ``make_specs(*args) -> (in_specs,
    out_specs)`` reads only the arguments' structure, so it runs as the
    program traces.  jit keys the executable on the arguments' shapes;
    callers dispatch under ``MESH_DISPATCH_LOCK``, so two tasks never
    compile one signature at once."""

    def program(*args):
        in_specs, out_specs = make_specs(*args)
        # ballista: allow=deprecated-jax-api — jax.shard_map is the installed jax's API
        return jax.shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs)(*args)

    return MeshProgram(observed_jit(sig, program, variant=variant),
                       collective, bytes_of)


def _make_join_runner(sig: str, per_shard, mesh, probe_names, build_names,
                      join_type, axis, collective: str,
                      bytes_of) -> MeshProgram:
    """Runner for the two join variants: ``run(pcols, pmask, bcols,
    bmask)``."""
    row = P(axis)

    def make_specs(pcols, pmask, bcols, bmask):
        in_specs = ({m: row for m in pcols}, row, {m: row for m in bcols}, row)
        out_names = (list(probe_names) if join_type in ("semi", "anti")
                     else list(probe_names) + list(build_names))
        out_specs = ({m: row for m in out_names}, row, P())
        return in_specs, out_specs

    return _make_runner(sig, join_type, per_shard, mesh, make_specs,
                        collective, bytes_of)


def _probe_emit(join_type, key_names, sflags, null_key_sentinel, probe_names,
                build_names, build_fill, out_capacity,
                p_cols, p_mask, b_cols, b_mask):
    """Local half of a hash join, shared by the partitioned and broadcast
    variants.  Both sides are already device-local (either shuffled to the
    bucket owner, or the build side all_gathered).  A semi or anti join
    asks only whether a live build row has the probe row's key: one sort
    of both sides' keys and one back (``K.key_membership``), no pair, and
    ``out_capacity`` unused.  An inner or left join takes the sorted build,
    one range lookup, pair expansion and collision re-verification, then
    emits by join type.  Returns ``(out_cols, out_mask, pairs)``:
    ``pairs``, the candidate pairs the lookup found (0 in a semi or anti
    join), past ``out_capacity`` means pairs were left out."""
    rpk = [p_cols[k] for k in key_names]
    rbk = [b_cols[k] for k in key_names]

    if join_type in ("semi", "anti"):
        hit = K.key_membership(rpk, p_mask, rbk, b_mask)
        for i, a in enumerate(rpk):
            if sflags[i]:   # a NULL string key meets nothing
                hit = hit & (a != jnp.asarray(null_key_sentinel,
                                              dtype=a.dtype))
        out_mask = p_mask & (hit if join_type == "semi" else ~hit)
        return ({m: p_cols[m] for m in probe_names}, out_mask,
                jnp.zeros((), jnp.int32))

    bh_sorted, border, _ = K.build_side_sort(rbk, b_mask)
    lo, counts, _ = K.probe_ranges(K.hash64(rpk), p_mask, bh_sorted)
    pi, bp, pair_valid, total = K.expand_pairs(lo, counts, b_mask.shape[0],
                                               out_capacity)
    bidx = border[bp]
    ok = pair_valid & b_mask[bidx]
    for i, (a, b) in enumerate(zip(rpk, rbk)):
        ok = ok & (a[pi] == b[bidx])
        if sflags[i]:
            ok = ok & (a[pi] != jnp.asarray(null_key_sentinel,
                                            dtype=a.dtype))

    out_cols = {m: p_cols[m][pi] for m in probe_names}
    out_cols.update({m: b_cols[m][bidx] for m in build_names})
    out_mask = ok
    if join_type == "left":
        hit = K.segment_any(ok, pi, p_mask.shape[0])
        miss = p_mask & ~hit
        out_cols = {
            m: jnp.concatenate([
                out_cols[m],
                p_cols[m] if m in probe_names else jnp.full(
                    p_mask.shape[0], build_fill[m], out_cols[m].dtype),
            ])
            for m in out_cols
        }
        out_mask = jnp.concatenate([out_mask, miss])
    return out_cols, out_mask, total


def _join_stats(axis, overflow, probe_need, build_need, pairs, out_mask):
    """A join program's last output, replicated int32 ``[overflow,
    probe_need, build_need, pairs_need, rows_out]``: whether any bound was
    passed on any device (the result is then short of rows and must not be
    used), the fullest probe and build send bucket on any device (the
    capacities at which nothing would have been left out), the most
    candidate pairs a device found (the ``out_capacity`` a re-run needs;
    exact where no send bucket overflowed, for a dropped row finds no pair)
    and the rows out in all."""
    return jnp.stack([
        lax.psum(overflow.astype(jnp.int32), axis),
        lax.pmax(probe_need.astype(jnp.int32), axis),
        lax.pmax(build_need.astype(jnp.int32), axis),
        lax.pmax(pairs.astype(jnp.int32), axis),
        lax.psum(jnp.sum(out_mask, dtype=jnp.int32), axis)])


def _pair_bound(join_type: str, out_capacity: Optional[int]) -> int:
    """``out_capacity`` as a join program compares its pairs with it: a
    semi or anti join expands no pair (its ``pairs`` is 0), so its bound
    may be None; an inner or left join's may not."""
    if out_capacity is None:
        if join_type not in ("semi", "anti"):
            raise ValueError(f"a {join_type} join needs an out_capacity")
        return 0
    return out_capacity


def distributed_broadcast_join(
    mesh: Mesh,
    n_keys: int,
    probe_names: Sequence[str],
    build_names: Sequence[str],
    join_type: str,
    out_capacity: Optional[int],
    build_fill: Dict[str, object],
    string_key_flags: Sequence[bool] = (),
    null_key_sentinel: int = 0,
    axis: str = PART_AXIS,
):
    """Broadcast hash join: ``all_gather`` the (small) build side onto every
    device, probe rows never move.  The TPU analog of DataFusion's
    CollectLeft hash join, which the reference planner leaves
    un-repartitioned when one side is small (SURVEY §2.5 exchange
    inventory; reference planner.rs inserts RepartitionExec only around
    Partitioned-mode joins).

    vs the partitioned variant: no all_to_all, no shuffle-capacity skew
    risk (a hot key can land every row of both sides on one device there);
    the build side costs ``n_devices x build_rows`` HBM, so the planner
    gates this on build-side size (MESH_BROADCAST_ROWS).

    Returns ``run(pcols, pmask, bcols, bmask)`` like
    ``distributed_hash_join``, whose ``stats`` has no send bucket to report
    (both needs 0); outputs stay probe-sharded.
    """
    n = mesh_axis_size(mesh, axis)
    out_capacity = _pair_bound(join_type, out_capacity)
    key_names = [f"__jk{i}" for i in range(n_keys)]
    sflags = list(string_key_flags) or [False] * n_keys

    def per_shard(pcols, pmask, bcols, bmask):
        b_all = {k: lax.all_gather(v, axis, tiled=True)
                 for k, v in bcols.items()}
        bm_all = lax.all_gather(bmask, axis, tiled=True)
        out_cols, out_mask, pairs = _probe_emit(
            join_type, key_names, sflags, null_key_sentinel, probe_names,
            build_names, build_fill, out_capacity,
            pcols, pmask, b_all, bm_all)
        none = jnp.zeros((), jnp.int32)
        return out_cols, out_mask, _join_stats(
            axis, pairs > out_capacity, none, none, pairs, out_mask)

    def gathered(pcols, pmask, bcols, bmask):
        # every device receives the whole build side
        return n * bmask.shape[0] * (_row_bytes(bcols.values()) + 1)

    return _make_join_runner("mesh.join_broadcast", per_shard, mesh,
                             probe_names, build_names, join_type, axis,
                             "all_gather", gathered)


def distributed_hash_join(
    mesh: Mesh,
    n_keys: int,
    probe_names: Sequence[str],
    build_names: Sequence[str],
    join_type: str,
    probe_capacity: int,
    build_capacity: int,
    out_capacity: Optional[int],
    build_fill: Dict[str, object],
    string_key_flags: Sequence[bool] = (),
    null_key_sentinel: int = 0,
    axis: str = PART_AXIS,
):
    """Fused partitioned hash join over the ICI mesh: key-bucket all_to_all
    of BOTH sides, then the per-device join of ``_probe_emit`` (a key sort
    for a semi or anti join, the sorted-build range lookup otherwise) —
    one XLA program replacing the reference's two shuffle stage pairs +
    reduce tasks (reference planner.rs:133-152 inserts hash RepartitionExec
    under each join side; exchange inventory SURVEY.md §2.5).

    Input cols carry join keys as ``__jk{i}`` (already compiled: numeric
    pass-through or stable string hashes, ops/expressions.compile_key) plus
    payload columns.  ``join_type``: inner | left | semi | anti.

    The static bounds, per device: ``probe_capacity`` and
    ``build_capacity`` rows a destination bucket of each side's send buffer
    (a device receives ``n`` such buckets a side), ``out_capacity``
    candidate pairs (None in a semi or anti join, which expands none).
    Returns ``run(pcols, pmask, bcols, bmask) ->
    (out_cols, out_mask, stats)`` with outputs sharded over the mesh,
    ``out_capacity`` rows per device for inner joins (left joins add the
    received probe rows for unmatched-row append, semi and anti joins are
    the received probe rows), and ``stats`` as ``_join_stats`` gives it.
    """
    n = mesh_axis_size(mesh, axis)
    out_capacity = _pair_bound(join_type, out_capacity)
    key_names = [f"__jk{i}" for i in range(n_keys)]
    sflags = list(string_key_flags) or [False] * n_keys

    def per_shard(pcols, pmask, bcols, bmask):
        if n == 1:
            # degenerate mesh (single chip): the exchange is an identity —
            # skip the dispatch/compaction entirely instead of paying for
            # worst-case send buffers
            p_recv, p_rmask = pcols, pmask
            b_recv, b_rmask = bcols, bmask
            ovf_exchange = jnp.zeros((), bool)
            p_need = b_need = jnp.zeros((), jnp.int32)
        else:
            pk = [pcols[k] for k in key_names]
            bk = [bcols[k] for k in key_names]
            # ship rows to their key-hash bucket owner (both sides agree)
            pdest = K.bucket_of(pk, n)
            bdest = K.bucket_of(bk, n)
            p_recv, p_rmask, ovf_p, p_need = shuffle_rows(
                pcols, pdest, pmask, axis, n, probe_capacity)
            b_recv, b_rmask, ovf_b, b_need = shuffle_rows(
                bcols, bdest, bmask, axis, n, build_capacity)
            ovf_exchange = ovf_p[0] | ovf_b[0]
            p_need, b_need = p_need[0], b_need[0]
        out_cols, out_mask, pairs = _probe_emit(
            join_type, key_names, sflags, null_key_sentinel, probe_names,
            build_names, build_fill, out_capacity,
            p_recv, p_rmask, b_recv, b_rmask)
        return out_cols, out_mask, _join_stats(
            axis, ovf_exchange | (pairs > out_capacity), p_need, b_need,
            pairs, out_mask)

    def sent(pcols, pmask, bcols, bmask):
        # every device sends n buckets of each side's capacity, with a mask
        # byte a row
        if n == 1:
            return 0
        return n * n * (probe_capacity * (_row_bytes(pcols.values()) + 1)
                        + build_capacity * (_row_bytes(bcols.values()) + 1))

    return _make_join_runner("mesh.join_partitioned", per_shard, mesh,
                             probe_names, build_names, join_type, axis,
                             "all_to_all", sent)


def distributed_grouped_aggregate(
    mesh: Mesh,
    key_names: Sequence[str],
    agg_specs: Sequence[Tuple[str, str]],
    partial_capacity: int,
    final_capacity: int,
    axis: str = PART_AXIS,
    shuffle_capacity: int = None,
):
    """Distributed GROUP BY without a fused filter stage."""
    return distributed_filter_aggregate(
        mesh, _identity_filter, key_names, agg_specs, partial_capacity,
        final_capacity, axis=axis, shuffle_capacity=shuffle_capacity)
