"""ICI-mesh shuffle: hash repartition as one all_to_all collective.

Parity mapping (SURVEY.md §2.5): the reference's shuffle is
ShuffleWriterExec hash-partitioning batches to IPC files
(reference ballista/core/src/execution_plans/shuffle_writer.rs:201-252)
followed by M×N Arrow Flight fetches in ShuffleReaderExec
(shuffle_reader.rs:267-318).  On-pod we collapse write+fetch into a single
`lax.all_to_all` over HBM buffers: no files, no serialization, no host.

Static-shape discipline (XLA cannot all_to_all ragged rows):
- each device sorts its rows on their destination bucket, every column
  carried along the one sort, and cuts the ``[n_dest, capacity]`` send
  buffer out of the sorted columns as ``n_dest`` contiguous slices: no row
  is moved by an index;
- ``capacity`` rows a bucket bound skew; rows past it are not sent, set an
  ``overflow`` flag and are counted in ``need`` (the fullest bucket's live
  rows), so the host never takes a flagged result and re-runs at ``need``,
  which cannot overflow again;
- the all_to_all swaps the leading axis, so device d ends up with every
  source's bucket-d block; flattening gives rows+mask again.

This file is pure device code usable inside `jax.shard_map`; host-side
orchestration (choosing the capacity, re-running on overflow) lives in the
mesh operators (ops/mesh_exec.py).
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def dispatch_to_buckets(
    cols: Dict[str, jnp.ndarray],
    dest: jnp.ndarray,
    mask: jnp.ndarray,
    num_dest: int,
    capacity: int,
) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Lay rows into a ``[num_dest, capacity]`` send buffer per column.

    Returns (send_cols, send_mask, overflow, need).  A bucket's live rows
    past ``capacity`` are left out and flagged via ``overflow``; ``need``
    (int32 scalar) is the fullest bucket's live rows, the capacity at which
    nothing would have been left out.  Slots where ``send_mask`` is False
    hold whatever the slice met (another bucket's rows, padding): every
    consumer reads the mask.
    """
    dkey = jnp.where(mask, dest, num_dest).astype(jnp.int32)
    # One unstable sort on the bucket carries every column: bucket 0's live
    # rows first, then bucket 1's, ..., dead rows last, so bucket b is the
    # contiguous run from its start, and the starts are sums of num_dest
    # counts (num_dest = mesh size, small and static).  The chip sorts a slot
    # with its payload in 5 ns and scatters or gathers a 64-bit word in
    # 44-130 ns; a row-long cumsum compiles for 5-25 s a shape (PERF.md
    # section 6, PRs 29 and 33).  At sf10_mesh4_q18's shapes (15.0M rows in,
    # an int64 key and an int64 state, 4 buckets of 7.5M) this takes 77 ms
    # on one v5e, 72 of them the sort; ranking rows by a cumsum a bucket and
    # scattering each column into the buffer took 3.8 s (PR 36's micro).
    sorted_cols = lax.sort((dkey, *cols.values()), num_keys=1,
                           is_stable=False)[1:]
    per_bucket = [jnp.sum(dkey == b, dtype=jnp.int32)
                  for b in range(num_dest)]
    starts = [jnp.zeros((), jnp.int32)]
    for c in per_bucket[:-1]:
        starts.append(starts[-1] + c)

    # capacity slots of padding keep the last start's slice inside the array
    # (a start is at most the row count): dynamic_slice would clamp it
    send_cols = {}
    for name, col in zip(cols, sorted_cols):
        padded = jnp.pad(col, (0, capacity))
        send_cols[name] = jnp.stack([
            lax.dynamic_slice(padded, (s,), (capacity,)) for s in starts])
    counts = jnp.stack(per_bucket)
    send_mask = jnp.arange(capacity, dtype=jnp.int32)[None, :] \
        < jnp.minimum(counts, capacity)[:, None]
    need = jnp.max(counts)
    return send_cols, send_mask, need > capacity, need


def all_to_all_rows(
    send_cols: Dict[str, jnp.ndarray],
    send_mask: jnp.ndarray,
    axis: str,
) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """Swap bucket blocks across the mesh axis and flatten to rows.

    Must run inside shard_map.  ``send_cols[name]`` is ``[n, capacity]``
    (bucket-major); the collective delivers ``[n, capacity]`` source-major
    blocks which flatten into this device's received rows.
    """
    recv_cols = {
        name: lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                             tiled=True).reshape(-1)
        for name, buf in send_cols.items()
    }
    recv_mask = lax.all_to_all(send_mask, axis, split_axis=0, concat_axis=0,
                               tiled=True).reshape(-1)
    return recv_cols, recv_mask


def shuffle_rows(
    cols: Dict[str, jnp.ndarray],
    dest: jnp.ndarray,
    mask: jnp.ndarray,
    axis: str,
    num_partitions: int,
    capacity: int,
) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full on-pod shuffle for one stage boundary (inside shard_map).

    Each device sends row i to device ``dest[i]``; returns the rows this
    device received (``num_partitions * capacity`` of them, masked), plus
    the local overflow flag and the local ``need`` (dispatch_to_buckets),
    each of shape (1,) (rank ≥1 so it can cross shard_map out_specs;
    callers psum/pmax them across the mesh).
    """
    send_cols, send_mask, overflow, need = dispatch_to_buckets(
        cols, dest, mask, num_partitions, capacity)
    recv_cols, recv_mask = all_to_all_rows(send_cols, send_mask, axis)
    return recv_cols, recv_mask, overflow[None], need[None]
