"""ICI-mesh shuffle: hash repartition as one all_to_all collective.

Parity mapping (SURVEY.md §2.5): the reference's shuffle is
ShuffleWriterExec hash-partitioning batches to IPC files
(reference ballista/core/src/execution_plans/shuffle_writer.rs:201-252)
followed by M×N Arrow Flight fetches in ShuffleReaderExec
(shuffle_reader.rs:267-318).  On-pod we collapse write+fetch into a single
`lax.all_to_all` over HBM buffers: no files, no serialization, no host.

Static-shape discipline (XLA cannot all_to_all ragged rows):
- each device ranks its live rows within their destination bucket and
  scatters them into a ``[n_dest, capacity]`` send buffer (MoE-style
  capacity-factor dispatch);
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
    """Scatter rows into a ``[num_dest, capacity]`` send buffer per column.

    Returns (send_cols, send_mask, overflow, need).  Rows whose
    within-bucket rank exceeds ``capacity`` are left out and flagged via
    ``overflow``; ``need`` (int32 scalar) is the fullest bucket's live rows,
    the capacity at which nothing would have been left out.
    """
    dkey = jnp.where(mask, dest, num_dest).astype(jnp.int32)
    # sort-free ranking: one cumsum per destination (num_dest = mesh size,
    # small and static).  Data-dependent device sorts are the one XLA
    # program measured to compile pathologically on TPU (kernels.py notes),
    # and this dispatch runs inside the fused mesh program.
    rank = jnp.zeros(mask.shape, dtype=jnp.int32)
    counts = []
    for b in range(num_dest):
        is_b = dkey == b
        within = jnp.cumsum(is_b.astype(jnp.int32))
        rank = jnp.where(is_b, within - 1, rank)
        counts.append(within[-1])
    counts = jnp.stack(counts)
    slot_ok = (dkey < num_dest) & (rank < capacity)
    flat = jnp.where(slot_ok, dkey * capacity + rank, num_dest * capacity)

    send_cols = {}
    for name, col in cols.items():
        buf = jnp.zeros((num_dest * capacity + 1,), dtype=col.dtype)
        buf = buf.at[flat].set(col, mode="drop")
        send_cols[name] = buf[:-1].reshape(num_dest, capacity)
    mbuf = jnp.zeros((num_dest * capacity + 1,), dtype=jnp.bool_)
    mbuf = mbuf.at[flat].set(slot_ok, mode="drop")
    send_mask = mbuf[:-1].reshape(num_dest, capacity)
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
