"""ColumnBatch: the unit of data flowing through the engine.

TPU-first design
----------------
The reference engine streams Arrow ``RecordBatch``es of arbitrary length
between operators (e.g. the ShuffleWriter hot loop,
reference ballista/core/src/execution_plans/shuffle_writer.rs:214-252).
XLA wants **static shapes**, so a ColumnBatch is:

- ``columns``: dict name -> device array of fixed *capacity* rows (padded),
- ``mask``: bool[capacity] device array marking live rows.  Filters simply
  clear mask bits — no data-dependent compaction inside a compiled stage.
- ``dicts``: host-side numpy string dictionaries for dictionary-encoded
  string columns (device holds int32 codes).

A whole operator pipeline (filter → project → partial-agg → hash-partition)
therefore compiles to ONE jitted function over ``(columns, mask)`` with a
single static capacity, which XLA fuses into a few HBM passes.  Compaction
happens only at materialization boundaries (shuffle write / host collect),
where it is one argsort+gather.

``ColumnBatch`` itself is a host-side handle, NOT a pytree: jitted kernels
take/return the raw ``(columns, mask)`` pytrees and the handle re-wraps them
with schema + dictionaries.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..obs import device as device_obs
from .schema import Schema


@functools.lru_cache(maxsize=1)
def _platform_remote() -> bool:
    return jax.devices()[0].platform != "cpu"


def pin_to_host() -> None:
    """Keep this process off the accelerator.  A chip belongs to one
    process, the executor; a scheduler daemon or a remote client plans and
    moves bytes but runs no stage, so its arrays live on the CPU platform.
    No-op once a backend is up: a process that already holds a device
    (in-process executors next to a remote context) keeps it."""
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        jax.config.update("jax_platforms", "cpu")


def remote_device() -> bool:
    """True when the default jax device makes device->host syncs expensive
    (a fixed latency per transfer, scalars included) — gates the
    sync-avoidance behaviors (skip shrink(), deferred metrics, join-retry
    elision).  ``BALLISTA_REMOTE_DEVICE=0/1`` overrides explicitly and is
    re-read on every call (only the backend-platform probe is cached): an
    accelerator with fast D2H should set 0 to keep the eager safety nets.
    Default proxy: cpu arrays share host memory; accelerator backends pay
    the transfer.  chip_smoke.py prints the fixed D2H latency this rests
    on."""
    from ..utils.config import env_flag

    env = env_flag("BALLISTA_REMOTE_DEVICE")
    if env is not None:
        return env
    return _platform_remote()


def _pad_to(arr: np.ndarray, capacity: int) -> np.ndarray:
    n = arr.shape[0]
    if n > capacity:
        raise ValueError(f"array of {n} rows exceeds capacity {capacity}")
    if n == capacity:
        return arr
    pad = np.zeros(capacity - n, dtype=arr.dtype)
    return np.concatenate([arr, pad])


def round_capacity(n: int, minimum: int = 1024) -> int:
    """Round a row count up to the next power of two (>= minimum).

    Shape-bucketing discipline: every distinct capacity is one XLA
    compilation, so capacities snap to powers of two to keep the set of
    compiled programs tiny."""
    cap = minimum
    while cap < n:
        cap <<= 1
    return cap


def _null_mask(f, arr: np.ndarray):
    """Boolean mask of NULL (in-band sentinel) positions for a nullable
    non-string field; None when the field can't hold NULLs.  This is the
    decode half of the sentinel discipline — the reference's Arrow validity
    bitmaps exist only at materialization boundaries here."""
    if not f.nullable or f.dtype.is_string:
        return None
    sent = f.dtype.null_sentinel
    if isinstance(sent, float) and sent != sent:  # NaN
        return np.isnan(arr)
    return arr == sent


class ColumnBatch:
    def __init__(
        self,
        schema: Schema,
        columns: Dict[str, jnp.ndarray],
        mask: jnp.ndarray,
        dicts: Optional[Dict[str, np.ndarray]] = None,
        num_rows: Optional[int] = None,
    ):
        self.schema = schema
        self.columns = columns
        self.mask = mask
        self.dicts = dicts or {}
        self._num_rows = num_rows  # lazily computed if None

    # --- construction ---------------------------------------------------
    @staticmethod
    def from_numpy(
        schema: Schema,
        data: Dict[str, np.ndarray],
        dicts: Optional[Dict[str, np.ndarray]] = None,
        capacity: Optional[int] = None,
    ) -> "ColumnBatch":
        """Build a device batch from host numpy columns (already physical:
        string columns passed as int32 codes + dicts)."""
        lengths = {f.name: np.asarray(data[f.name]).shape[0] for f in schema}
        if len(set(lengths.values())) > 1:
            raise ValueError(f"column lengths differ: {lengths}")
        n = next(iter(lengths.values())) if lengths else 0
        cap = capacity or round_capacity(n)
        cols = {}
        for f in schema:
            raw = np.asarray(data[f.name])
            if raw.dtype.kind == "f" and f.dtype.np_dtype.kind in ("i", "u"):
                raise TypeError(
                    f"column {f.name!r}: float data passed for {f.dtype} "
                    "(int-backed); convert to the physical representation first "
                    "(e.g. scaled int64 for decimals)"
                )
            arr = raw.astype(f.dtype.np_dtype, copy=False)
            cols[f.name] = _pad_to(arr, cap)
        mask = np.zeros(cap, dtype=np.bool_)
        mask[:n] = True
        # ONE transfer call for the whole batch: per-column jnp.asarray would
        # pay a host->device dispatch round-trip per column
        nbytes = mask.nbytes + sum(c.nbytes for c in cols.values())
        with device_obs.h2d(nbytes):
            cols, mask = jax.device_put((cols, mask))
        return ColumnBatch(schema, cols, mask, dicts, num_rows=n)

    @staticmethod
    def empty(schema: Schema, capacity: int = 1024) -> "ColumnBatch":
        cols = {f.name: jnp.zeros(capacity, dtype=f.dtype.np_dtype) for f in schema}
        return ColumnBatch(schema, cols, jnp.zeros(capacity, dtype=jnp.bool_), {}, num_rows=0)

    # --- basic properties ----------------------------------------------
    @property
    def capacity(self) -> int:
        return int(self.mask.shape[0])

    @property
    def num_rows(self) -> int:
        if self._num_rows is None:
            with device_obs.device_wait("scalar"):
                self._num_rows = int(_live_rows(self.mask))
        return self._num_rows

    def column(self, name: str) -> jnp.ndarray:
        return self.columns[name]

    def with_data(
        self,
        columns: Dict[str, jnp.ndarray],
        mask: jnp.ndarray,
        schema: Optional[Schema] = None,
        dicts: Optional[Dict[str, np.ndarray]] = None,
    ) -> "ColumnBatch":
        """Re-wrap raw kernel outputs, keeping host-side metadata."""
        return ColumnBatch(schema or self.schema, columns, mask, dicts if dicts is not None else self.dicts)

    def shrink(self, fetch_count: bool = False) -> "ColumnBatch":
        """Compact live rows to the front and drop to the smallest
        power-of-two capacity.  A host decision (syncs on num_rows), used at
        blocking boundaries (agg/join/sort/shuffle inputs) so downstream
        programs compile for small static shapes after selective filters.

        Where remote_device() holds, an unknown num_rows costs a
        fixed-latency fetch, and skipping the shrink merely keeps the
        producer's (already shape-bucketed) capacity — fewer distinct
        compile shapes, cheap extra FLOPs — so the sync is not paid there
        unless ``fetch_count`` asks for it (a batch that many programs
        will read, such as a broadcast join's build)."""
        if self._num_rows is None and remote_device() and not fetch_count:
            return self
        n = self.num_rows
        target = round_capacity(n)
        if target >= self.capacity:
            return self
        cols, mask = _shrink_device(self.columns, self.mask, target)
        return ColumnBatch(self.schema, cols, mask, self.dicts, num_rows=n)

    # --- host materialization ------------------------------------------
    def _pack_layout(self, extra32: Sequence[str] = ()):
        """Static pack layout for this schema: int64 / float64 / 32-bit
        column groups (see kernels.pack_for_host).  ``extra32`` appends
        synthetic int32 columns (e.g. shuffle bucket ids)."""
        i64, f64, f32 = [], [], []
        for f in self.schema:
            dt = f.dtype.np_dtype
            if dt.itemsize == 8:
                (f64 if dt.kind == "f" else i64).append((f.name, dt))
            else:
                f32.append((f.name, dt))
        for name in extra32:
            f32.append((name, np.dtype(np.int32)))
        return tuple(i64), tuple(f64), tuple(f32)

    def packed_numpy(self, hint: Optional[int] = None,
                     extra32: Optional[Dict[str, jnp.ndarray]] = None
                     ) -> tuple:
        """Host numpy columns of live rows only, via ONE device->host
        transfer that also carries the live-row count (no separate num_rows
        sync).  Returns (cols, n).  ``hint`` guesses the packed capacity —
        when the real count exceeds it, one more exact-size fetch happens
        (the count arrived in the first buffer).  ``extra32`` packs extra
        int32 device arrays (same length as mask) alongside the columns."""
        from ..ops.kernels import pack_for_host, unpack_from_host

        extra32 = extra32 or {}
        i64, f64, f32 = self._pack_layout(tuple(extra32))
        namesi64 = tuple(n for n, _ in i64)
        namesf64 = tuple(n for n, _ in f64)
        names32 = tuple(n for n, _ in f32)
        cap = self.capacity
        if self._num_rows is not None:
            target = min(round_capacity(self._num_rows), cap)
        else:
            target = min(hint if hint else max(1024, cap >> 2), cap)
        cols = dict(self.columns)
        cols.update(extra32)
        while True:
            packed = pack_for_host(cols, self.mask, target, namesi64,
                                   namesf64, names32)
            with device_obs.device_wait("d2h") as wait:
                buf, fbuf = jax.device_get(packed)
                wait.nbytes = buf.nbytes + (fbuf.nbytes if fbuf is not None
                                            else 0)
            out, n = unpack_from_host(buf, fbuf, target, i64, f64, f32)
            if out is not None:
                break
            target = min(round_capacity(n), cap)
        self._num_rows = n
        return out, n

    def compacted_numpy(self, hint: Optional[int] = None) -> Dict[str, np.ndarray]:
        """Return host numpy columns containing only live rows, in order.
        One packed device->host transfer for the whole batch (per-column
        np.asarray would pay a fixed transfer latency per column)."""
        out, _ = self.packed_numpy(hint=hint)
        return out

    def to_arrow(self):
        """Decode to a pyarrow Table with logical types restored: strings from
        dictionaries, date32, decimal128(38, scale) from fixed-point int64."""
        import pyarrow as pa

        from ..utils.errors import InternalError

        data = self.compacted_numpy()
        arrays, fields = [], []
        for f in self.schema:
            arr = data[f.name]
            null_mask = _null_mask(f, arr)  # in-band sentinels -> arrow nulls
            if f.dtype.is_string:
                dic = self.dicts.get(f.name)
                if dic is None or len(dic) == 0:
                    if len(arr) and arr.max(initial=-1) >= 0:
                        raise InternalError(
                            f"string column {f.name!r} has live codes but no dictionary"
                        )
                    dic = np.array([], dtype=object)
                pa_arr = pa.DictionaryArray.from_arrays(
                    pa.array(arr, type=pa.int32()), pa.array(dic, type=pa.string())
                )
                fields.append(pa.field(f.name, pa_arr.type))
            elif f.dtype.kind == "date32":
                pa_arr = pa.array(arr, type=pa.date32(), mask=null_mask)
                fields.append(pa.field(f.name, pa.date32()))
            elif f.dtype.is_decimal:
                import decimal as pydec

                t = pa.decimal128(38, f.dtype.scale)
                scale_exp = -f.dtype.scale
                vals = [pydec.Decimal(int(v)).scaleb(scale_exp) for v in arr]
                if null_mask is not None:
                    vals = [None if m else v for v, m in zip(vals, null_mask)]
                pa_arr = pa.array(vals, type=t)
                fields.append(pa.field(f.name, t))
            else:
                pa_arr = pa.array(arr, mask=null_mask)
                fields.append(pa.field(f.name, pa_arr.type))
            arrays.append(pa_arr)
        return pa.table(arrays, schema=pa.schema(fields))

    def to_pandas(self):
        """Decode to pandas with logical values (decimals -> float)."""
        import pandas as pd

        data = self.compacted_numpy()
        out = {}
        for f in self.schema:
            arr = data[f.name]
            if f.dtype.is_string:
                dic = np.asarray(self.dicts.get(f.name, np.array([], dtype=object)), dtype=object)
                if len(dic) == 0:
                    out[f.name] = np.full(len(arr), None, dtype=object)
                else:
                    vals = dic[np.clip(arr, 0, len(dic) - 1)]
                    out[f.name] = np.where((arr >= 0) & (arr < len(dic)), vals, None)
            elif f.dtype.is_decimal:
                vals = arr.astype(np.float64) / (10.0 ** f.dtype.scale)
                m = _null_mask(f, arr)
                if m is not None:
                    vals = np.where(m, np.nan, vals)
                out[f.name] = vals
            elif f.dtype.kind == "date32":
                vals = arr.astype("datetime64[D]")
                m = _null_mask(f, arr)
                if m is not None:
                    vals = vals.copy()
                    vals[m] = np.datetime64("NaT")
                out[f.name] = vals
            else:
                m = _null_mask(f, arr)
                if m is not None and m.any() and arr.dtype.kind in ("i", "u"):
                    # pandas convention: nullable ints materialize as float64
                    # with NaN holes
                    out[f.name] = np.where(m, np.nan, arr.astype(np.float64))
                else:
                    out[f.name] = arr
        return pd.DataFrame(out)

    def __repr__(self):
        return f"ColumnBatch({self.num_rows}/{self.capacity} rows, {len(self.schema)} cols)"


def _unify_string_dicts(schema: Schema, batches: "list[ColumnBatch]") -> "list[ColumnBatch]":
    """Re-encode string columns against one union dictionary when batches
    disagree (e.g. local-mode repartition mixing scan partitions).  Shuffle
    readers already unify on ingest, so the fast path is an identity check."""
    string_fields = [f.name for f in schema if f.dtype.is_string]
    if not string_fields:
        return batches
    out = list(batches)
    for name in string_fields:
        dicts = [b.dicts.get(name) for b in out]
        first = dicts[0]
        if all(d is first or (d is not None and first is not None and np.array_equal(d, first))
               for d in dicts):
            continue
        union = np.asarray(
            sorted(set().union(*[set(d.tolist()) for d in dicts if d is not None])),
            dtype=object,
        )
        for i, b in enumerate(out):
            d = b.dicts.get(name)
            if d is None or len(d) == 0:
                lut = np.zeros(1, dtype=np.int32)
            else:
                lut = np.searchsorted(union, d).astype(np.int32)
            codes = b.columns[name]
            new_codes = jnp.where(codes >= 0, jnp.asarray(lut)[jnp.clip(codes, 0, None)], -1)
            new_cols = dict(b.columns)
            new_cols[name] = new_codes.astype(jnp.int32)
            new_dicts = dict(b.dicts)
            new_dicts[name] = union
            out[i] = ColumnBatch(b.schema, new_cols, b.mask, new_dicts)
    return out


def concat_batches(schema: Schema, batches: Sequence[ColumnBatch], capacity: Optional[int] = None) -> ColumnBatch:
    """Concatenate batches: device concat of padded arrays, unifying string
    dictionaries across inputs when they differ."""
    batches = list(batches)
    if not batches:
        return ColumnBatch.empty(schema, capacity or 1024)
    if len(batches) == 1 and (capacity is None or batches[0].capacity == capacity):
        return batches[0]
    batches = _unify_string_dicts(schema, batches)
    total_cap = sum(b.capacity for b in batches)
    if capacity is not None and capacity < total_cap:
        raise ValueError(
            f"requested capacity {capacity} < combined batch capacity {total_cap}; "
            "compact batches before concatenating to a smaller shape"
        )
    pad = (capacity - total_cap) if capacity is not None else 0
    cols_list = [{f.name: b.columns[f.name] for f in schema} for b in batches]
    mask_list = [b.mask for b in batches]
    if len({b.capacity for b in batches}) == 1:
        # one fused dispatch for the whole concat (vs one eager op per
        # column: each eager op is a device dispatch round-trip).  Gated on
        # equal capacities so
        # the jit cache keys on (count, capacity, pad) only — mixed-capacity
        # sequences would compile one program per ORDERED capacity tuple,
        # trading transfer latency for compile stalls on the slow-compile
        # TPU backend.
        cols, mask = _concat_device(cols_list, mask_list, pad)
    else:
        cols, mask = _concat_impl(cols_list, mask_list, pad)  # eager
    dicts = {}
    for b in batches:
        dicts.update(b.dicts)
    # propagate host-known row counts: a num_rows sync is a fixed-latency
    # device fetch on remote-attached accelerators, so never discard counts
    # the host already has
    known = [b._num_rows for b in batches]
    total = sum(known) if all(k is not None for k in known) else None
    return ColumnBatch(schema, cols, mask, dicts, num_rows=total)


def _concat_impl(cols_list, mask_list, pad: int):
    names = cols_list[0].keys()
    cols = {}
    for k in names:
        parts = [c[k] for c in cols_list]
        if pad:
            parts.append(jnp.zeros(pad, dtype=parts[0].dtype))
        cols[k] = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    mparts = list(mask_list)
    if pad:
        mparts.append(jnp.zeros(pad, dtype=jnp.bool_))
    mask = jnp.concatenate(mparts) if len(mparts) > 1 else mparts[0]
    return cols, mask


_live_rows = device_obs.observed_jit("batch.num_rows",
                                     lambda mask: jnp.sum(mask))

_concat_device = device_obs.observed_jit("batch.concat", _concat_impl,
                                         static_argnames=("pad",))


@device_obs.observed_jit("batch.shrink", static_argnames=("target",))
def _shrink_device(cols, mask, target: int):
    """The first ``target`` slots with the live rows in front, in their
    order, then the dead ones in theirs: the order of
    ``kernels.compaction_order``.  One sort finds it, of each slot's index
    plus the capacity where the slot is dead (keys are distinct, so no
    stability is needed): the chip sorts a slot in a few nanoseconds where
    a scatter across the capacity costs tens.  Only ``target`` rows of each
    column are gathered."""
    n = mask.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    key = jax.lax.sort(jnp.where(mask, idx, idx + n))[:target]
    live = key < n
    order = jnp.where(live, key, key - n)
    return {k: v[order] for k, v in cols.items()}, live
