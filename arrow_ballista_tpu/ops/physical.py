"""Physical plan base + scan operators.

The ExecutionPlan interface mirrors the one trait the whole reference leans
on (DataFusion's ExecutionPlan as used by e.g.
reference ballista/core/src/execution_plans/shuffle_writer.rs:291-415):
``execute(partition) -> batches``, ``output_partition_count``, ``schema``,
``children``.  TPU-first difference: ``execute`` returns a *list* of
fixed-capacity device ColumnBatches (usually exactly one large batch per
partition — big static shapes feed the VPU/MXU well), not a pull-based
stream of small batches.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..models import expr as E
from ..models.batch import ColumnBatch, concat_batches, round_capacity
from ..models.schema import DataType, Schema
from ..obs import device as device_obs
from ..obs.device import observed_jit
from ..obs.tracing import TracedLock
from ..utils.config import BallistaConfig
from ..utils.errors import ExecutionError, InternalError
from .expressions import ExprCompiler


# --------------------------------------------------------------------------
# execution context & metrics
# --------------------------------------------------------------------------


class MetricsSet:
    """Per-operator metrics, the analog of the reference's OperatorMetric
    proto (reference ballista/core/proto/ballista.proto:248-281).
    Thread-safe: same-stage tasks share the operator instance and record
    concurrently once device dispatch runs outside the xla_lock."""

    def __init__(self):
        self.values: Dict[str, float] = {}
        # RLock: deferred resolvers run under the lock in to_dict and may
        # themselves record metrics (e.g. a fused aggregate latching its
        # passthrough fallback once the output row count becomes host-known)
        self._lock = threading.RLock()
        self._deferred = []  # [(name, fn)] resolved lazily in to_dict

    def add(self, name: str, v: float):
        with self._lock:
            self.values[name] = self.values.get(name, 0) + v

    def add_deferred(self, name: str, fn):
        """Record a metric whose value would cost a device->host sync right
        now (a fixed latency where remote_device() holds).  ``fn()``
        must return the value, or None while it is not yet host-known —
        not-ready entries stay queued for the next snapshot.  Downstream
        materialization (the shuffle writer's packed fetch) normally makes
        the value free before any snapshot happens."""
        with self._lock:
            self._deferred.append((name, fn))

    def timer(self, name: str):
        return _Timer(self, name)

    def to_dict(self):
        with self._lock:
            pending = []
            for name, fn in self._deferred:
                v = fn()
                if v is None:
                    pending.append((name, fn))
                else:
                    self.values[name] = self.values.get(name, 0) + v
            self._deferred = pending
            return dict(self.values)


# --------------------------------------------------------------------------
# cross-job compiled-program cache
# --------------------------------------------------------------------------
#
# Operators lazily build their compiled closures (ExprCompiler output +
# jax.jit wrappers) per plan INSTANCE, and plan instances are per job — so
# re-running the same query re-traced every program (~0.2 s per program on
# the remote TPU backend even with the in-process executable cache, ~1.5-2 s
# per TPC-H query).  Closures whose behavior depends only on (exprs, input
# schema) are shared process-wide here, keyed by that signature.  The jit
# wrapper travels with the closure, so its shape-keyed executable cache is
# shared too.  Instance-local adaptive state (capacity hints, build caches)
# stays on the operator.  The reference has no analog: its operators are
# interpreted, not compiled (DataFusion executes loose; only the TPU
# backend pays per-trace costs).

_program_cache = collections.OrderedDict()
_PROGRAM_CACHE_MAX = 256
_program_cache_lock = TracedLock("program_cache")


def shared_program(key, build):
    """Memoize ``build()`` under ``key`` (hashable compile signature).
    Concurrent builders may race outside the lock; first insert wins so
    every caller converges on one closure/jit object.  A key containing
    None (an expression with no serde signature) disables sharing."""
    if any(k is None for k in key):
        return build()
    with _program_cache_lock:
        hit = _program_cache.get(key)
        if hit is not None:
            _program_cache.move_to_end(key)
            device_obs.record_program_cache(hit=True)
            return hit
    device_obs.record_program_cache(hit=False)
    built = build()
    with _program_cache_lock:
        now = _program_cache.get(key)
        if now is not None:
            return now
        _program_cache[key] = built
        while len(_program_cache) > _PROGRAM_CACHE_MAX:
            _program_cache.popitem(last=False)
    return built


def schema_sig(s) -> tuple:
    return tuple((f.name, f.dtype.kind, f.dtype.scale, f.nullable)
                 for f in s)


def exprs_sig(exprs):
    """Stable signature of expressions via their serde form; None when any
    expression has no serde (callers must then skip program sharing).
    UDF calls bake the registry's current fn into the compiled closure, so
    the signature carries the registry generation — a re-registered UDF
    must never be served from a stale cached program."""
    import json

    from .. import serde
    from ..models import expr as E

    def has_udf(e):
        if e is None:
            return False
        return isinstance(e, E.Udf) or any(has_udf(c) for c in e.children())

    try:
        sig = json.dumps([serde.expr_to_obj(e) if e is not None else None
                          for e in exprs], sort_keys=True,
                         separators=(",", ":"))
    except Exception:  # noqa: BLE001 — unknown expr node: don't share
        return None
    if any(has_udf(e) for e in exprs):
        from ..udf import GLOBAL_UDFS

        sig = f"udfgen={GLOBAL_UDFS.generation};{sig}"
    return sig


def has_scalar_subquery(*exprs) -> bool:
    """True when any expression embeds a ScalarSubquery: its value is
    substituted per job (ctx.scalars), so the compiled closure bakes a
    job-specific literal and must NOT be shared across jobs."""
    from ..models import expr as E

    def walk(e):
        if e is None:
            return False
        if isinstance(e, E.ScalarSubquery):
            return True
        return any(walk(c) for c in e.children())

    return any(walk(e) for e in exprs)


def deferred_rows(ms: MetricsSet, name: str, batch) -> None:
    """Record ``batch``'s row count as a deferred metric WITHOUT pinning the
    batch: the closure holds a weakref, so device buffers are never kept
    alive by metrics.  If the batch is GC'd before its count became
    host-known (it was never materialized), the entry resolves to 0 rather
    than staying queued forever."""
    import weakref

    ref = weakref.ref(batch)

    def fn():
        b = ref()
        if b is None:
            return 0
        return b._num_rows

    ms.add_deferred(name, fn)


class _Timer:
    def __init__(self, ms: MetricsSet, name: str):
        self.ms, self.name = ms, name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.ms.add(self.name, time.perf_counter() - self.t0)


@contextlib.contextmanager
def _span_and_device(span_cm, op):
    """Tracing span + device-attribution scope around one operator
    execute: the device scope nests inside the span so the span's
    metric-delta snapshot (TaskSpanRecorder.op_span) sees the device
    counters this call added."""
    with span_cm, device_obs.op_scope(op):
        yield


# --------------------------------------------------------------------------
# cooperative cancellation token (query lifecycle guardrails)
# --------------------------------------------------------------------------
#
# The executor's task wrapper installs a CancelToken in thread-local
# storage around each task run; cancel/deadline fanout flips the token.
# ``TaskContext.check_cancelled`` (and the free function ``checkpoint()``
# for code paths with no ctx handle, e.g. between fused-kernel
# invocations) consult it in addition to the wired probe, so a cancel
# lands at the next batch boundary even in contexts constructed without a
# probe.  Cost when unset: one thread-local attribute read.

class CancelToken:
    """One task attempt's cancel flag.  Plain bool write/read — flips are
    idempotent and the reader tolerates staleness by one batch."""

    __slots__ = ("cancelled",)

    def __init__(self):
        self.cancelled = False

    def cancel(self) -> None:
        self.cancelled = True


_CANCEL_TLS = threading.local()


def install_cancel_token(token: Optional[CancelToken]) -> None:
    """Bind ``token`` to the calling thread (None uninstalls).  Called by
    the executor's task wrapper around each task run."""
    _CANCEL_TLS.token = token


def current_cancel_token() -> Optional[CancelToken]:
    return getattr(_CANCEL_TLS, "token", None)


def checkpoint(job_id: str = "") -> None:
    """Module-level cancellation checkpoint: raises CancelledError when
    the calling thread's installed token has been cancelled.  A no-op
    (one thread-local read) when no token is installed — library code may
    call it unconditionally."""
    token = getattr(_CANCEL_TLS, "token", None)
    if token is not None and token.cancelled:
        from .. import faults
        from ..utils.errors import CancelledError

        # delay failpoint: widen the window between the flag flip and the
        # raise so chaos tests can race cancellation against completion
        faults.inject("executor.task.cancel.checkpoint", job_id=job_id)
        raise CancelledError(f"job {job_id} cancelled" if job_id
                             else "task cancelled")


@dataclasses.dataclass
class TaskContext:
    config: BallistaConfig = dataclasses.field(default_factory=BallistaConfig)
    scalars: Dict[str, object] = dataclasses.field(default_factory=dict)
    work_dir: str = "/tmp/ballista_tpu"
    job_id: str = ""
    stage_id: int = 0
    executor_id: str = ""  # identity of the executing node (shuffle locality)
    # advertised host of the executing node: a PartitionLocation whose host
    # matches is on the same machine, so its shuffle file can be mmap'd
    # locally instead of fetched over the data plane ("" = unknown, never
    # host-matches)
    executor_host: str = ""
    # shuffle partition locations: (stage_id, partition) -> list of paths/addrs
    shuffle_locations: Dict = dataclasses.field(default_factory=dict)
    # cooperative cancellation probe (executor wires the job's cancel flag);
    # operators call check_cancelled() at batch/operator boundaries so a
    # cancelled job frees its slot without waiting out the whole plan
    # (reference: abortable execution, executor.rs:114-144)
    cancelled: Optional[Callable[[], bool]] = None
    # obs.tracing.TaskSpanRecorder for the running task; None = tracing off
    span_recorder: Optional[object] = None
    # memory.MemoryGovernor of the executing node; None = ungoverned
    # (operators then materialize unbounded state without reservations)
    governor: Optional[object] = None

    def check_cancelled(self) -> None:
        # thread-local token first: it covers contexts constructed without
        # a wired probe (subplan execution, fused-kernel interiors) and is
        # one attribute read when no token is installed
        token = getattr(_CANCEL_TLS, "token", None)
        if token is not None and token.cancelled:
            from .. import faults
            from ..utils.errors import CancelledError

            faults.inject("executor.task.cancel.checkpoint",
                          job_id=self.job_id, stage_id=self.stage_id)
            raise CancelledError(f"job {self.job_id} cancelled")
        if self.cancelled is not None and self.cancelled():
            from .. import faults
            from ..utils.errors import CancelledError

            faults.inject("executor.task.cancel.checkpoint",
                          job_id=self.job_id, stage_id=self.stage_id)
            raise CancelledError(f"job {self.job_id} cancelled")

    def op_span(self, op):
        """Context manager spanning one operator's execute call: always
        enters the device-observatory attribution scope (obs/device.py —
        a shared null context when that is off), plus the tracing span
        when a recorder rides along; operators instrument
        unconditionally."""
        if self.span_recorder is None:
            return device_obs.op_scope(op)
        return _span_and_device(self.span_recorder.op_span(op), op)


# --------------------------------------------------------------------------
# partitioning descriptors (reference: datafusion Partitioning / proto
# PhysicalHashRepartition, ballista.proto)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Partitioning:
    kind: str  # 'unknown' | 'hash' | 'single'
    count: int
    exprs: Sequence[E.Expr] = ()

    @staticmethod
    def unknown(n: int) -> "Partitioning":
        return Partitioning("unknown", n)

    @staticmethod
    def hash(exprs: Sequence[E.Expr], n: int) -> "Partitioning":
        return Partitioning("hash", n, tuple(exprs))

    @staticmethod
    def single() -> "Partitioning":
        return Partitioning("single", 1)


_LOCK_CREATE = threading.Lock()


class ExecutionPlan:
    """Base physical operator."""

    _schema: Schema

    @property
    def schema(self) -> Schema:
        return self._schema

    def xla_lock(self) -> TracedLock:
        """Per-operator lock guarding the lazy jit-closure build.

        Same-stage tasks share one operator instance; without this, N pool
        threads race the lazy ``self._compiled`` build and trigger N
        duplicate XLA compilations (minutes each on TPU).  Hold it ONLY
        around the build: device dispatch runs outside so one task's
        host<->device transfers overlap another's device compute
        (HashAggregateExec/JoinExec do this) — which also means the lock
        does NOT protect shared state touched during execution; any such
        state needs its own synchronization (MetricsSet and the
        ExprCompiler aux cache carry their own locks)."""
        lock = getattr(self, "_xla_lock", None)
        if lock is None:
            with _LOCK_CREATE:
                lock = getattr(self, "_xla_lock", None)
                if lock is None:
                    self._xla_lock = lock = TracedLock(
                        f"xla:{type(self).__name__}")
        return lock

    def children(self) -> List["ExecutionPlan"]:
        return []

    def output_partitioning(self) -> Partitioning:
        return Partitioning.unknown(self.output_partition_count())

    def output_partition_count(self) -> int:
        raise NotImplementedError

    def execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        raise NotImplementedError

    def metrics(self) -> MetricsSet:
        # double-checked under the module lock: concurrent first calls from
        # same-stage tasks (dispatch runs outside xla_lock) must not create
        # two MetricsSet instances and lose one task's records
        ms = getattr(self, "_metrics", None)
        if ms is None:
            with _LOCK_CREATE:
                ms = getattr(self, "_metrics", None)
                if ms is None:
                    self._metrics = ms = MetricsSet()
        return ms

    # display
    def display(self, indent: int = 0) -> str:
        s = "  " * indent + self._label()
        for c in self.children():
            s += "\n" + c.display(indent + 1)
        return s

    def _label(self) -> str:
        return type(self).__name__

    def __repr__(self):
        return self.display()


# --------------------------------------------------------------------------
# arrow -> physical conversion
# --------------------------------------------------------------------------


def _sorted_dictionary(dic: np.ndarray, codes: np.ndarray):
    """Re-sort a dictionary lexicographically and remap codes (engine
    invariant: dictionaries are sorted, so code order == string order)."""
    order = np.argsort(dic)
    rank = np.empty(len(order), dtype=np.int32)
    rank[order] = np.arange(len(order), dtype=np.int32)
    new_codes = np.where(codes >= 0, rank[np.clip(codes, 0, None)], -1).astype(np.int32)
    return dic[order], new_codes


def table_to_physical(table, schema: Schema):
    """pyarrow Table -> (numpy cols dict, dicts dict) in physical repr."""
    import pyarrow as pa
    import pyarrow.compute as pc

    cols: Dict[str, np.ndarray] = {}
    dicts: Dict[str, np.ndarray] = {}
    for f in schema:
        arr = table.column(f.name)
        if f.dtype.is_string:
            combined = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
            if not pa.types.is_dictionary(combined.type):
                combined = pc.dictionary_encode(combined)
            if isinstance(combined, pa.ChunkedArray):
                combined = combined.combine_chunks()
            indices = pc.fill_null(combined.indices, -1)
            codes = indices.to_numpy(zero_copy_only=False).astype(np.int32)
            dic = np.asarray(combined.dictionary.to_pylist(), dtype=object)
            dic_sorted, codes = _sorted_dictionary(dic, codes) if len(dic) else (dic, codes)
            cols[f.name] = codes
            dicts[f.name] = dic_sorted if len(dic) else dic
        elif f.dtype.kind == "date32":
            a = arr
            if not pa.types.is_date32(a.type if not isinstance(a, pa.ChunkedArray) else a.type):
                a = a.cast(pa.date32())
            a = a.cast(pa.int32())
            if a.null_count:
                a = pc.fill_null(a, int(f.dtype.null_sentinel))
            cols[f.name] = a.to_numpy(zero_copy_only=False).astype(np.int32)
        elif f.dtype.is_decimal:
            ftype = table.schema.field(f.name)
            if pa.types.is_integer(ftype.type):
                # int64-stored decimal (unscaled values; metadata carries
                # the storage scale — benchmarks/tpch.py
                # decimal_to_int64_storage / models/ipc.py convention):
                # already the engine's physical representation, up to a
                # power-of-ten rescale when schemas disagree
                from ..models.ipc import int64_decimal_storage_scale

                sscale = int64_decimal_storage_scale(ftype) or 0
                nulls = None
                a = arr
                if a.null_count:
                    if isinstance(a, pa.ChunkedArray):
                        a = a.combine_chunks()
                    nulls = pc.is_null(a).to_numpy(zero_copy_only=False)
                    a = pc.fill_null(a, 0)
                vals = a.cast(pa.int64()).to_numpy(zero_copy_only=False)
                if sscale != f.dtype.scale:
                    if f.dtype.scale > sscale:
                        factor = np.int64(10 ** (f.dtype.scale - sscale))
                        # int64 multiplication wraps silently: keep the
                        # overflow guard the float path had
                        if len(vals) and np.abs(vals).max() > (2**63 - 1) // int(factor):
                            raise ExecutionError(
                                f"decimal column {f.name} exceeds int64 "
                                "range after rescale")
                        vals = vals * factor
                    else:
                        vals = vals // np.int64(10 ** (sscale - f.dtype.scale))
                vals = vals.astype(np.int64, copy=False)
                if nulls is not None:
                    vals = vals.copy()
                    vals[nulls] = np.int64(f.dtype.null_sentinel)
                cols[f.name] = vals
                continue
            # NULLs can't ride the float64 conversion (the int64-min
            # sentinel exceeds the 2^52 exact range): remember them, fill
            # with 0 for conversion, then stamp the sentinel back in
            nulls = None
            a = arr
            if a.null_count:
                if isinstance(a, pa.ChunkedArray):
                    a = a.combine_chunks()
                nulls = pc.is_null(a).to_numpy(zero_copy_only=False)
                a = pc.fill_null(a, 0)
            fl = a.cast(pa.float64()).to_numpy(zero_copy_only=False)
            scaled = np.round(fl * (10 ** f.dtype.scale))
            if np.any(np.abs(scaled) > 2**52):
                raise ExecutionError(
                    f"decimal column {f.name} exceeds exact float64 conversion range"
                )
            out = scaled.astype(np.int64)
            if nulls is not None:
                out[nulls] = np.int64(f.dtype.null_sentinel)
            cols[f.name] = out
        else:
            a = arr
            if a.null_count:
                # real input NULLs -> the per-dtype in-band sentinel; the
                # field must be declared nullable for aggregate/IS NULL
                # semantics to see them (providers set this from null stats)
                sent = f.dtype.null_sentinel
                if isinstance(sent, float):
                    a = a.cast(pa.float64())
                    vals = a.to_numpy(zero_copy_only=False)  # nulls -> NaN
                    cols[f.name] = vals.astype(f.dtype.np_dtype)
                    continue
                a = pc.fill_null(a, int(sent) if not isinstance(sent, bool) else sent)
            cols[f.name] = a.to_numpy(zero_copy_only=False).astype(f.dtype.np_dtype)
    return cols, dicts


def table_to_batches(table, schema: Schema, capacity: int) -> List[ColumnBatch]:
    """Split an arrow table into fixed-capacity device batches (shared,
    sorted dictionaries across all batches of this table)."""
    cols, dicts = table_to_physical(table, schema)
    n = table.num_rows
    if n == 0:
        return [ColumnBatch.empty(schema, min(capacity, 1024))]
    out = []
    for start in range(0, n, capacity):
        end = min(start + capacity, n)
        chunk = {k: v[start:end] for k, v in cols.items()}
        cap = capacity if end - start == capacity else round_capacity(end - start)
        out.append(ColumnBatch.from_numpy(schema, chunk, dicts=dicts, capacity=cap))
    return out


# --------------------------------------------------------------------------
# scans
# --------------------------------------------------------------------------


class ScanExec(ExecutionPlan):
    """Base: reads arrow tables per partition, converts to device batches,
    applies pushed-down filters inside the scan."""

    def __init__(self, schema: Schema, filters: Sequence[E.Expr] = ()):
        self._schema = schema
        self.filters = list(filters)
        self._filter_compiler: Optional[ExprCompiler] = None
        self._filter_fn = None

    def _read_partition(self, partition: int):  # -> pyarrow table
        raise NotImplementedError

    def _cache_key(self, partition: int, capacity: int):
        """Key for the device-resident scan cache, or None when this scan
        can't be cached (volatile source).  Must embed source versioning
        (file mtime/size) so stale data can never be served."""
        return None

    def output_partition_count(self) -> int:
        raise NotImplementedError

    def _produce_batches(self, partition: int, ctx: TaskContext,
                         capacity: int) -> List[ColumnBatch]:
        """Read + convert one partition to device batches (pre-filter)."""
        with self.metrics().timer("scan_read_time"):
            table = self._read_partition(partition)
        ctx.check_cancelled()
        with self.metrics().timer("scan_convert_time"):
            return table_to_batches(table, self._schema, capacity)

    def execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        with ctx.op_span(self):
            return self._execute(partition, ctx)

    def _execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        import jax
        import jax.numpy as jnp

        from ..utils import table_cache
        from ..utils.config import SCAN_CACHE_BYTES

        ctx.check_cancelled()
        capacity = ctx.config.batch_size
        budget = table_cache.resolve_budget(ctx.config.get(SCAN_CACHE_BYTES))
        key = self._cache_key(partition, capacity) if budget else None
        batches = table_cache.CACHE.get(key) if key is not None else None
        if batches is None:
            batches = self._produce_batches(partition, ctx, capacity)
            if key is not None:
                table_cache.CACHE.set_budget(budget)
                table_cache.CACHE.put(key, batches)
        else:
            self.metrics().add("scan_cache_hits", 1)
        self.metrics().add("output_rows", sum(b.num_rows for b in batches))
        if not self.filters:
            return batches
        # compile the conjunction once per (schema, filters) — shared
        # across jobs re-running the same query (scan filters never embed
        # scalar subqueries; those stay above the scan)
        with self.xla_lock():
            if self._filter_fn is None:
                def build():
                    comp = ExprCompiler(self._schema, "device")
                    pred = comp.compile_pred(E.and_all(self.filters))
                    return comp, observed_jit(
                        "scan.filter",
                        lambda cols, mask, aux: mask & pred.fn(cols, aux))

                self._filter_compiler, self._filter_fn = shared_program(
                    ("scanfilter", schema_sig(self._schema),
                     exprs_sig(self.filters)), build)
            out = []
            for b in batches:
                aux = self._filter_compiler.aux_arrays(b.dicts)
                new_mask = self._filter_fn(b.columns, b.mask, aux)
                out.append(ColumnBatch(b.schema, b.columns, new_mask, b.dicts))
        return out


class MemoryScanExec(ScanExec):
    """In-memory table scan, row-sliced into partitions."""

    def __init__(self, schema: Schema, table, partitions: int = 1,
                 filters: Sequence[E.Expr] = ()):
        super().__init__(schema, filters)
        self.table = table.select(schema.names())
        self.partitions = max(1, min(partitions, max(1, self.table.num_rows)))

    def output_partition_count(self) -> int:
        return self.partitions

    def _read_partition(self, partition: int):
        n = self.table.num_rows
        per = (n + self.partitions - 1) // self.partitions
        start = partition * per
        return self.table.slice(start, per)

    def _label(self):
        return f"MemoryScanExec: {self.table.num_rows} rows, {self.partitions} partitions"


def _simple_predicates(filters: Sequence[E.Expr], schema: Schema):
    """Extract ``column <op> literal`` conjuncts usable against parquet
    row-group statistics.  Returns [(col_name, op, value, dtype)] with the
    literal converted to the column's **physical** value domain — the same
    one the executed predicate compares in (dates as epoch days, decimals
    as scaled ints via the same rounding as ExprCompiler._lit_physical) —
    so pruning can never disagree with execution."""
    from .expressions import ExprCompiler, fold_constants

    conv = ExprCompiler(schema, "host")
    out = []
    for f in filters:
        for c in E.conjuncts(f):
            c = fold_constants(c)
            if not (isinstance(c, E.BinOp) and c.op in ("=", "<", "<=", ">", ">=")):
                continue
            col, lit, op = None, None, c.op
            if isinstance(c.left, E.Column) and isinstance(c.right, E.Lit):
                col, lit = c.left, c.right
            elif isinstance(c.right, E.Column) and isinstance(c.left, E.Lit):
                col, lit = c.right, c.left
                op = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "="}[op]
            if col is None or col.name not in schema:
                continue
            v = lit.value
            if isinstance(v, bool) or v is None:
                continue
            dt = schema.field(col.name).dtype
            if not isinstance(v, str):
                try:
                    v = conv._lit_physical(lit, dt)
                except Exception:
                    continue
            out.append((col.name, op, v, dt))
    return out


def _stats_refute(stats, op: str, value, dt: DataType,
                  stats_scale: Optional[int] = None) -> bool:
    """True iff row-group stats prove no row can satisfy ``col op value``.
    ``value`` is in the column's physical domain (see _simple_predicates);
    stats min/max are converted into that same domain before comparing.
    ``stats_scale``: for int64-stored decimal columns, the storage scale —
    integer stats are then already scaled by 10^stats_scale and must NOT
    be scaled again (double-scaling would wrongly refute matching row
    groups)."""
    if stats is None or not stats.has_min_max:
        return False
    lo, hi = stats.min, stats.max
    try:
        if isinstance(value, str):
            if not isinstance(lo, (str, bytes)):
                return False
            if isinstance(lo, bytes):
                lo, hi = lo.decode("utf-8", "replace"), hi.decode("utf-8", "replace")
        else:
            import datetime
            import decimal as pydec

            def phys(x):
                # datetime.datetime must be checked before datetime.date
                # (it's a subclass); both map to epoch days
                if isinstance(x, datetime.datetime):
                    return (x.date() - datetime.date(1970, 1, 1)).days
                if isinstance(x, datetime.date):
                    return (x - datetime.date(1970, 1, 1)).days
                if dt.is_decimal:
                    if stats_scale is not None and isinstance(x, int):
                        # python ints: exact; floor division matches the
                        # row conversion's // so pruning can never disagree
                        # with execution
                        if dt.scale >= stats_scale:
                            return x * (10 ** (dt.scale - stats_scale))
                        return x // (10 ** (stats_scale - dt.scale))
                    if isinstance(x, pydec.Decimal):
                        return int(x.scaleb(dt.scale))  # exact
                    return float(x) * (10 ** dt.scale)
                if isinstance(x, (int, float, pydec.Decimal)):
                    return float(x)
                raise TypeError(f"unusable stats value {x!r}")

            lo, hi = phys(lo), phys(hi)
        if op == "=":
            return value < lo or value > hi
        if op == "<":
            return lo >= value
        if op == "<=":
            return lo > value
        if op == ">":
            return hi <= value
        if op == ">=":
            return hi < value
    except (TypeError, ValueError, ArithmeticError):
        return False
    return False


class ParquetScanExec(ScanExec):
    """Parquet scan at **row-group granularity**: the partition unit is a
    (file, row_group) pair, balanced across ``target_partitions`` by row
    count, so a single large file still scans in parallel (the reference
    gets file-level parallelism from DataFusion's ParquetExec; row groups
    are the TPU-friendly unit because each becomes one padded device batch).

    Pushdown: simple ``col <op> literal`` conjuncts are checked against
    row-group min/max statistics at plan time — refuted row groups are never
    read.  All predicates are re-applied on device afterwards (pruning is
    only ever an over-approximation)."""

    def __init__(self, schema: Schema, paths: List[str], target_partitions: int,
                 filters: Sequence[E.Expr] = (), table_schema: Optional[Schema] = None):
        super().__init__(schema, filters)
        from ..utils import object_store as obs

        self.table_schema = table_schema or schema
        files = []
        for p in paths:
            files.extend(obs.list_files(p, (".parquet",)))
        if not files:
            raise ExecutionError(f"no parquet files found in {paths}")
        self.files = files

        import pyarrow as pa

        preds = _simple_predicates(self.filters, self.table_schema)
        units: List[Tuple[str, int, int]] = []  # (file, row_group, rows)
        self.pruned_row_groups = 0
        for f in files:
            pf = obs.parquet_file(f)
            meta = pf.metadata
            name_to_idx = {meta.schema.column(i).name: i
                           for i in range(meta.num_columns)}
            # int64-stored decimal columns: their integer stats are in the
            # storage-scaled domain (metadata convention, see
            # table_to_physical)
            from ..models.ipc import int64_decimal_storage_scale

            stats_scales = {}
            for af in pf.schema_arrow:
                s = int64_decimal_storage_scale(af)
                if s is not None:
                    stats_scales[af.name] = s
            for rg in range(meta.num_row_groups):
                g = meta.row_group(rg)
                refuted = False
                for col, op, v, dt in preds:
                    ci = name_to_idx.get(col)
                    if ci is None:
                        continue
                    if _stats_refute(g.column(ci).statistics, op, v, dt,
                                     stats_scale=stats_scales.get(col)):
                        refuted = True
                        break
                if refuted:
                    self.pruned_row_groups += 1
                else:
                    units.append((f, rg, g.num_rows))
        self._total_rows = sum(u[2] for u in units)
        if not units:  # everything pruned: keep one empty partition
            self.groups: List[List[Tuple[str, int, int]]] = [[]]
        else:
            # greedy row-count balancing into k partitions
            k = max(1, min(target_partitions, len(units)))
            heaps = [(0, i) for i in range(k)]
            groups: List[List[Tuple[str, int, int]]] = [[] for _ in range(k)]
            import heapq

            heapq.heapify(heaps)
            for u in sorted(units, key=lambda u: -u[2]):
                rows, i = heapq.heappop(heaps)
                groups[i].append(u)
                heapq.heappush(heaps, (rows + u[2], i))
            self.groups = [g for g in groups if g]

    def output_partition_count(self) -> int:
        return len(self.groups)

    def _cache_key(self, partition: int, capacity: int):
        """(file, row-group, mtime, size) units + projection + capacity.
        Local files embed stat() versioning; object-store URLs (no local
        stat) skip caching rather than risk staleness."""
        units = self.groups[partition]
        if not units:
            return None
        import os as _os

        versioned = []
        for f, rg, _rows in units:
            try:
                st = _os.stat(f)
            except OSError:
                return None
            versioned.append((f, rg, st.st_mtime_ns, st.st_size))
        return ("parquet", tuple(versioned), tuple(self._schema.names()), capacity)

    def _read_units(self, units):
        import pyarrow as pa

        from ..utils import object_store as obs

        if not units:
            return self._schema.to_arrow_empty()
        by_file: Dict[str, List[int]] = {}
        for f, rg, _ in units:
            by_file.setdefault(f, []).append(rg)
        cols = self._schema.names()
        # string columns come back dictionary-decoded straight from the
        # parquet pages: the engine dictionary-codes them on device anyway,
        # so this skips a full re-encode pass in table_to_physical
        rd = [f.name for f in self._schema if f.dtype.is_string] or None
        if len(by_file) == 1:
            f, rgs = next(iter(by_file.items()))
            return obs.read_parquet_row_groups(f, sorted(rgs), cols,
                                               read_dictionary=rd)
        # overlap reads across files (each pyarrow read releases the GIL;
        # object-store fetches overlap their network latency)
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(max_workers=min(8, len(by_file))) as pool:
            tables = list(pool.map(
                lambda kv: obs.read_parquet_row_groups(
                    kv[0], sorted(kv[1]), cols, read_dictionary=rd),
                by_file.items()))
        return pa.concat_tables(tables)

    def _read_partition(self, partition: int):
        return self._read_units(self.groups[partition])

    def _produce_batches(self, partition: int, ctx: TaskContext,
                         capacity: int) -> List[ColumnBatch]:
        """Double-buffered cold path: read chunk i+1 on a background thread
        while chunk i converts and transfers to the device, so a cold scan
        costs ~max(read, convert+H2D) instead of their sum (the streaming
        shape of the reference's shuffle-writer pull loop,
        reference shuffle_writer.rs:214-252, applied to the scan).

        Chunks group row-group units to >= ``capacity`` rows, so the device
        batch shapes match the unpipelined path and the jit cache stays
        small.  Per-chunk string dictionaries can differ across chunks;
        downstream consumers unify on demand (models/batch.py
        _unify_string_dicts) — same contract as mixed scan partitions."""
        units = self.groups[partition]
        chunks: List[List[Tuple[str, int, int]]] = []
        cur, cur_rows = [], 0
        for u in sorted(units):
            cur.append(u)
            cur_rows += u[2]
            if cur_rows >= capacity:
                chunks.append(cur)
                cur, cur_rows = [], 0
        if cur:
            chunks.append(cur)
        if len(chunks) <= 1:
            return super()._produce_batches(partition, ctx, capacity)
        from concurrent.futures import ThreadPoolExecutor

        batches: List[ColumnBatch] = []
        pool = ThreadPoolExecutor(max_workers=1)
        try:
            fut = pool.submit(self._read_units, chunks[0])
            for i in range(len(chunks)):
                ctx.check_cancelled()
                # scan_read_time records time BLOCKED on IO; overlapped
                # read time hides behind the previous chunk's convert+H2D
                with self.metrics().timer("scan_read_time"):
                    table = fut.result()
                if i + 1 < len(chunks):
                    fut = pool.submit(self._read_units, chunks[i + 1])
                with self.metrics().timer("scan_convert_time"):
                    batches.extend(table_to_batches(table, self._schema, capacity))
        finally:
            pool.shutdown(wait=False, cancel_futures=True)
        return batches

    def row_count_estimate(self) -> int:
        return self._total_rows

    def clustered_ranges(self, col_name: str):
        """If the data is CLUSTERED on ``col_name`` (per-row-group min/max
        stats non-decreasing in row order), compute a regroup of this
        scan's partitions into contiguous row-group runs and return
        ``(groups, ranges)`` — the new partition groups and their
        per-partition (min, max) key ranges; else None.

        Side-effect free: the caller commits ``groups`` to ``self.groups``
        only when the annotation is accepted.  (Probing used to mutate the
        scan in place, so a probe that produced a single range — possible
        when one huge trailing row group absorbs the whole regroup — was
        rejected by the planner AFTER having already collapsed the scan's
        partitions.)

        Basis of the clustered group-by early-HAVING rewrite
        (scheduler/physical_planner.py): for a clustered key, a partial
        aggregate over a contiguous partition is already FINAL for every
        key except those in range overlaps between neighboring partitions.
        The reference has no analog — DataFusion's partial/final agg split
        (the reference's stage shape for q18's subquery) always ships every
        partial state through the exchange.

        Memoized per column: the planner pass may probe the same scan
        twice (presorted-only annotate, then the early-HAVING upgrade),
        and the stats sweep walks every row group's metadata."""
        cache = getattr(self, "_clustered_cache", None)
        if cache is None:
            self._clustered_cache = cache = {}
        if col_name in cache:
            return cache[col_name]
        cache[col_name] = self._clustered_ranges_impl(col_name)
        return cache[col_name]

    def _clustered_ranges_impl(self, col_name: str):
        from ..utils import object_store as obs

        units = sorted(u for g in self.groups for u in g)
        if len(units) <= 1 or not units:
            return None
        stats_per_unit = []
        for f, rg, _rows in units:
            pf = obs.parquet_file(f)
            meta = pf.metadata
            idx = None
            for i in range(meta.num_columns):
                if meta.schema.column(i).name == col_name:
                    idx = i
                    break
            if idx is None:
                return None
            st = meta.row_group(rg).column(idx).statistics
            if st is None or not st.has_min_max:
                return None
            if not isinstance(st.min, int) or not isinstance(st.max, int):
                return None  # int keys only (exact, order-stable)
            stats_per_unit.append((st.min, st.max))
        # clustered iff unit ranges are non-decreasing in row order
        for (lo_a, hi_a), (lo_b, hi_b) in zip(stats_per_unit,
                                              stats_per_unit[1:]):
            if hi_a > lo_b:
                return None
        # contiguous regroup at the same partition count, row-balanced
        k = len(self.groups)
        total = sum(u[2] for u in units)
        per = max(1, -(-total // k))
        new_groups, new_ranges = [], []
        cur, cur_rows, cur_lo, cur_hi = [], 0, None, None
        for u, (lo, hi) in zip(units, stats_per_unit):
            cur.append(u)
            cur_rows += u[2]
            cur_lo = lo if cur_lo is None else min(cur_lo, lo)
            cur_hi = hi if cur_hi is None else max(cur_hi, hi)
            if cur_rows >= per and len(new_groups) < k - 1:
                new_groups.append(cur)
                new_ranges.append((cur_lo, cur_hi))
                cur, cur_rows, cur_lo, cur_hi = [], 0, None, None
        if cur:
            new_groups.append(cur)
            new_ranges.append((cur_lo, cur_hi))
        return new_groups, new_ranges

    def _label(self):
        pruned = f", {self.pruned_row_groups} row-groups pruned" if self.pruned_row_groups else ""
        n_units = sum(len(g) for g in self.groups)
        return (f"ParquetScanExec: {len(self.files)} files, {n_units} row-groups, "
                f"{len(self.groups)} partitions{pruned}")


def _arrow_type_of(dt: DataType):
    """Engine dtype -> the arrow type file readers should parse into."""
    import pyarrow as pa

    return {
        "int32": pa.int32(), "int64": pa.int64(), "float32": pa.float32(),
        "float64": pa.float64(), "bool": pa.bool_(), "date32": pa.date32(),
        "decimal": pa.float64(), "string": pa.string(),
    }[dt.kind]


class FileListScanExec(ScanExec):
    """Shared scaffolding for whole-file scans (csv/json/avro): object-store
    listing, round-robin file grouping into partitions, per-file read +
    concat.  Parquet scans stay separate (row-group granularity)."""

    SUFFIXES: Tuple[str, ...] = ()
    FORMAT = "file"

    def __init__(self, schema: Schema, paths: List[str], target_partitions: int,
                 filters: Sequence[E.Expr] = (), table_schema: Optional[Schema] = None):
        super().__init__(schema, filters)
        from ..utils import object_store as obs

        self.table_schema = table_schema or schema
        files = []
        for p in paths:
            files.extend(obs.list_files(p, self.SUFFIXES))
        if not files:
            raise ExecutionError(f"no {self.FORMAT} files found in {paths}")
        self.files = files
        k = max(1, min(target_partitions, len(files)))
        self.groups = [files[i::k] for i in range(k)]

    def output_partition_count(self) -> int:
        return len(self.groups)

    def _read_one(self, path: str):
        raise NotImplementedError

    def _read_partition(self, partition: int):
        import pyarrow as pa

        tables = [self._read_one(f) for f in self.groups[partition]]
        return pa.concat_tables(tables) if len(tables) > 1 else tables[0]

    def _label(self):
        return (f"{type(self).__name__}: {len(self.files)} files, "
                f"{len(self.groups)} partitions")


class CsvScanExec(FileListScanExec):
    """CSV scan (including TPC-H ``.tbl`` pipe-delimited files)."""

    SUFFIXES = (".csv", ".tbl")
    FORMAT = "csv"

    def __init__(self, schema: Schema, paths: List[str], target_partitions: int,
                 filters: Sequence[E.Expr] = (), table_schema: Optional[Schema] = None,
                 delimiter: str = ",", has_header: bool = True):
        super().__init__(schema, paths, target_partitions, filters, table_schema)
        self.delimiter = delimiter
        self.has_header = has_header

    def _read_one(self, path: str):
        import pyarrow.csv as pacsv

        from ..utils import object_store as obs

        names = self.table_schema.names()
        column_types = {f.name: _arrow_type_of(f.dtype) for f in self.table_schema}
        trailing = _has_trailing_delimiter(path, self.delimiter)
        read_names = None if self.has_header else names + (["__trail"] if trailing else [])
        ropts = pacsv.ReadOptions(column_names=read_names)
        popts = pacsv.ParseOptions(delimiter=self.delimiter)
        copts = pacsv.ConvertOptions(
            column_types=column_types, include_columns=self._schema.names()
        )
        with obs.open_input(path) as fh:
            return pacsv.read_csv(fh, read_options=ropts, parse_options=popts,
                                  convert_options=copts)


class JsonScanExec(FileListScanExec):
    """Newline-delimited JSON scan (reference reads json via DataFusion's
    NdJson reader, client context.rs register_json).  Parsing uses the
    TABLE schema explicitly — per-file type inference would let two files
    of one table disagree (int vs null vs double) and break the concat."""

    SUFFIXES = (".json", ".jsonl", ".ndjson")
    FORMAT = "json"

    def _read_one(self, path: str):
        import pyarrow as pa
        import pyarrow.json as pajson

        from ..utils import object_store as obs

        explicit = pa.schema([
            pa.field(f.name, _arrow_type_of(f.dtype))
            for f in self.table_schema])
        popts = pajson.ParseOptions(explicit_schema=explicit)
        with obs.open_input(path) as fh:
            table = pajson.read_json(fh, parse_options=popts)
        return table.select(self._schema.names())


class AvroScanExec(FileListScanExec):
    """Avro object-container-file scan (reference reads avro via DataFusion;
    the container codec lives in utils/avro.py — no external avro library
    exists in this image)."""

    SUFFIXES = (".avro",)
    FORMAT = "avro"

    def _read_one(self, path: str):
        from ..utils import object_store as obs
        from ..utils.avro import avro_to_arrow

        with obs.open_input(path) as fh:
            return avro_to_arrow(fh).select(self._schema.names())


def _has_trailing_delimiter(path: str, delim: str) -> bool:
    from ..utils import object_store as obs

    buf = b""
    with obs.open_input(path) as fh:
        # read until the first newline (or EOF) — never misjudge a first
        # line longer than one chunk
        while b"\n" not in buf:
            chunk = fh.read(1 << 16)
            if not chunk:
                break
            buf += chunk
    line = buf.split(b"\n", 1)[0].rstrip(b"\r")
    return line.endswith(delim.encode())
