"""Physical operators: projection, filter, aggregate, join, sort, limit.

These replace the DataFusion single-node operator set the reference depends
on (FilterExec/AggregateExec/HashJoinExec/SortExec — external to the
reference repo, wired in via ballista/executor's DataFusion runtime).  Each
is an XLA program over fixed-capacity batches; data-dependent cardinalities
(groups, join fan-out) use static capacities + masks (see ops/kernels.py).
"""
from __future__ import annotations

import dataclasses
import threading
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..models import expr as E
from ..models.batch import ColumnBatch, concat_batches
from ..models.schema import BOOL, DataType, Field, INT64, Schema
from ..utils.config import JOIN_MAX_CAPACITY
from ..utils.errors import CapacityError, ExecutionError, InternalError
from ..obs.device import device_wait, observed_jit
from .expressions import Compiled, ExprCompiler
from . import kernels as K
from .physical import (ExecutionPlan, Partitioning, TaskContext,
                       deferred_rows, exprs_sig, has_scalar_subquery,
                       schema_sig, shared_program)


@observed_jit("agg.null_restore")
def _null_restore(cnt, col, sentinel):
    """All-NULL groups: the output sentinel where the hidden valid count
    is zero."""
    return jnp.where(cnt > 0, col, sentinel)


def _scalar(x) -> int:
    """A device scalar on the host: the thread waits here for the program
    that produces it."""
    with device_wait("scalar"):
        return int(x)


# job-keyed weakref registry of join operators holding a materialized
# broadcast build side.  The executor calls clear_job_build_caches() when a
# job's shuffle data is removed (scheduler-driven cleanup or TTL janitor) so
# a cached stage plan can't pin the build table in memory after the job.
_build_cache_registry: Dict[str, list] = {}
_build_cache_lock = threading.Lock()


def _register_build_cache(job_id: str, op) -> None:
    with _build_cache_lock:
        _build_cache_registry.setdefault(job_id, []).append(weakref.ref(op))


def clear_job_build_caches(job_id: str) -> None:
    """Drop materialized broadcast build sides cached for ``job_id``."""
    with _build_cache_lock:
        refs = _build_cache_registry.pop(job_id, [])
    for r in refs:
        op = r()
        if op is None:
            continue
        # the operator reads/installs its cache only under xla_lock — take
        # it here too so the check-then-null can't race a concurrent task
        # installing a DIFFERENT job's cache between the check and the
        # assignment
        with op.xla_lock():
            cached = getattr(op, "_build_cache", None)
            if cached is not None and cached[0] == job_id:
                op._build_cache = None
            pc = getattr(op, "_prep_cache", None)
            if pc is not None and pc[0] == job_id:
                op._prep_cache = None


def _substitute_scalars(e: E.Expr, scalars: Dict[str, object]) -> E.Expr:
    """Replace ScalarSubquery placeholders with literal values computed
    before stage launch (ctx.scalars keyed by id of the subquery plan)."""
    if isinstance(e, E.ScalarSubquery):
        key = getattr(e, "scalar_id", None) or id(e.plan)
        if key not in scalars:
            raise InternalError("scalar subquery value missing at execution time")
        v = scalars[key]
        # deserialized refs carry the dtype instead of the plan (serde
        # ships {"t": "scalarref", "id", "dt"}; the plan never crosses)
        dt = getattr(e, "scalar_dtype", None)
        if dt is None:
            dt = e.plan.schema.fields[0].dtype
        if dt.is_decimal:
            # value arrives as raw scaled int -> keep exact by re-scaling to float
            return E.Lit(v / (10 ** dt.scale) if isinstance(v, int) else v)
        return E.Lit(v)
    from ..sql.planner import _map_children

    return _map_children(e, lambda c: _substitute_scalars(c, scalars))


def _null_transparent(e: E.Expr) -> bool:
    """True when NULL inputs imply a NULL output (plain columns, arithmetic,
    casts).  IS NULL and CASE can *launder* NULLs into real values, so
    sentinel re-assertion must not run over them."""
    if isinstance(e, (E.IsNull, E.Case)):
        return False
    return all(_null_transparent(c) for c in e.children())


# the single nullability rule lives next to the logical schemas so the
# Flight-advertised schema cannot drift from the physical stream
from ..models.logical import expr_nullable as _expr_nullable  # noqa: E402


def null_check_of(cc, operand, in_schema: Schema):
    """Value-based NULL test spec for an aggregate operand: None when no
    nullable column feeds the operand; else 'string' (dict code < 0) or the
    computed dtype's in-band sentinel.  The check is VALUE-based — the
    computed operand equals its dtype's sentinel — so CASE/IS NULL
    expressions that launder NULLs into real values still count (a
    ref-based check would wrongly skip those rows).  Shared by the plain
    and mesh-fused aggregates so their NULL semantics cannot drift."""
    if cc is None or operand is None:
        return None
    refs_nullable = any(n in in_schema and in_schema.field(n).nullable
                        for n in operand.column_refs())
    if not refs_nullable:
        return None
    return "string" if cc.dtype.is_string else cc.dtype.null_sentinel


def valid_of(v, null_check):
    """Per-row validity under a ``null_check_of`` spec."""
    if null_check == "string":
        return v >= 0
    if isinstance(null_check, float) and null_check != null_check:  # NaN
        return ~jnp.isnan(v)
    return v != jnp.asarray(null_check, dtype=v.dtype)


class ProjectionExec(ExecutionPlan):
    """Computes output columns; ``host_mode`` runs in numpy float64 (used for
    tiny post-aggregation projections containing division)."""

    def __init__(self, input: ExecutionPlan, exprs: List[Tuple[E.Expr, str]],
                 host_mode: bool = False):
        self.input = input
        self.exprs = exprs
        self.host_mode = host_mode
        in_schema = input.schema
        self._schema = Schema(
            Field(n, e.dtype(in_schema), _expr_nullable(e, in_schema))
            for e, n in exprs
        )
        self._compiled = None

    def children(self):
        return [self.input]

    def output_partition_count(self):
        return self.input.output_partition_count()

    def output_partitioning(self):
        return self.input.output_partitioning()

    def _compile(self, scalars):
        comp = ExprCompiler(self.input.schema, "host" if self.host_mode else "device")
        xp = np if self.host_mode else jnp
        compiled = []
        for e, n in self.exprs:
            c = comp.compile(_substitute_scalars(e, scalars))
            # NULL propagation: an expression over a NULL input is NULL, so
            # non-bool, non-string outputs re-assert the *output* dtype's
            # sentinel wherever any nullable input column holds its sentinel
            # (arithmetic on in-band sentinels otherwise yields garbage)
            out_f = self._schema.field(n)
            if out_f.nullable and _null_transparent(e) \
                    and not c.dtype.is_string and c.dtype.kind != "bool":
                valid = comp.validity_fn(comp.nullable_refs(e))
                if valid is not None:
                    sent = xp.asarray(out_f.dtype.null_sentinel,
                                      dtype=out_f.dtype.np_dtype)
                    c = Compiled(
                        lambda cols, a, f=c.fn, v=valid, s=sent: xp.where(
                            v(cols, a), f(cols, a), s),
                        c.dtype, c.dict_fn, c.lit_value)
            compiled.append((c, n))
        if not self.host_mode:
            fns = [(c.fn, n) for c, n in compiled]

            def proj_fn(cols, mask, aux):
                return {n: f(cols, aux) for f, n in fns}, mask

            jfn = observed_jit("project", proj_fn)
        else:
            jfn = None
        return comp, compiled, jfn

    def execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        with ctx.op_span(self):
            return self._execute(partition, ctx)

    def _execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        with self.xla_lock():
            if self._compiled is None:
                if has_scalar_subquery(*[e for e, _ in self.exprs]):
                    self._compiled = self._compile(ctx.scalars)
                else:
                    self._compiled = shared_program(
                        ("proj", self.host_mode,
                         schema_sig(self.input.schema),
                         tuple(n for _, n in self.exprs),
                         exprs_sig([e for e, _ in self.exprs])),
                        lambda: self._compile(ctx.scalars))
        comp, compiled, jfn = self._compiled
        out = []
        for b in self.input.execute(partition, ctx):
            with self.metrics().timer("compute_time"):
                dicts = {}
                for c, n in compiled:
                    if c.dict_fn is not None:
                        dicts[n] = c.dict_fn(b.dicts)
                if self.host_mode:
                    # host_mode exists precisely to run python UDF exprs on
                    # host — the materialization IS the execution model here
                    # ballista: allow=hot-path-purity — host-mode UDF path
                    cols_np = {k: np.asarray(v) for k, v in b.columns.items()}
                    aux = comp.aux_arrays(b.dicts)
                    with np.errstate(divide="ignore", invalid="ignore"):
                        # ballista: allow=hot-path-purity — host-mode UDF path
                        new_cols = {n: np.broadcast_to(np.asarray(c.fn(cols_np, aux)), (b.capacity,))
                                    for c, n in compiled}
                    out.append(ColumnBatch(
                        self._schema,
                        {k: jnp.asarray(np.ascontiguousarray(v)) for k, v in new_cols.items()},
                        b.mask, dicts))
                else:
                    aux = comp.aux_arrays(b.dicts)
                    new_cols, mask = jfn(b.columns, b.mask, aux)
                    # broadcast scalar literals to full columns
                    new_cols = {
                        k: (jnp.broadcast_to(v, (b.capacity,)) if v.ndim == 0 else v)
                        for k, v in new_cols.items()
                    }
                    out.append(ColumnBatch(self._schema, new_cols, mask, dicts))
        return out

    def _label(self):
        mode = " (host)" if self.host_mode else ""
        return "ProjectionExec" + mode + ": " + ", ".join(n for _, n in self.exprs)


class RenameExec(ExecutionPlan):
    """Zero-cost column rename (alias qualification): rewraps batches with a
    new schema; no device work."""

    def __init__(self, input: ExecutionPlan, schema: Schema):
        if len(schema) != len(input.schema):
            raise InternalError("rename schema arity mismatch")
        self.input = input
        self._schema = schema
        self._mapping = list(zip(input.schema.names(), schema.names()))

    def children(self):
        return [self.input]

    def output_partition_count(self):
        return self.input.output_partition_count()

    def output_partitioning(self):
        return self.input.output_partitioning()

    def execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        with ctx.op_span(self):
            return self._execute(partition, ctx)

    def _execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        out = []
        for b in self.input.execute(partition, ctx):
            cols = {new: b.columns[old] for old, new in self._mapping}
            dicts = {new: b.dicts[old] for old, new in self._mapping if old in b.dicts}
            out.append(ColumnBatch(self._schema, cols, b.mask, dicts))
        return out

    def _label(self):
        return "RenameExec: " + ", ".join(n for n in self._schema.names())


class FilterExec(ExecutionPlan):
    """``host_mode`` evaluates the predicate in numpy float64 — used when
    the predicate contains float arithmetic (e.g. decorrelated scalar
    comparisons like ``l_quantity < 0.2 * avg``), which the device compiler
    refuses to keep the XLA programs f64-free."""

    def __init__(self, input: ExecutionPlan, predicate: E.Expr,
                 host_mode: bool = False):
        self.input = input
        self.predicate = predicate
        self.host_mode = host_mode
        self._schema = input.schema
        self._compiled = None

    def children(self):
        return [self.input]

    def output_partition_count(self):
        return self.input.output_partition_count()

    def output_partitioning(self):
        return self.input.output_partitioning()

    def execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        with ctx.op_span(self):
            return self._execute(partition, ctx)

    def _execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        with self.xla_lock():
            if self._compiled is None:
                def build():
                    comp = ExprCompiler(self.input.schema,
                                        "host" if self.host_mode else "device")
                    pred = comp.compile_pred(_substitute_scalars(self.predicate, ctx.scalars))
                    if pred.dtype != BOOL:
                        raise InternalError("filter predicate must be boolean")
                    if self.host_mode:
                        jfn = None
                    else:
                        jfn = observed_jit(
                            "filter",
                            lambda cols, mask, aux: mask & pred.fn(cols, aux))
                    return comp, pred, jfn

                if has_scalar_subquery(self.predicate):
                    self._compiled = build()
                else:
                    self._compiled = shared_program(
                        ("filter", self.host_mode,
                         schema_sig(self.input.schema),
                         exprs_sig([self.predicate])), build)
        comp, pred, jfn = self._compiled
        out = []
        for b in self.input.execute(partition, ctx):
            with self.metrics().timer("compute_time"):
                aux = comp.aux_arrays(b.dicts)
                if self.host_mode:
                    # ballista: allow=hot-path-purity — host-mode UDF path
                    cols_np = {k: np.asarray(v) for k, v in b.columns.items()}
                    with np.errstate(divide="ignore", invalid="ignore"):
                        keep = np.broadcast_to(
                            # ballista: allow=hot-path-purity — host-mode UDF path
                            np.asarray(pred.fn(cols_np, aux)), (b.capacity,))
                    # ballista: allow=hot-path-purity — host-mode UDF path
                    mask = jnp.asarray(np.asarray(b.mask) & keep)
                else:
                    mask = jfn(b.columns, b.mask, aux)
                out.append(ColumnBatch(b.schema, b.columns, mask, b.dicts))
        return out

    def _label(self):
        mode = " (host)" if self.host_mode else ""
        return f"FilterExec{mode}: {self.predicate}"


# --------------------------------------------------------------------------
# aggregation
# --------------------------------------------------------------------------


class _SchemaSource:
    """Schema-only plan stub for ephemeral operators (the spill-merge
    aggregation) whose input is never executed."""

    def __init__(self, schema: Schema):
        self.schema = schema

    def output_partition_count(self):
        return 1


def _state_bytes(batches: Sequence[ColumnBatch], *schemas: Schema) -> int:
    """Reservation estimate for materializing ``batches`` plus the
    derived state the given schemas describe: total capacity x physical
    row width (sub-4-byte columns still occupy padded device lanes, so
    4 bytes is the per-column floor; +1 for the mask)."""
    cap = sum(b.capacity for b in batches)
    width = sum(1 + sum(max(f.dtype.np_dtype.itemsize, 4) for f in s)
                for s in schemas)
    return cap * width


@dataclasses.dataclass
class AggSpec:
    func: str  # sum | count | min | max
    operand: Optional[E.Expr]  # None for count(*)
    name: str


class HashAggregateExec(ExecutionPlan):
    """Sort-based grouped aggregation with static group capacity.

    ``mode``:
    - 'partial': per input partition, emits group states (runs before the
      shuffle, like DataFusion's partial AggregateExec in reference stage
      plans, planner.rs:80-165);
    - 'final': merges states after a hash repartition on group keys;
    - 'single': both in one (single-partition plans).
    """

    MERGE = {"sum": "sum", "count": "sum", "min": "min", "max": "max"}

    def __init__(self, input: ExecutionPlan, group_exprs: List[Tuple[E.Expr, str]],
                 aggs: List[AggSpec], mode: str):
        assert mode in ("partial", "final", "single")
        self.input = input
        self.group_exprs = group_exprs
        self.aggs = aggs
        self.mode = mode
        in_schema = input.schema
        fields = [Field(n, e.dtype(in_schema), _expr_nullable(e, in_schema))
                  for e, n in group_exprs]
        for a in self.aggs:
            fields.append(Field(a.name, self._agg_dtype(a, in_schema),
                                self._agg_nullable(a, in_schema)))
        self._schema = Schema(fields)
        self._compiled = None

    def _agg_nullable(self, a: AggSpec, in_schema: Schema) -> bool:
        """SQL: sum/min/max yield NULL for an all-NULL group (nullable
        operand) and for a global aggregate over empty input; count never
        does."""
        if a.func == "count":
            return False
        if self.mode == "final":
            return in_schema.field(a.name).nullable
        op_nullable = a.operand is not None and _expr_nullable(a.operand, in_schema)
        return op_nullable or not self.group_exprs

    def _agg_dtype(self, a: AggSpec, in_schema: Schema) -> DataType:
        if self.mode == "final":
            # input columns are already agg states named a.name
            return in_schema.field(a.name).dtype
        if a.func == "count":
            return INT64
        t = a.operand.dtype(in_schema)
        if a.func == "sum" and t.kind == "int32":
            return INT64
        return t

    def children(self):
        return [self.input]

    def output_partition_count(self):
        return self.input.output_partition_count() if self.mode != "single" else 1

    def output_partitioning(self):
        if self.mode == "final":
            return self.input.output_partitioning()
        return Partitioning.unknown(self.output_partition_count())

    def execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        with ctx.op_span(self):
            return self._execute(partition, ctx)

    def _execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        ctx.check_cancelled()
        batches = self.input.execute(partition, ctx)
        in_schema = self.input.schema

        # memory governor (memory/governor.py): reserve the concatenated
        # input + group-state footprint before materializing it.  A denial
        # degrades to the spill path — per-batch partial runs on disk,
        # merged by a final-mode pass on read — instead of an OOM.  The
        # clustered/presorted paths are exempt (their early-filter
        # correctness depends on seeing the whole partition at once, and
        # their state is bounded by the overlap windows).
        gov = getattr(ctx, "governor", None)
        reservation = None
        if gov is not None and getattr(self, "clustered", None) is None \
                and not getattr(self, "_passthrough", False):
            est = _state_bytes(batches, in_schema, self._schema)
            reservation = gov.try_reserve(est, site=f"agg:{self.mode}")
            if reservation is None:
                return self._execute_spilled(ctx, batches, in_schema)
        try:
            return self._execute_inmem(partition, ctx, batches, in_schema)
        finally:
            if reservation is not None:
                reservation.release()

    def _execute_inmem(self, partition, ctx, batches, in_schema):
        big = concat_batches(in_schema, batches).shrink()

        if self.mode == "partial" and self.group_exprs \
                and getattr(self, "_passthrough", False) \
                and getattr(self, "clustered", None) is None:
            # adaptive partial-agg skip (DataFusion does the same): when a
            # sibling task observed near-no reduction (high-cardinality
            # keys like l_orderkey), aggregating before the shuffle burns
            # kernel time for nothing — emit per-row states instead.  Any
            # mix of aggregated and passthrough partials merges correctly
            # at the final (sum of sums == sum of values, etc.).
            return self._execute_passthrough(ctx, big, in_schema)

        # lock covers ONLY the compiled-closure build: concurrent tasks
        # must not race the lazy build (N duplicate jit objects = N
        # compiles), but dispatch+sync run outside so one task's transfer
        # overlaps another's device compute; jax's own jit cache dedupes
        # concurrent first-calls of the shared jfn
        with self.xla_lock():
            self._ensure_compiled(ctx, in_schema)
        out, disorder = self._execute_device(ctx, big)
        if self.mode == "partial" and getattr(self, "clustered", None) \
                is not None and self.clustered[0] is None:
            # presorted-only clustering: no early filter, but the disorder
            # flag must still gate.  The scalar sync costs its fixed latency
            # per task on remote devices — a deliberate trade against the
            # sort-program family it replaces, which compiles for tens of
            # seconds per shape on the TPU backend (capacity ladders mint
            # several shapes per query)
            if disorder is not None:
                # stale-stats guard rides the same sync: declared range
                # vs observed min/max (both device scalars, one roundtrip)
                mismatch = self._declared_range_mismatch(ctx, big, partition)
                if mismatch is not None:
                    with device_wait("scalar"):
                        # ballista: allow=hot-path-purity — deliberate single batched scalar sync; a handful of scalar bytes, a device_wait span rather than transfer volume
                        dis_v, mis_v = jax.device_get((disorder, mismatch))
                    if bool(mis_v):
                        self.metrics().add("clustered_range_mismatches", 1)
                    bad = bool(dis_v) or bool(mis_v)
                else:
                    with device_wait("scalar"):
                        bad = bool(disorder)
                if bad:
                    out = self._latch_sorted_fallback(ctx, in_schema, big)
            return out
        if self.mode == "partial" and getattr(self, "clustered", None) \
                is not None:
            if getattr(self, "_stale_ranges", False):
                # parquet stats lied about key ranges earlier in this
                # stage: the overlap windows are untrustworthy, ship full
                # partials (the downstream HAVING still applies after the
                # final agg, so this only costs exchange volume)
                return out
            mismatch = (self._declared_range_mismatch(ctx, big, partition)
                        if disorder is not None else None)
            filtered = [self._apply_clustered_filter(ctx, b, disorder,
                                                     mismatch)
                        for b in out]
            if any(f is None for f in filtered):
                out = self._latch_sorted_fallback(ctx, in_schema, big)
                if getattr(self, "_stale_ranges", False):
                    return out
                filtered = [self._apply_clustered_filter(ctx, b, None, None)
                            for b in out]
            out = filtered
        return out

    def _execute_spilled(self, ctx, batches, in_schema):
        """Reservation denied: bound the state to one input batch at a
        time.  Each batch is aggregated independently (its state is
        capped by the batch capacity — the engine's functional floor),
        the per-batch result spills to disk as an Arrow IPC run, and the
        runs are merged on read by ONE final-mode pass (the MERGE ops
        are exactly the partial-state merge semantics, NULL sentinels
        included) — the sort-merge finalize.

        Bit-identical to the in-memory path: group emission order is
        ascending key order in both grouping kernels (ops/kernels.py),
        dictionaries are sorted everywhere (spill read included), and
        the decimal columns TPC-H aggregates are int64-stored, so the
        partial merges are exact and associative."""
        from ..memory.spill import Spiller

        with self.xla_lock():
            self._ensure_compiled(ctx, in_schema)
        spiller = Spiller(ctx.work_dir, ctx.job_id, tag="agg")
        try:
            for b in batches:
                ctx.check_cancelled()
                out, _ = self._execute_device(ctx, b)
                for r in out:
                    spiller.write_batch(r)
            self.metrics().add("spill_runs", len(spiller.runs))
            self.metrics().add("spill_bytes",
                               sum(r.num_bytes for r in spiller.runs))
            merged = concat_batches(self._schema,
                                    spiller.read(self._schema)).shrink()
            mop = self._merge_op()
            with mop.xla_lock():
                mop._ensure_compiled(ctx, self._schema)
            out, _ = mop._execute_device(ctx, merged)
            if out[0]._num_rows is not None:
                self.metrics().add("output_rows", out[0]._num_rows)
            else:
                deferred_rows(self.metrics(), "output_rows", out[0])
            return out
        finally:
            spiller.cleanup()

    def _merge_op(self) -> "HashAggregateExec":
        """Ephemeral final-mode aggregation over this operator's OWN
        output schema: merging per-run states is the same computation
        for every mode (sum of sums, min of mins; final counts merge by
        summing), and idempotent over already-final states."""
        with self.xla_lock():
            if getattr(self, "_merge", None) is None:
                self._merge = HashAggregateExec(
                    _SchemaSource(self._schema),
                    [(E.Column(n), n) for _, n in self.group_exprs],
                    self.aggs, "final")
            return self._merge

    def _latch_sorted_fallback(self, ctx, in_schema, big):
        """Row groups lied about ordering (runtime disorder detection):
        latch off the presorted grouping, recompile the sorted path, and
        re-run — correctness first.  _make_compiled returns the tuple, so
        the shared instance swaps atomically and concurrent tasks never
        observe a half-published state."""
        self.metrics().add("presort_fallbacks", 1)
        with self.xla_lock():
            self._no_presort = True
            self._compiled = self._make_compiled(ctx, in_schema)
        out, _ = self._execute_device(ctx, big)
        return out

    def _declared_range_mismatch(self, ctx, big, partition):
        """Stale-parquet-stats guard for the clustered annotation: compare
        this partition's OBSERVED key min/max (the same cheap masked
        reduction family as the disorder flag) against the range the
        planner declared from row-group stats.  A mutated file whose stats
        were not rewritten would otherwise let the early filter drop
        non-final partials.  Returns a device bool scalar (True = the
        declared range is wrong), or None when no declared range applies
        to this partition (legacy annotation, or partition out of range
        after a repartition)."""
        cl = getattr(self, "clustered", None)
        ranges = cl[2] if cl is not None and len(cl) > 2 else None
        if not ranges or not (0 <= partition < len(ranges)):
            return None
        comp = self._compiled[0]
        with self.xla_lock():
            if getattr(self, "_range_check", None) is None:
                self._range_check = self._make_range_check(comp.schema)
        lo, hi = ranges[partition]
        aux = comp.aux_arrays(big.dicts)
        # host scalars: an eager jnp.asarray of a Python int is a program
        # of its own (convert_element_type) per bound per task
        return self._range_check(big.columns, big.mask, aux,
                                 np.int64(lo), np.int64(hi))

    def _make_range_check(self, in_schema):
        """The range check's program, shared across jobs like the
        aggregate's own (``_make_compiled``): it bakes in the compiled
        group key and the key's NULL sentinel, nothing of the job.  The
        key expression is a bare integer column (the planner annotates
        nothing else), so ``kc`` reads no aux slot and any instance's
        compiler serves it."""
        kc, key_name = self._compiled[1][0]
        field = self._schema.field(key_name)
        # NULL keys ride an in-band sentinel that parquet min/max
        # stats exclude — it must not trip the range check
        sent = int(field.dtype.null_sentinel) if field.nullable else None

        def build():
            def check(cols, mask, aux, lo, hi):
                k = kc.fn(cols, aux)
                if k.ndim == 0:
                    k = jnp.broadcast_to(k, mask.shape)
                k = k.astype(jnp.int64)
                live = mask if sent is None else mask & (k != sent)
                kmin = jnp.min(jnp.where(live, k,
                                         jnp.iinfo(jnp.int64).max))
                kmax = jnp.max(jnp.where(live, k,
                                         jnp.iinfo(jnp.int64).min))
                return jnp.any(live) & ((kmin < lo) | (kmax > hi))

            return observed_jit("sort.range_check", check)

        return self._shared(
            ("agg.range_check", schema_sig(in_schema),
             exprs_sig([self.group_exprs[0][0]]), key_name,
             "not null" if sent is None else sent), build)

    def _apply_clustered_filter(self, ctx, result, disorder, mismatch=None):
        """Clustered group-by early-HAVING (see
        scheduler/physical_planner.py _clustered_having_pushdown): the
        input is clustered on the single group key, so this partition's
        partial state is FINAL for every key outside the neighbor-overlap
        windows — apply the downstream HAVING predicate here and ship only
        survivors plus the (few) window keys.  Collapses q18's 15M-state
        exchange to ~hundreds of rows."""
        pred_expr, intervals = self.clustered[0], self.clustered[1]
        with self.xla_lock():
            if getattr(self, "_cl_compiled", None) is None:
                # pad the window vectors to a power of two so every
                # partition (and every instance at this schema) shares one
                # compiled shape
                from ..models.batch import round_capacity as _rc

                n = max(1, len(intervals))
                padn = _rc(n, 4)
                los = np.full(padn, 1, dtype=np.int64)
                his = np.full(padn, 0, dtype=np.int64)  # empty: lo > hi
                for i, (lo, hi) in enumerate(intervals):
                    los[i], his[i] = lo, hi
                comp, keep_fn = self._make_clustered_keep(
                    _substitute_scalars(pred_expr, ctx.scalars), padn)
                self._cl_compiled = (comp, keep_fn,
                                     jnp.asarray(los), jnp.asarray(his))
        comp, keep_fn, los, his = self._cl_compiled
        aux = comp.aux_arrays(result.dicts)
        new_mask, live = keep_fn(result.columns, result.mask, aux, los, his)
        if disorder is not None:
            # ONE device->host roundtrip for all scalars (device_get
            # batches pytree leaves — separate bool() + int() calls would
            # pay the fixed transfer latency once per scalar)
            fetch = (live, disorder,
                     mismatch if mismatch is not None else np.False_)
            with device_wait("scalar"):
                # ballista: allow=hot-path-purity — deliberate single batched scalar sync; a handful of scalar bytes, a device_wait span rather than transfer volume
                live_v, dis_v, mis_v = jax.device_get(fetch)
            if bool(mis_v):
                # declared ranges are wrong (stale stats): the overlap
                # windows can't be trusted, so the early filter itself is
                # invalid — latch it off; the caller re-runs sorted and
                # ships unfiltered partials
                self.metrics().add("clustered_range_mismatches", 1)
                self._stale_ranges = True
                return None
            if bool(dis_v):
                return None  # caller re-runs the sorted path
        else:
            live_v = int(live)
        self.metrics().add("clustered_early_filters", 1)
        out = ColumnBatch(result.schema, result.columns, new_mask,
                          result.dicts, num_rows=int(live_v))
        return out.shrink()

    def _make_clustered_keep(self, pred_expr, padn):
        """The early filter's compiler and program, shared across jobs:
        they bake in the HAVING predicate as this job sees it (scalars
        substituted: two jobs whose constant differs never meet in one
        program) and the key's name; the windows stay arguments."""
        key_name = self.group_exprs[0][1]

        def build():
            comp = ExprCompiler(self._schema, "device")
            pred = comp.compile_pred(pred_expr)

            def keep_fn(cols, mask, aux, los, his):
                k = cols[key_name]
                shared = jnp.any(
                    (k[:, None] >= los[None, :])
                    & (k[:, None] <= his[None, :]), axis=1)
                keep = mask & (shared | pred.fn(cols, aux))
                # live count rides along: the result is tiny by
                # construction, so one scalar sync buys a shrink that
                # saves the shuffle writer a full-capacity repartition
                return keep, jnp.sum(keep)

            return comp, observed_jit("agg.clustered_keep", keep_fn)

        if has_scalar_subquery(self.clustered[0]):
            return build()
        return shared_program(
            ("agg.clustered_keep", schema_sig(self._schema),
             exprs_sig([pred_expr]), key_name, padn), build)

    def _execute_passthrough(self, ctx, big, in_schema):
        with self.xla_lock():
            if getattr(self, "_pt_compiled", None) is None:
                self._pt_compiled = self._make_passthrough(ctx, in_schema)
        comp, group_c, ptfn = self._pt_compiled
        with self.metrics().timer("agg_time"):
            aux = comp.aux_arrays(big.dicts)
            cols = ptfn(big.columns, big.mask, aux)
        dicts = {}
        for cc, name in group_c:
            if cc.dict_fn is not None:
                dicts[name] = cc.dict_fn(big.dicts)
        result = ColumnBatch(self._schema, dict(cols), big.mask, dicts,
                             num_rows=big._num_rows)
        self.metrics().add("passthrough_partials", 1)
        if result._num_rows is not None:
            self.metrics().add("output_rows", result._num_rows)
        else:
            deferred_rows(self.metrics(), "output_rows", result)
        return [result]

    def _make_passthrough(self, ctx, in_schema):
        """The per-row states' compiler and program, shared across jobs
        as ``_make_compiled`` shares the aggregate's; the output schema
        is in the key for the state dtypes it bakes in."""
        return self._shared(
            ("agg.passthrough", schema_sig(self._schema))
            + self._exprs_key(in_schema),
            lambda: self._build_passthrough(ctx, in_schema))

    def _build_passthrough(self, ctx, in_schema):
        comp = ExprCompiler(in_schema, "device")
        group_c = [(comp.compile(_substitute_scalars(e, ctx.scalars)), n)
                   for e, n in self.group_exprs]
        agg_items = []
        for a in self.aggs:
            f = self._schema.field(a.name)
            cc = comp.compile(_substitute_scalars(a.operand, ctx.scalars)) \
                if a.operand is not None else None
            nc = null_check_of(cc, a.operand, in_schema)
            agg_items.append((cc, a.func, a.name, nc, f.dtype))

        def pt_fn(cols, mask, aux):
            out = {}
            for c, n in group_c:
                k = c.fn(cols, aux)
                out[n] = jnp.broadcast_to(k, mask.shape) if k.ndim == 0 else k
            for cc, how, name, nc, dt in agg_items:
                np_dt = dt.np_dtype
                if cc is None:  # count(*): one per row
                    out[name] = jnp.ones(mask.shape, np_dt)
                    continue
                v = cc.fn(cols, aux)
                if v.ndim == 0:
                    v = jnp.broadcast_to(v, mask.shape)
                valid = valid_of(v, nc) if nc is not None else None
                if how == "count":
                    ones = jnp.ones(mask.shape, np_dt)
                    out[name] = (jnp.where(valid, ones, 0)
                                 if valid is not None else ones)
                else:  # sum/min/max state = the value (NULL -> sentinel)
                    v = v.astype(np_dt)
                    if valid is not None:
                        sent = jnp.asarray(dt.null_sentinel, dtype=np_dt)
                        v = jnp.where(valid, v, sent)
                    out[name] = v
            return out

        return comp, group_c, observed_jit("agg.passthrough", pt_fn)

    def _presorted(self) -> bool:
        """Clustered single-key partials group WITHOUT the first sort
        (input is in key order by construction;
        kernels.grouped_aggregate_presorted reduces the rows where they
        lie).  ``_no_presort`` latches after a runtime disorder
        detection."""
        return (self.mode == "partial"
                and getattr(self, "clustered", None) is not None
                and len(self.group_exprs) == 1
                and not getattr(self, "_no_presort", False))

    def program_variant(self) -> str:
        """What tells this aggregate's program from the others that share
        the ``agg.grouped`` signature, in the trace: mode, number of group
        keys (q6's global aggregate is ``k0``, q1's ``k2``), presorted."""
        return (f"{self.mode}_k{len(self.group_exprs)}"
                + ("_presorted" if self._presorted() else ""))

    def _make_compiled(self, ctx, in_schema):
        """Build (or fetch shared) compiled closures and RETURN them —
        callers assign to self._compiled in one atomic statement so
        concurrent tasks never observe a half-published state."""
        return self._shared(
            ("agg", self.mode, self._presorted()) + self._exprs_key(
                in_schema),
            lambda: self._build_compiled(ctx, in_schema))

    def _shared(self, key, build):
        """``build()`` shared across jobs under ``key`` (re-running a query
        re-traces every program otherwise, ~0.2 s each on the remote TPU
        backend), unless a group key or operand holds a scalar subquery:
        a compiled closure bakes its value in per job (``ctx.scalars``)."""
        if has_scalar_subquery(*[e for e, _ in self.group_exprs],
                               *[a.operand for a in self.aggs]):
            return build()
        return shared_program(key, build)

    def _exprs_key(self, in_schema) -> tuple:
        """What a closure over this aggregate's keys and operands bakes
        in, as part of a ``shared_program`` key."""
        return (schema_sig(in_schema),
                exprs_sig([e for e, _ in self.group_exprs]),
                tuple(n for _, n in self.group_exprs),
                tuple((a.func, a.name) for a in self.aggs),
                exprs_sig([a.operand for a in self.aggs]))

    def _ensure_compiled(self, ctx, in_schema):
        if self._compiled is None:
            self._compiled = self._make_compiled(ctx, in_schema)

    def _build_compiled(self, ctx, in_schema):
        comp = ExprCompiler(in_schema, "device")
        group_c = [(comp.compile(_substitute_scalars(e, ctx.scalars)), n)
                   for e, n in self.group_exprs]
        agg_c = []
        for a in self.aggs:
            if self.mode == "final":
                operand = E.Column(a.name)
                how = self.MERGE[a.func]
            else:
                operand = a.operand if a.operand is not None else None
                how = a.func
            cc = comp.compile(_substitute_scalars(operand, ctx.scalars)) if operand is not None else None
            # SQL NULL semantics: aggregates skip NULL inputs
            null_check = null_check_of(cc, operand, in_schema)
            agg_c.append((cc, how, a.name, null_check))
        # nullable sum/min/max also aggregate a hidden per-group valid
        # count, so an all-NULL group can be restored to NULL afterwards
        tracked = [i for i, (cc, how, _, nc) in enumerate(agg_c)
                   if nc is not None and how in ("sum", "min", "max")]

        presorted = self._presorted()

        def agg_fn(cols, mask, aux, out_cap, key_ranges):
            # literal keys/operands compile to scalars; kernels index
            # per row (GROUP BY 1 with a literal select item is legal)
            keys = [jnp.broadcast_to(k, mask.shape) if k.ndim == 0 else k
                    for k in (c.fn(cols, aux) for c, _ in group_c)]
            vals = []
            valids = {}
            for i, (cc, how, _, null_check) in enumerate(agg_c):
                if cc is None:  # count(*)
                    vals.append((jnp.zeros(mask.shape, jnp.int64), K.AGG_COUNT))
                    continue
                v = cc.fn(cols, aux)
                if v.ndim == 0:
                    # literal operands (count(1), sum(2)) compile to
                    # scalars; aggregation kernels index per row
                    v = jnp.broadcast_to(v, mask.shape)
                if null_check is not None:
                    valid = valid_of(v, null_check)
                    valids[i] = valid
                    if how == "count":
                        vals.append((valid.astype(jnp.int64), K.AGG_SUM))
                        continue
                    if how == "sum":
                        v = jnp.where(valid, v, jnp.zeros((), v.dtype))
                    elif how == "min":
                        v = jnp.where(valid, v, K._max_ident(v.dtype))
                    elif how == "max":
                        v = jnp.where(valid, v, K._min_ident(v.dtype))
                vals.append((v, how))
            for i in tracked:
                vals.append((valids[i].astype(jnp.int64), K.AGG_SUM))
            if presorted:
                return K.grouped_aggregate_presorted(keys, vals, mask,
                                                     out_cap)
            return K.grouped_aggregate(keys, vals, mask, out_cap,
                                       key_ranges=key_ranges)

        return (comp, group_c, agg_c, tracked,
                observed_jit("agg.grouped", agg_fn, static_argnums=(3, 4),
                             variant=self.program_variant()))

    def _execute_device(self, ctx, big):
        comp, group_c, agg_c, tracked, jfn = self._compiled
        # static key ranges enable the dense (sort-free) grouping path:
        # dictionary-coded strings have host-known code ranges, bools are
        # {0,1}.  On TPU this is the difference between a minutes-long sort
        # compile and a seconds-long segment-sum compile (kernels.py).
        key_ranges = []
        for cc, _n in group_c:
            if cc.dtype.is_string and cc.dict_fn is not None:
                dic = cc.dict_fn(big.dicts)
                # round the code range up to a power of two: key_ranges is a
                # static jit argument, and per-task dictionary sizes (pruned
                # shuffle dicts) would otherwise compile one program per
                # task.  Codes stay < len(dic), so the wider range only
                # over-allocates the dense domain by <2x.  Same bucketing
                # rule as the aux-LUT padding (expressions._pad_pow2).
                from ..models.batch import round_capacity

                key_ranges.append((-1, round_capacity(len(dic), 16) - 1))
            elif cc.dtype.kind == "bool":
                key_ranges.append((0, 1))
            else:
                key_ranges.append(None)
        key_ranges = tuple(key_ranges)
        # plan-ahead capacity: the group count is bounded a priori — by
        # one when there are no keys, by the dense key domain when the
        # ranges are static, else by the input capacity (distinct groups
        # can never exceed live rows) —
        # so out_cap provably holds every group and the kernel's overflow
        # flag is statically None (kernels.py returns None whenever
        # out_cap covers the bound).  ONE kernel call per input: the old
        # overflow-retry ladder re-ran the whole kernel on the same
        # buffers at growing capacities, which is what blocked donation
        # on agg-headed fused chains (ROADMAP #2; compile/fused.py now
        # donates).  State that outgrows memory is the governor's problem
        # (reserve -> spill), not a recompile loop's.
        out_cap = big.capacity
        domain = K.dense_domain(key_ranges)
        if not group_c:
            # no keys, one group: the kernel reduces into one row, and
            # everything downstream (pack, D2H, partition file) is one row
            out_cap = 1
            self.metrics().add("global_reductions", 1)
        elif domain is not None:
            # dense domain bounds distinct groups exactly: don't allocate
            # (or device->host transfer) a 64k-row output for 12 groups
            out_cap = min(out_cap, domain)
            # its sums, counts and rows per slot are one contraction on
            # the matrix unit where the kernel says so.  (The sort path's
            # capacities start at 1024 rows, a slot past what the
            # contraction takes: only a dense domain reaches it from here.)
            if not self._presorted() and K.i64_sum_path(
                    domain + 1, big.capacity) == "contraction":
                self.metrics().add("mxu_grouped_sums", 1)
        if group_c and (domain is None or self._presorted()):
            # keys and no dense domain (the presorted entry takes no
            # ranges): the kernel reduces runs of equal keys by segmented
            # scans and moves no row by an index
            self.metrics().add("run_scan_aggregates", 1)
        disorder = None
        with self.metrics().timer("agg_time"):
            aux = comp.aux_arrays(big.dicts)
            res = jfn(big.columns, big.mask, aux, out_cap, key_ranges)
            if len(res) == 5:  # presorted path carries a disorder flag
                # NOT synced here: the clustered filter fetches it
                # together with its live count in one roundtrip
                out_keys, out_vals, out_mask, overflow, disorder = res
            else:
                out_keys, out_vals, out_mask, overflow = res
            # overflow is None == statically impossible (the kernel
            # proved out_cap bounds the group count) on every reachable
            # shape here; the check is a pure backstop against a future
            # kernel change and costs a scalar sync only if one happens
            if overflow is not None and bool(overflow):
                raise CapacityError(
                    f"aggregation overflowed {out_cap} groups with "
                    f"{big.capacity}-row input; this should be impossible"
                )

        cols: Dict[str, jnp.ndarray] = {}
        dicts: Dict[str, np.ndarray] = {}
        for (cc, name), arr in zip(group_c, out_keys):
            cols[name] = arr
            if cc.dict_fn is not None:
                dicts[name] = cc.dict_fn(big.dicts)
        main_vals = out_vals[: len(agg_c)]
        for (cc, how, name, _), arr in zip(agg_c, main_vals):
            cols[name] = arr
        # all-NULL groups: restore NULL (output sentinel) where the hidden
        # valid count is zero
        for i, cnt in zip(tracked, out_vals[len(agg_c) :]):
            name = agg_c[i][2]
            f = self._schema.field(name)
            cols[name] = _null_restore(cnt, cols[name],
                                       f.dtype.np_dtype.type(
                                           f.dtype.null_sentinel))

        result = ColumnBatch(self._schema, cols, out_mask, dicts)

        # SQL semantics: a global aggregate ('single'/'final' with no keys)
        # over empty input yields one row: count = 0, sum/min/max = NULL
        if not self.group_exprs and self.mode in ("single", "final") and result.num_rows == 0:
            data = {}
            for a in self.aggs:
                f = self._schema.field(a.name)
                if f.nullable:
                    # ballista: allow=hot-path-purity — builds the 1-row empty-input agg result on host
                    data[a.name] = np.asarray([f.dtype.null_sentinel],
                                              dtype=f.dtype.np_dtype)
                else:
                    data[a.name] = np.zeros(1, dtype=f.dtype.np_dtype)
            result = ColumnBatch.from_numpy(self._schema, data, dicts={})
        # output_rows and the adaptive passthrough probe both want the
        # result's row count, which is device-resident here.  Defer them:
        # the downstream shuffle writer's packed fetch sets _num_rows on
        # this same batch object, so by the task-status snapshot
        # (collect_plan_metrics -> to_dict) the count is free — an eager
        # .num_rows would pay a scalar sync per task.  Weakrefs so
        # the metrics queue never pins device buffers.
        res_ref, inp_ref = weakref.ref(result), weakref.ref(big)
        inp_cap = big.capacity

        def _finish():
            res = res_ref()
            if res is None:
                return 0  # GC'd unmaterialized: count unknowable
            rn = res._num_rows
            if rn is None:
                return None  # not materialized yet; stay queued
            # poor reduction on a large input => sibling tasks (same
            # cardinality profile) skip partial aggregation entirely and
            # emit per-row states.  The input count may itself be unknown
            # (post-filter device mask); its capacity upper-bounds it, so
            # rn > 0.6*capacity still certifies poor reduction.
            if self.mode == "partial" and self.group_exprs:
                inp = inp_ref()
                bn = inp._num_rows if inp is not None else None
                if bn is not None:
                    if bn >= (1 << 17) and rn > 0.6 * bn:
                        self._passthrough = True
                elif inp_cap >= (1 << 17) and rn > 0.6 * inp_cap:
                    self._passthrough = True
            return rn

        if result._num_rows is not None:
            self.metrics().add("output_rows", _finish())
        else:
            self.metrics().add_deferred("output_rows", _finish)
        return [result], disorder

    def _label(self):
        g = ", ".join(n for _, n in self.group_exprs)
        a = ", ".join(f"{x.func}({x.name})" for x in self.aggs)
        return f"HashAggregateExec({self.mode}): groupBy=[{g}] aggr=[{a}]"


# --------------------------------------------------------------------------
# join
# --------------------------------------------------------------------------


@observed_jit("join.window_mask")
def _window_mask(mask, counts, lo, hi):
    """One probe window of a chunked join: liveness and candidate counts of
    the rows with index in [lo, hi), zero outside.  One compiled program
    serves every window of every chunked join at this capacity."""
    idx = jnp.arange(mask.shape[0], dtype=jnp.int32)
    inside = (idx >= lo) & (idx < hi)
    return mask & inside, jnp.where(inside, counts, 0)


@observed_jit("join.window_counts", static_argnums=(1, 2))
def _window_counts(counts, chunk_rows, n_windows):
    """Candidate pairs per probe window, from the join's one range lookup:
    ONE program + ONE host transfer for every window (a per-window scalar
    sync would cost its fixed latency each)."""
    wid = jnp.arange(counts.shape[0], dtype=jnp.int32) // jnp.int32(chunk_rows)
    return jax.ops.segment_sum(counts, wid, num_segments=n_windows)


_mask_or = observed_jit("join.mask_or", lambda a, b: a | b)
# spilled semi/anti accumulate verdict masks across build partitions:
# semi ORs hit masks, anti ANDs the surviving masks (pmask & ~hit_p)
_mask_and = observed_jit("join.mask_and", lambda a, b: a & b)


class JoinExec(ExecutionPlan):
    """Equi-join: sorted build, one range lookup per probe batch, then a
    static-capacity pair expansion (ops/kernels.py).  Probe = left child,
    build = right child.

    Three programs: ``join.prep`` hashes and sorts the build; ``join.count``
    is the range lookup — the one pass over the build's sorted hashes a
    probe batch makes — and returns the candidate total with every probe
    row's ``lo`` and ``counts``; ``join.probe`` takes those two arrays as
    operands (it never sees the sorted hashes), expands the pairs and emits
    by join type.  The chunked path's windows and their sizes come from the
    same ``counts``.  Metrics ``range_lookups`` and ``probe_rows_searched``
    count the lookups.

    ``dist``: 'partitioned' (both children hash-partitioned on keys — the
    planner inserts shuffles) or 'broadcast' (build side read fully by every
    probe partition; for small tables, avoids a shuffle).

    Hash collisions cannot corrupt results: real key equality is re-verified
    on every candidate pair.
    """

    def __init__(self, left: ExecutionPlan, right: ExecutionPlan,
                 on: List[Tuple[E.Expr, E.Expr]], join_type: str = "inner",
                 filter: Optional[E.Expr] = None, dist: str = "partitioned"):
        assert join_type in ("inner", "left", "full", "semi", "anti")
        assert dist in ("partitioned", "broadcast")
        # broadcast replicates the build side to every probe partition; a
        # full join would then emit each unmatched build row once PER
        # partition — the planner must use the partitioned path instead
        assert not (join_type == "full" and dist == "broadcast")
        self.left = left
        self.right = right
        self.on = on
        self.join_type = join_type
        self.filter = filter
        self.dist = dist
        if join_type in ("semi", "anti"):
            self._schema = left.schema
        elif join_type == "left":
            self._schema = Schema(
                list(left.schema)
                + [Field(f.name, f.dtype, nullable=True) for f in right.schema])
        elif join_type == "full":
            self._schema = Schema(
                [Field(f.name, f.dtype, nullable=True) for f in left.schema]
                + [Field(f.name, f.dtype, nullable=True) for f in right.schema])
        else:
            self._schema = left.schema.merge(right.schema)
        self._compiled = None

    def children(self):
        return [self.left, self.right]

    def output_partition_count(self):
        return self.left.output_partition_count()

    def output_partitioning(self):
        return self.left.output_partitioning()

    def execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        with ctx.op_span(self):
            return self._execute(partition, ctx)

    def _execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        ctx.check_cancelled()
        probe = concat_batches(self.left.schema, self.left.execute(partition, ctx)).shrink()
        ctx.check_cancelled()
        if self.dist == "broadcast":
            # materialize the build side ONCE per job: same-stage tasks
            # share this operator instance, and re-executing the build
            # subtree (scans included) per probe partition multiplied the
            # scan volume by the task count (the reference's CollectLeft
            # shares one built table the same way).  Keyed by job_id so any
            # cross-job instance reuse can't serve stale rows.  Eviction is
            # job-scoped, not partition-counted: in a multi-executor
            # deployment each process runs only a subset of probe
            # partitions, so a local consumption counter would never reach
            # the plan-wide partition count and the table would stay pinned.
            # The executor drops the cache when the job's data is cleaned
            # (remove_job_data / janitor) via clear_job_build_caches().
            with self.xla_lock():
                cached = getattr(self, "_build_cache", None)
                if cached is None or cached[0] != ctx.job_id:
                    build_parts = []
                    for p in range(self.right.output_partition_count()):
                        build_parts.extend(self.right.execute(p, ctx))
                    build = concat_batches(self.right.schema,
                                           build_parts).shrink()
                    cached = (ctx.job_id, build)
                    self._build_cache = cached
                    _register_build_cache(ctx.job_id, self)
                build = cached[1]
            reservation = None
        else:
            bparts = self.right.execute(partition, ctx)
            lsch, rsch = self.left.schema, self.right.schema
            # memory governor: reserve the build-side footprint before
            # concatenating it.  On denial, inner/semi/anti degrade to a
            # partitioned-build spill (hash-range partitions on disk,
            # rehydrated one at a time); left/full need every build row
            # live for their single-pass unmatched-row append, so they
            # take an over-budget grant instead (visible in the pressure
            # signal — the doctor points at the query shape).  Broadcast
            # builds are exempt: the job-scoped cache outlives this task,
            # and the device pool's watermark sampler accounts for it.
            gov = getattr(ctx, "governor", None)
            reservation = None
            if gov is not None:
                est = _state_bytes(bparts, rsch)
                if self.join_type in ("inner", "semi", "anti"):
                    reservation = gov.try_reserve(
                        est, site=f"join:{self.join_type}")
                    if reservation is None:
                        return self._join_spilled(ctx, probe, bparts,
                                                  lsch, rsch)
                else:
                    reservation = gov.force_reserve(
                        est, site=f"join:{self.join_type}")
            build = concat_batches(self.right.schema, bparts).shrink()

        lsch, rsch = self.left.schema, self.right.schema

        try:
            # lock covers only the jit-closure build (see
            # HashAggregateExec): concurrent reduce tasks dispatch outside
            # it so transfers overlap device compute
            with self.xla_lock():
                self._ensure_compiled(ctx, lsch, rsch)
            return self._join_device(ctx, probe, build, lsch, rsch)
        finally:
            if reservation is not None:
                reservation.release()

    def _ensure_compiled(self, ctx, lsch, rsch):
        if self._compiled is None:
            join_exprs = [e for pair in self.on for e in pair] + [self.filter]
            if not has_scalar_subquery(*join_exprs):
                key = ("join", self.join_type, self.dist,
                       schema_sig(lsch), schema_sig(rsch),
                       schema_sig(self._schema), exprs_sig(join_exprs))
                self._compiled = shared_program(
                    key, lambda: self._build_join(ctx, lsch, rsch))
            else:
                self._compiled = self._build_join(ctx, lsch, rsch)

    def _build_join(self, ctx, lsch, rsch):
        lcomp = ExprCompiler(lsch, "device")
        rcomp = ExprCompiler(rsch, "device")
        lkeys = [lcomp.compile_key(le) for le, _ in self.on]
        rkeys = [rcomp.compile_key(re_) for _, re_ in self.on]
        # NULL join keys never match (string keys handle this via the
        # NULL_KEY_SENTINEL below; numeric nullable keys via validity)
        lkey_valid = [lcomp.validity_fn(lcomp.nullable_refs(le)) for le, _ in self.on]
        rkey_valid = [rcomp.validity_fn(rcomp.nullable_refs(re_)) for _, re_ in self.on]
        fcomp = fpred = None
        if self.filter is not None:
            merged = lsch.merge(rsch)
            fcomp = ExprCompiler(merged, "device")
            fpred = fcomp.compile_pred(_substitute_scalars(self.filter, ctx.scalars))

        jt = self.join_type
        lnames = [f.name for f in lsch]
        rnames = [f.name for f in rsch]
        rfill = {f.name: f.dtype.null_sentinel for f in rsch}
        lfill = {f.name: f.dtype.null_sentinel for f in lsch}
        # pair filter: gather ONLY the columns the predicate references.
        # q21's semi join (l2.suppkey <> l1.suppkey over ~7 build rows per
        # orderkey) was gathering all ~20 lineitem columns into multi-M-row
        # pair buffers to evaluate a 2-column predicate.
        fnames = self.filter.column_refs() if self.filter is not None else set()

        def prep_fn(bcols, bmask, raux):
            # build-side hash + sort, hoisted out of the per-task probe:
            # a broadcast build is shared by every probe partition, and
            # re-sorting a 1.5M-row build inside all 12 task dispatches
            # was measured at 61 task-seconds on q21's l1/orders join
            bk = [c.fn(bcols, raux) for c in rkeys]
            bh_sorted, border, _ = K.build_side_sort(bk, bmask)
            return bh_sorted, border

        def join_fn(pcols, pmask, bcols, bmask, border, lo, counts,
                    laux, raux, faux, out_cap):
            # lo/counts: the range lookup count_fn made for this probe
            # batch (a chunked join's window passes its rows' counts, zero
            # elsewhere) — no search of the build's hashes happens here
            pk = [c.fn(pcols, laux) for c in lkeys]
            bk = [c.fn(bcols, raux) for c in rkeys]
            pi, bp, pair_valid, _ = K.expand_pairs(
                lo, counts, bmask.shape[0], out_cap)
            bidx = border[bp]
            # verify real key equality (hash collisions) + build liveness;
            # string keys are value-hashes: exclude the NULL sentinel so
            # NULL never equals NULL (SQL semantics)
            ok = pair_valid & bmask[bidx]
            for i, ((a, b), ck) in enumerate(zip(zip(pk, bk), lkeys)):
                ok = ok & (a[pi] == b[bidx])
                if ck.dtype.is_string:
                    sent = ExprCompiler.NULL_KEY_SENTINEL
                    ok = ok & (a[pi] != sent)
                if lkey_valid[i] is not None:
                    ok = ok & lkey_valid[i](pcols, laux)[pi]
                if rkey_valid[i] is not None:
                    ok = ok & rkey_valid[i](bcols, raux)[bidx]
            if fpred is not None:
                pair_cols = {n: pcols[n][pi] for n in lnames if n in fnames}
                pair_cols.update({n: bcols[n][bidx] for n in rnames
                                  if n in fnames})
                ok = ok & fpred.fn(pair_cols, faux)

            if jt in ("semi", "anti"):
                hit = K.segment_any(ok, pi, pmask.shape[0])
                new_mask = pmask & (hit if jt == "semi" else ~hit)
                return pcols, new_mask

            out_cols = {n: pcols[n][pi] for n in lnames}
            out_cols.update({n: bcols[n][bidx] for n in rnames})
            out_mask = ok
            if jt in ("left", "full"):
                hit = K.segment_any(ok, pi, pmask.shape[0])
                miss = pmask & ~hit
                # append unmatched probe rows; build side filled with the
                # per-dtype NULL sentinel (schema marks those nullable)
                out_cols = {
                    n: jnp.concatenate([
                        out_cols[n],
                        pcols[n] if n in lnames else jnp.full(
                            pmask.shape[0],
                            rfill[n],
                            out_cols[n].dtype,
                        ),
                    ])
                    for n in out_cols
                }
                out_mask = jnp.concatenate([out_mask, miss])
            if jt == "full":
                # unmatched BUILD rows too, probe side NULL-filled
                hit_b = K.segment_any(ok, bidx, bmask.shape[0])
                miss_b = bmask & ~hit_b
                out_cols = {
                    n: jnp.concatenate([
                        out_cols[n],
                        bcols[n] if n in rnames else jnp.full(
                            bmask.shape[0],
                            lfill[n],
                            out_cols[n].dtype,
                        ),
                    ])
                    for n in out_cols
                }
                out_mask = jnp.concatenate([out_mask, miss_b])
            if jt == "inner":
                # probe-row index per output pair rides along for the
                # spilled path's order-restoring merge (all matches of
                # one probe row share one hash, hence one build
                # partition; a stable host sort on pi reconstructs the
                # exact single-build emission order).  Device-resident
                # unless the spill path fetches it.
                return out_cols, out_mask, pi.astype(jnp.int32)
            return out_cols, out_mask

        def count_fn(pcols, pmask, bh_sorted, laux):
            # the join's range lookup, once per probe batch: each probe
            # row's run [lo, lo + counts) of equal hashes in the sorted
            # build.  The total sizes the output buffers to reality instead
            # of out_factor x probe capacity (a 1M-row probe batch with 30k
            # matches would otherwise gather every output column into
            # 2M-row buffers); lo and counts stay on the device as
            # join_fn's operands
            pk = [c.fn(pcols, laux) for c in lkeys]
            lo, counts, total = K.probe_ranges(K.hash64(pk), pmask,
                                               bh_sorted)
            return total, lo, counts

        return (lcomp, rcomp, fcomp,
                observed_jit("join.probe", join_fn, static_argnums=(10,)),
                observed_jit("join.count", count_fn),
                observed_jit("join.prep", prep_fn))

    def _lookup(self, cfn, probe, bh_sorted, laux):
        """Dispatch the range lookup of one probe batch against one sorted
        build: (candidate total, lo, counts), all on the device."""
        self.metrics().add("range_lookups", 1)
        self.metrics().add("probe_rows_searched", probe.capacity)
        return cfn(probe.columns, probe.mask, bh_sorted, laux)

    def _out_row_bytes(self) -> int:
        return self._schema.row_byte_width()

    def _join_device(self, ctx, probe, build, lsch, rsch):
        lcomp, rcomp, fcomp, jfn, cfn, pfn = self._compiled

        laux = lcomp.aux_arrays(probe.dicts)
        raux = rcomp.aux_arrays(build.dicts)
        faux = fcomp.aux_arrays({**probe.dicts, **build.dicts}) if fcomp is not None else {}

        with self.metrics().timer("join_time"):
            # build-side hash+sort: computed once per broadcast build and
            # shared by every probe task (cache keyed like _build_cache);
            # partitioned builds differ per task and prep inline
            prep = None
            if self.dist == "broadcast":
                pc = getattr(self, "_prep_cache", None)
                if pc is not None and pc[0] == ctx.job_id and pc[1] is build:
                    prep = pc[2]
            if prep is None:
                prep = pfn(build.columns, build.mask, raux)
                if self.dist == "broadcast":
                    # install under xla_lock and only while the build cache
                    # for this job is still alive: a concurrent
                    # clear_job_build_caches (which pops the registry entry)
                    # must not be followed by a re-install nothing would
                    # ever evict
                    with self.xla_lock():
                        bc = getattr(self, "_build_cache", None)
                        if bc is not None and bc[0] == ctx.job_id:
                            self._prep_cache = (ctx.job_id, build, prep)
            bh_sorted, border = prep
            # range lookup -> exact candidate total -> power-of-two
            # capacity bucket (static shapes stay static per bucket — the
            # XLA-friendly answer to data-dependent join fan-out,
            # SURVEY.md §7 hard parts).  Floored at probe.capacity/4 so
            # same-shaped batches with modest counts share ONE compiled
            # bucket instead of compiling per data-dependent power of two
            # (compiles cost minutes on TPU); clamped to the ceiling so
            # pow2 rounding can never allocate above the configured cap.
            # The lookup is the join's own first step, not a sizing pass
            # beside it: the expansion below starts from its lo/counts.
            ceiling = ctx.config.get(JOIN_MAX_CAPACITY)
            total_dev, lo, counts = self._lookup(cfn, probe, bh_sorted,
                                                 laux)
            total_est = _scalar(total_dev)
            if total_est > ceiling:
                raise CapacityError(
                    f"join produced {total_est} candidate pairs, above "
                    f"the {ceiling}-row ceiling; likely an accidental "
                    f"near-cross join — check join keys, or raise "
                    f"{JOIN_MAX_CAPACITY}")
            # two capacity buckets per probe shape: selective joins
            # (the common case after semi/HAVING reductions) share the
            # LOW bucket instead of gathering cap//4-row buffers for a
            # handful of matches; everything else shares cap//4
            low_floor = max(64, probe.capacity // 64)
            if total_est <= low_floor:
                out_cap = low_floor
            else:
                out_cap = max(1 << max(0, total_est - 1).bit_length(),
                              probe.capacity // 4)
            if out_cap > ceiling:
                # ballista: allow=trace-key-stability — above-ceiling exact-size fallback: compiles once at the true match count instead of a doubled pow2 bucket that would blow the capacity ceiling; rare by construction (needs a near-cross join past JOIN_MAX_CAPACITY)
                out_cap = max(total_est, 64)
            # memory control (VERDICT r4 #6): when the expansion working set
            # would exceed the per-task budget, run the probe loop in
            # bounded windows against the (already prepped) build instead of
            # one oversized allocation.  A static-shape engine cannot spill
            # mid-kernel, so the budget is enforced before allocation; the
            # disk tier stays the shuffle's IPC files (the reference's own
            # spill story: shuffle files as checkpoints, utils.rs:176-212).
            # Only inner/semi/anti chunk: a full join's unmatched-build pass
            # needs hits accumulated across every probe row, and a left
            # join's miss-append block is probe-capacity-sized per window,
            # so windowing would multiply memory instead of bounding it.
            from ..utils.config import resolve_task_budget

            budget = resolve_task_budget(ctx.config)
            if (budget and self.join_type in ("inner", "semi", "anti")
                    and probe.capacity >= 2048
                    and out_cap * self._out_row_bytes() > budget):
                return self._join_chunked(
                    ctx, probe, build, border, lo, counts,
                    laux, raux, faux, budget, ceiling, out_cap)
            # inner joins return a 3rd element (pi, for the spilled
            # path's merge) — every in-memory caller slices it off.
            # out_cap >= total_est, and the expansion's own total is the
            # sum of the very counts array total_est summed, so the pairs
            # always fit: there is no overflow to check after the join and
            # no retry at a larger capacity.
            out_cols, out_mask = jfn(
                probe.columns, probe.mask, build.columns, build.mask,
                border, lo, counts, laux, raux, faux, out_cap
            )[:2]

        dicts = dict(probe.dicts)
        if self.join_type in ("inner", "left", "full"):
            dicts.update(build.dicts)
        result = ColumnBatch(self._schema, dict(out_cols), out_mask, dicts)
        if result._num_rows is not None:
            self.metrics().add("output_rows", result._num_rows)
        else:
            deferred_rows(self.metrics(), "output_rows", result)
        return [result]

    #: hash-range partitions a spilled build splits into; each rehydrates
    #: alone, so peak build memory is ~1/8th of the in-memory path
    _SPILL_PARTS = 8

    def _make_spill_part(self, rsch):
        """The spill partitioner's compiler and program, shared across
        jobs: they bake in the build side's key expressions and the
        partition count.  The keys compile against a compiler of their
        own: the join's is shared, and a string key registers aux slots
        on the compiler it compiles against."""
        rexprs = [re_ for _, re_ in self.on]
        nparts = self._SPILL_PARTS

        def build():
            pcomp = ExprCompiler(rsch, "device")
            rkeys = [pcomp.compile_key(re_) for re_ in rexprs]
            bits = (nparts - 1).bit_length()

            def part_fn(bcols, bmask, raux):
                h = K.hash64([c.fn(bcols, raux) for c in rkeys])
                # arithmetic shift + mask = top ``bits`` bits
                return ((h >> (64 - bits)) & (nparts - 1)).astype(jnp.int32)

            return pcomp, observed_jit("join.spill_part", part_fn)

        if has_scalar_subquery(*rexprs):
            return build()
        return shared_program(
            ("join.spill_part", schema_sig(rsch), exprs_sig(rexprs), nparts),
            build)

    def _join_spilled(self, ctx, probe, build_parts, lsch, rsch):
        """Reservation denied: partitioned-build spill for
        inner/semi/anti.  Build batches are split by the TOP BITS OF THE
        JOIN-KEY HASH into ``_SPILL_PARTS`` disk partitions (IPC runs),
        then each partition rehydrates alone and the full probe runs
        against it.

        Bit-identity with the single in-memory build:

        - every candidate match of a probe row shares that row's key
          hash, so ALL of its matches live in exactly one partition;
        - build rows keep their original relative order within a
          partition (batches split in order, runs read in write order),
          and ``build_side_sort`` breaks equal-hash ties by position, so
          the per-probe-row match order equals the single build's;
        - inner outputs carry the probe-row index ``pi``: a stable host
          sort on pi re-interleaves the per-partition outputs into
          exactly the single-build emission order;
        - semi/anti are mask algebra over the probe (hit = OR of
          per-partition hits), order-free by construction.
        """
        from ..memory.spill import Spiller

        with self.xla_lock():
            self._ensure_compiled(ctx, lsch, rsch)
            if getattr(self, "_spill_part", None) is None:
                self._spill_part = self._make_spill_part(rsch)
        lcomp, rcomp, fcomp, jfn, cfn, pfn = self._compiled
        pcomp, part_fn = self._spill_part
        nparts = self._SPILL_PARTS
        spiller = Spiller(ctx.work_dir, ctx.job_id, tag="join")
        runs: List[list] = [[] for _ in range(nparts)]
        try:
            with self.metrics().timer("join_time"):
                for b in build_parts:
                    ctx.check_cancelled()
                    part = part_fn(b.columns, b.mask,
                                   pcomp.aux_arrays(b.dicts))
                    cols, _n = b.packed_numpy(extra32={"__part": part})
                    pids = cols.pop("__part")
                    for p in range(nparts):
                        sel = pids == p
                        if not sel.any():
                            continue
                        runs[p].append(spiller.write_run(
                            rsch,
                            {f.name: cols[f.name][sel] for f in rsch},
                            b.dicts))
                self.metrics().add("spill_runs", len(spiller.runs))
                self.metrics().add(
                    "spill_bytes",
                    sum(r.num_bytes for r in spiller.runs))

                laux = lcomp.aux_arrays(probe.dicts)
                ceiling = ctx.config.get(JOIN_MAX_CAPACITY)
                low_floor = max(64, probe.capacity // 64)
                grand_total = 0
                inner_parts = []  # (packed cols incl __pi, partition dicts)
                mask_acc = None
                for p in range(nparts):
                    if not runs[p]:
                        # no build rows hash here: inner/semi add nothing,
                        # anti keeps pmask (AND identity) — skip
                        continue
                    ctx.check_cancelled()
                    build_p = concat_batches(
                        rsch, spiller.read(rsch, runs=runs[p])).shrink()
                    raux = rcomp.aux_arrays(build_p.dicts)
                    faux = (fcomp.aux_arrays({**probe.dicts,
                                              **build_p.dicts})
                            if fcomp is not None else {})
                    bh_sorted, border = pfn(build_p.columns, build_p.mask,
                                            raux)
                    # one range lookup per rehydrated partition: its exact
                    # candidate count sizes the output; the cross-join
                    # guard sees the partition SUM
                    total_dev, lo, counts = self._lookup(
                        cfn, probe, bh_sorted, laux)
                    total_est = _scalar(total_dev)
                    grand_total += total_est
                    if grand_total > ceiling:
                        raise CapacityError(
                            f"join produced {grand_total}+ candidate "
                            f"pairs, above the {ceiling}-row ceiling; "
                            f"likely an accidental near-cross join — "
                            f"check join keys, or raise "
                            f"{JOIN_MAX_CAPACITY}")
                    out_cap = max(low_floor,
                                  1 << max(0, total_est - 1).bit_length())
                    res = jfn(probe.columns, probe.mask, build_p.columns,
                              build_p.mask, border, lo, counts, laux, raux,
                              faux, out_cap)
                    if self.join_type in ("semi", "anti"):
                        new_mask = res[1]
                        if mask_acc is None:
                            mask_acc = new_mask
                        elif self.join_type == "semi":
                            mask_acc = _mask_or(mask_acc, new_mask)
                        else:
                            mask_acc = _mask_and(mask_acc, new_mask)
                        continue
                    out_cols, out_mask, pi = res
                    pb = ColumnBatch(self._schema, dict(out_cols),
                                     out_mask,
                                     {**probe.dicts, **build_p.dicts})
                    cols, _n = pb.packed_numpy(extra32={"__pi": pi})
                    inner_parts.append((cols, build_p.dicts))
            if self.join_type in ("semi", "anti"):
                if mask_acc is None:  # empty build side
                    mask_acc = probe.mask if self.join_type == "anti" \
                        else jnp.zeros_like(probe.mask)
                out = ColumnBatch(self._schema, dict(probe.columns),
                                  mask_acc, dict(probe.dicts))
                deferred_rows(self.metrics(), "output_rows", out)
                return [out]
            return [self._merge_spilled_inner(probe, inner_parts, rsch)]
        finally:
            spiller.cleanup()

    def _merge_spilled_inner(self, probe, inner_parts, rsch):
        """Order-restoring merge of per-partition inner outputs: remap
        each partition's build-side dictionary codes onto the sorted
        union dictionary, concatenate, stable-sort by probe-row index."""
        rstr = [f.name for f in rsch if f.dtype.is_string]
        union: Dict[str, np.ndarray] = {}
        for n in rstr:
            vals = [d.get(n) for _c, d in inner_parts
                    if d.get(n) is not None and len(d.get(n))]
            union[n] = (np.unique(np.concatenate(vals)) if vals
                        else np.array([], dtype=object))
        cols: Dict[str, list] = {f.name: [] for f in self._schema}
        pis = []
        for cols_np, dicts_p in inner_parts:
            for n in rstr:
                dic = dicts_p.get(n)
                codes = cols_np[n]
                if dic is not None and len(dic):
                    idx = np.searchsorted(union[n], dic).astype(np.int32)
                    live = codes >= 0
                    codes = codes.copy()
                    codes[live] = idx[codes[live]]
                    cols_np[n] = codes
            for f in self._schema:
                cols[f.name].append(cols_np[f.name])
            pis.append(cols_np["__pi"])
        pi = np.concatenate(pis) if pis else np.array([], dtype=np.int32)
        if pi.size == 0:
            out = ColumnBatch.empty(self._schema, 64)
            self.metrics().add("output_rows", 0)
            return out
        order = np.argsort(pi, kind="stable")
        data = {n: np.concatenate(v)[order] for n, v in cols.items()}
        dicts = {}
        for f in self._schema:
            if not f.dtype.is_string:
                continue
            dicts[f.name] = union[f.name] if f.name in union \
                else probe.dicts.get(f.name)
        dicts = {n: d for n, d in dicts.items() if d is not None}
        out = ColumnBatch.from_numpy(self._schema, data, dicts=dicts)
        self.metrics().add("output_rows", int(pi.size))
        return out

    def _join_chunked(self, ctx, probe, build, border, lo, counts,
                      laux, raux, faux, budget: int, ceiling: int,
                      planned_cap: int):
        """Bounded-footprint probe loop: the probe is windowed by row-range
        masks (static shapes preserved — no reslicing, so ONE compiled
        program serves every window) and each window's expansion buffer is
        sized by its own share of ``counts``, the one range lookup the
        caller made for the whole probe: no window searches the build
        again.  Exact for inner/semi/anti: a probe
        row's matches depend only on that row and the build side.
        Semi/anti windows OR their verdict masks into one output batch;
        inner windows each emit a bounded batch.

        Skew caveat: window counts are data-dependent, so a window holding
        most of the matches still allocates its real match count — the
        overrun is bounded by that window's genuine output size (which must
        be materialized regardless), not by fan-out across the whole probe."""
        jfn = self._compiled[3]
        cap = probe.capacity
        width = self._out_row_bytes()
        want = max(1, -(-planned_cap * width // budget))
        chunks = 1 << (want - 1).bit_length()
        chunks = min(chunks, max(1, cap // 1024))
        chunk_rows = -(-cap // chunks)
        # shared capacity bucket: windows whose counts fit half the budget
        # all compile into ONE program (compiles cost minutes on TPU — the
        # same reason the single-pass path floors at probe.capacity//4)
        bucket_floor = 64
        half_budget_rows = budget // (2 * width)
        if half_budget_rows > 64:
            bucket_floor = 1 << (half_budget_rows.bit_length() - 1)
        bucket_floor = min(bucket_floor, max(64, chunk_rows))
        self.metrics().add("join_probe_chunks", chunks)
        out_batches: List[ColumnBatch] = []
        mask_acc = None  # semi/anti: accumulated verdict mask
        dicts = dict(probe.dicts)
        if self.join_type == "inner":
            dicts.update(build.dicts)
        # all window counts in ONE program + ONE host transfer (per-window
        # scalar syncs would cost their fixed latency each)
        counts_dev = _window_counts(counts, chunk_rows, chunks)
        with device_wait("scalar"):
            # ballista: allow=hot-path-purity — deliberate single batched transfer
            window_counts = np.asarray(counts_dev)
        grand_total = 0  # the cross-join guard must see the SUM of windows
        for i in range(chunks):
            ctx.check_cancelled()
            pmask_c, counts_c = _window_mask(
                probe.mask, counts, i * chunk_rows,
                min((i + 1) * chunk_rows, cap))
            total_c = int(window_counts[i])
            grand_total += total_c
            if grand_total > ceiling:
                raise CapacityError(
                    f"join produced {grand_total}+ candidate pairs, above "
                    f"the {ceiling}-row ceiling; likely an accidental "
                    f"near-cross join — check join keys, or raise "
                    f"{JOIN_MAX_CAPACITY}")
            out_cap = max(64, 1 << max(0, total_c - 1).bit_length(),
                          bucket_floor)
            if out_cap > ceiling:
                # ballista: allow=trace-key-stability — above-ceiling exact-size fallback, same trade as the unchunked probe: one exact-size compile beats blowing the window capacity ceiling; rare by construction
                out_cap = max(total_c, 64)
            # out_cap >= total_c = sum(counts_c): the window's pairs fit
            out_cols, out_mask = jfn(
                probe.columns, pmask_c, build.columns, build.mask,
                border, lo, counts_c, laux, raux, faux, out_cap)[:2]
            if self.join_type in ("semi", "anti"):
                mask_acc = out_mask if mask_acc is None \
                    else _mask_or(mask_acc, out_mask)
            else:
                b = ColumnBatch(self._schema, dict(out_cols), out_mask, dicts)
                deferred_rows(self.metrics(), "output_rows", b)
                out_batches.append(b)
        if self.join_type in ("semi", "anti"):
            b = ColumnBatch(self._schema, dict(probe.columns), mask_acc, dicts)
            deferred_rows(self.metrics(), "output_rows", b)
            return [b]
        return out_batches

    def _label(self):
        on = ", ".join(f"{l} = {r}" for l, r in self.on)
        f = f" filter={self.filter}" if self.filter is not None else ""
        return f"JoinExec({self.join_type}, {self.dist}): on=[{on}]{f}"


# --------------------------------------------------------------------------
# sort / limit / coalesce
# --------------------------------------------------------------------------


class SortExec(ExecutionPlan):
    """Total sort of a single-partition input (the planner shuffles to one
    partition first, like the reference's SortPreservingMerge stage split,
    reference ballista/scheduler/src/planner.rs:80-165).  ``fetch`` fuses
    LIMIT into the sort."""

    def __init__(self, input: ExecutionPlan, keys: List[Tuple[E.Expr, bool]],
                 fetch: Optional[int] = None):
        self.input = input
        self.keys = keys
        self.fetch = fetch
        self._schema = input.schema
        self._compiled = None

    def children(self):
        return [self.input]

    def output_partition_count(self):
        return 1

    def output_partitioning(self):
        return Partitioning.single()

    def execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        with ctx.op_span(self):
            return self._execute(partition, ctx)

    def _execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        parts = []
        for p in range(self.input.output_partition_count()):
            ctx.check_cancelled()
            parts.extend(self.input.execute(p, ctx))
        big = concat_batches(self.input.schema, parts).shrink()

        with self.xla_lock():
            if self._compiled is None:
                def build():
                    comp = ExprCompiler(self.input.schema, "device")
                    keys_c = [(comp.compile(_substitute_scalars(e, ctx.scalars)), asc) for e, asc in self.keys]

                    def sort_fn(cols, mask, aux):
                        key_arrays = [(c.fn(cols, aux), asc) for c, asc in keys_c]
                        order = K.sort_order(key_arrays, mask)
                        return {k: v[order] for k, v in cols.items()}, mask[order]

                    return comp, observed_jit("sort.order", sort_fn)

                if has_scalar_subquery(*[e for e, _ in self.keys]):
                    self._compiled = build()
                else:
                    self._compiled = shared_program(
                        ("sort", schema_sig(self.input.schema),
                         tuple(asc for _, asc in self.keys),
                         exprs_sig([e for e, _ in self.keys])), build)
            comp, jfn = self._compiled
            with self.metrics().timer("sort_time"):
                aux = comp.aux_arrays(big.dicts)
                cols, mask = jfn(big.columns, big.mask, aux)
        b = ColumnBatch(self._schema, cols, mask, big.dicts)
        if self.fetch is not None and self.fetch < b.capacity:
            keep = max(self.fetch, 1)
            cols = {k: v[:keep] for k, v in cols.items()}
            mask = mask[:keep] & (jnp.arange(keep) < self.fetch)
            b = ColumnBatch(self._schema, cols, mask, big.dicts)
        return [b]

    def _label(self):
        k = ", ".join(f"{e} {'ASC' if asc else 'DESC'}" for e, asc in self.keys)
        f = f" fetch={self.fetch}" if self.fetch is not None else ""
        return f"SortExec: [{k}]{f}"


class LimitExec(ExecutionPlan):
    def __init__(self, input: ExecutionPlan, n: int):
        self.input = input
        self.n = n
        self._schema = input.schema

    def children(self):
        return [self.input]

    def output_partition_count(self):
        return 1

    def execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        with ctx.op_span(self):
            return self._execute(partition, ctx)

    def _execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        parts = []
        for p in range(self.input.output_partition_count()):
            parts.extend(self.input.execute(p, ctx))
        big = concat_batches(self.input.schema, parts)
        cols, mask = K.compact_columns(big.columns, big.mask)
        keep = max(self.n, 1)
        cols = {k: v[:keep] for k, v in cols.items()}
        mask = mask[:keep] & (jnp.arange(keep) < self.n)
        return [ColumnBatch(self._schema, cols, mask, big.dicts)]

    def _label(self):
        return f"LimitExec: {self.n}"


class CoalescePartitionsExec(ExecutionPlan):
    """Merges all input partitions into one (reference analog:
    CoalescePartitionsExec, a stage-split point in planner.rs:117-131)."""

    def __init__(self, input: ExecutionPlan):
        self.input = input
        self._schema = input.schema

    def children(self):
        return [self.input]

    def output_partition_count(self):
        return 1

    def output_partitioning(self):
        return Partitioning.single()

    def execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        with ctx.op_span(self):
            return self._execute(partition, ctx)

    def _execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        out = []
        for p in range(self.input.output_partition_count()):
            out.extend(self.input.execute(p, ctx))
        return out
