"""FusedStageExec: one jitted program for a whole operator chain.

The kernel builder composes the EXISTING per-operator compute closures
(the same ExprCompiler output the interpreted operators run) into a
single traced function — filter masks, projection columns and the
partial-aggregate kernel all execute inside one XLA program, so the
intermediate ColumnBatches the interpreted chain would materialize
between operators never exist.  Bit-identical by construction: every
step calls the function the interpreted operator would have called, in
the same order, on the same values.

Plan-shape contract (what makes fused stages transparently rollback- and
speculation-safe): ``ops[0]`` is the chain head (closest to the shuffle
writer), ``ops[-1]`` the tail, and the ops keep their own ``.input``
links — ``ops[i].input is ops[i+1]`` — so ``self.input`` is just a
property over ``ops[-1].input``.  Planner walks (``map_children``,
``rollback_resolved_shuffles``), AQE grafts and serde therefore treat a
fused stage like any single-input operator, with no defuse step.

Runtime safety valve: any unexpected failure inside the fused path
latches ``_fallback`` and delegates to the interpreted chain head —
fusion is a pure performance rewrite and must never be the reason a
query errors.
"""
from __future__ import annotations

import threading
import weakref
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from ..models.batch import ColumnBatch, concat_batches, round_capacity
from ..models.schema import BOOL
from ..obs.device import observed_jit
from ..ops import kernels as K
from ..ops.expressions import ExprCompiler
from ..ops.operators import (FilterExec, HashAggregateExec, ProjectionExec,
                             _null_restore,
                             RenameExec, _substitute_scalars, null_check_of)
from ..ops.physical import (ExecutionPlan, TaskContext, deferred_rows,
                            schema_sig, shared_program)
from ..utils.errors import (CancelledError, CapacityError, IntegrityError,
                            InternalError, MemoryExhausted)
from .chains import chain_fingerprint

_warned_fallback = set()
_warn_lock = threading.Lock()


def _warn_once(sig: str, exc: BaseException) -> None:
    with _warn_lock:
        if sig in _warned_fallback:
            return
        _warned_fallback.add(sig)
    import logging

    logging.getLogger(__name__).warning(
        "fused kernel %s failed (%s: %s); stage latched back to the "
        "interpreted path", sig, type(exc).__name__, exc)


class FusedStageExec(ExecutionPlan):
    """A fused operator chain executing as one jitted program.

    ``ops``: chain operators head-first with intact ``.input`` links
    (``ops[i].input is ops[i+1]``).  ``donate``: donate the input column
    buffers to the fused program (non-CPU backends).  Agg-headed chains
    donate too since the plan-ahead capacity protocol (PR 19): the
    aggregate runs as ONE call whose out_cap provably bounds the group
    count, so the inputs are dead after the call — the donation-safety
    analyzer (analysis/jit_discipline.py) checks the proof.
    """

    def __init__(self, ops: List[ExecutionPlan], donate: bool = False):
        if len(ops) < 2:
            raise InternalError("fused chain needs at least 2 operators")
        for a, b in zip(ops, ops[1:]):
            if a.input is not b:
                raise InternalError("fused chain ops must be input-linked")
        self.ops = list(ops)
        self.donate = donate
        self._schema = ops[0].schema
        self._compiled = None
        self._fallback = False

    # --- plan-shape interface (single-input operator) --------------------
    @property
    def input(self) -> ExecutionPlan:
        return self.ops[-1].input

    @input.setter
    def input(self, node: ExecutionPlan) -> None:
        self.ops[-1].input = node

    def children(self):
        return [self.input]

    def output_partition_count(self):
        return self.ops[0].output_partition_count()

    def output_partitioning(self):
        return self.ops[0].output_partitioning()

    def _head_agg(self) -> Optional[HashAggregateExec]:
        head = self.ops[0]
        return head if isinstance(head, HashAggregateExec) else None

    def fused_sig(self) -> str:
        return "fused:" + "+".join(type(o).__name__ for o in self.ops)

    # --- kernel builder --------------------------------------------------
    def _row_step(self, op: ExecutionPlan, ctx: TaskContext):
        """(trace_fn, compiler_or_None, dict_transform) for one non-head
        (or row-only head) operator.  ``trace_fn(cols, mask, aux) ->
        (cols, mask)`` runs inside the fused trace; the compiler supplies
        per-batch aux LUTs; ``dict_transform`` threads the host-side
        string dictionaries the way the interpreted operator would."""
        if isinstance(op, FilterExec):
            comp = ExprCompiler(op.input.schema, "device")
            pred = comp.compile_pred(
                _substitute_scalars(op.predicate, ctx.scalars))
            if pred.dtype != BOOL:
                raise InternalError("filter predicate must be boolean")

            def tr_filter(cols, mask, aux, pred=pred):
                return cols, mask & pred.fn(cols, aux)

            return tr_filter, comp, lambda dicts: dicts
        if isinstance(op, ProjectionExec):
            comp, compiled, _jfn = op._compile(ctx.scalars)

            def tr_proj(cols, mask, aux, compiled=compiled):
                new = {}
                for c, n in compiled:
                    v = c.fn(cols, aux)
                    new[n] = jnp.broadcast_to(v, mask.shape) \
                        if v.ndim == 0 else v
                return new, mask

            def dicts_proj(dicts, compiled=compiled):
                return {n: c.dict_fn(dicts) for c, n in compiled
                        if c.dict_fn is not None}

            return tr_proj, comp, dicts_proj
        if isinstance(op, RenameExec):
            mapping = list(op._mapping)

            def tr_rename(cols, mask, aux, mapping=mapping):
                return {new: cols[old] for old, new in mapping}, mask

            def dicts_rename(dicts, mapping=mapping):
                return {new: dicts[old] for old, new in mapping
                        if old in dicts}

            return tr_rename, None, dicts_rename
        raise InternalError(
            f"operator {type(op).__name__} is not fusable as a row step")

    def _build(self, ctx: TaskContext):
        agg = self._head_agg()
        row_ops = self.ops[1:] if agg is not None else self.ops
        steps = [self._row_step(op, ctx) for op in reversed(row_ops)]
        traces = [t for t, _c, _d in steps]
        thread = [(c, d) for _t, c, d in steps]

        donate_kw = {}
        if self.donate:
            import jax

            if jax.default_backend() != "cpu":
                # donation is a no-op warning on CPU.  The mask (arg 1)
                # rides the same donation-safety proof as the columns: both
                # come off a fresh ShuffleReaderExec batch rebound per loop
                # iteration and are dead after the call, so XLA can alias
                # the output mask into the input mask buffer too.  Agg
                # heads qualify since plan-ahead capacity (ONE call per
                # input — no retry ladder re-reading donated buffers).
                donate_kw["donate_argnums"] = (0, 1)

        if agg is None:
            def fused_rows(cols, mask, auxs):
                for i, tr in enumerate(traces):
                    cols, mask = tr(cols, mask, auxs[i])
                return cols, mask

            jfn = observed_jit(self.fused_sig(), fused_rows, **donate_kw)
            return (thread, jfn, None)

        # agg-headed chain: reuse the aggregate's own (possibly shared)
        # compiled closures — the raw traced function composes under the
        # fused trace via __wrapped__, and NULL semantics/tracked hidden
        # valid counts travel with agg_c/tracked unchanged
        comp_a, group_c, agg_c, tracked, agg_jfn = \
            agg._make_compiled(ctx, agg.input.schema)
        raw_agg = agg_jfn.__wrapped__

        def fused_agg(cols, mask, auxs, out_cap, key_ranges):
            for i, tr in enumerate(traces):
                cols, mask = tr(cols, mask, auxs[i])
            return raw_agg(cols, mask, auxs[-1], out_cap, key_ranges)

        jfn = observed_jit(self.fused_sig(), fused_agg,
                           static_argnums=(3, 4),
                           variant=agg.program_variant(), **donate_kw)
        return (thread, jfn, (comp_a, group_c, agg_c, tracked))

    def _ensure_compiled(self, ctx: TaskContext):
        if self._compiled is None:
            # the chain is allowlisted scalar-subquery-free, so the fused
            # program is job-independent: share it process-wide under the
            # chain's structural fingerprint — repeated/templated queries
            # (plan cache) hit the same trace cache and report 0 compiles
            key = ("fused", self.donate,
                   tuple(type(o).__name__ for o in self.ops),
                   chain_fingerprint(self.ops,
                                     schema_sig(self.input.schema)))
            self._compiled = shared_program(key, lambda: self._build(ctx))

    def _auxs_and_dicts(self, thread, dicts: Dict[str, np.ndarray]):
        """Per-step aux LUTs + the dictionary threading the interpreted
        chain would do batch-by-batch, host-side, bottom-up."""
        auxs = []
        for comp, dict_tr in thread:
            auxs.append(comp.aux_arrays(dicts) if comp is not None else {})
            dicts = dict_tr(dicts)
        return auxs, dicts

    # --- execution -------------------------------------------------------
    def execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        with ctx.op_span(self):
            return self._execute(partition, ctx)

    def _execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        if self._fallback:
            return self.ops[0].execute(partition, ctx)
        try:
            with self.xla_lock():
                self._ensure_compiled(ctx)
            if self._head_agg() is not None:
                return self._execute_agg(partition, ctx)
            return self._execute_rows(partition, ctx)
        except (CancelledError, CapacityError, IntegrityError,
                MemoryExhausted):
            # memory denials and spill-integrity failures are the
            # governor's retry/spill protocol speaking, not a fused-path
            # defect — never latch the fallback for them
            raise
        except Exception as exc:  # noqa: BLE001 — pure perf rewrite:
            # never let fusion be the reason a query fails; latch the
            # interpreted path and re-run this partition through it
            self._fallback = True
            self.metrics().add("fused_fallbacks", 1)
            _warn_once(self.fused_sig(), exc)
            return self.ops[0].execute(partition, ctx)

    def _execute_rows(self, partition: int, ctx: TaskContext):
        thread, jfn, _ = self._compiled
        out = []
        for b in self.input.execute(partition, ctx):
            ctx.check_cancelled()
            with self.metrics().timer("compute_time"):
                auxs, dicts = self._auxs_and_dicts(thread, b.dicts)
                cols, mask = jfn(b.columns, b.mask, tuple(auxs))
                result = ColumnBatch(self._schema, dict(cols), mask, dicts)
                deferred_rows(self.metrics(), "output_rows", result)
                out.append(result)
        return out

    def _execute_agg(self, partition: int, ctx: TaskContext):
        """Mirror of HashAggregateExec._execute_device with the row
        pipeline fused in front of the aggregate kernel (same plan-ahead
        capacity, dense-domain bound, hidden-valid-count NULL restore
        and adaptive passthrough probe)."""
        agg = self._head_agg()
        batches = self.input.execute(partition, ctx)
        ctx.check_cancelled()

        # memory governor: same reserve-before-materialize protocol as
        # the interpreted aggregate.  A denial delegates this partition
        # to the interpreted chain head — whose own governor check denies
        # again and takes the per-batch spill path — WITHOUT latching
        # _fallback: the next partition may well be granted and fuse.
        gov = getattr(ctx, "governor", None)
        reservation = None
        if gov is not None:
            from ..ops.operators import _state_bytes

            est = _state_bytes(batches, self.input.schema, agg.schema)
            reservation = gov.try_reserve(est, site="fused-agg")
            if reservation is None:
                self.metrics().add("fused_spill_delegations", 1)
                return self.ops[0].execute(partition, ctx)
        try:
            return self._execute_agg_inmem(ctx, batches)
        finally:
            if reservation is not None:
                reservation.release()

    def _execute_agg_inmem(self, ctx: TaskContext, batches):
        agg = self._head_agg()
        big = concat_batches(self.input.schema, batches).shrink()
        thread, jfn, (comp_a, group_c, agg_c, tracked) = self._compiled

        with self.metrics().timer("agg_time"):
            auxs, dicts_in = self._auxs_and_dicts(thread, big.dicts)
            aux_a = comp_a.aux_arrays(dicts_in)
            all_auxs = tuple(auxs) + (aux_a,)
            key_ranges = []
            for cc, _n in group_c:
                if cc.dtype.is_string and cc.dict_fn is not None:
                    dic = cc.dict_fn(dicts_in)
                    key_ranges.append((-1, round_capacity(len(dic), 16) - 1))
                elif cc.dtype.kind == "bool":
                    key_ranges.append((0, 1))
                else:
                    key_ranges.append(None)
            key_ranges = tuple(key_ranges)
            # plan-ahead capacity (see HashAggregateExec._execute_device):
            # the input capacity (or the dense key domain) provably bounds
            # the group count, so the overflow flag is statically None and
            # the program runs EXACTLY ONCE per input — which is what
            # makes the donated input buffers dead after the call
            out_cap = big.capacity
            domain = K.dense_domain(key_ranges)
            if domain is not None:
                out_cap = min(out_cap, domain)
                if K.i64_sum_path(domain + 1, big.capacity) == "contraction":
                    self.metrics().add("mxu_grouped_sums", 1)
            elif group_c:
                # keys and no dense domain: the kernel's run scans
                self.metrics().add("run_scan_aggregates", 1)
            # read host-side facts BEFORE the call: the donated column and
            # mask buffers are dead after it, so nothing below may touch
            # the input batch (donation-safety analyzer enforces this)
            inp_rows, inp_cap = big._num_rows, big.capacity
            out_keys, out_vals, out_mask, overflow = jfn(
                big.columns, big.mask, all_auxs, out_cap, key_ranges)
            del big
            if overflow is not None and bool(overflow):
                raise CapacityError(
                    f"fused aggregation overflowed {out_cap} groups "
                    f"with {big.capacity}-row input; this should be "
                    "impossible")

        cols: Dict[str, jnp.ndarray] = {}
        dicts: Dict[str, np.ndarray] = {}
        for (cc, name), arr in zip(group_c, out_keys):
            cols[name] = arr
            if cc.dict_fn is not None:
                dicts[name] = cc.dict_fn(dicts_in)
        for (cc, how, name, _), arr in zip(agg_c, out_vals[: len(agg_c)]):
            cols[name] = arr
        for i, cnt in zip(tracked, out_vals[len(agg_c):]):
            name = agg_c[i][2]
            f = agg.schema.field(name)
            cols[name] = _null_restore(cnt, cols[name],
                                       f.dtype.np_dtype.type(
                                           f.dtype.null_sentinel))
        result = ColumnBatch(agg.schema, cols, out_mask, dicts)

        # adaptive passthrough probe (same thresholds as the interpreted
        # aggregate): poor reduction on a large input latches BOTH the
        # aggregate's passthrough flag and this stage's interpreted
        # fallback, so sibling tasks emit per-row states
        res_ref = weakref.ref(result)
        self_ref, agg_ref = weakref.ref(self), weakref.ref(agg)

        def _finish():
            res = res_ref()
            if res is None:
                return 0
            rn = res._num_rows
            if rn is None:
                return None
            bn = inp_rows
            poor = (bn is not None and bn >= (1 << 17) and rn > 0.6 * bn) \
                or (bn is None and inp_cap >= (1 << 17)
                    and rn > 0.6 * inp_cap)
            if poor:
                me, ag = self_ref(), agg_ref()
                if me is not None and ag is not None:
                    ag._passthrough = True
                    me._fallback = True
                    me.metrics().add("fused_passthrough_fallbacks", 1)
            return rn

        if result._num_rows is not None:
            self.metrics().add("output_rows", _finish())
        else:
            self.metrics().add_deferred("output_rows", _finish)
        return [result]

    def _label(self):
        extra = ", donated" if self.donate else ""
        inner = " <- ".join(type(o).__name__ for o in self.ops)
        return (f"FusedStageExec[{len(self.ops)} ops, 1 kernel{extra}]: "
                f"{inner}")
