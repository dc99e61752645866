"""Mesh-fused operators: whole stage *pairs* as one XLA program.

Where the reference always materializes the exchange (partial-agg tasks ->
shuffle files -> final-agg tasks; planner.rs:80-165 + shuffle_writer.rs),
the TPU-native fast path executes

    derive keys/values -> partial agg -> ICI all_to_all -> final agg

as a single compiled program over the jax.sharding.Mesh
(parallel/distributed.py): XLA overlaps the collective with compute, no
byte touches the host or disk.  Enabled per-session via
``ballista.shuffle.mesh``; the planner falls back to the file-shuffle
stage pair whenever the pattern doesn't fit (SURVEY.md §2.5 "fuse
co-located stages").
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..models import expr as E
from ..models.batch import ColumnBatch, concat_batches
from ..models.schema import Field, Schema
from ..obs import device as device_obs
from ..obs.tracing import span
from ..utils.config import JOIN_OUTPUT_FACTOR, MESH_BROADCAST_ROWS
from ..utils.errors import CapacityError
from .expressions import ExprCompiler
from .operators import AggSpec, HashAggregateExec, null_check_of, valid_of
from .physical import (ExecutionPlan, Partitioning, TaskContext, deferred_rows,
                       exprs_sig, has_scalar_subquery, schema_sig,
                       shared_program)


def _pow2(n: int) -> int:
    """Round a capacity up to a power of two (min 64): skewed partitions
    would otherwise give every task a distinct capacity signature, missing
    the shared run cache and compiling per task."""
    return max(64, 1 << max(0, int(n) - 1).bit_length())


def _unshard(tree, keep: Optional[int] = None):
    """Collapse a mesh program's outputs to ordinary arrays on the default
    device.

    Downstream operators run eager single-device ops; feeding them sharded
    arrays makes every eager op an 8-device collective program, and
    concurrently dispatched collective programs deadlock XLA's CPU
    rendezvous (observed: 'Expected 8 threads to join ... only 6 arrived'
    -> hard abort).  Device to device: every shard is copied to the default
    device and the copies are concatenated there in shard order (a
    replicated output is the default device's own replica: no copy); no
    byte crosses the host, whose bulk D2H runs at 0.2 GB/s on the v5e.
    ``keep``: the slots at the front of every shard that can hold a live
    row (an exchange's final states are compacted to the front of their
    ``final_capacity``); the rest are not moved.  A ``mesh_unshard`` span
    that ends when the arrays are in place (``rows``: those of the tree's
    last leaf, the mask), counted in ``mesh_unshard_bytes``."""
    dev = jax.devices()[0]

    def one(x):
        shards = sorted(x.addressable_shards,
                        key=lambda s: (s.index[0].start or 0,
                                       s.device != dev))
        if x.is_fully_replicated:
            return jax.device_put(shards[0].data, dev)
        parts = [s.data if keep is None or keep >= s.data.shape[0]
                 else s.data[:keep] for s in shards]
        # ballista: allow=host-device-boundary — device to device, not a host crossing: counted as mesh_unshard_bytes
        return jnp.concatenate([jax.device_put(p, dev) for p in parts])

    with span("mesh_unshard", "device", via="device") as sp:
        out = jax.tree_util.tree_map(one, tree)
        leaves = jax.tree_util.tree_leaves(out)
        nbytes = sum(x.nbytes for x in leaves)
        sp.set(bytes=nbytes, rows=int(leaves[-1].shape[0]))
        with device_obs.device_wait("ready"):
            jax.block_until_ready(out)
    device_obs.record_mesh_unshard(nbytes)
    return out


def _shard_rows(cols: Dict[str, jnp.ndarray], mask: jnp.ndarray, mesh,
                n_dev: int):
    """Rows data-parallel over the mesh, padded to a device-count multiple:
    ``(cols, mask, padded_rows)``.  A ``mesh_reshard`` span that ends when
    every shard is in place (the copies are asynchronous; the wait is a
    ``device_wait``), and ``mesh_reshard_bytes`` for what was placed."""
    from ..parallel.mesh import row_sharding

    rows = int(mask.shape[0])
    padded = -(-rows // n_dev) * n_dev

    def pad(arr, fill=0):
        if padded == rows:
            return arr
        return jnp.concatenate(
            [arr, jnp.full((padded - rows,), fill, arr.dtype)])

    with span("mesh_reshard", "device", devices=n_dev, rows=rows) as sp:
        cols = {k: pad(v) for k, v in cols.items()}
        mask = pad(mask, fill=False)
        nbytes = mask.nbytes + sum(v.nbytes for v in cols.values())
        sp.set(bytes=nbytes)
        # ballista: allow=host-device-boundary — mesh placement, not a host crossing: the source is already device-resident; counted as mesh_reshard_bytes
        cols, mask = jax.device_put((cols, mask), row_sharding(mesh))
        with device_obs.device_wait("ready"):
            jax.block_until_ready((cols, mask))
    device_obs.record_mesh_reshard(nbytes)
    return cols, mask, padded


def _dispatch(prog, *args, describe=None, **attrs):
    """One call of a mesh program (parallel/distributed.py ``MeshProgram``):
    a ``mesh_program`` span (``attrs`` beside its own) from the dispatch,
    under the process-wide dispatch lock, until the outputs are ready.  The
    program's last output is its overflow flag, or the ``stats`` of an
    exchange or a join whose first element is one, so fetching it (a
    ``device_wait``) waits for all of them.  Returns the outputs with the
    last as a host array; ``describe(last)`` gives the span what only that
    tells.  A join program's (``mesh_join_*``) collective bytes also count
    as the joins' own."""
    from ..parallel.mesh import MESH_DISPATCH_LOCK

    with span("mesh_program", "device", program=prog.name,
              collective=prog.collective, **attrs) as sp:
        with MESH_DISPATCH_LOCK:
            *out, last = prog(*args)
        with device_obs.device_wait("scalar"):
            last = np.asarray(last)
        if describe is not None:
            sp.set(**describe(last))
    device_obs.record_mesh_program(prog.collective_bytes(*args),
                                   join=prog.name.startswith("mesh_join"))
    return (*out, last)


def _exchange_bounds(rows: int, n_dev: int, shuffle_cap: Optional[int] = None):
    """``(partial, shuffle, final)`` capacities a device of an exchange
    aggregate over shards of ``rows`` rows, none from configuration: a
    shard has at most ``rows`` groups; a destination bucket of the send
    buffer holds ``shuffle_cap`` states, twice a bucket's even share of them
    unless a first run has said what it needs, and never more than the
    shard could fill it with; a device can receive, so own, at most
    ``n_dev`` full buckets.  The first and the last cannot be passed (the
    kernel's overflow flag is statically absent for both); the second can,
    by keys that hash unevenly, and the program then says by how much."""
    from ..parallel.distributed import shuffle_capacity_of

    shuffle_cap = shuffle_capacity_of(rows, n_dev) if shuffle_cap is None \
        else max(1, min(rows, shuffle_cap))
    return rows, shuffle_cap, n_dev * shuffle_cap


def _join_send_capacity(live: int, shard: int, n_dev: int) -> int:
    """Rows a destination bucket of one side's send buffer holds in a
    partitioned mesh join, from the side's live rows and not its slots:
    twice a bucket's even share of a device's even share of them
    (``shuffle_capacity_of``), rounded up to a power of two so that seeds
    of one scale share the program, and never more than a device's shard
    of ``shard`` slots could fill it with."""
    from ..parallel.distributed import shuffle_capacity_of

    share = shuffle_capacity_of(-(-live // n_dev), n_dev)
    return max(1, min(_pow2(share), shard))


# bounds that first runs found they needed, by what the program is shared
# under and the rows: a later execution of the same statement over the same
# rows starts there and pays no re-run.  An exchange aggregate keeps its
# send-bucket capacity, a join its (probe, build, output) capacities.
_EXCHANGE_NEED: Dict[tuple, object] = {}


# --- shared pieces of the two mesh aggregate operators ---------------------


_HIDDEN_PREFIX = "__vld_"


def _hidden_name(agg_name: str) -> str:
    return _HIDDEN_PREFIX + agg_name


def _hidden_base(hname: str) -> str:
    return hname[len(_HIDDEN_PREFIX):]


def _compile_agg_exprs(in_schema, group_exprs, aggs):
    comp = ExprCompiler(in_schema, "device")
    key_c = [(comp.compile(e), n) for e, n in group_exprs]
    val_c = []
    for a in aggs:
        cc = comp.compile(a.operand) if a.operand is not None else None
        val_c.append((cc, a, null_check_of(cc, a.operand, in_schema)))
    return comp, key_c, val_c


def _agg_specs(val_c):
    """(name, how) pairs to feed the distributed aggregate, plus the hidden
    per-group valid-count states that let all-NULL sum/min/max groups be
    restored to NULL after the exchange (SQL semantics; the file path's
    hidden-count trick in operators.py, carried through the collective
    here)."""
    specs, hidden = [], []
    for cc, a, nc in val_c:
        if a.func == "count":
            # count(*) counts live rows (AGG_COUNT ignores values); a
            # nullable count(col) sums the validity indicator instead
            specs.append((a.name, "sum" if nc is not None else "count"))
        else:
            specs.append((a.name, a.func))
            if nc is not None:
                hidden.append((_hidden_name(a.name), "sum"))
    return specs, hidden


def _make_derive(key_c, val_c):
    """Per-shard projection: group keys + aggregate operand columns.
    NULL operand rows are neutralized per aggregate (0 for sum, the
    fold identity for min/max, a 0/1 indicator for count) and tracked via
    hidden validity columns.  ``aux`` (the expressions' lookup tables) is
    an argument of the program, replicated, so one compiled program serves
    every dictionary of the same shape."""

    from . import kernels as K

    def derive(cols, mask, aux):
        out = {}
        for kc, n in key_c:
            out[n] = kc.fn(cols, aux)
        for cc, a, nc in val_c:
            if cc is None:
                out[a.name] = jnp.ones(mask.shape, jnp.int64)
                continue
            v = cc.fn(cols, aux)
            v = jnp.broadcast_to(v, mask.shape) if v.ndim == 0 else v
            if nc is None:
                out[a.name] = (jnp.ones(mask.shape, jnp.int64)
                               if a.func == "count" else v)
                continue
            valid = valid_of(v, nc)
            if a.func == "count":
                out[a.name] = valid.astype(jnp.int64)
            elif a.func == "sum":
                out[a.name] = jnp.where(valid, v, jnp.zeros((), v.dtype))
            elif a.func == "min":
                out[a.name] = jnp.where(valid, v, K._max_ident(v.dtype))
            else:  # max
                out[a.name] = jnp.where(valid, v, K._min_ident(v.dtype))
            if a.func in ("sum", "min", "max"):
                out[_hidden_name(a.name)] = valid.astype(jnp.int64)
        return out, mask

    return derive


def _agg_key_ranges(key_c, dicts):
    """Static per-key bounds for the dense sort-free grouping path
    (kernels.grouped_aggregate): dict-code ranges for strings, {0,1} for
    bools, None otherwise."""
    return tuple(
        (-1, int(len(kc.dict_fn(dicts))) - 1)
        if kc.dtype.is_string and kc.dict_fn is not None
        else ((0, 1) if kc.dtype.kind == "bool" else None)
        for kc, _n in key_c)


def _finish_states(schema, key_c, val_c, ks, vs, msk, big_dicts,
                   hidden_specs=(), keep=None):
    """Unshard fused-program outputs into one ordinary ColumnBatch, casting
    values to the operator's declared schema dtypes.  ``vs`` carries the
    main aggregate states followed by the hidden valid-count states
    (``hidden_specs`` order); all-NULL groups are restored to the output
    sentinel here, after the exchange.  ``keep``: see ``_unshard``."""
    n_main = len(val_c)
    ks, vs, msk = _unshard((list(ks), list(vs), msk), keep)
    out_cols: Dict[str, jnp.ndarray] = {}
    dicts: Dict[str, np.ndarray] = {}
    for (kc, name), arr in zip(key_c, ks):
        out_cols[name] = arr
        if kc.dict_fn is not None:
            dicts[name] = kc.dict_fn(big_dicts)
    for (cc, a, _nc), arr in zip(val_c, vs[:n_main]):
        want = schema.field(a.name).dtype.np_dtype
        out_cols[a.name] = arr.astype(want) if arr.dtype != want else arr
    for (hname, _how), cnt in zip(hidden_specs, vs[n_main:]):
        name = _hidden_base(hname)
        sentinel = out_cols[name].dtype.type(
            schema.field(name).dtype.null_sentinel)
        out_cols[name] = jnp.where(cnt > 0, out_cols[name], sentinel)
    return ColumnBatch(schema, out_cols, msk, dicts)


def _program(op, key, build):
    """The mesh program for ``key``: shared across jobs
    (``shared_program``), and kept on the operator, which is all the
    sharing a key holding None gets (one build for a stage's tasks)."""
    prog = op._progs.get(key)
    if prog is None:
        prog = op._progs[key] = shared_program(key, build)
    return prog


def _agg_program_key(in_schema, group_exprs, aggs):
    """What a mesh aggregate's compiled expressions and programs depend on
    besides shapes: the key they are shared under across jobs
    (``shared_program``), or one holding None where a scalar subquery bakes
    a job's own literal in."""
    exprs = [e for e, _ in group_exprs] + [a.operand for a in aggs]
    if has_scalar_subquery(*exprs):
        return (None,)
    return (schema_sig(in_schema), exprs_sig([e for e, _ in group_exprs]),
            tuple(n for _, n in group_exprs),
            tuple((a.func, a.name) for a in aggs),
            exprs_sig([a.operand for a in aggs]))


class MeshAggregateExec(ExecutionPlan):
    """Fused grouped aggregation over every local device.

    Replaces HashAggregateExec(final) <- Repartition(hash) <-
    HashAggregateExec(partial) when the mesh path is enabled.  Output is a
    single partition holding all groups (device d owns the key-hash
    bucket d; results are concatenated on fetch).
    """

    def __init__(self, input: ExecutionPlan, group_exprs: List[Tuple[E.Expr, str]],
                 aggs: List[AggSpec]):
        self.input = input
        self.group_exprs = group_exprs
        self.aggs = aggs
        in_schema = input.schema
        fields = [Field(n, e.dtype(in_schema)) for e, n in group_exprs]
        ref = HashAggregateExec(input, group_exprs, aggs, mode="single")
        for a in aggs:
            fields.append(ref.schema.field(a.name))
        self._schema = Schema(fields)
        self._compiled = None
        self._progs = {}

    @staticmethod
    def eligible(group_exprs, aggs, in_schema) -> bool:
        if not group_exprs:
            # global aggregates: the plain path is cheap — one masked
            # reduction into one row per partition since PR 27 (before it,
            # a scatter into capacity + 1 groups: 8 s a q6 at SF10)
            return False
        for a in aggs:
            if a.name.startswith(_HIDDEN_PREFIX):
                # the hidden validity columns ride in-band under this prefix;
                # a user aggregate aliased into it would collide with the
                # hidden state and silently corrupt results — keep such
                # plans on the (name-agnostic) file path
                return False
            if a.func not in ("sum", "count", "min", "max"):
                return False
            if a.operand is not None:
                # nullable operands ARE fused: derive neutralizes NULL rows
                # per aggregate and hidden valid counts ride the exchange
                # (_make_derive/_agg_specs); floats stay off the mesh path
                # (the partial+merge sum order differs from the file path's,
                # breaking bit-identical results)
                try:
                    if a.operand.dtype(in_schema).is_float:
                        return False
                except Exception:  # noqa: BLE001
                    return False
        for e, _ in group_exprs:
            try:
                if e.dtype(in_schema).is_float:
                    return False
            except Exception:  # noqa: BLE001
                return False
        return True

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
        from ..parallel.distributed import (distributed_dense_aggregate,
                                            distributed_filter_aggregate)
        from ..parallel.mesh import make_mesh
        from .kernels import dense_domain, i64_sum_path

        assert partition == 0
        in_schema = self.input.schema
        batches = []
        for p in range(self.input.output_partition_count()):
            batches.extend(self.input.execute(p, ctx))
        big = concat_batches(in_schema, batches)
        del batches

        n_dev = len(jax.devices())
        mesh = make_mesh(n_dev)

        base = _agg_program_key(in_schema, self.group_exprs, self.aggs)
        if self._compiled is None:
            self._compiled = shared_program(
                ("mesh_agg_exprs",) + base,
                lambda: _compile_agg_exprs(in_schema, self.group_exprs,
                                           self.aggs))
        comp, key_c, val_c = self._compiled
        dicts = big.dicts
        aux = comp.aux_arrays(dicts)  # replicated operands of the program

        key_names = [n for _, n in key_c]
        specs, hidden = _agg_specs(val_c)
        agg_specs = specs + hidden
        cols, mask, padded = _shard_rows(big.columns, big.mask, mesh, n_dev)
        # the concatenated copy is dead once its shards are in place: drop
        # it before the program asks for its workspace beside it
        del big

        key_ranges = _agg_key_ranges(key_c, dicts)
        domain = dense_domain(key_ranges)
        keep = None
        # one program a plan shape, device count and static bound, shared
        # across jobs: a re-run of the query traces and compiles nothing
        if domain is not None:
            # dense domain: slot-aligned accumulators merge by ONE
            # psum/pmin/pmax — the exchange disappears entirely
            # (distributed_dense_aggregate); overflow here can only mean a
            # key escaped its declared range
            prog = _program(
                self, ("mesh_agg_dense", n_dev, key_ranges, domain) + base,
                lambda: distributed_dense_aggregate(
                    mesh, _make_derive(key_c, val_c), key_names, agg_specs,
                    key_ranges, domain))
            fk, fv, fmask, overflow = _dispatch(prog, cols, mask, aux)
            if overflow:
                raise CapacityError(
                    "mesh dense aggregation saw keys outside their declared "
                    "ranges (dictionary/batch mismatch)")
            self.metrics().add("dense_reduce_collective", 1)
            if i64_sum_path(domain + 1, padded // n_dev) == "contraction":
                self.metrics().add("mxu_grouped_sums", 1)
        else:
            fk, fv, fmask, keep = self._exchange(
                n_dev, padded // n_dev, key_ranges, base,
                lambda partial, shuffle, final: distributed_filter_aggregate(
                    mesh, _make_derive(key_c, val_c), key_names, agg_specs,
                    partial_capacity=partial, final_capacity=final,
                    key_ranges=key_ranges, shuffle_capacity=shuffle),
                cols, mask, aux)
        del cols, mask

        result = _finish_states(self._schema, key_c, val_c, fk, fv, fmask,
                                dicts, hidden_specs=hidden, keep=keep)
        # deferred: the count becomes host-known for free when the shuffle
        # writer's packed fetch materializes this batch (an eager .num_rows
        # costs a scalar sync per task where remote_device() holds)
        deferred_rows(self.metrics(), "output_rows", result)
        self.metrics().add("mesh_devices", n_dev)
        return [result]

    def _exchange(self, n_dev, rows, key_ranges, base, build, *args):
        """The partial aggregate, ``all_to_all`` and final aggregate of
        shards of ``rows`` rows as one program (``build(partial, shuffle,
        final)``, called on ``args``) at ``_exchange_bounds``.  A send
        bucket fuller than its bound leaves states unsent: the program
        flags it and says what the fullest bucket needed, the flagged
        outputs are dropped whole, and it runs once more at that need,
        which the same rows cannot pass.  ``(keys, states, mask, keep)``:
        ``keep`` slots at the front of every device's share hold all of its
        groups."""
        memo = None if base == (None,) else (n_dev, rows, key_ranges) + base
        learned = _EXCHANGE_NEED.get(memo)
        for retries in (0, 1):
            bounds = partial_cap, shuffle_cap, final_cap = _exchange_bounds(
                rows, n_dev, learned)
            prog = _program(
                self, ("mesh_agg_exchange", n_dev, key_ranges) + bounds + base,
                lambda: build(*bounds))
            fk, fv, fmask, stats = _dispatch(
                prog, *args,
                describe=lambda st: {"groups_out": int(st[3])},
                partial_capacity=partial_cap, shuffle_capacity=shuffle_cap,
                final_capacity=final_cap, retries=retries)
            overflow, need, groups_max, _groups_out = (int(v) for v in stats)
            self.metrics().add("exchange_collective", 1)
            # the program's partial and final aggregates (keys, no dense
            # domain) each reduce runs of equal keys by segmented scans
            self.metrics().add("run_scan_aggregates", 2)
            if not overflow:
                return fk, fv, fmask, min(final_cap, _pow2(groups_max))
            del fk, fv, fmask
            if retries or need <= shuffle_cap:
                break
            learned = _pow2(need)
            if memo is not None:
                _EXCHANGE_NEED[memo] = learned
            self.metrics().add("exchange_retries", 1)
            device_obs.record_mesh_exchange_retry()
        raise CapacityError(
            f"mesh aggregation passed a bound that its input cannot pass "
            f"(partial {partial_cap}, shuffle {shuffle_cap}, final "
            f"{final_cap} a device over shards of {rows} rows; the fullest "
            f"send bucket needed {need})")

    def _label(self):
        g = ", ".join(n for _, n in self.group_exprs)
        a = ", ".join(f"{x.func}({x.name})" for x in self.aggs)
        return f"MeshAggregateExec(fused partial+all_to_all+final): groupBy=[{g}] aggr=[{a}]"


class MeshPartialAggregateExec(ExecutionPlan):
    """HYBRID mesh composition: the partial aggregate of a file-shuffled
    stage pair, fused over the executing host's LOCAL device mesh.

    Where MeshAggregateExec fuses the whole exchange in-process (one task,
    one host), this operator keeps the reference's stage structure — one
    task per input partition, file shuffle between stages — and uses the
    mesh only WITHIN each task: rows shard across the host's chips, every
    chip reduces its shard to group states, and the states ship through the
    ordinary shuffle to the final aggregate.  On a multi-host cluster this
    is "ICI within a host, Flight/file across hosts"
    (BASELINE.json.north_star; SURVEY §2.5 comm-backend row).

    Output schema/dtypes mirror HashAggregateExec(mode='partial') exactly,
    so the downstream RepartitionExec + final HashAggregateExec are
    untouched.
    """

    def __init__(self, input: ExecutionPlan, group_exprs: List[Tuple[E.Expr, str]],
                 aggs: List[AggSpec]):
        self.input = input
        self.group_exprs = group_exprs
        self.aggs = aggs
        ref = HashAggregateExec(input, group_exprs, aggs, mode="partial")
        self._schema = ref.schema
        self._compiled = None
        self._progs = {}

    eligible = MeshAggregateExec.eligible

    def children(self):
        return [self.input]

    def output_partition_count(self):
        return self.input.output_partition_count()

    def execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        with ctx.op_span(self):
            return self._execute(partition, ctx)

    def _execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        from ..parallel.distributed import distributed_partial_aggregate
        from ..parallel.mesh import make_mesh
        from .kernels import dense_domain

        in_schema = self.input.schema
        big = concat_batches(in_schema, self.input.execute(partition, ctx))

        n_dev = len(jax.devices())
        mesh = make_mesh(n_dev)

        base = _agg_program_key(in_schema, self.group_exprs, self.aggs)
        with self.xla_lock():
            if self._compiled is None:
                self._compiled = shared_program(
                    ("mesh_agg_exprs",) + base,
                    lambda: _compile_agg_exprs(
                        in_schema, self.group_exprs, self.aggs))
            comp, key_c, val_c = self._compiled
            dicts = big.dicts
            aux = comp.aux_arrays(dicts)

            key_names = [n for _, n in key_c]
            specs, hidden = _agg_specs(val_c)
            agg_specs = specs + hidden
            cols, mask, padded = _shard_rows(big.columns, big.mask, mesh,
                                             n_dev)
            del big

            # a shard has no more groups than rows: the sort path's
            # overflow flag is statically absent at this bound
            per_dev_cap = padded // n_dev
            key_ranges = _agg_key_ranges(key_c, dicts)
            domain = dense_domain(key_ranges)
            if domain is not None:
                per_dev_cap = min(per_dev_cap, domain)
            else:
                self.metrics().add("run_scan_aggregates", 1)
            # one program for a stage's N partition tasks and for every
            # later job of the same plan shape (the lookup tables are
            # operands, so per-partition dictionaries share it too)
            prog = _program(
                self,
                ("mesh_agg_partial", n_dev, key_ranges, per_dev_cap) + base,
                lambda: distributed_partial_aggregate(
                    mesh, _make_derive(key_c, val_c), key_names, agg_specs,
                    per_dev_cap, key_ranges=key_ranges))
            pk, pv, pmask, overflow = _dispatch(prog, cols, mask, aux)
            if overflow:
                raise CapacityError(
                    f"mesh partial aggregation passed {per_dev_cap} groups a "
                    f"device over shards of {padded // n_dev} rows: keys "
                    f"outside their declared ranges")
            del cols, mask

        # all-NULL partial states become sentinels here, exactly like the
        # file partial mode — the downstream final aggregate's value-based
        # null_check then skips them when merging across hosts
        result = _finish_states(self._schema, key_c, val_c, pk, pv, pmask,
                                dicts, hidden_specs=hidden)
        # deferred: the count becomes host-known for free when the shuffle
        # writer's packed fetch materializes this batch (an eager .num_rows
        # costs a scalar sync per task where remote_device() holds)
        deferred_rows(self.metrics(), "output_rows", result)
        self.metrics().add("mesh_devices", n_dev)
        return [result]

    def _label(self):
        g = ", ".join(n for _, n in self.group_exprs)
        a = ", ".join(f"{x.func}({x.name})" for x in self.aggs)
        return (f"MeshPartialAggregateExec(per-host mesh, file exchange): "
                f"groupBy=[{g}] aggr=[{a}]")


class MeshJoinExec(ExecutionPlan):
    """Fused partitioned equi-join over every local device.

    Replaces JoinExec(partitioned) <- Repartition(hash) x2 when the mesh
    path is enabled: both sides all_to_all by key bucket, then a per-device
    join — ONE XLA program where the
    reference materializes two shuffles and a reduce stage (exchange rules
    planner.rs:133-152; SURVEY.md §2.5 TP row).  Results are identical to
    the file-shuffle JoinExec path — verified by tests/test_mesh_exec.py.
    The per-device join takes one of two paths, by join type: an inner or
    left join sorts the build, looks up each probe row's range and expands
    the pairs; a semi or anti join asks only whether a key is present, by
    one sort of both sides' keys and one back (``kernels.key_membership``),
    and counts ``membership_joins``.

    Its bounds come from each side's live rows, fetched once a join (each
    side compacted where that frees a power of two of slots), not from the
    slots they arrive in: a build side of at most
    ``ballista.shuffle.mesh.broadcast_rows`` rows is all_gathered instead
    and the probe rows stay where they are; otherwise each side's send
    bucket holds twice its even share (``_join_send_capacity``), and the
    candidate pairs a device may expand are
    ``ballista.join.output_factor`` times a device's even share of the
    smaller side's rows (of the probe side's in a broadcast join; a semi or
    anti join expands none and has no such bound).  A
    program that passes a bound reports what it needed; the join runs
    again at that, and a later execution over the same rows starts there
    (``_EXCHANGE_NEED``).  A send bucket's need is exact at once, the
    pairs' once no send bucket dropped a row, so a join re-runs at most
    twice.
    """

    def __init__(self, left: ExecutionPlan, right: ExecutionPlan,
                 on: List[Tuple[E.Expr, E.Expr]], join_type: str = "inner"):
        assert join_type in ("inner", "left", "semi", "anti")
        self.left = left
        self.right = right
        self.on = on
        self.join_type = join_type
        if join_type in ("semi", "anti"):
            self._schema = left.schema
        elif join_type == "left":
            self._schema = Schema(
                list(left.schema)
                + [Field(f.name, f.dtype, nullable=True) for f in right.schema])
        else:
            self._schema = left.schema.merge(right.schema)
        self._compiled = None
        self._progs = {}

    @staticmethod
    def eligible(on, join_type, filter, lsch, rsch) -> bool:
        if join_type not in ("inner", "left", "semi", "anti"):
            return False
        if filter is not None:
            return False  # pair filters not fused yet
        for le, re_ in on:
            for e, sch in ((le, lsch), (re_, rsch)):
                try:
                    dt = e.dtype(sch)
                except Exception:  # noqa: BLE001
                    return False
                if dt.is_float:
                    return False
        return True

    def children(self):
        return [self.left, self.right]

    def output_partition_count(self):
        return 1

    def output_partitioning(self):
        return Partitioning.single()

    def execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        with ctx.op_span(self):
            return self._execute(partition, ctx)

    def _execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        assert partition == 0
        lsch, rsch = self.left.schema, self.right.schema
        probe = concat_batches(lsch, [b for p in range(self.left.output_partition_count())
                                      for b in self.left.execute(p, ctx)])
        build = concat_batches(rsch, [b for p in range(self.right.output_partition_count())
                                      for b in self.right.execute(p, ctx)])
        return self._join_batches(probe, build, ctx)

    def _join_batches(self, probe: ColumnBatch, build: ColumnBatch,
                      ctx: TaskContext) -> List[ColumnBatch]:
        from ..parallel.mesh import make_mesh

        lsch, rsch = self.left.schema, self.right.schema
        n_dev = len(jax.devices())
        mesh = make_mesh(n_dev)
        # each side's live rows, one scalar fetch a side where the count is
        # not known yet, and the side compacted where that frees a power of
        # two of slots
        probe = probe.shrink(fetch_count=True)
        build = build.shrink(fetch_count=True)
        p_live, b_live = probe.num_rows, build.num_rows

        # the compiled keys are shared across a stage's tasks
        # (MeshTaskJoinExec runs one task per partition)
        with self.xla_lock():
            if self._compiled is None:
                lcomp = ExprCompiler(lsch, "device")
                rcomp = ExprCompiler(rsch, "device")
                lkeys = [lcomp.compile_key(le) for le, _ in self.on]
                rkeys = [rcomp.compile_key(re_) for _, re_ in self.on]
                self._compiled = (lcomp, rcomp, lkeys, rkeys)
        lcomp, rcomp, lkeys, rkeys = self._compiled
        laux = lcomp.aux_arrays(probe.dicts)
        raux = rcomp.aux_arrays(build.dicts)

        sflags = [c.dtype.is_string for c in lkeys]

        def with_keys(cols, mask, keys_c, aux):
            out = dict(cols)
            for i, kc in enumerate(keys_c):
                k = kc.fn(cols, aux)
                out[f"__jk{i}"] = (jnp.broadcast_to(k, mask.shape)
                                   if k.ndim == 0 else k)
            return out

        pcols = with_keys(probe.columns, probe.mask, lkeys, laux)
        bcols = with_keys(build.columns, build.mask, rkeys, raux)
        if self.join_type in ("semi", "anti"):
            # no build column leaves the join: only its keys travel
            bcols = {k: v for k, v in bcols.items() if k.startswith("__jk")}

        # NULL join keys never match (SQL): drop NULL-key build rows always;
        # drop NULL-key probe rows too for inner/semi (left/anti must keep
        # them — they surface as unmatched).  String-key NULLs are excluded
        # in-join via the NULL_KEY_SENTINEL; this covers nullable numerics.
        def key_valid(comp, exprs, cols, mask, aux):
            m = mask
            for e in exprs:
                vf = comp.validity_fn(comp.nullable_refs(e))
                if vf is not None:
                    m = m & vf(cols, aux)
            return m

        bmask_in = key_valid(rcomp, [re_ for _, re_ in self.on],
                             build.columns, build.mask, raux)
        pmask_in = probe.mask
        if self.join_type in ("inner", "semi"):
            pmask_in = key_valid(lcomp, [le for le, _ in self.on],
                                 probe.columns, probe.mask, laux)

        dicts = dict(probe.dicts)
        if self.join_type in ("inner", "left"):
            dicts.update(build.dicts)
        # shard rows over the mesh (pad to a multiple of the device count);
        # the concatenated copies are dead once their shards are in place
        dp, dpm, p_rows = _shard_rows(pcols, pmask_in, mesh, n_dev)
        db, dbm, b_rows = _shard_rows(bcols, bmask_in, mesh, n_dev)
        del probe, build, pcols, bcols, pmask_in, bmask_in

        out_cols, out_mask, rows_out = self._run(
            ctx, mesh, n_dev, p_live, b_live, p_rows // n_dev,
            b_rows // n_dev, sflags, dp, dpm, db, dbm)
        del dp, dpm, db, dbm

        out_cols, out_mask = _unshard((out_cols, out_mask))
        result = ColumnBatch(self._schema, out_cols, out_mask, dicts,
                             num_rows=rows_out)
        self.metrics().add("output_rows", rows_out)
        self.metrics().add("mesh_devices", n_dev)
        return [result]

    def _run(self, ctx, mesh, n_dev, p_live, b_live, p_shard, b_shard,
             sflags, *args):
        """The join program at the bounds of the class docstring, run again
        at what a flagged run reported it needed.  ``(out_cols, out_mask,
        rows_out)``."""
        from ..parallel.distributed import (distributed_broadcast_join,
                                            distributed_hash_join)

        lsch, rsch = self.left.schema, self.right.schema
        rfill = {f.name: f.dtype.null_sentinel for f in rsch}
        sentinel = int(ExprCompiler.NULL_KEY_SENTINEL)
        broadcast = b_live <= ctx.config.get(MESH_BROADCAST_ROWS)
        # the join programs depend on names, dtypes (through the shapes jit
        # keys on) and these statics alone, so jobs share them
        base = (n_dev, len(self.on), tuple(lsch.names()),
                tuple(rsch.names()), self.join_type,
                tuple(sorted(rfill.items())), tuple(sflags), sentinel)
        # a semi or anti join asks only whether a key is present (the key
        # sort of kernels.key_membership): no pair, so no pair bound
        membership = self.join_type in ("semi", "anti")
        out_cap = None
        if not membership:
            factor = ctx.config.get(JOIN_OUTPUT_FACTOR)
            out_cap = _pow2(factor * -(-(p_live if broadcast
                                         else min(p_live, b_live)) // n_dev))
        if broadcast:
            # the probe rows stay where they are: no send bucket
            bounds = (None, None, out_cap)
        else:
            bounds = (_join_send_capacity(p_live, p_shard, n_dev),
                      _join_send_capacity(b_live, b_shard, n_dev), out_cap)
        memo = ("mesh_join", broadcast, p_live, b_live, p_shard,
                b_shard) + base
        bounds = _EXCHANGE_NEED.get(memo, bounds)
        attrs = {"probe_rows": p_live, "build_rows": b_live}
        if membership:
            attrs["membership"] = 1
        for retries in range(3):
            p_cap, b_cap, out_cap = bounds
            if not broadcast:
                attrs.update(probe_capacity=p_cap, build_capacity=b_cap)
            if out_cap is not None:
                attrs["out_capacity"] = out_cap
            # the bounds a program has (a key holding None is not shared)
            held = tuple(b for b in bounds if b is not None)
            with self.xla_lock():
                if broadcast:
                    prog = _program(
                        self, ("mesh_join_broadcast",) + held + base,
                        lambda: distributed_broadcast_join(
                            mesh, len(self.on), list(lsch.names()),
                            list(rsch.names()), self.join_type, out_cap,
                            rfill, string_key_flags=sflags,
                            null_key_sentinel=sentinel))
                else:
                    prog = _program(
                        self, ("mesh_join_partitioned",) + held + base,
                        lambda: distributed_hash_join(
                            mesh, len(self.on), list(lsch.names()),
                            list(rsch.names()), self.join_type, p_cap,
                            b_cap, out_cap, rfill, string_key_flags=sflags,
                            null_key_sentinel=sentinel))
            out_cols, out_mask, stats = _dispatch(
                prog, *args, describe=lambda st: {"rows_out": int(st[4])},
                retries=retries, **attrs)
            overflow, p_need, b_need, pairs, rows_out = (int(v)
                                                         for v in stats)
            if not overflow:
                self.metrics().add(
                    "broadcast_joins" if broadcast else "partitioned_joins",
                    1)
                if membership:
                    self.metrics().add("membership_joins", 1)
                return out_cols, out_mask, rows_out
            del out_cols, out_mask
            passed = [side for side, need, cap in (
                ("probe", p_need, p_cap), ("build", b_need, b_cap),
                ("output", pairs, out_cap)) if cap is not None and need > cap]
            if not passed:
                break
            # a send bucket's need is at most its shard, so the capped
            # power of two still holds it
            bounds = (
                min(_pow2(p_need), p_shard) if "probe" in passed else p_cap,
                min(_pow2(b_need), b_shard) if "build" in passed else b_cap,
                _pow2(pairs) if "output" in passed else out_cap)
            _EXCHANGE_NEED[memo] = bounds
            attrs["side_overflowed"] = "+".join(passed)
            self.metrics().add("exchange_retries", 1)
            device_obs.record_mesh_join_retry()
        raise CapacityError(
            f"mesh {'broadcast' if broadcast else 'partitioned'} join passed "
            f"a bound that its input cannot pass (send buckets {p_cap} / "
            f"{b_cap} and {out_cap} pairs a device over {p_live} probe and "
            f"{b_live} build rows; the run needed {p_need} / {b_need} and "
            f"{pairs})")

    def _label(self):
        on = ", ".join(f"{l} = {r}" for l, r in self.on)
        return (f"MeshJoinExec({self.join_type}, fused all_to_all both sides): "
                f"on=[{on}]")


class MeshTaskJoinExec(MeshJoinExec):
    """HYBRID join composition: the per-partition join of a file-shuffled
    stage, fused over the executing host's LOCAL device mesh.

    Where MeshJoinExec fuses the whole exchange in-process (one task, one
    host), this keeps the reference's partitioned stage structure — both
    sides hash-repartitioned via the ordinary shuffle, one join task per
    partition spread over executors — and uses the mesh only WITHIN each
    task: the partition's probe rows shard across the host's chips and the
    per-partition build side is all_gathered (or locally all_to_all'd when
    large).  On a multi-host cluster this is the join half of "ICI within
    a host, file shuffle across hosts" (BASELINE.json.north_star), joining
    MeshPartialAggregateExec on the aggregate side."""

    def output_partition_count(self):
        return self.left.output_partition_count()

    def output_partitioning(self):
        return self.left.output_partitioning()

    def _execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        probe = concat_batches(
            self.left.schema, self.left.execute(partition, ctx))
        build = concat_batches(
            self.right.schema, self.right.execute(partition, ctx))
        return self._join_batches(probe, build, ctx)

    def _label(self):
        on = ", ".join(f"{l} = {r}" for l, r in self.on)
        return (f"MeshTaskJoinExec({self.join_type}, per-task mesh, "
                f"file exchange): on=[{on}]")
