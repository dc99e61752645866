"""Compile the main path's device programs for a described v5e chip.

No chip is attached here: the TPU's compiler is installed and compiles for
a topology that is described (``v5e:2x2``), so what it refuses is found
without chip time.  Nothing runs, so these say nothing about results or
speed; each prints its compile seconds, because a sort that takes a minute
to compile here takes it on the chip.

Shapes are TPC-H SF1's under chip_smoke.py's configuration (batch size 2^20,
8 shuffle partitions), read off a CPU run of the same queries.

The topology is described inside a module-scoped fixture and nowhere at
import: only one process may load the TPU library at a time, the suite
runs under several workers that each import this file, and only the worker
that is given the file may load it.
"""
import os
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from arrow_ballista_tpu.ops import kernels as K

BATCH = 1 << 20  # ballista.batch.size of the SF1 runs


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def compile_for_chip(name, fn, *args, **jit_kw):
    t0 = time.perf_counter()
    compiled = jax.jit(fn, **jit_kw).lower(*args).compile()
    print(f"\n[tpu-compile] {name}: {time.perf_counter() - t0:.1f}s")
    return compiled


def contraction_operands(text):
    """(result type, operand types) of every convolution (a dot, to the
    TPU's compiler) in a compiled program's text."""
    types = dict(re.findall(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[", text, re.M))
    return [(res, [types.get(a.strip().lstrip("%"), "?")
                   for a in args.split(",")])
            for res, args in re.findall(
                r"= (\w+)\[[^\]]*\]\S* convolution\(([^)]*)\)", text)]


def has_scatter(text):
    """A scatter among the program's ops (names of functions and tests
    ride the text's stack frames, so not a substring test)."""
    return re.search(r"\bscatter\(", text) is not None


def assert_exact_mxu_contraction(text):
    """The kept form: operands in the matrix unit's own bfloat16 (the
    one-hot may still be the compare's ``pred``, converted inside the
    fusion), accumulated in float32; no emulated int32 dot."""
    dots = contraction_operands(text)
    assert dots, "no contraction in the program"
    for res, operands in dots:
        assert res == "f32", dots
        assert set(operands) <= {"bf16", "pred"}, dots


@pytest.mark.parametrize("segments,values", [
    pytest.param(64, 8, id="dense-onehot-matmul"),
    pytest.param(290, 9, id="q1-dense-290-slots-nine-values"),
    pytest.param(4 * K._MATMUL_SEG_LIMIT, 8, id="chunk-offset"),
])
def test_grouped_sums_i64_tpu_branch(one_chip, tpu_branches, segments,
                                     values):
    """q1's aggregate: int64 value vectors over one batch.  Up to
    ``_MATMUL_SEG_LIMIT`` slots it is one exact contraction on the matrix
    unit and holds no scatter; past it the chunk-offset segment_sums."""
    vals = [sds((BATCH,), jnp.int64, one_chip) for _ in range(values)]
    seg = sds((BATCH,), jnp.int32, one_chip)
    text = compile_for_chip(
        f"grouped_sums_i64 S={segments}",
        lambda vals, seg: K.grouped_sums_and_rows_i64(vals, seg, segments),
        vals, seg).as_text()
    if K.i64_sum_path(segments, BATCH) == "contraction":
        assert not has_scatter(text)
        assert_exact_mxu_contraction(text)
    else:
        assert has_scatter(text) and " convolution(" not in text


def test_dense_aggregate_q1_shape_is_one_contraction(one_chip, tpu_branches):
    """q1's partial aggregate as ``sf10_scanagg`` runs it: one scan task's
    2^23 slots, two dictionary keys rounded to 17 x 17 = 289 dense slots,
    five int64 sums and four counts.  Sums, counts and the rows per slot are
    one contraction in bfloat16; nothing in the program is a scatter or an
    int32 dot."""
    n = 1 << 23
    key_ranges = ((-1, 15), (-1, 15))
    hows = [K.AGG_SUM] * 4 + [K.AGG_COUNT] + [K.AGG_SUM] + [K.AGG_COUNT] * 3

    def q1_partial(keys, vals, mask):
        return K.grouped_aggregate(keys, list(zip(vals, hows)), mask,
                                   K.dense_domain(key_ranges),
                                   key_ranges=key_ranges)

    text = compile_for_chip(
        "dense grouped_aggregate, q1 at SF10, one task", q1_partial,
        [sds((n,), jnp.int32, one_chip) for _ in range(2)],
        [sds((n,), jnp.int64, one_chip) for _ in hows],
        sds((n,), jnp.bool_, one_chip)).as_text()
    assert not has_scatter(text)
    assert_exact_mxu_contraction(text)


@pytest.mark.parametrize("is_min", [True, False], ids=["min", "max"])
def test_grouped_minmax_i64_tpu_branch(one_chip, tpu_branches, is_min):
    v = sds((BATCH,), jnp.int64, one_chip)
    ok = sds((BATCH,), jnp.bool_, one_chip)
    seg = sds((BATCH,), jnp.int32, one_chip)
    compile_for_chip(
        f"grouped_minmax_i64 is_min={is_min}",
        lambda v, ok, seg: K.grouped_minmax_i64(v, ok, seg, 1024, is_min),
        v, ok, seg)


@pytest.mark.parametrize("entry", ["sort_path", "presorted"])
def test_run_scan_aggregate_sf1_batch(one_chip, tpu_branches, entry):
    """q18's partial aggregate over one lineitem batch at SF1 (2^20 slots,
    ``l_orderkey`` -> ``sum(l_quantity)``, as many group slots), by the sort
    path and by the presorted entry (``agg_grouped__partial_k1_presorted``,
    the first of ``sf1_join``'s device seconds): sorts and scans, nothing
    scattered and nothing gathered."""
    fn = K.grouped_aggregate_presorted if entry == "presorted" \
        else K.grouped_aggregate
    text = compile_for_chip(
        f"grouped_aggregate, {entry}, 2^20 slots",
        lambda k, v, m: fn([k], [(v, K.AGG_SUM)], m, BATCH),
        sds((BATCH,), jnp.int64, one_chip), sds((BATCH,), jnp.int64, one_chip),
        sds((BATCH,), jnp.bool_, one_chip)).as_text()
    assert not has_scatter(text)
    assert re.search(r"\bgather\(", text) is None
    assert len(re.findall(r"\bsort\(", text)) >= (
        1 if entry == "presorted" else 2)


def test_pack_for_host_mixed_columns(one_chip):
    """int64, f64 and 32-bit columns in one packed transfer: the s32<->s64
    bitcasts under x64 emulation and the separate f64 leaf."""
    rows = 1 << 17
    cols = {"k": sds((rows,), jnp.int64, one_chip),
            "s": sds((rows,), jnp.int64, one_chip),
            "avg": sds((rows,), jnp.float64, one_chip),
            "d": sds((rows,), jnp.int32, one_chip),
            "f": sds((rows,), jnp.float32, one_chip),
            "b": sds((rows,), jnp.bool_, one_chip)}
    mask = sds((rows,), jnp.bool_, one_chip)
    raw = K.pack_for_host.__wrapped__
    compile_for_chip(
        "pack_for_host i64+f64+32-bit",
        lambda cols, mask: raw(cols, mask, rows // 2, ("k", "s"), ("avg",),
                               ("d", "f", "b")),
        cols, mask)


def test_join_build_sort_and_probe_q3_shapes(one_chip):
    """q3's orders x lineitem join: one partition of orders as the build
    side (2^18 slots), one lineitem batch as the probe."""
    build_cap, out_cap = 1 << 18, BATCH

    def join(bk, bmask, pk, pmask):
        bh_sorted, border, _ = K.build_side_sort([bk], bmask)
        lo, counts, _ = K.probe_ranges(K.hash64([pk]), pmask, bh_sorted)
        pi, bp, valid, total = K.expand_pairs(lo, counts, build_cap, out_cap)
        bidx = border[bp]
        return valid & bmask[bidx] & (pk[pi] == bk[bidx]), total

    compile_for_chip(
        "build_side_sort + probe_ranges + expand_pairs", join,
        sds((build_cap,), jnp.int64, one_chip),
        sds((build_cap,), jnp.bool_, one_chip),
        sds((BATCH,), jnp.int64, one_chip),
        sds((BATCH,), jnp.bool_, one_chip))


def test_sort_order_and_topk_q3_shapes(one_chip):
    """q3's ORDER BY revenue desc, o_orderdate LIMIT 10 over one final
    partition (2^14 slots)."""
    n = 1 << 14
    rev = sds((n,), jnp.int64, one_chip)
    date = sds((n,), jnp.int32, one_chip)
    mask = sds((n,), jnp.bool_, one_chip)
    keys = lambda r, d: [(r, False), (d, True)]  # noqa: E731
    # one program: topk_order is sort_order's head, and each device sort
    # costs about twenty seconds of compiling
    compile_for_chip(
        "sort_order + topk_order",
        lambda r, d, m: (K.sort_order(keys(r, d), m),
                         K.topk_order(keys(r, d), m, 10)),
        rev, date, mask)


def test_fused_stage_q6_with_donation(one_chip, tpu_branches):
    """q6's filter -> projection -> partial aggregate as ONE fused program,
    lowered with the donation compile/fused.py asks for off the CPU."""
    import pyarrow as pa

    from arrow_ballista_tpu.compile.fused import FusedStageExec
    from arrow_ballista_tpu.models import expr as E
    from arrow_ballista_tpu.models.schema import DATE32, Field, Schema, decimal
    from arrow_ballista_tpu.ops import operators as O
    from arrow_ballista_tpu.ops.physical import MemoryScanExec, TaskContext
    from arrow_ballista_tpu.utils.config import BallistaConfig

    schema = Schema([Field("l_quantity", decimal(2)),
                     Field("l_extendedprice", decimal(2)),
                     Field("l_discount", decimal(2)),
                     Field("l_shipdate", DATE32)])
    table = pa.table({
        "l_quantity": pa.array([1], pa.int64()),
        "l_extendedprice": pa.array([1], pa.int64()),
        "l_discount": pa.array([1], pa.int64()),
        "l_shipdate": pa.array([9000], pa.int32())})
    scan = MemoryScanExec(schema, table, 1, [])
    col, lit = E.Column, E.Lit
    pred = None
    for p in (E.BinOp(">=", col("l_shipdate"), lit("1994-01-01", "date")),
              E.BinOp("<", col("l_shipdate"), lit("1995-01-01", "date")),
              E.BinOp(">=", col("l_discount"), lit(0.05)),
              E.BinOp("<=", col("l_discount"), lit(0.07)),
              E.BinOp("<", col("l_quantity"), lit(24))):
        pred = p if pred is None else E.BinOp("and", pred, p)
    filt = O.FilterExec(scan, pred)
    proj = O.ProjectionExec(
        filt, [(E.BinOp("*", col("l_extendedprice"), col("l_discount")),
                "x")])
    agg = O.HashAggregateExec(proj, [], [O.AggSpec("sum", col("x"), "rev")],
                              "partial")
    fused = FusedStageExec([agg, proj, filt], donate=True)
    ctx = TaskContext(config=BallistaConfig(), job_id="tpu-compile")
    thread, jfn, (comp_a, _group_c, _agg_c, _tracked) = fused._build(ctx)
    auxs, dicts = fused._auxs_and_dicts(thread, {})
    auxs = tuple(auxs) + (comp_a.aux_arrays(dicts),)
    assert not jax.tree_util.tree_leaves(auxs), "q6 needs no lookup tables"

    cols = {f.name: sds((BATCH,), f.dtype.np_dtype, one_chip) for f in schema}
    mask = sds((BATCH,), jnp.bool_, one_chip)
    # ObservedJit offers no .lower: compile the function it wraps, with the
    # arguments static and donated as compile/fused.py's _build makes them
    c = compile_for_chip(
        "fused q6 filter+projection+partial-agg, donate (0, 1)",
        jfn.__wrapped__, cols, mask, auxs, BATCH, (),
        static_argnums=(3, 4), donate_argnums=(0, 1))
    assert "fusion" in c.as_text()


def _compile_exchange(topo, per_device: int):
    """q18's inner aggregate (``l_orderkey`` -> ``sum(l_quantity)``) as the
    exchange program over the four described devices, shards of
    ``per_device`` rows, at the bounds ``MeshAggregateExec`` derives from
    them (ops/mesh_exec.py ``_exchange_bounds``): the program's own
    function (parallel/distributed.py, what its ``observed_jit`` wraps),
    arguments as shapes with shardings."""
    from arrow_ballista_tpu.ops.mesh_exec import _exchange_bounds
    from arrow_ballista_tpu.parallel import distributed

    mesh = Mesh(np.asarray(topo.devices), ("part",))
    rows = NamedSharding(mesh, P("part"))
    n = 4 * per_device
    partial, shuffle, final = _exchange_bounds(per_device, 4)
    assert (partial, final) == (per_device, 4 * shuffle)
    run = distributed.distributed_grouped_aggregate(
        mesh, ["l_orderkey"], [("l_quantity", "sum")],
        partial_capacity=partial, final_capacity=final, axis="part",
        shuffle_capacity=shuffle)
    assert run.name == "mesh_agg_exchange__k1"
    cols = {"l_orderkey": sds((n,), jnp.int64, rows),
            "l_quantity": sds((n,), jnp.int64, rows)}
    compiled = compile_for_chip(
        f"mesh exchange aggregate, {per_device} rows a device",
        run.jit.__wrapped__, cols, sds((n,), jnp.bool_, rows))
    assert "all-to-all" in compiled.as_text(), \
        "no all-to-all in the mesh program"
    mem = compiled.memory_analysis()
    print(f"[tpu-compile] exchange program bytes per device: "
          f"args {mem.argument_size_in_bytes}, temp {mem.temp_size_in_bytes}, "
          f"out {mem.output_size_in_bytes}")
    return mem


def test_mesh_exchange_all_to_all_on_four_devices(topo, tpu_branches):
    """The exchange across chips at a quarter-million rows a device: the
    grouped aggregate's key repartition compiles for the chips and holds an
    all-to-all."""
    _compile_exchange(topo, 1 << 18)


@pytest.mark.slow
def test_mesh_exchange_at_sf10_q18_shards(topo, tpu_branches):
    """The same at the benchmark cell's shapes (``sf10_mesh4_q18``:
    60 030 976 scanned slots, 15 007 744 a device; send buckets of
    7 503 872 states, 30 015 488 final slots a device): the chips' compiler
    takes it (minutes here, so not in Tier-1), and what it asks of a chip
    leaves room beside chip 0's scan cache in 16 GB."""
    mem = _compile_exchange(topo, 15_007_744)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes < 4 << 30


def test_mesh_dense_reduce_q1_shape_on_four_devices(topo, tpu_branches):
    """q1's mesh aggregate at SF10's shard (2^24 slots a device, the
    benchmark's ``sf10_mesh4_scanagg``): two dictionary keys, int64 sums and
    counts into 12 dense slots, merged by all-reduce and no all-to-all; what
    it asks of a chip beside the 4.9 GB scan cache must leave room in 16 GB."""
    from arrow_ballista_tpu.parallel import distributed

    mesh = Mesh(np.asarray(topo.devices), ("part",))
    rows = NamedSharding(mesh, P("part"))
    n = 4 * (1 << 24)
    key_ranges = ((-1, 2), (-1, 1))
    aggs = [("qty", "sum"), ("price", "sum"), ("disc_price", "sum"),
            ("charge", "sum"), ("qty", "count"), ("disc", "sum"),
            ("ones", "count")]

    def derive(cols, mask):
        out = dict(cols)
        out["disc_price"] = cols["price"] * (100 - cols["disc"])
        out["charge"] = out["disc_price"] * (100 + cols["tax"])
        out["ones"] = jnp.ones(mask.shape, jnp.int64)
        return out, mask

    run = distributed.distributed_dense_aggregate(
        mesh, derive, ["flag", "status"], aggs, key_ranges,
        K.dense_domain(key_ranges))
    assert run.name == "mesh_agg_dense__k2"
    cols = {name: sds((n,), jnp.int64, rows)
            for name in ("qty", "price", "disc", "tax")}
    cols.update({name: sds((n,), jnp.int32, rows)
                 for name in ("flag", "status")})
    compiled = compile_for_chip("mesh dense aggregate, q1 at SF10",
                                run.jit.__wrapped__, cols,
                                sds((n,), jnp.bool_, rows))
    text = compiled.as_text()
    assert "all-reduce" in text and "all-to-all" not in text
    # every aggregate is an int64 sum or a count: one exact contraction,
    # the rows per slot among its rows
    assert not has_scatter(text)
    assert_exact_mxu_contraction(text)
    mem = compiled.memory_analysis()
    print(f"[tpu-compile] q1 mesh program bytes per device: "
          f"args {mem.argument_size_in_bytes}, temp {mem.temp_size_in_bytes}")
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8 << 30
