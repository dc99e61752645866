"""Start the system on the chip and check what comes out.

    python chip_smoke.py            # one TPU chip: phases data, standalone, cluster
    python chip_smoke.py --mesh     # four chips: phases data, mesh (and nothing else)

The parent in this file never imports jax or the package: a chip belongs to
one process at a time, so each phase is a child process, run one after the
other.  A child that exits non-zero, times out or reports a mismatch makes
the parent exit non-zero; nothing is caught and carried past.

- ``data``: TPC-H at ``--scale`` (default 1) from ``--seed`` (default 0),
  all eight tables, written as parquet under ``.bench_data/``.  CPU only.
- ``standalone`` (holds the chip): device facts, the budgets the program
  resolved for itself, the platform's transfer constants, a sweep of random
  and edge operands through the int64 grouped-sum kernels against numpy, then
  ``BallistaContext.standalone`` over the parquet: q1, q6, q3, q18, each
  cold and then warm, every answer compared with a pandas oracle.
- ``cluster``: scheduler daemon, executor daemon (holds the chip) and a
  ``BallistaContext.remote`` client, three processes on this host; q6 and q3
  compared with the same oracle.  Scheduler and client stay on the CPU
  platform.
- ``mesh`` (``--mesh`` only): q3 and q1 with the exchange over the device
  mesh and again over files, both compared with the oracle, and the devices
  the mesh programs' inputs live on.

Without a TPU every phase that needs one fails.  ``--allow-cpu`` exists for
rehearsing the control flow on a CPU: the last line then names the CPU, so a
rehearsal can never be read as a chip run.  On success the last line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(REPO, ".smoke_logs")
T0 = time.time()
# the chip tool cuts the run at 1200 s; leave room to stop the daemons
TOTAL_BUDGET_S = 1150.0
STANDALONE_QUERIES = (1, 6, 3, 18)
CLUSTER_QUERIES = (6, 3)
MESH_QUERIES = (3, 1)
# the configuration benchmarks/sf1_correctness.py runs all 22 queries under
BASE_CONFIG = {
    "ballista.shuffle.partitions": "8",
    "ballista.batch.size": str(1 << 20),
    "ballista.job.timeout.seconds": "1800",
}
# the configuration of tests/test_tpch.py's mesh_ctx, at SF1's batch size
MESH_CONFIG = {
    "ballista.shuffle.partitions": "4",
    "ballista.batch.size": str(1 << 20),
    "ballista.job.timeout.seconds": "1800",
    "ballista.shuffle.mesh": "true",
    "ballista.shuffle.mesh.min_rows": "0",
}


def say(msg: str) -> None:
    print(f"[smoke +{time.time() - T0:7.1f}s] {msg}", flush=True)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def data_dir(args) -> str:
    return os.path.join(REPO, ".bench_data",
                        f"tpch-sf{args.scale:g}-seed{args.seed}")


# --------------------------------------------------------------------------
# the oracle: the same four queries in pandas over the same parquet.
# Decimals stay unscaled int64 (the files store them so), so sums are exact.
# Each function returns (rows, order_keys, limit): rows in query order as
# tuples of python values, order_keys as [(column index, ascending)].
# --------------------------------------------------------------------------

EPOCH = datetime.date(1970, 1, 1)


def _days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - EPOCH).days


def _date(days) -> datetime.date:
    return EPOCH + datetime.timedelta(days=int(days))


def _dec(unscaled, scale: int):
    from decimal import Decimal

    return Decimal(int(unscaled)).scaleb(-scale)


def _load(ddir: str, table: str, columns):
    """Columns of one table as a DataFrame: dates as int days, decimals as
    the unscaled int64 the files store, strings as objects."""
    import pandas as pd
    import pyarrow as pa
    import pyarrow.parquet as pq

    t = pq.read_table(os.path.join(ddir, f"{table}.parquet"),
                      columns=list(columns))
    cols = {}
    for name in t.column_names:
        col = t.column(name)
        if pa.types.is_date32(col.type):
            col = col.cast(pa.int32())
        elif pa.types.is_dictionary(col.type):
            col = col.cast(pa.string())
        cols[name] = col.to_pandas() if pa.types.is_string(col.type) \
            else col.to_numpy()
    return pd.DataFrame(cols)


def oracle_q1(ddir: str):
    li = _load(ddir, "lineitem", [
        "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
        "l_discount", "l_tax", "l_shipdate"])
    li = li[li.l_shipdate <= _days(1998, 12, 1) - 90]
    disc_price = li.l_extendedprice * (100 - li.l_discount)       # scale 4
    li = li.assign(disc_price=disc_price,
                   charge=disc_price * (100 + li.l_tax))          # scale 6
    g = li.groupby(["l_returnflag", "l_linestatus"], sort=True).agg(
        sum_qty=("l_quantity", "sum"), sum_base=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"), sum_charge=("charge", "sum"),
        sum_disc=("l_discount", "sum"), n=("l_quantity", "size")).reset_index()
    rows = [(r.l_returnflag, r.l_linestatus, _dec(r.sum_qty, 2),
             _dec(r.sum_base, 2), _dec(r.sum_disc_price, 4),
             _dec(r.sum_charge, 6), r.sum_qty / r.n / 100.0,
             r.sum_base / r.n / 100.0, r.sum_disc / r.n / 100.0, int(r.n))
            for r in g.itertuples()]
    return rows, [(0, True), (1, True)], None


def oracle_q6(ddir: str):
    li = _load(ddir, "lineitem", ["l_quantity", "l_extendedprice",
                                  "l_discount", "l_shipdate"])
    li = li[(li.l_shipdate >= _days(1994, 1, 1))
            & (li.l_shipdate < _days(1995, 1, 1))
            & (li.l_discount >= 5) & (li.l_discount <= 7)
            & (li.l_quantity < 2400)]
    return [(_dec((li.l_extendedprice * li.l_discount).sum(), 4),)], [], None


def oracle_q3(ddir: str):
    cutoff = _days(1995, 3, 15)
    cust = _load(ddir, "customer", ["c_custkey", "c_mktsegment"])
    cust = cust[cust.c_mktsegment == "BUILDING"]
    orders = _load(ddir, "orders", ["o_orderkey", "o_custkey", "o_orderdate",
                                    "o_shippriority"])
    orders = orders[(orders.o_orderdate < cutoff)
                    & orders.o_custkey.isin(cust.c_custkey)]
    li = _load(ddir, "lineitem", ["l_orderkey", "l_extendedprice",
                                  "l_discount", "l_shipdate"])
    li = li[li.l_shipdate > cutoff]
    li = li.assign(revenue=li.l_extendedprice * (100 - li.l_discount))
    j = li.merge(orders, left_on="l_orderkey", right_on="o_orderkey")
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  sort=False).revenue.sum().reset_index()
    g = g.sort_values(["revenue", "o_orderdate"], ascending=[False, True],
                      kind="mergesort")
    rows = [(int(r.l_orderkey), _dec(r.revenue, 4), _date(r.o_orderdate),
             int(r.o_shippriority)) for r in g.itertuples()]
    return rows, [(1, False), (2, True)], 10


def oracle_q18(ddir: str):
    li = _load(ddir, "lineitem", ["l_orderkey", "l_quantity"])
    qty = li.groupby("l_orderkey").l_quantity.sum()
    qty = qty[qty > 300 * 100].rename("sum_qty").reset_index()
    orders = _load(ddir, "orders", ["o_orderkey", "o_custkey", "o_orderdate",
                                    "o_totalprice"])
    cust = _load(ddir, "customer", ["c_custkey", "c_name"])
    j = qty.merge(orders, left_on="l_orderkey", right_on="o_orderkey") \
           .merge(cust, left_on="o_custkey", right_on="c_custkey")
    j = j.sort_values(["o_totalprice", "o_orderdate"],
                      ascending=[False, True], kind="mergesort")
    rows = [(r.c_name, int(r.c_custkey), int(r.o_orderkey),
             _date(r.o_orderdate), _dec(r.o_totalprice, 2),
             _dec(r.sum_qty, 2)) for r in j.itertuples()]
    return rows, [(4, False), (3, True)], 100


ORACLES = {1: oracle_q1, 6: oracle_q6, 3: oracle_q3, 18: oracle_q18}


def table_rows(table) -> list:
    """An engine answer (pyarrow Table) as tuples of python values."""
    import pyarrow as pa

    cols = []
    for col in table.columns:
        if pa.types.is_dictionary(col.type):
            col = col.cast(pa.string())
        cols.append(col.to_pylist())
    return list(zip(*cols)) if cols else []


class Mismatch(Exception):
    """An answer differs from the oracle's."""


def compare(q: int, got: list, oracle) -> None:
    """Raise Mismatch unless ``got`` answers the query as the oracle
    does.  Fixed-point sums, counts, keys and dates are compared exactly;
    floats (the averages) to rtol/atol 1e-6 and ORDER BY as a monotone
    check over the order keys, the rules of tests/test_tpch.py.  Under a
    LIMIT, rows that tie with the last one may differ between engines, so
    every row before the tie must be there and the rest must come from it."""
    import math
    from collections import Counter

    want, order_keys, limit = oracle

    def same(a, b) -> bool:
        if isinstance(a, float) or isinstance(b, float):
            return math.isclose(float(a), float(b), rel_tol=1e-6,
                                abs_tol=1e-6)
        return a == b

    def okey(row):
        return tuple(row[i] for i, _ in order_keys)

    def require(ok: bool, msg: str) -> None:
        if not ok:
            raise Mismatch(f"q{q}: {msg}")

    for a, b in zip(got, got[1:]):
        for i, asc in order_keys:
            if a[i] != b[i]:
                require((a[i] < b[i]) == asc,
                        f"ORDER BY violated: {a} then {b}")
                break
    if limit is not None:
        k = min(limit, len(want))
        require(len(got) == k, f"{len(got)} rows, want {k}")
        if k == 0:
            return
        last = okey(want[k - 1])
        before = Counter(r for r in want[:k] if okey(r) != last)
        ties = Counter(r for r in want if okey(r) == last)
        rest = Counter(got) - before
        require(not before - Counter(got),
                f"rows missing: {list(before - Counter(got))[:3]}")
        require(not rest - ties,
                f"rows not in the oracle's answer: {list(rest - ties)[:3]}")
        return
    require(len(got) == len(want), f"{len(got)} rows, want {len(want)}")
    exact = [i for i in range(len(want[0]))
             if not isinstance(want[0][i], float)] if want else []

    def ekey(row):
        return tuple(str(row[i]) for i in exact)

    for g, w in zip(sorted(got, key=ekey), sorted(want, key=ekey)):
        require(len(g) == len(w) and all(same(a, b) for a, b in zip(g, w)),
                f"row differs:\n got  {g}\n want {w}")


# --------------------------------------------------------------------------
# children.  Everything below this line up to the parent runs in a child
# process; jax and the package are imported there and nowhere else.
# --------------------------------------------------------------------------


def _device_or_die(args, need: int = 1) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": str(d.device_kind),
            "count": len(devs)}
    if d.platform != "tpu" and not args.allow_cpu:
        raise SystemExit(f"no TPU: jax found {info}; this run needs the "
                         "chip (--allow-cpu rehearses on a CPU)")
    if len(devs) < need:
        raise SystemExit(f"need {need} devices, jax found {info}")
    return info


def _platform_constants() -> dict:
    """Dispatch round trip, H2D/D2H bandwidth and the FIXED latency of a
    scalar D2H: the constants the sync-avoidance design (remote_device())
    rests on."""
    import jax
    import numpy as np

    def med(f, n=5):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            f()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    small = jax.device_put(np.zeros(128, np.int32))
    tiny = jax.jit(lambda x: x + 1)
    jax.block_until_ready(tiny(small))
    rtt = med(lambda: jax.block_until_ready(tiny(small)), 20)
    big = np.zeros(8 << 20, np.int64)  # 64 MiB
    h2d = med(lambda: jax.block_until_ready(jax.device_put(big)), 3)
    # a fresh device array per read: jax caches an array's first host copy
    bigs = [tiny(jax.device_put(big)) for _ in range(3)]
    jax.block_until_ready(bigs)
    it = iter(bigs)
    d2h = med(lambda: np.asarray(next(it)), 3)
    scalars = [tiny(small)[0] for _ in range(20)]
    jax.block_until_ready(scalars)
    it2 = iter(scalars)
    d2h_scalar = med(lambda: int(next(it2)), 20)
    return {"dispatch_rtt_ms": rtt * 1e3,
            "h2d_gbytes_per_s": big.nbytes / h2d / 1e9,
            "d2h_gbytes_per_s": big.nbytes / d2h / 1e9,
            "d2h_scalar_fixed_ms": d2h_scalar * 1e3}


def _int64_sum_sweep(seed: int) -> dict:
    """Random and edge operands through ``kernels.grouped_sums_i64`` on each
    of its paths and through ``dense_group_states``, on this device, against
    numpy's wrapping int64: the chip's emulated 64-bit arithmetic is what
    the kernel avoids, and this is where a compiler that gets it wrong
    shows (PR 28 met one).  Raises on the first difference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from arrow_ballista_tpu.ops import kernels as K

    rng = np.random.default_rng(seed)
    i64 = np.iinfo(np.int64)

    def operands(n):
        full = rng.integers(i64.min, i64.max, n, dtype=np.int64,
                            endpoint=True)
        full[:4] = [i64.min, i64.max, i64.min, -1][:n]
        price = rng.integers(90_000, 10_500_000, n, dtype=np.int64)
        return [full, -rng.integers(1, 2**62, n, dtype=np.int64),
                price * rng.integers(90, 101, n) * rng.integers(100, 109, n),
                np.ones(n, np.int64), np.zeros(n, np.int64)]

    def plain(vals, seg, S):
        out = []
        with np.errstate(over="ignore"):
            for v in vals:
                acc = np.zeros(S, np.int64)
                np.add.at(acc, seg, v)
                out.append(acc)
        return out

    paths = {}
    shapes = [(1, 1000), (13, (1 << 15) + 7), (290, 1 << 20),
              (290, (1 << 22) + 12345), (1024, 1 << 18),
              (1025, 1 << 18), (50_000, 1 << 20)]
    for S, n in shapes:
        live = rng.random(n) < 0.9
        seg = np.where(live, rng.integers(0, max(S - 1, 1), n),
                       S - 1).astype(np.int32)
        vals = [np.where(live, v, 0) for v in operands(n)]
        path = K.i64_sum_path(S, n)
        paths[path] = paths.get(path, 0) + 1
        sums, rows = jax.jit(
            lambda vals, seg, S=S: K.grouped_sums_and_rows_i64(vals, seg, S)
        )([jnp.asarray(v) for v in vals], jnp.asarray(seg))
        for i, (got, want) in enumerate(zip(sums, plain(vals, seg, S))):
            if not np.array_equal(np.asarray(got), want):
                bad = np.flatnonzero(np.asarray(got) != want)[:3]
                raise SystemExit(
                    f"grouped_sums_i64 ({path}) S={S} n={n} value {i}: slots "
                    f"{bad.tolist()} read {np.asarray(got)[bad].tolist()}, "
                    f"numpy {want[bad].tolist()}")
        if not np.array_equal(np.asarray(rows), np.bincount(seg, minlength=S)):
            raise SystemExit(f"rows per slot ({path}) S={S} n={n} differ")

    # q1's shape of dense_group_states: 17 x 17 slots, sums and counts
    n = (1 << 21) + 77
    key_ranges = ((-1, 15), (-1, 15))
    domain = K.dense_domain(key_ranges)
    k0 = rng.integers(-1, 3, n).astype(np.int32)
    k1 = rng.integers(-1, 2, n).astype(np.int32)
    mask = rng.random(n) < 0.97
    vals = operands(n)[:3]
    hows = [K.AGG_SUM, K.AGG_COUNT, K.AGG_SUM, K.AGG_SUM, K.AGG_COUNT]
    cols = [vals[0], vals[0], vals[1], vals[2], vals[2]]
    dense, exists, bad = jax.jit(
        lambda k0, k1, mask, *cols: K.dense_group_states(
            [k0, k1], list(zip(cols, hows)), mask, key_ranges, domain)
    )(k0, k1, mask, *cols)
    slot = np.where(mask, (k0 + 1) * 17 + (k1 + 1), domain)
    count = np.bincount(slot, minlength=domain + 1)[:domain]
    if bool(bad) or not np.array_equal(np.asarray(exists), count):
        raise SystemExit("dense_group_states: exists_cnt differs from numpy")
    for got, col, how in zip(dense, cols, hows):
        want = count if how == K.AGG_COUNT else \
            plain([np.where(mask, col, 0)], slot, domain + 1)[0][:domain]
        if not np.array_equal(np.asarray(got), want):
            raise SystemExit(f"dense_group_states: a {how} differs from numpy")
    return {"shapes": len(shapes) + 1, "paths": paths}


def _run_queries(ctx, queries, oracles, runs, label: str) -> None:
    """Run each query ``runs`` times through ``ctx``, print seconds and the
    device accounting of each run, compare every answer with the oracle."""
    from arrow_ballista_tpu.obs import device as device_obs
    from benchmarks.queries import QUERIES

    keys = ("jit_compiles", "jit_retraces", "jit_cache_hits",
            "jit_compile_time", "program_cache_misses", "h2d_bytes",
            "d2h_bytes")
    for q in queries:
        for run in runs:
            s0 = device_obs.STATS.snapshot()
            t0 = time.perf_counter()
            table = ctx.sql(QUERIES[q]).to_arrow()
            secs = time.perf_counter() - t0
            s1 = device_obs.STATS.snapshot()
            rec = {"phase": label, "query": f"q{q}", "run": run,
                   "seconds": secs, "rows": table.num_rows}
            rec.update({k: s1[k] - s0[k] for k in keys})
            rec["device_live_peak_bytes"] = s1["device_live_peak_bytes"]
            compare(q, table_rows(table), oracles[q])
            rec["equal_to_oracle"] = True
            emit(rec)
            say(f"{label} q{q} {run}: {secs:.2f}s, "
                f"{rec['jit_compiles'] + rec['jit_retraces']:.0f} programs "
                f"compiled in {rec['jit_compile_time']:.1f}s, equal to oracle")
            if label == "cluster":
                continue  # the accounting above is the executor's, not ours
            if not s1["device_live_peak_bytes"]:
                raise SystemExit(f"q{q} {run}: device live peak is 0 — "
                                 "nothing ran on the device")
            if run == "warm" and rec["program_cache_misses"]:
                # what the cold run built and shared must be found again.
                # Compiles WITHOUT a miss are allowed and printed above: the
                # first run teaches size hints and sortedness, so the warm
                # run may retrace pack_for_host at a smaller target or take
                # a path (presorted group-by) whose per-operator programs
                # are never shared.
                raise SystemExit(
                    f"q{q} warm run missed the program cache "
                    f"{rec['program_cache_misses']:.0f} times: it built "
                    "programs it should have found in memory")


def _oracles(ddir: str, queries) -> dict:
    out = {}
    for q in queries:
        t0 = time.perf_counter()
        out[q] = ORACLES[q](ddir)
        say(f"oracle q{q}: {time.perf_counter() - t0:.1f}s, "
            f"{len(out[q][0])} rows before any limit")
    return out


def child_standalone(args) -> None:
    info = _device_or_die(args)
    import jax

    from arrow_ballista_tpu.client.context import BallistaContext
    from arrow_ballista_tpu.utils import table_cache
    from arrow_ballista_tpu.utils.config import (
        MEM_DEVICE_BUDGET,
        MEM_TASK_BUDGET,
        SCAN_CACHE_BYTES,
        BallistaConfig,
        resolve_pool_budget,
        resolve_task_budget,
    )
    from benchmarks.queries import QUERIES
    from benchmarks.tpch import register_tables

    config = BallistaConfig(dict(BASE_CONFIG))
    stats = jax.devices()[0].memory_stats() or {}
    emit({"phase": "standalone", "device": info,
          "bytes_limit": stats.get("bytes_limit"),
          "compilation_cache_dir": jax.config.jax_compilation_cache_dir,
          "budgets": {
              MEM_TASK_BUDGET: resolve_task_budget(config),
              MEM_DEVICE_BUDGET: resolve_pool_budget(config,
                                                     MEM_DEVICE_BUDGET),
              SCAN_CACHE_BYTES: table_cache.resolve_budget(
                  config.get(SCAN_CACHE_BYTES))}})
    emit({"phase": "standalone", "platform_constants": _platform_constants()})
    emit({"phase": "standalone",
          "int64_sum_sweep": _int64_sum_sweep(args.seed)})
    ddir = data_dir(args)
    oracles = _oracles(ddir, STANDALONE_QUERIES)
    ctx = BallistaContext.standalone(config, concurrent_tasks=4,
                                     num_executors=1)
    try:
        register_tables(ctx, ddir)
        _run_queries(ctx, STANDALONE_QUERIES, oracles, ("cold", "warm"),
                     "standalone")
        # q1's dense-domain aggregates reduce on the matrix unit, one
        # contraction a kernel call; q6 has no keys and none
        for q in (1, 6):
            report = ctx.explain_analyze(QUERIES[q])
            counted = {f"{stage['stage_id']}:{op['op']}":
                       op["metrics"]["mxu_grouped_sums"]
                       for stage in report["stages"]
                       for op in stage["operator_tree"]
                       if op["metrics"].get("mxu_grouped_sums")}
            emit({"phase": "standalone", "query": f"q{q}",
                  "mxu_grouped_sums": counted})
            if info["platform"] == "tpu" and bool(counted) != (q == 1):
                raise SystemExit(f"q{q}: mxu_grouped_sums {counted}")
    finally:
        ctx.shutdown()
    emit({"phase": "standalone", "ok": True, "device": info})


def child_client(args) -> None:
    """The remote client of the cluster phase.  It needs no device, and the
    executor daemon holds the chip: any accelerator backend here is a
    failure."""
    from jax._src import xla_bridge

    from arrow_ballista_tpu.client.context import BallistaContext
    from arrow_ballista_tpu.utils.config import BallistaConfig
    from benchmarks.tpch import register_tables

    ddir = data_dir(args)
    oracles = _oracles(ddir, CLUSTER_QUERIES)
    ctx = BallistaContext.remote("127.0.0.1", args.scheduler_port,
                                 BallistaConfig(dict(BASE_CONFIG)))
    try:
        register_tables(ctx, ddir)
        _run_queries(ctx, CLUSTER_QUERIES, oracles, ("cold",), "cluster")
    finally:
        ctx.shutdown()
    backends = sorted(xla_bridge._backends)
    emit({"phase": "cluster", "client_backends": backends})
    if backends != ["cpu"]:
        raise SystemExit(f"the client started backends {backends}; it must "
                         "stay on the CPU platform")


def child_mesh(args) -> None:
    info = _device_or_die(args, need=4)
    import jax

    from arrow_ballista_tpu.client.context import BallistaContext
    from arrow_ballista_tpu.obs.tracing import RING
    from arrow_ballista_tpu.ops import mesh_exec
    from arrow_ballista_tpu.utils.config import BallistaConfig
    from benchmarks.tpch import register_tables

    # where the mesh programs' inputs live: read off every batch the mesh
    # operators place over the devices, as they hand it on
    placements = {}
    shard_rows = mesh_exec._shard_rows

    def watching(cols, mask, mesh, n_dev):
        out = shard_rows(cols, mask, mesh, n_dev)
        for leaf in jax.tree_util.tree_leaves(out[:2]):
            rec = placements.setdefault(
                (tuple(leaf.shape), str(leaf.dtype)), {})
            for s in leaf.addressable_shards:
                # a mask counts its live rows, a column its slots
                n = int(s.data.sum()) if leaf.dtype == bool \
                    else int(s.data.shape[0])
                rec[s.device.id] = max(rec.get(s.device.id, 0), n)
        return out

    mesh_exec._shard_rows = watching

    ddir = data_dir(args)
    oracles = _oracles(ddir, MESH_QUERIES)
    file_config = {k: v for k, v in MESH_CONFIG.items()
                   if not k.startswith("ballista.shuffle.mesh")}
    for label, conf in (("mesh", MESH_CONFIG), ("mesh-off", file_config)):
        ctx = BallistaContext.standalone(BallistaConfig(dict(conf)),
                                         concurrent_tasks=4, num_executors=1)
        try:
            register_tables(ctx, ddir)
            _run_queries(ctx, MESH_QUERIES, oracles, ("cold", "warm"), label)
        finally:
            ctx.shutdown()
        if label == "mesh":
            holders = sorted({d for rec in placements.values()
                              for d, rows in rec.items() if rows})
            live = [rec for (_, dt), rec in placements.items()
                    if dt == "bool"]
            # the programs by the names they carry into a device trace
            programs = sorted({s.attrs["program"] for s in RING.snapshot()
                               if s.name == "mesh_program"})
            emit({"phase": "mesh", "mesh_program_inputs": len(placements),
                  "mesh_programs": programs,
                  "devices_holding_rows": holders,
                  "live_rows_per_device_of_largest_mask": max(
                      live, key=lambda r: sum(r.values()), default={})})
            if len(holders) < 4:
                raise SystemExit(f"mesh inputs live on devices {holders} "
                                 "only; four must hold rows")
            if not programs or not all(p.startswith("mesh_")
                                       for p in programs):
                raise SystemExit(f"mesh programs are named {programs}; "
                                 "program_name's mesh_* names are expected")
    emit({"phase": "mesh", "ok": True, "device": info})


# --------------------------------------------------------------------------
# parent
# --------------------------------------------------------------------------

def _env(**extra) -> dict:
    env = dict(os.environ)
    # the C++ log channel is where the chip's compiler and runtime say why
    # they refused something: errors stay visible in every child
    env.pop("TF_CPP_MIN_LOG_LEVEL", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra)
    return env


def _remaining() -> float:
    return TOTAL_BUDGET_S - (time.time() - T0)


def _stop(proc, name: str) -> None:
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    say(f"{name} stopped, exit code {proc.returncode}")


def run_child(name: str, cmd, env, limit_s: float) -> list:
    """Run one child to its end, echo its output, return the JSON objects
    it printed.  Non-zero exit or timeout ends the whole run."""
    limit = min(limit_s, _remaining())
    if limit <= 0:
        raise SystemExit(f"phase {name}: no time left")
    say(f"phase {name}: start (limit {limit:.0f}s)")
    t0 = time.time()
    proc = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    objs = []
    timer = threading.Timer(limit, proc.kill)
    timer.start()
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            print(line, flush=True)
            if line.startswith("{"):
                try:
                    objs.append(json.loads(line))
                except json.JSONDecodeError:
                    pass
        rc = proc.wait()
    finally:
        timer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    took = time.time() - t0
    if rc != 0:
        raise SystemExit(f"phase {name}: child exited {rc} after {took:.0f}s"
                         + (" (time limit)" if took >= limit else ""))
    say(f"phase {name}: done in {took:.1f}s")
    return objs


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_for_log(path: str, needle: str, proc, what: str,
                  limit_s: float) -> str:
    deadline = time.time() + min(limit_s, _remaining())
    while time.time() < deadline:
        if proc.poll() is not None:
            raise SystemExit(f"{what} exited {proc.returncode} at start; "
                             f"see {path}:\n{_tail(path)}")
        for line in _read(path).splitlines():
            if needle in line:
                return line
        time.sleep(0.5)
    raise SystemExit(f"{what}: no '{needle}' in {path} in time:\n"
                     f"{_tail(path)}")


def _read(path: str) -> str:
    try:
        with open(path, errors="replace") as fh:
            return fh.read()
    except OSError:
        return ""


def _tail(path: str, n: int = 3000) -> str:
    return _read(path)[-n:]


def phase_cluster(args, child_args) -> None:
    """Scheduler daemon, executor daemon and client: three processes, the
    deployment the reference's users run.  Only the executor may hold the
    chip."""
    os.makedirs(LOG_DIR, exist_ok=True)
    sched_port = _free_port()
    sched_log = os.path.join(LOG_DIR, "scheduler.log")
    exec_log = os.path.join(LOG_DIR, "executor.log")
    want = "cpu" if args.allow_cpu else "tpu"
    say(f"phase cluster: start (scheduler port {sched_port})")
    daemons = []  # (process, name, log), in the order they must stop
    try:
        with open(sched_log, "w") as so, open(exec_log, "w") as eo:
            sched = subprocess.Popen(
                [sys.executable, "-m", "arrow_ballista_tpu.scheduler_daemon",
                 "--bind-host", "127.0.0.1", "--bind-port", str(sched_port),
                 "--rest-port", "-1", "--shuffle-partitions", "8"],
                cwd=REPO, env=_env(), stdout=so, stderr=subprocess.STDOUT)
            daemons.append((sched, "scheduler", sched_log))
            line = _wait_for_log(sched_log, "scheduler listening", sched,
                                 "scheduler", 120)
            if "jax platforms: cpu" not in line:
                raise SystemExit(f"scheduler is not pinned to the CPU: {line}")
            say("scheduler up, on the CPU platform")
            # the executor must get the chip or fail: JAX_PLATFORMS names
            # the one platform it may start, so there is no way back to the
            # CPU
            executor = subprocess.Popen(
                [sys.executable, "-m", "arrow_ballista_tpu.executor_daemon",
                 "--scheduler-host", "127.0.0.1",
                 "--scheduler-port", str(sched_port),
                 "--bind-host", "127.0.0.1", "--concurrent-tasks", "4",
                 "--work-dir", os.path.join(LOG_DIR, "executor-work")],
                cwd=REPO, env=_env(JAX_PLATFORMS=want), stdout=eo,
                stderr=subprocess.STDOUT)
            daemons.insert(0, (executor, "executor", exec_log))
        line = _wait_for_log(exec_log, "device ", executor, "executor", 180)
        if f"device {want}/" not in line:
            raise SystemExit(f"executor is not on {want}: {line}")
        say("executor up: " + line.split("device ", 1)[1].rstrip(")"))
        try:
            run_child("cluster-client",
                      child_args + ["--child", "client",
                                    "--scheduler-port", str(sched_port)],
                      _env(), 500)
        except SystemExit:
            for _, name, log in daemons:
                say(f"{name} log tail:\n" + _tail(log))
            raise
        for proc, name, log in daemons:
            if proc.poll() is not None:
                raise SystemExit(f"{name} died during the phase (exit "
                                 f"{proc.returncode}):\n" + _tail(log))
            _stop(proc, name)
    finally:
        for proc, _, _ in daemons:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    left = [name for proc, name, _ in daemons if proc.poll() is None]
    if left:
        raise SystemExit(f"left running after the phase: {left}")
    say("phase cluster: done")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on a CPU; the last line then names the "
                         "CPU and is not the success line")
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: the mesh phase and what it is "
                         "compared with, and no other phase")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--scheduler-port", type=int, default=0,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child:
        {"standalone": child_standalone, "client": child_client,
         "mesh": child_mesh}[args.child](args)
        return 0

    child_args = [sys.executable, os.path.abspath(__file__),
                  "--seed", str(args.seed), "--scale", str(args.scale)]
    if args.allow_cpu:
        child_args.append("--allow-cpu")
    ddir = data_dir(args)
    if not os.path.exists(os.path.join(ddir, "lineitem.parquet")):
        run_child("data", [sys.executable, "-m", "benchmarks.tpch",
                           "convert", "--scale", str(args.scale),
                           "--seed", str(args.seed), "--output", ddir],
                  _env(JAX_PLATFORMS="cpu"), 600)
    else:
        say(f"phase data: found {ddir}")
    if args.mesh:
        objs = run_child("mesh", child_args + ["--child", "mesh"],
                         _env(), 1100)
    else:
        objs = run_child("standalone",
                         child_args + ["--child", "standalone"],
                         _env(), 1000)
        phase_cluster(args, child_args)
    device = next(o["device"] for o in reversed(objs)
                  if o.get("ok") and "device" in o)
    say(f"all phases passed in {time.time() - T0:.0f}s")
    if device["platform"] != "tpu":
        emit({"ok": False, "rehearsal": True, "device": device})
        return 0
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
