"""Headline benchmark: TPC-H through the engine on one chip.

Two layers, both reported:

- **engine**: TPC-H q1/q6/q3/q5/q18 at SF1 run END-TO-END through
  ``BallistaContext.standalone`` — parquet scan -> device pipeline ->
  shuffle -> final aggregate -> collect.  The headline metric is engine
  rows/s on q1 (lineitem rows / wall-clock), matching how the reference's
  README chart is computed (reference README.md:52-60: q1 SF10 in ~3.1 s on
  a 24-core executor => ~19.35M rows/s, see BASELINE.md).  When SF10 data
  exists the like-for-like SF10 numbers become the headline.
- **kernel**: the fused q1 pipeline (filter -> derived columns -> grouped
  aggregate) over HBM-resident arrays, isolating device throughput from IO.

One process, one platform: the numbers are the chip's or there are none.
``python bench.py`` exits non-zero unless jax finds a TPU; ``--platform cpu``
measures the CPU when it is asked for by name.  Every result line names the
platform and device kind its numbers came from, and a complete line is
printed after every milestone, so a run that is cut leaves what it measured.

The FINAL stdout line is the result:
  {"metric": ..., "value": N, "unit": "rows/s", "platform": ..., ...}
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
BASELINE_ROWS_PER_S = 59_986_052 / 3.1  # reference q1 SF10 wall-clock
SCALE = float(os.environ.get("BENCH_SCALE", "1"))
QUERIES = os.environ.get("BENCH_QUERIES", "1,6,3,5,18")
MESH_QUERIES = os.environ.get("BENCH_MESH_QUERIES", "1,6,3")
SF10_QUERIES = os.environ.get("BENCH_SF10_QUERIES", "1,3,5,18")
# iteration knobs: drop to 1 to trade steady-state fidelity for budget
ITERS = int(os.environ.get("BENCH_ITERS", "2"))
SF10_ITERS = int(os.environ.get("BENCH_SF10_ITERS", "2"))
DATA_DIR = os.environ.get(
    "BENCH_DATA", os.path.join(REPO, ".bench_data", f"tpch-sf{SCALE:g}")
)
KERNEL_ROWS = int(os.environ.get("BENCH_KERNEL_ROWS", str(8_000_000)))


def ensure_data() -> None:
    marker = os.path.join(DATA_DIR, "lineitem.parquet")
    if os.path.exists(marker):
        return
    os.makedirs(DATA_DIR, exist_ok=True)
    print(f"[bench] generating TPC-H SF{SCALE:g} under {DATA_DIR}", file=sys.stderr)
    subprocess.run(
        [sys.executable, "-m", "benchmarks.tpch", "convert",
         "--scale", str(SCALE), "--output", DATA_DIR],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"}, check=True,
        timeout=1800,
        stdout=sys.stderr,
    )


# --------------------------------------------------------------------------
# the benchmark proper
# --------------------------------------------------------------------------


def _worker(deadline: float) -> None:
    import numpy as np
    import jax

    # int64 columns (fixed-point decimals, keys) need x64; the device path
    # never produces f64 arrays (divisions are host-finalize), so this is
    # TPU-safe
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    dev = jax.devices()[0]
    print(f"[worker] backend up: {dev.platform} ({dev.device_kind})", file=sys.stderr)

    result: dict = {
        "metric": f"tpch_q1_sf{SCALE:g}_engine_rows_per_sec",
        "value": 0.0, "unit": "rows/s", "vs_baseline": 0.0,
        "partial": "backend-up",
        "platform": dev.platform, "device": str(dev.device_kind),
    }

    def emit(stage: str) -> None:
        """Milestone emission: every print is a complete, parseable result,
        so a run that is cut still leaves everything measured so far."""
        result["partial"] = stage
        print(json.dumps(result), flush=True)

    emit("backend-up")

    # --- platform characterization: the constants needed to interpret the
    # engine numbers (per-transfer latency, not FLOPs, can dominate) -----
    def _med(f, n=5):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            f()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    small = np.zeros(128, np.int32)
    big = np.zeros(8 << 20, np.int64)  # 64 MB
    d_small = jax.device_put(small)
    jax.block_until_ready(d_small)
    tiny = jax.jit(lambda x: x + 1)
    jax.block_until_ready(tiny(d_small))
    rtt = _med(lambda: jax.block_until_ready(tiny(d_small)))
    h2d = _med(lambda: jax.block_until_ready(jax.device_put(big)), 3)
    # d2h must use a FRESH device array per iteration: ArrayImpl caches the
    # first host copy (_npy_value), so re-reading the same array measures a
    # cache hit, not the transfer
    d_bigs = [jax.device_put(tiny(jax.device_put(big))) for _ in range(3)]
    jax.block_until_ready(d_bigs)
    it = iter(d_bigs)
    d2h = _med(lambda: np.asarray(next(it)), 3)
    result["platform_rtt_ms"] = round(rtt * 1000, 2)
    result["platform_h2d_gbps"] = round(big.nbytes / h2d / 1e9, 2)
    result["platform_d2h_gbps"] = round(big.nbytes / d2h / 1e9, 2)
    print(f"[worker] platform: rtt {rtt*1000:.2f} ms, "
          f"h2d {big.nbytes/h2d/1e9:.2f} GB/s, d2h {big.nbytes/d2h/1e9:.2f} GB/s",
          file=sys.stderr)
    del d_bigs, big
    emit("platform-constants")

    # --- kernel microbench ---------------------------------------------
    sys.path.insert(0, REPO)
    from __graft_entry__ import _q1_augment, _q1_example, _q1_filter, _Q1_AGGS, _Q1_KEYS
    from arrow_ballista_tpu.ops import kernels as K

    cols_np, mask_np = _q1_example(KERNEL_ROWS, seed=7)
    cols = {k: jax.device_put(jnp.asarray(v)) for k, v in cols_np.items()}
    mask = jax.device_put(jnp.asarray(mask_np))

    # key_ranges mirrors the engine: returnflag/linestatus are dict-coded
    # strings with host-known code ranges, which selects the dense sort-free
    # grouping path (kernels.grouped_aggregate) — the path engine q1 runs
    @jax.jit
    def step(cols, mask):
        cols, mask = _q1_filter(cols, mask)
        cols = _q1_augment(cols)
        keys = [cols[k] for k in _Q1_KEYS]
        vals = [(cols[v], how) for v, how in _Q1_AGGS]
        return K.grouped_aggregate(keys, vals, mask, 16,
                                   key_ranges=((0, 2), (0, 1)))

    t_c = time.perf_counter()
    out = step(cols, mask)  # compile + warmup
    jax.block_until_ready(out)
    result["kernel_q1_compile_s"] = round(time.perf_counter() - t_c, 1)
    # block on the WHOLE output tree AND force a 1-element host read: a
    # D2H read cannot return before the work is done (its cost is one rtt,
    # reported above for subtraction)
    def _timed_step():
        out = step(cols, mask)
        jax.block_until_ready(out)
        # tiny D2H read (16-slot group mask): completion proof — overflow
        # (out[3]) is None on the dense path since it became statically
        # impossible there
        np.asarray(out[2])

    med = _med(_timed_step, 10)
    kernel_rows_s = KERNEL_ROWS / med
    # sanity companion: effective HBM read bandwidth implied by the input
    # columns alone — if this exceeds the chip's spec the measurement is
    # wrong, not the kernel fast
    in_bytes = sum(v.nbytes for v in cols.values()) + mask.nbytes
    result["kernel_q1_rows_per_sec"] = round(kernel_rows_s, 1)
    result["kernel_q1_ms"] = round(med * 1000, 3)
    result["kernel_q1_gbps"] = round(in_bytes / med / 1e9, 1)
    print(f"[worker] kernel q1: {kernel_rows_s/1e6:.1f}M rows/s "
          f"({med*1000:.2f} ms, {in_bytes/med/1e9:.0f} GB/s implied)",
          file=sys.stderr)
    del cols, mask, out
    emit("kernel-q1")

    # --- engine bench: TPC-H through BallistaContext --------------------
    from arrow_ballista_tpu.client.context import BallistaContext
    from arrow_ballista_tpu.utils.config import BallistaConfig
    from benchmarks.queries import QUERIES as SQL
    from benchmarks.tpch import register_tables

    # ONE base config shared by the file and mesh runs so the two transports
    # stay knob-for-knob comparable
    base_config = {
        # auto -> ceil(rows/batch) partitions; measured best on SF1 (6 for
        # the 12-row-group lineitem: 2 row groups per scan task)
        "ballista.shuffle.partitions": "auto",
        "ballista.batch.size": str(1 << 20),
        # engine deadline: generous, so slow first-compile runs finish
        "ballista.job.timeout.seconds": "1800",
    }
    def _warm_cache(paths, label):
        # warm the OS page cache first: whichever run goes first would
        # otherwise pay cold disk reads the others don't (observed: file
        # q1 7.3 s cold vs 3.0 s warm on the same code)
        t_w = time.perf_counter()
        for path in paths:
            with open(path, "rb") as fh:
                while fh.read(1 << 24):
                    pass
        print(f"[worker] {label} page-cache warmup: "
              f"{time.perf_counter()-t_w:.1f}s", file=sys.stderr)

    _warm_cache([os.path.join(DATA_DIR, f)
                 for f in sorted(os.listdir(DATA_DIR))
                 if f.endswith(".parquet")], "sf1")

    ctx = BallistaContext.standalone(BallistaConfig(dict(base_config)),
                                     concurrent_tasks=4)
    register_tables(ctx, DATA_DIR)
    lineitem_rows = ctx.catalog.provider("lineitem").row_count()
    result["lineitem_rows"] = lineitem_rows

    def _job_metrics(ctx):
        """Aggregate per-operator metrics of the most recent job, per stage —
        every bench run doubles as a profile (the round-2 lesson: a failed
        run with no metrics tells you nothing about WHERE the time went)."""
        try:
            sched = ctx._standalone.scheduler
            jobs = list(sched.jobs._status)
            if not jobs:
                return {}
            graph = sched.jobs.get_graph(jobs[-1])
            out = {}
            for sid in sorted(graph.stages):
                s = graph.stages[sid]
                spans = []
                for t in s.task_infos:
                    if not t or not t.status:
                        continue
                    st = t.status
                    if st.start_time_ms and st.end_time_ms:
                        spans.append((st.start_time_ms, st.end_time_ms))
                entry = {k: round(v, 2)
                         for k, v in sorted(s.aggregate_metrics().items())
                         if v >= 0.05}
                if spans:
                    entry["stage_wall_s"] = round(
                        (max(b for _, b in spans) - min(a for a, _ in spans))
                        / 1000, 2)
                out[f"stage{sid}"] = entry
            return out
        except Exception as e:  # noqa: BLE001 — profiling must never kill a bench
            return {"error": str(e)}

    def _headline_from_q1(engine, rows, sf_label):
        q1_s = engine.get("q1_ms", 0.0) / 1000.0
        if q1_s:
            value = rows / q1_s
            result["metric"] = f"tpch_q1_{sf_label}_engine_rows_per_sec"
            result["value"] = round(value, 1)
            result["vs_baseline"] = round(value / BASELINE_ROWS_PER_S, 4)

    def _stage_breakdown(ctx):
        """Compact per-stage runtime stats of the most recent job, read off
        the graph's RuntimeStatsStore fold (obs/stats.py): rows/bytes
        shuffled, partition skew, and task-duration p50/max.  Lands in the
        bench JSON so a regression is attributable to a STAGE, not just a
        query."""
        try:
            sa = ctx._standalone
            graph = sa.scheduler.jobs.get_graph(sa.last_job_id)
            if graph is None:
                return {}
            out = {}
            for s in graph.stats.snapshot()["stages"]:
                d = s["task_duration_s"]
                out[f"s{s['stage_id']}"] = {
                    "rows": s["output_rows"],
                    "mb": round(s["output_bytes"] / 1048576.0, 2),
                    "skew": s["skew"],
                    "p50_s": d.get("p50", 0.0),
                    "max_s": d.get("max", 0.0),
                }
            return out
        except Exception as e:  # noqa: BLE001 — profiling must never kill a bench
            return {"error": str(e)}

    def _aqe_decisions(ctx):
        """The most recent job's adaptive-rewrite decisions (scheduler/
        aqe.py's graph.aqe_log): which stages were coalesced / switched to
        broadcast / skew-split, with before/after partition counts.  Lands
        next to the stage breakdown so a perf delta is attributable to a
        plan DECISION, not just a stage."""
        try:
            sa = ctx._standalone
            graph = sa.scheduler.jobs.get_graph(sa.last_job_id)
            if graph is None:
                return []
            return [{"stage": r["stage_id"],
                     "kinds": list(r.get("kinds", ())),
                     "before": r.get("partitions_before"),
                     "after": r.get("partitions_after")}
                    for r in getattr(graph, "aqe_log", [])]
        except Exception as e:  # noqa: BLE001 — profiling must never kill a bench
            return [{"error": str(e)}]

    def _fusion_decisions(ctx):
        """The most recent job's whole-stage-compilation decisions
        (compile/fuse.py's graph.compile_log): which chains fused into one
        kernel, and which were rejected with what reason — the evidence
        that a fusion-leg delta comes from the compiler, not noise."""
        try:
            sa = ctx._standalone
            graph = sa.scheduler.jobs.get_graph(sa.last_job_id)
            if graph is None:
                return []
            return [{"stage": r["stage_id"],
                     "fused": [list(run) for run in r.get("fused_ops", ())],
                     "rejected": len(r.get("rejected", ()))}
                    for r in getattr(graph, "compile_log", [])
                    if r.get("fused")]
        except Exception as e:  # noqa: BLE001 — profiling must never kill a bench
            return [{"error": str(e)}]

    def run_queries(ctx, queries, label, dest, iters=ITERS, rows=None,
                    sf_label=None, min_slack_s=60.0):
        # min_slack_s: don't START a query with less than this left on the
        # clock — SF10 legs pass a larger slack since one iteration there
        # can run minutes (the BENCH_r05 rc=124 overrun)
        for q in queries:
            if time.time() > deadline - min_slack_s:
                dest[f"q{q}_skipped"] = "deadline"
                print(f"[worker] {label} q{q} skipped: deadline", file=sys.stderr)
                continue
            per = []
            try:
                for it in range(iters):
                    t0 = time.perf_counter()
                    res = ctx.sql(SQL[q]).collect()
                    nrows = sum(b.num_rows for b in res)
                    per.append(time.perf_counter() - t0)
                    print(f"[worker] {label} q{q} iter{it}: {per[-1]*1000:.0f} ms "
                          f"({nrows} rows)", file=sys.stderr)
                dest[f"q{q}_ms"] = round(min(per) * 1000, 1)
                dest[f"q{q}_stages"] = _stage_breakdown(ctx)
                dest[f"q{q}_aqe"] = _aqe_decisions(ctx)
                fused = _fusion_decisions(ctx)
                if fused:
                    dest[f"q{q}_fused"] = fused
                print(f"[worker] {label} q{q} metrics: "
                      f"{json.dumps(_job_metrics(ctx))}", file=sys.stderr)
            except Exception as e:  # noqa: BLE001 — record, keep benching
                dest[f"q{q}_error"] = f"{type(e).__name__}: {e}"
                print(f"[worker] {label} q{q} FAILED: {e}", file=sys.stderr)
            if rows is not None and sf_label:
                _headline_from_q1(dest, rows, sf_label)
            emit(f"{label}-q{q}")
        return dest

    queries = [int(x) for x in QUERIES.split(",") if x.strip()]
    engine = result["engine"] = {}
    run_queries(ctx, queries, "file", engine, rows=lineitem_rows,
                sf_label=f"sf{SCALE:g}")
    ctx.shutdown()

    # --- AQE A/B leg: q1/q18 with runtime re-optimization OFF -----------
    # same iteration count as the on-leg so min-vs-min compares like with
    # like; the ratio is still order-biased (the off leg reuses the warm
    # process / XLA cache), so it's recorded as a raw ratio, not a claim
    if time.time() < deadline - 120:
        try:
            ctx_off = BallistaContext.standalone(
                BallistaConfig({**base_config,
                                "ballista.aqe.enabled": "false"}),
                concurrent_tasks=4)
            try:
                register_tables(ctx_off, DATA_DIR)
                aqe_off = result.setdefault("engine_aqe_off", {})
                run_queries(ctx_off, [q for q in (1, 18) if q in queries],
                            "aqe-off", aqe_off)
                for q in (1, 18):
                    on, off = engine.get(f"q{q}_ms"), aqe_off.get(f"q{q}_ms")
                    if on and off:
                        aqe_off[f"q{q}_off_over_on"] = round(off / on, 3)
            finally:
                ctx_off.shutdown()
        except Exception as e:  # noqa: BLE001 — A/B leg must not kill the run
            result["engine_aqe_off"] = {"error": f"{type(e).__name__}: {e}"}

    # --- fusion A/B leg: whole-stage compiler OFF ------------------------
    # q1/q18 reuse the main engine leg's fusion-ON numbers; q21 (deep
    # multi-join with a fusable filter+partial-agg leaf pipeline) gets its
    # ON number here first.  Same caveat as the AQE leg: the OFF leg runs
    # in a warm process, so the ratio is a recorded observation, not a
    # controlled claim — the stage breakdown and compile_log land next to
    # it so deltas are attributable to the fused stages specifically.
    if time.time() < deadline - 120:
        fusion_qs = [1, 18, 21]
        try:
            extra_on = [q for q in fusion_qs if not engine.get(f"q{q}_ms")]
            if extra_on:
                ctx_fon = BallistaContext.standalone(
                    BallistaConfig(dict(base_config)), concurrent_tasks=4)
                try:
                    register_tables(ctx_fon, DATA_DIR)
                    run_queries(ctx_fon, extra_on, "fusion-on", engine)
                finally:
                    ctx_fon.shutdown()
            ctx_foff = BallistaContext.standalone(
                BallistaConfig({**base_config,
                                "ballista.compile.enabled": "false"}),
                concurrent_tasks=4)
            try:
                register_tables(ctx_foff, DATA_DIR)
                fus_off = result.setdefault("engine_fusion_off", {})
                run_queries(ctx_foff, fusion_qs, "fusion-off", fus_off)
                for q in fusion_qs:
                    on = engine.get(f"q{q}_ms")
                    off = fus_off.get(f"q{q}_ms")
                    if on and off:
                        fus_off[f"q{q}_fusion_off_over_on"] = round(off / on, 3)
            finally:
                ctx_foff.shutdown()
            emit("fusion-ab")
        except Exception as e:  # noqa: BLE001 — A/B leg must not kill the run
            result["engine_fusion_off"] = {"error": f"{type(e).__name__}: {e}"}

    if not engine.get("q1_ms"):
        # a 0.0 headline must be distinguishable from a measured zero
        result["error"] = ("q1 not measured: " +
                           engine.get("q1_error", "not in BENCH_QUERIES"))
    else:
        result.pop("error", None)

    # --- SF10 rider: the reference baseline IS SF10 (README.md:52-60) ---
    # runs whenever a prior round generated the data, without making the
    # headline depend on a 13-minute generation step.  Deliberately BEFORE
    # the mesh and kernel-join legs: SF10 q1 is the headline metric, so it
    # gets first claim on whatever budget remains (BENCH_r05 ran it last
    # and timed out with no SF10 number at all)
    sf10_dir = os.path.join(REPO, ".bench_data", "tpch-sf10")
    if (SCALE == 1 and os.path.exists(os.path.join(sf10_dir, "lineitem.parquet"))
            and time.time() < deadline - 180):
        try:
            _warm_cache([os.path.join(sf10_dir, "lineitem.parquet")], "sf10")
            ctx10 = BallistaContext.standalone(
                BallistaConfig(dict(base_config)), concurrent_tasks=4)
            try:
                register_tables(ctx10, sf10_dir)
                rows10 = ctx10.catalog.provider("lineitem").row_count()
                sf10 = result.setdefault("engine_sf10", {})
                sf10_queries = [int(x) for x in SF10_QUERIES.split(",") if x.strip()]
                # warm iterations (default 2): the warm number is the steady
                # state the scan cache is designed for, and iter0 alone would
                # publish conversion-cold walls (observed: q3 80 s cold vs
                # 29 s warm).  min_slack 180 s: one SF10 iteration can run
                # minutes, so don't start one that can't finish in budget.
                run_queries(ctx10, [q for q in sf10_queries if q == 1],
                            "sf10", sf10, iters=SF10_ITERS, min_slack_s=180)
                q1_10 = sf10.get("q1_ms", 0.0) / 1000.0
                if q1_10:
                    sf10["q1_rows_per_sec"] = round(rows10 / q1_10, 1)
                    sf10["vs_baseline_sf10"] = round(
                        rows10 / q1_10 / BASELINE_ROWS_PER_S, 4)
                    # the like-for-like datapoint becomes the headline; the
                    # SF1 numbers stay in `engine`
                    result["metric"] = "tpch_q1_sf10_engine_rows_per_sec"
                    result["value"] = sf10["q1_rows_per_sec"]
                    result["vs_baseline"] = sf10["vs_baseline_sf10"]
                    emit("sf10-q1")
                run_queries(ctx10, [q for q in sf10_queries if q != 1],
                            "sf10", sf10, iters=SF10_ITERS, min_slack_s=180)
            finally:
                ctx10.shutdown()
        except Exception as e:  # noqa: BLE001 — rider must not kill the run
            result["engine_sf10"] = {"error": f"{type(e).__name__}: {e}"}

    # --- shuffle-transport A/B leg: the shuffle-heavy queries through a
    # REAL 2-executor TCP cluster (standalone's identity-local path never
    # touches the transport), one cluster per leg:
    #   mmap   — shipped defaults: host-match mmap + streaming + lz4
    #   wire   — host-match off, so co-located reads take the compressed
    #            chunked streaming path (bytes-on-wire measurement)
    #   legacy — streaming off too: the whole-file uncompressed protocol
    # DataPlaneStats is process-global and the executors are in-proc
    # threads, so snapshot deltas attribute bytes/chunks to each query.
    if time.time() < deadline - 240:
        try:
            import shutil
            import tempfile

            from arrow_ballista_tpu.executor.server import ExecutorServer
            from arrow_ballista_tpu.net import dataplane as dp
            from arrow_ballista_tpu.scheduler.netservice import SchedulerNetService

            transport_queries = [
                int(x) for x in
                os.environ.get("BENCH_TRANSPORT_QUERIES", "3,5,21").split(",")
                if x.strip()]
            # legacy first: the first leg pays the cold XLA compiles, so
            # giving that to the BASELINE biases the ms ratios against the
            # new transports, never for them.  byte counts are exact either
            # way — they're the headline; ms is a raw corroborating ratio.
            legs = [
                ("legacy", {"ballista.shuffle.local.host_match": "false",
                            "ballista.shuffle.wire.streaming": "false"}),
                ("wire", {"ballista.shuffle.local.host_match": "false"}),
                ("mmap", {}),
            ]
            transport = result.setdefault("engine_transport", {})
            for leg, overrides in legs:
                if time.time() > deadline - 150:
                    transport[f"{leg}_skipped"] = "deadline"
                    break
                conf = {**base_config, **overrides}
                tmp = tempfile.mkdtemp(prefix=f"bench-transport-{leg}-")
                sched = SchedulerNetService(
                    "127.0.0.1", 0, config=BallistaConfig(dict(conf)))
                sched.start()
                executors = []
                try:
                    for i in range(2):
                        work = os.path.join(tmp, f"exec{i}")
                        os.makedirs(work)
                        ex = ExecutorServer(
                            "127.0.0.1", sched.port, "127.0.0.1", 0,
                            work_dir=work, concurrent_tasks=2,
                            executor_id=f"bench-{leg}-{i}",
                            config=BallistaConfig(dict(conf)))
                        ex.start()
                        executors.append(ex)
                    tctx = BallistaContext.remote(
                        "127.0.0.1", sched.port, BallistaConfig(dict(conf)))
                    try:
                        register_tables(tctx, DATA_DIR)
                        for q in transport_queries:
                            if time.time() > deadline - 100:
                                transport[f"q{q}_skipped"] = "deadline"
                                continue
                            s0 = dp.STATS.snapshot()
                            t0 = time.perf_counter()
                            res = tctx.sql(SQL[q]).collect()
                            wall = time.perf_counter() - t0
                            s1 = dp.STATS.snapshot()
                            rec = transport.setdefault(
                                f"q{q}_shuffle_transport", {})
                            rec[leg] = {
                                "ms": round(wall * 1000, 1),
                                "rows": sum(b.num_rows for b in res),
                                "local_bytes": (
                                    s1["bytes_fetched"]["local_mmap"]
                                    - s0["bytes_fetched"]["local_mmap"]
                                    + s1["bytes_fetched"]["local_copy"]
                                    - s0["bytes_fetched"]["local_copy"]),
                                "remote_bytes": (
                                    s1["bytes_fetched"]["remote"]
                                    - s0["bytes_fetched"]["remote"]),
                                "chunks": s1["chunks"] - s0["chunks"],
                                "raw_bytes": s1["raw_bytes"] - s0["raw_bytes"],
                                "wire_bytes": (s1["wire_bytes"]
                                               - s0["wire_bytes"]),
                            }
                            print(f"[worker] transport {leg} q{q}: "
                                  f"{wall*1000:.0f} ms "
                                  f"{json.dumps(rec[leg])}", file=sys.stderr)
                    finally:
                        tctx.shutdown()
                finally:
                    for ex in executors:
                        ex.stop(notify=False)
                    sched.stop()
                    shutil.rmtree(tmp, ignore_errors=True)
                emit(f"transport-{leg}")
            # headline deltas per query: wall-clock of the default path vs
            # the legacy wire, and bytes-on-wire of compressed streaming vs
            # whole-file (the remote series counts post-compression bytes)
            for q in transport_queries:
                rec = transport.get(f"q{q}_shuffle_transport")
                if not rec:
                    continue
                mmap_l, wire_l, legacy_l = (rec.get("mmap"), rec.get("wire"),
                                            rec.get("legacy"))
                if mmap_l and legacy_l and mmap_l["ms"]:
                    rec["legacy_over_mmap_ms"] = round(
                        legacy_l["ms"] / mmap_l["ms"], 3)
                if wire_l and legacy_l and wire_l["remote_bytes"]:
                    rec["legacy_over_wire_bytes"] = round(
                        legacy_l["remote_bytes"] / wire_l["remote_bytes"], 3)
            emit("transport-ab")
        except Exception as e:  # noqa: BLE001 — A/B leg must not kill the run
            result["engine_transport"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[worker] transport bench failed: {e}", file=sys.stderr)

    # --- serving leg: concurrent sessions, caches on vs off -------------
    # SF0.01 on purpose: per-query work is tiny so scheduler+planning
    # overhead — what the serving caches attack — dominates the off leg.
    # BENCH_SERVING=0 skips it; sessions/queries are env-tunable.
    if (os.environ.get("BENCH_SERVING", "1") != "0"
            and time.time() < deadline - 150):
        try:
            from benchmarks.serving import run_serving_benchmark

            result["serving"] = run_serving_benchmark(
                sessions=int(os.environ.get("BENCH_SERVING_SESSIONS", "32")),
                queries_per_session=int(
                    os.environ.get("BENCH_SERVING_QUERIES", "8")))
            sv = result["serving"]
            print(f"[worker] serving: {sv['on']['qps']} qps on vs "
                  f"{sv['off']['qps']} off "
                  f"({sv.get('qps_on_over_off', 0)}x), "
                  f"p99 q2l on={sv['on']['queue_to_launch_p99_ms']} ms "
                  f"off={sv['off']['queue_to_launch_p99_ms']} ms",
                  file=sys.stderr)
            emit("serving")
        except Exception as e:  # noqa: BLE001 — A/B leg must not kill the run
            result["serving"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[worker] serving bench failed: {e}", file=sys.stderr)

    # --- mesh path: same queries, ICI all_to_all shuffle ----------------
    # guarded end to end: a mesh-path failure must never discard the file
    # numbers already measured above
    if time.time() < deadline - 300:
        try:
            # min_rows=0: the default transport is ADAPTIVE (small exchanges
            # plan onto the file path), so the mesh leg forces mesh to keep
            # measuring the raw transport — the adaptive default is what
            # users get and equals the better of the two legs
            mesh_config = BallistaConfig(
                {**base_config, "ballista.shuffle.mesh": "true",
                 "ballista.shuffle.mesh.min_rows": "0"})
            result["mesh_forced"] = True
            mctx = BallistaContext.standalone(mesh_config, concurrent_tasks=4)
            try:
                register_tables(mctx, DATA_DIR)
                mesh_queries = [int(x) for x in MESH_QUERIES.split(",") if x.strip()]
                run_queries(mctx, mesh_queries, "mesh",
                            result.setdefault("engine_mesh", {}))
            finally:
                mctx.shutdown()
        except Exception as e:  # noqa: BLE001 — record, keep the file numbers
            result["engine_mesh"] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[worker] mesh bench failed: {e}", file=sys.stderr)
    else:
        result["engine_mesh"] = {"skipped": "deadline"}

    # --- kernel: join shape (sorted build, range lookup, expansion) -----
    # evidences the device join path: the build argsort is the one program
    # family measured to compile slowly on this backend, so compile time is
    # reported separately from steady-state
    if time.time() < deadline - 300:
        rngj = np.random.default_rng(11)
        n_probe, n_build = KERNEL_ROWS // 2, KERNEL_ROWS // 8
        pk = jax.device_put(jnp.asarray(
            rngj.integers(0, n_build * 2, n_probe).astype(np.int64)))
        bk = jax.device_put(jnp.asarray(np.arange(n_build, dtype=np.int64)))
        pmask_j = jax.device_put(jnp.ones(n_probe, bool))
        bmask_j = jax.device_put(jnp.ones(n_build, bool))
        out_cap = n_probe

        @jax.jit
        def join_step(pk, bk, pmask, bmask):
            bh_sorted, border, _ = K.build_side_sort([bk], bmask)
            lo, counts, _ = K.probe_ranges(K.hash64([pk]), pmask, bh_sorted)
            pi, bp, pair_valid, total = K.expand_pairs(lo, counts, bmask.shape[0], out_cap)
            bidx = border[bp]
            ok = pair_valid & bmask[bidx] & (pk[pi] == bk[bidx])
            return jnp.sum(ok), total

        t_c = time.perf_counter()
        jax.block_until_ready(join_step(pk, bk, pmask_j, bmask_j))
        result["kernel_join_compile_s"] = round(time.perf_counter() - t_c, 1)

        def _timed_join():
            out = join_step(pk, bk, pmask_j, bmask_j)
            jax.block_until_ready(out)
            np.asarray(out[0])  # scalar D2H: completion proof

        medj = _med(_timed_join)
        result["kernel_join_rows_per_sec"] = round(n_probe / medj, 1)
        result["kernel_join_ms"] = round(medj * 1000, 3)
        print(f"[worker] kernel join: {n_probe/medj/1e6:.1f}M probe rows/s "
              f"({medj*1000:.2f} ms, compile {result['kernel_join_compile_s']}s)",
              file=sys.stderr)
        del pk, bk, pmask_j, bmask_j
        emit("kernel-join")

    emit("done")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--platform", default="tpu", choices=["tpu", "cpu"],
                    help="the platform to measure; anything but the chip "
                         "must be asked for by name")
    args = ap.parse_args()
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    dev = jax.devices()[0]
    if dev.platform != args.platform:
        raise SystemExit(
            f"bench.py: jax found platform {dev.platform!r} "
            f"({dev.device_kind}), not {args.platform!r}: nothing is "
            "measured.  --platform cpu measures the CPU by name.")
    ensure_data()
    _worker(time.time() + float(os.environ.get("BENCH_TOTAL_TIMEOUT",
                                                "3600")))


if __name__ == "__main__":
    main()
