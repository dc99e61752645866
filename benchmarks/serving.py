"""High-concurrency serving benchmark: N client sessions hammering one
scheduler with small repeated queries, caches on vs caches off.

What it measures (the serving story of docs/user-guide/serving.md):

- **QPS** per leg — the headline; the acceptance bar is >= 2x with the
  prepared-plan + result caches on vs both explicitly disabled, same box,
  same run.
- **e2e latency** p50/p99 per query, measured client-side.
- **queue-to-launch** p50/p99 — queued_at -> record_submitted on the
  scheduler, i.e. admission wait + parse/plan/validate/graph build; the
  slice the plan cache is built to collapse.  A result-cache hit never
  submits a job, so only planned submissions contribute samples.
- **event-loop lag** — max enqueue->dequeue lag of the scheduler's
  single-consumer loop over the leg (EventLoop.stats()), the saturation
  signal for the batched status-ingestion work.
- **cache hit rates** from the serving caches' own snapshots.

Topology: one ``SchedulerNetService`` + in-proc TCP executors per leg, one
``BallistaContext.remote`` per session (its own server-side session, so
session creation, per-session config fingerprinting and the shared-catalog
overlay are all on the measured path).  Tables are registered on the
scheduler's SHARED catalog so sessions share plan templates, as a serving
deployment would.

Each leg warms every distinct query once before the timer starts: the
comparison is steady-state serving throughput, not first-compile walls
(XLA compile alone would otherwise dominate both legs identically).

CLI:
    python -m benchmarks.serving                 # full A/B, JSON on stdout
    python -m benchmarks.serving --smoke         # 8 sessions x q6: asserts
                                                 # zero errors + plan-cache
                                                 # hits > 0, exit 1 on fail
    python -m benchmarks.serving --shards 2      # fleet benchmark: single vs
                                                 # 2-shard aggregate QPS plus
                                                 # a mid-leg shard-kill
                                                 # failover leg
    python -m benchmarks.serving --smoke --shards 2   # fleet + failover
                                                      # smoke gate
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# q6-shaped (filter + global agg, 1 stage) and q1-shaped (group-by agg,
# 2 stages) templates; literals vary per variant so the plan cache sees
# ONE normalized text per shape while the result cache sees each variant
# as its own entry — both tiers are exercised.
_Q6 = ("select sum(l_extendedprice * l_discount) as revenue "
       "from lineitem where l_discount between {lo} and {hi} "
       "and l_quantity < {q}")
_Q1 = ("select l_returnflag, count(*) as n, sum(l_quantity) as sum_qty, "
       "avg(l_extendedprice) as avg_price from lineitem "
       "where l_quantity < {q} group by l_returnflag order by l_returnflag")

_Q6_PARAMS = [(0.02, 0.04, 20), (0.03, 0.05, 24), (0.04, 0.06, 28),
              (0.05, 0.07, 32)]
_Q1_PARAMS = [18, 24, 30, 36]


def build_workload(shapes: Tuple[str, ...] = ("q6", "q1")) -> List[str]:
    """The distinct query pool; sessions cycle through it round-robin."""
    pool: List[str] = []
    if "q6" in shapes:
        pool.extend(_Q6.format(lo=lo, hi=hi, q=q) for lo, hi, q in _Q6_PARAMS)
    if "q1" in shapes:
        pool.extend(_Q1.format(q=q) for q in _Q1_PARAMS)
    return pool


def ensure_data(scale: float = 0.01, data_dir: Optional[str] = None) -> str:
    """Generate (once) and return a tiny TPC-H directory for the serving
    workload; SF0.01 keeps per-query work small so scheduling and planning
    overheads — the thing the caches attack — dominate the uncached leg."""
    data_dir = data_dir or os.path.join(REPO, ".bench_data",
                                        f"tpch-sf{scale:g}")
    # two layouts exist: <name>.parquet dirs and datagen's bare
    # <name> dirs — accept either, generate the latter when absent
    if not (os.path.exists(os.path.join(data_dir, "lineitem"))
            or os.path.exists(os.path.join(data_dir, "lineitem.parquet"))):
        from benchmarks.datagen import generate_to_dir

        os.makedirs(data_dir, exist_ok=True)
        generate_to_dir(scale, data_dir, files_per_table=2)
    return data_dir


def _quantile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


#: fleet-mode timings for benchmark legs: a killed shard's jobs must be
#: adopted within ~2 s so a failover leg resolves inside the measured wall;
#: the short RPC retry deadline is what bounds reporter/client failover —
#: with the defaults one dead-shard round burns ~30 s before rerouting
_FLEET_TIMINGS = {
    "ballista.fleet.lease.ttl.seconds": "1.5",
    "ballista.fleet.lease.renew.seconds": "0.4",
    "ballista.fleet.adopt.interval.seconds": "0.4",
    "ballista.fleet.registry.stale.seconds": "5.0",
    "ballista.rpc.connect.timeout.seconds": "1.0",
    "ballista.rpc.read.timeout.seconds": "10.0",
    "ballista.rpc.retry.base.seconds": "0.05",
    "ballista.rpc.retry.cap.seconds": "0.2",
    "ballista.rpc.retry.deadline.seconds": "1.5",
}


def _run_leg(label: str, data_dir: str, sessions: int,
             queries_per_session: int, pool: List[str],
             overrides: Dict[str, str], executors: int = 2,
             concurrent_tasks: int = 4, shards: int = 1,
             kill_shard_after_s: Optional[float] = None) -> Dict:
    """One serving leg.  ``shards > 1`` runs a scheduler FLEET behind a
    shared KV (lease-owned jobs, shared slot accounting): sessions spread
    their sticky primaries round-robin and QPS aggregates the fleet.
    ``kill_shard_after_s`` arms the failover leg: shard 0 is crash-killed
    mid-leg and its sessions must fail over (lease adoption + client
    endpoint rotation) with zero errors."""
    from arrow_ballista_tpu.catalog import ParquetTable
    from arrow_ballista_tpu.client.context import BallistaContext
    from arrow_ballista_tpu.executor.server import ExecutorServer
    from arrow_ballista_tpu.scheduler.netservice import SchedulerNetService
    from arrow_ballista_tpu.utils.config import BallistaConfig
    from benchmarks.schema import TABLES

    conf = {"ballista.shuffle.partitions": "2", **overrides}
    fleet = shards > 1
    kv = None
    if fleet:
        from arrow_ballista_tpu.scheduler.kv import MemoryKv
        from arrow_ballista_tpu.scheduler.kv_remote import KvServer

        conf.update(_FLEET_TIMINGS)
        kv = KvServer(MemoryKv(), "127.0.0.1", 0)
        kv.start()
    tmp = tempfile.mkdtemp(prefix=f"serving-{label}-")
    svcs = []
    for _ in range(shards):
        svc = SchedulerNetService(
            "127.0.0.1", 0, config=BallistaConfig(dict(conf)),
            cluster_url=f"kv://{kv.host}:{kv.port}" if fleet else None)
        svc.start()
        svcs.append(svc)
    eps = [("127.0.0.1", s.port) for s in svcs]

    # raw queue-to-launch samples across every shard: shadow
    # record_submitted on each metrics instance (queued_at -> graph
    # submitted, ms); appends are atomic
    q2l_ms: List[float] = []
    for s in svcs:
        _orig_submitted = s.server.metrics.record_submitted

        def _rec_submitted(job_id, queued_at_ms, submitted_at_ms,
                           _orig=_orig_submitted):
            q2l_ms.append(max(0.0, submitted_at_ms - queued_at_ms))
            _orig(job_id, queued_at_ms, submitted_at_ms)

        s.server.metrics.record_submitted = _rec_submitted

    exs = []
    result: Dict = {"label": label, "sessions": sessions,
                    "queries_per_session": queries_per_session,
                    "shards": shards}
    try:
        for i in range(executors):
            work = os.path.join(tmp, f"exec{i}")
            os.makedirs(work)
            ex = ExecutorServer("127.0.0.1", eps[i % shards][1],
                                "127.0.0.1", 0,
                                work_dir=work,
                                concurrent_tasks=concurrent_tasks,
                                executor_id=f"serving-{label}-{i}",
                                config=BallistaConfig(dict(conf)),
                                scheduler_endpoints=eps if fleet else None)
            ex.start()
            exs.append(ex)

        # shared catalog: register once PER SHARD, sessions resolve the
        # same providers (and therefore share plan templates on the on-leg)
        for svc in svcs:
            for name in TABLES:
                path = os.path.join(data_dir, f"{name}.parquet")
                if not os.path.exists(path):
                    path = os.path.join(data_dir, name)
                svc.catalog.register(ParquetTable(name, path))

        # warmup: every distinct query once per shard (XLA compiles, scan
        # caches; on the on-leg this also seeds each shard's plan/result
        # caches — the timed phase measures the steady serving state)
        for svc in svcs:
            warm = BallistaContext.remote("127.0.0.1", svc.port,
                                          BallistaConfig(dict(conf)))
            try:
                for sql in pool:
                    warm.sql(sql).collect()
            finally:
                warm.shutdown()

        # fleet: session i's endpoint list starts at shard i%N — sticky
        # primaries spread round-robin, failover order wraps the ring
        if fleet:
            ctxs = [BallistaContext.remote(
                        config=BallistaConfig(dict(conf)),
                        endpoints=eps[i % shards:] + eps[:i % shards])
                    for i in range(sessions)]
        else:
            ctxs = [BallistaContext.remote("127.0.0.1", svcs[0].port,
                                           BallistaConfig(dict(conf)))
                    for _ in range(sessions)]
        e2e_ms: List[float] = []
        errors: List[str] = []
        lock = threading.Lock()
        q2l_before = len(q2l_ms)
        start_gate = threading.Event()

        def session_worker(si: int, ctx) -> None:
            start_gate.wait()
            for k in range(queries_per_session):
                if k % 4 == 3:
                    # fresh literal: normalizes to the same template (plan
                    # cache hit) but is a new result key (result miss) —
                    # keeps planned submissions, and therefore
                    # queue-to-launch samples, on BOTH legs
                    sql = _Q6.format(lo=0.01, hi=0.09,
                                     q=40 + (si * queries_per_session + k)
                                     % 50)
                else:
                    sql = pool[(si + k) % len(pool)]
                t0 = time.perf_counter()
                try:
                    ctx.sql(sql).collect()
                    dt = (time.perf_counter() - t0) * 1000
                    with lock:
                        e2e_ms.append(dt)
                except Exception as e:  # noqa: BLE001 — counted + reported
                    with lock:
                        errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=session_worker, args=(i, c),
                                    name=f"serving-sess-{i}", daemon=True)
                   for i, c in enumerate(ctxs)]
        for t in threads:
            t.start()
        t_wall = time.perf_counter()
        start_gate.set()
        if kill_shard_after_s is not None and fleet:
            # crash-kill shard 0 mid-leg: no lease release, no registry
            # withdrawal, established conns severed — its sessions must
            # complete via lease adoption + client endpoint rotation
            def _kill_shard():
                time.sleep(kill_shard_after_s)
                svcs[0].kill()

            threading.Thread(target=_kill_shard,
                             name="serving-shard-killer",
                             daemon=True).start()
            result["killed_shard"] = 0
            result["kill_after_s"] = kill_shard_after_s
        for t in threads:
            t.join()
        wall = time.perf_counter() - t_wall
        for c in ctxs:
            c.shutdown()

        total = sessions * queries_per_session
        e2e = sorted(e2e_ms)
        q2l = sorted(q2l_ms[q2l_before:])
        loop_lag = 0.0
        pc = {"hits": 0, "misses": 0}
        rc = {"hits": 0, "subplan_hits": 0, "misses": 0, "entries": 0}
        for s in svcs:
            try:
                stats = s.server._event_loop.stats()
                p = s.server.plan_cache.snapshot()
                r = s.server.result_cache.snapshot()
            except Exception:  # noqa: BLE001 — killed shard: best-effort
                continue
            loop_lag = max(loop_lag, stats.get("max_lag_s", 0.0))
            pc["hits"] += p["hits"]
            pc["misses"] += p["misses"]
            for k in rc:
                rc[k] += r[k]
        result.update({
            "queries": total,
            "ok": len(e2e_ms),
            "errors": len(errors),
            "error_sample": errors[:3],
            "wall_s": round(wall, 3),
            "qps": round(len(e2e_ms) / wall, 1) if wall > 0 else 0.0,
            "e2e_p50_ms": round(_quantile(e2e, 0.50), 2),
            "e2e_p99_ms": round(_quantile(e2e, 0.99), 2),
            "queue_to_launch_p50_ms": round(_quantile(q2l, 0.50), 2),
            "queue_to_launch_p99_ms": round(_quantile(q2l, 0.99), 2),
            "planned_submissions": len(q2l),
            "event_loop_max_lag_s": loop_lag,
            "plan_cache": {"hits": pc["hits"], "misses": pc["misses"],
                           "hit_rate": round(
                               pc["hits"] / max(1, pc["hits"] + pc["misses"]),
                               3)},
            "result_cache": {"hits": rc["hits"],
                             "subplan_hits": rc["subplan_hits"],
                             "misses": rc["misses"],
                             "entries": rc["entries"]},
        })
        return result
    finally:
        for ex in exs:
            ex.stop(notify=False)
        for s in svcs:
            try:
                s.stop()
            except Exception:  # noqa: BLE001 — failover leg's killed shard
                pass
        if kv is not None:
            kv.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def run_serving_benchmark(data_dir: Optional[str] = None, scale: float = 0.01,
                          sessions: int = 64, queries_per_session: int = 8,
                          shapes: Tuple[str, ...] = ("q6", "q1"),
                          executors: int = 2, concurrent_tasks: int = 4
                          ) -> Dict:
    """Both legs, off first (any residual process-level warmth — XLA
    caches, page cache — then favors the BASELINE, never the caches)."""
    data_dir = ensure_data(scale, data_dir)
    pool = build_workload(shapes)
    off = _run_leg(
        "caches-off", data_dir, sessions, queries_per_session, pool,
        {"ballista.plan.cache.enabled": "false",
         "ballista.result.cache.enabled": "false"},
        executors=executors, concurrent_tasks=concurrent_tasks)
    on = _run_leg(
        "caches-on", data_dir, sessions, queries_per_session, pool,
        {"ballista.plan.cache.enabled": "true",
         "ballista.result.cache.enabled": "true"},
        executors=executors, concurrent_tasks=concurrent_tasks)
    out = {"scale": scale, "sessions": sessions,
           "queries_per_session": queries_per_session,
           "distinct_queries": len(pool), "on": on, "off": off}
    if off.get("qps"):
        out["qps_on_over_off"] = round(on["qps"] / off["qps"], 2)
    return out


def run_fleet_benchmark(data_dir: Optional[str] = None, scale: float = 0.01,
                        sessions: int = 32, queries_per_session: int = 8,
                        shapes: Tuple[str, ...] = ("q6", "q1"),
                        shards: int = 2, executors: int = 2,
                        concurrent_tasks: int = 4) -> Dict:
    """Fleet A/B + failover: the same workload against one shard, then an
    N-shard fleet behind a shared KV (aggregate QPS must hold the
    single-shard line), then the fleet again with shard 0 crash-killed
    mid-leg — every in-flight session must complete with zero errors via
    lease adoption + client endpoint rotation.  The failover leg runs with
    the result cache OFF so every query is a real job and the kill lands
    on in-flight work, not on cache hits."""
    data_dir = ensure_data(scale, data_dir)
    pool = build_workload(shapes)
    caches_on = {"ballista.plan.cache.enabled": "true",
                 "ballista.result.cache.enabled": "true"}
    single = _run_leg(
        "fleet-single", data_dir, sessions, queries_per_session, pool,
        dict(caches_on), executors=executors,
        concurrent_tasks=concurrent_tasks)
    fleet = _run_leg(
        f"fleet-{shards}shard", data_dir, sessions, queries_per_session,
        pool, dict(caches_on), executors=executors,
        concurrent_tasks=concurrent_tasks, shards=shards)
    failover = _run_leg(
        f"fleet-{shards}shard-failover", data_dir, sessions,
        queries_per_session, pool,
        {"ballista.plan.cache.enabled": "true",
         "ballista.result.cache.enabled": "false"},
        executors=executors, concurrent_tasks=concurrent_tasks,
        shards=shards, kill_shard_after_s=0.5)
    out = {"scale": scale, "sessions": sessions,
           "queries_per_session": queries_per_session, "shards": shards,
           "single": single, "fleet": fleet, "failover": failover}
    if single.get("qps"):
        out["qps_fleet_over_single"] = round(fleet["qps"] / single["qps"], 2)
    out["fleet_pass"] = (fleet["errors"] == 0
                         and fleet["ok"] == fleet["queries"]
                         and failover["errors"] == 0
                         and failover["ok"] == failover["queries"]
                         and fleet["qps"] >= single["qps"])
    return out


def run_smoke(sessions: int = 8, queries_per_session: int = 6,
              shards: int = 1) -> Dict:
    """The run_checks.sh gate: N sessions of repeated q6 variants with the
    caches on; zero errors and a nonzero plan-cache hit rate required.
    With ``shards > 1`` the leg runs against a shared-KV scheduler fleet
    and a second failover leg crash-kills shard 0 mid-run — both legs must
    complete every query with zero errors."""
    data_dir = ensure_data(0.01)
    pool = build_workload(("q6",))
    caches_on = {"ballista.plan.cache.enabled": "true",
                 "ballista.result.cache.enabled": "true"}
    if shards > 1:
        fleet = _run_leg(
            "smoke-fleet", data_dir, sessions, queries_per_session, pool,
            dict(caches_on), executors=2, concurrent_tasks=4, shards=shards)
        failover = _run_leg(
            "smoke-failover", data_dir, sessions, queries_per_session, pool,
            {"ballista.plan.cache.enabled": "true",
             "ballista.result.cache.enabled": "false"},
            executors=2, concurrent_tasks=4, shards=shards,
            kill_shard_after_s=0.4)
        ok = (fleet["errors"] == 0 and fleet["ok"] == fleet["queries"]
              and fleet["plan_cache"]["hits"] > 0
              and failover["errors"] == 0
              and failover["ok"] == failover["queries"])
        return {"shards": shards, "fleet": fleet, "failover": failover,
                "smoke_pass": ok}
    leg = _run_leg(
        "smoke", data_dir, sessions, queries_per_session, pool,
        dict(caches_on), executors=1, concurrent_tasks=4)
    ok = (leg["errors"] == 0 and leg["ok"] == leg["queries"]
          and leg["plan_cache"]["hits"] > 0)
    leg["smoke_pass"] = ok
    return leg


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sessions", type=int, default=None,
                    help="concurrent client sessions (default 64; smoke 8)")
    ap.add_argument("--queries", type=int, default=None,
                    help="queries per session (default 8; smoke 6)")
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--data", default=None, help="TPC-H data dir "
                    "(default .bench_data/tpch-sf<scale>, generated)")
    ap.add_argument("--executors", type=int, default=2)
    ap.add_argument("--shards", type=int, default=1,
                    help="scheduler fleet size; >1 switches to the fleet "
                    "benchmark (single vs N-shard aggregate QPS) plus a "
                    "mid-leg shard-kill failover leg")
    ap.add_argument("--smoke", action="store_true",
                    help="run_checks gate: q6-only, assert zero errors + "
                    "plan-cache hits, exit 1 on failure; with --shards 2 "
                    "also runs the fleet + failover smoke legs")
    args = ap.parse_args()

    # BALLISTA_LOCK_ORDER_RUNTIME=1: record every package lock acquisition
    # during the run and assert consistency with the static concurrency
    # model afterwards (analysis/lock_order.py).  Installed before the
    # cluster is built so scheduler/executor locks get recording proxies.
    from arrow_ballista_tpu.analysis import lock_order

    lock_order_on = lock_order.enabled()
    if lock_order_on:
        lock_order.install()

    def _validate_lock_order() -> None:
        if not lock_order_on:
            return
        rep = lock_order.validate()
        print(rep.details(), file=sys.stderr)
        if not rep.ok:
            print("lock-order runtime validation FAILED", file=sys.stderr)
            sys.exit(2)

    if args.smoke:
        leg = run_smoke(sessions=args.sessions or 8,
                        queries_per_session=args.queries or 6,
                        shards=args.shards)
        print(json.dumps(leg, indent=2))
        if not leg["smoke_pass"]:
            print("serving smoke FAILED", file=sys.stderr)
            sys.exit(1)
        _validate_lock_order()
        print("serving smoke passed", file=sys.stderr)
        return

    if args.shards > 1:
        out = run_fleet_benchmark(
            data_dir=args.data, scale=args.scale,
            sessions=args.sessions or 32,
            queries_per_session=args.queries or 8,
            shards=args.shards, executors=args.executors)
    else:
        out = run_serving_benchmark(
            data_dir=args.data, scale=args.scale,
            sessions=args.sessions or 64,
            queries_per_session=args.queries or 8,
            executors=args.executors)
    print(json.dumps(out, indent=2))
    _validate_lock_order()


if __name__ == "__main__":
    main()
