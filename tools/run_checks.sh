#!/usr/bin/env bash
# CI entrypoint for the repository's consistency checks:
#   1. the static-analysis lint suite (AST rules + metrics-docs),
#   2. generated-docs freshness (docs/user-guide/configs.md),
#   3. the static-analysis + concurrency + wire-serde + speculation +
#      observability + adaptive-execution + doctor test files (rule
#      fixtures, plan-validator cases, seeded-interleaving stress +
#      lock-order shim units, exhaustive wire round-trips, speculation
#      policy math and attempt-dedup races, runtime-stats folding /
#      EXPLAIN ANALYZE / cluster history, device observatory: jit
#      compile/retrace accounting, transfer bytes, watermarks, fusion
#      advisor, AQE rewrites + rollback + serde, flight-recorder journal
#      + forensics bundles + seeded-pathology diagnosis, whole-stage
#      compiler: chain detection, allowlist verdicts, fused-vs-interpreted
#      equality, fusion serde + rollback/speculation/chaos interplay,
#      live observability: watch-stream ordering/gap semantics, the
#      progress/ETA estimator, in-flight doctor alerts, SLO burn rates,
#      query-lifecycle guardrails: server-side deadlines, cooperative
#      cancel tokens + the public cancel surface, poison-query
#      containment with quarantine refund, retry anti-affinity,
#      zombie-task reconciliation, the janitor live-job guard),
#   4. the chaos recovery suite (deterministic fault injection: seeded
#      failpoint plans, kill/fetch-failure/drop/restart scenarios,
#      quarantine, straggler speculation, corrupt-shuffle checksums,
#      lifecycle guardrails under chaos: deadline expiry mid-stage,
#      lost cancel fanout reaped by heartbeat, poison containment) plus
#      the scheduler-fleet HA suite (tests/test_fleet.py: shard killed
#      mid-job and adopted by a sibling, lease fencing under partition,
#      adoption/completion races, real-process SIGKILL failover) —
#      proves the fault-tolerance paths still recover.  Runs with the
#      runtime lock-order validator on (BALLISTA_LOCK_ORDER_RUNTIME=1):
#      every real lock acquisition is checked against the static
#      concurrency model, and any inversion or unpredicted nesting fails
#      the leg,
#   5. the memory-governor oracle sweep (tools/memory_sweep.py): the
#      TPC-H suite twice — unlimited memory vs a budget tiny enough that
#      the governor denies every join-build and aggregation-state
#      reservation — every query bit-identical between the legs, spills
#      proven to have happened, zero reservation leaks,
#   5b. the query-lifecycle sweep (tools/lifecycle_sweep.py): the TPC-H
#      suite with a generous server-side deadline vs none — bit-identical
#      and the deadline reaper never fires — then 100 mixed
#      cancel/deadline-expiry/poison cycles against one standalone
#      context with a residual audit at the end: zero in-flight tasks,
#      cancel tokens, slot reservations, pending tasks, active graphs,
#      or admission permits, and no executor quarantined by poison,
#   6. the doctor smoke: one standalone query with the flight recorder
#      on — the forensics bundle must validate against the
#      ballista.forensics/v1 schema, carry a complete journal timeline,
#      and the query doctor must return zero findings on the healthy
#      run,
#   7. the live-obs smoke: one standalone query with the live plane on,
#      then watched via ctx.watch() — at least one progress frame with a
#      monotonically non-decreasing fraction, a terminal frame, and zero
#      journal drops,
#   8. the serving smoke (benchmarks/serving.py --smoke): 8 concurrent
#      sessions of repeated q6 variants through the prepared-plan +
#      result caches — zero errors and a nonzero plan-cache hit rate,
#      also under the runtime lock-order validator,
#   9. the fleet serving smoke (--smoke --shards 2): the same workload
#      against a 2-shard scheduler fleet behind a shared KV, then a
#      failover leg that crash-kills shard 0 mid-run — both legs must
#      complete every query with zero errors,
# tests/test_static_analysis.py also runs the lint suite inside tier-1, so
# pytest alone still gates new violations; this script is the fast
# standalone form for CI and pre-push hooks.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "== static analysis (lint suite) =="
python -m arrow_ballista_tpu.analysis
# SARIF artifact for CI inline annotation (same findings, machine form;
# the gating text run above already decided the exit status)
python -m arrow_ballista_tpu.analysis --sarif > analysis.sarif || true

echo "== generated docs up to date =="
python docs/gen_configs.py --check

echo "== analysis + concurrency + serde + speculation + observability + aqe + compile + live-obs + lifecycle test files =="
python -m pytest tests/test_static_analysis.py tests/test_concurrency.py \
    tests/test_serde_wire.py tests/test_speculation.py \
    tests/test_observatory.py tests/test_device_obs.py tests/test_aqe.py \
    tests/test_doctor.py tests/test_compile.py tests/test_live_obs.py \
    tests/test_lifecycle.py tests/test_cancellation.py \
    -q -p no:cacheprovider -m 'not chaos'

echo "== chaos recovery + fleet HA suites (-m chaos, runtime lock-order validation on) =="
BALLISTA_LOCK_ORDER_RUNTIME=1 \
    python -m pytest tests/test_chaos.py tests/test_fleet.py \
    tests/test_doctor.py tests/test_compile.py tests/test_live_obs.py \
    -q -m chaos -p no:cacheprovider

echo "== memory-governor oracle sweep (tiny budget: every join/agg spills, bit-identical) =="
python -m tools.memory_sweep

echo "== query-lifecycle sweep (deadline oracle bit-identical + 100-cycle leak audit) =="
python -m tools.lifecycle_sweep

echo "== doctor smoke (flight recorder on: bundle validates, clean run diagnoses clean) =="
python - <<'EOF'
import json

import numpy as np
import pyarrow as pa

from arrow_ballista_tpu.client.context import BallistaContext
from arrow_ballista_tpu.obs import journal
from arrow_ballista_tpu.obs.doctor import diagnose, validate_bundle
from arrow_ballista_tpu.utils.config import BallistaConfig

ctx = BallistaContext.standalone(
    BallistaConfig({"ballista.journal.enabled": "true",
                    "ballista.shuffle.partitions": "4"}),
    concurrent_tasks=2, num_executors=2)
try:
    rng = np.random.default_rng(7)
    ctx.register_table("t", pa.table({
        "g": pa.array(rng.integers(0, 7, 4000), type=pa.int64()),
        "v": pa.array(rng.integers(0, 100, 4000), type=pa.int64())}))
    ctx.sql("select g, sum(v) as s from t group by g order by g").collect()
    bundle = ctx.forensics()
    problems = validate_bundle(bundle)
    assert not problems, f"forensics bundle invalid: {problems}"
    kinds = [e["kind"] for e in bundle["journal"]]
    assert "job.submitted" in kinds and "job.successful" in kinds, kinds
    json.dumps(bundle)  # the bundle is a self-contained JSON artifact
    diag = diagnose(bundle)
    assert not diag["findings"], \
        f"doctor found pathologies on a clean run: {diag['text']}"
    emitted, dropped = journal.counters()
    assert emitted > 0 and dropped == 0, (emitted, dropped)
    print(f"doctor smoke ok: {len(bundle['journal'])} journal events, "
          f"{len(diag['rules_evaluated'])} rules evaluated clean")
finally:
    ctx.shutdown()
EOF

echo "== live-obs smoke (watch a real query: progress frames, terminal frame, zero drops) =="
python - <<'EOF'
import numpy as np
import pyarrow as pa

from arrow_ballista_tpu.client.context import BallistaContext
from arrow_ballista_tpu.obs import journal
from arrow_ballista_tpu.utils.config import BallistaConfig

ctx = BallistaContext.standalone(
    BallistaConfig({"ballista.journal.enabled": "true",
                    "ballista.live.enabled": "true",
                    "ballista.live.doctor.interval.seconds": "0.5",
                    "ballista.shuffle.partitions": "4"}),
    concurrent_tasks=2, num_executors=2)
try:
    rng = np.random.default_rng(17)
    ctx.register_table("t", pa.table({
        "g": pa.array(rng.integers(0, 7, 4000), type=pa.int64()),
        "v": pa.array(rng.integers(0, 100, 4000), type=pa.int64())}))
    ctx.sql("select g, sum(v) as s from t group by g order by g").collect()
    frames = list(ctx.watch())
    kinds = [f["t"] for f in frames]
    assert kinds.count("progress") >= 1, kinds
    assert kinds[-1] == "end" and frames[-1]["state"] == "successful", \
        frames[-1]
    fracs = [f["progress"]["fraction"] for f in frames
             if f["t"] == "progress"]
    assert all(a <= b for a, b in zip(fracs, fracs[1:])), fracs
    emitted, dropped = journal.counters()
    assert emitted > 0 and dropped == 0, (emitted, dropped)
    assert journal.watcher_count() == 0  # the stream detached cleanly
    print(f"live-obs smoke ok: {kinds.count('event')} event frames, "
          f"{kinds.count('progress')} progress frames, final fraction "
          f"{fracs[-1] if fracs else 'n/a'}, 0 journal drops")
finally:
    ctx.shutdown()
EOF

echo "== serving smoke (8 sessions x q6, caches on, runtime lock-order validation on) =="
BALLISTA_LOCK_ORDER_RUNTIME=1 python -m benchmarks.serving --smoke

echo "== fleet serving smoke (2 shards + mid-run shard-kill failover) =="
BALLISTA_LOCK_ORDER_RUNTIME=1 python -m benchmarks.serving --smoke --shards 2

echo "all checks passed"
