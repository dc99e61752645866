"""Session configuration: typed, validated key-value settings.

Parity with the reference's ``BallistaConfig``
(reference ballista/core/src/config.rs:30-192): same shape (string KV with
typed validation + defaults, propagated client -> scheduler -> tasks), with
TPU-specific knobs added (batch capacity, static agg/join capacities, mesh
axis sizes) since static shapes are the engine's core discipline.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

from .errors import ConfigurationError

# canonical keys (reference core/config.rs:30-39 defines the first five)
SHUFFLE_PARTITIONS = "ballista.shuffle.partitions"
BATCH_SIZE = "ballista.batch.size"
JOB_NAME = "ballista.job.name"
REPARTITION_JOINS = "ballista.repartition.joins"
REPARTITION_AGGREGATIONS = "ballista.repartition.aggregations"
PARQUET_PRUNING = "ballista.parquet.pruning"
# TPU-native knobs
JOIN_OUTPUT_FACTOR = "ballista.join.output_factor"  # mesh joins: first bound on a device's candidate pairs
JOIN_MAX_CAPACITY = "ballista.join.max_capacity"  # ceiling for adaptive retry
COLLECT_STATISTICS = "ballista.collect_statistics"
MESH_SHUFFLE = "ballista.shuffle.mesh"  # use ICI all-to-all when executors co-located on a mesh
MESH_HYBRID = "ballista.shuffle.mesh.hybrid"  # mesh WITHIN a host, file shuffle ACROSS hosts
MESH_BROADCAST_ROWS = "ballista.shuffle.mesh.broadcast_rows"  # build side <= this -> all_gather broadcast join
MESH_MIN_ROWS = "ballista.shuffle.mesh.min_rows"  # adaptive: fuse on mesh only when the exchange's estimated rows >= this
TASK_SLOTS = "ballista.executor.task_slots"
BROADCAST_THRESHOLD = "ballista.join.broadcast_threshold"  # rows; build sides smaller skip the shuffle
JOB_TIMEOUT_S = "ballista.job.timeout.seconds"  # client-side wait_for_job deadline
SCAN_CACHE_BYTES = "ballista.scan.cache.bytes"  # HBM-resident scan cache budget ('auto' | bytes | 0=off)
MEM_TASK_BUDGET = "ballista.memory.task.budget.bytes"  # per-task device working-set bound ('auto' | bytes | 0=unlimited)
# memory governor (arrow_ballista_tpu/memory/): reserve->grant->release
# accounting over a host-RSS pool and a device-HBM pool; operators that
# hold unbounded state reserve before materializing and spill on denial
MEM_HOST_BUDGET = "ballista.memory.host.budget.bytes"
MEM_DEVICE_BUDGET = "ballista.memory.device.budget.bytes"
MEM_SPILL_ENABLED = "ballista.memory.spill.enabled"
MEM_PRESSURE_SHED = "ballista.memory.pressure.shed.threshold"
# admission control / multi-tenancy (arrow_ballista_tpu/admission/) — all
# default to 0/"" = pass-through, the subsystem activates only when set
ADMISSION_TENANT = "ballista.admission.tenant"
ADMISSION_PRIORITY = "ballista.admission.priority"
ADMISSION_MAX_CONCURRENT_JOBS = "ballista.admission.max_concurrent_jobs"
ADMISSION_MAX_QUEUED_JOBS = "ballista.admission.max_queued_jobs"
ADMISSION_QUEUE_TIMEOUT_S = "ballista.admission.queue.timeout.seconds"
ADMISSION_MAX_PENDING_TASKS = "ballista.admission.max_pending_tasks"
ADMISSION_SLOT_SHARE = "ballista.admission.tenant.slot_share"
ADMISSION_RETRY_AFTER_S = "ballista.admission.retry_after.seconds"
# observability / tracing (arrow_ballista_tpu/obs/)
OBS_TRACING = "ballista.observability.tracing"
OBS_PROFILE_RETENTION = "ballista.observability.profile.retention"
OBS_COLLECTOR = "ballista.observability.collector"
OBS_OTLP_ENDPOINT = "ballista.observability.otlp.endpoint"
# device-level observatory (arrow_ballista_tpu/obs/device.py)
OBS_DEVICE_ENABLED = "ballista.observability.device.enabled"
OBS_DEVICE_WATERMARKS = "ballista.observability.device.watermarks"
OBS_DEVICE_ADVISOR_MIN_SAVINGS_MS = \
    "ballista.observability.device.advisor.min_savings_ms"
# flight recorder (arrow_ballista_tpu/obs/journal.py): causal event journal
JOURNAL_ENABLED = "ballista.journal.enabled"
JOURNAL_CAPACITY = "ballista.journal.capacity"
JOURNAL_SPILL_PATH = "ballista.journal.spill_path"
# structured logging (utils/logsetup.py): 'text' (default) or 'json'
LOG_FORMAT = "ballista.log.format"
# static analysis (arrow_ballista_tpu/analysis/)
ANALYSIS_PLAN_CHECKS = "ballista.analysis.plan_checks"
ANALYSIS_LOCK_ORDER_RUNTIME = "ballista.analysis.lock_order.runtime"
# RPC hardening (net/retry.py): client-side deadlines + bounded backoff
RPC_CONNECT_TIMEOUT_S = "ballista.rpc.connect.timeout.seconds"
RPC_READ_TIMEOUT_S = "ballista.rpc.read.timeout.seconds"
RPC_RETRY_BASE_S = "ballista.rpc.retry.base.seconds"
RPC_RETRY_CAP_S = "ballista.rpc.retry.cap.seconds"
RPC_RETRY_DEADLINE_S = "ballista.rpc.retry.deadline.seconds"
# cluster membership (scheduler/cluster.py): one timeout, documented grace
CLUSTER_EXECUTOR_TIMEOUT_S = "ballista.cluster.executor_timeout_s"
# executor quarantine (scheduler/quarantine.py)
QUARANTINE_FAILURES = "ballista.scheduler.quarantine.failures"
QUARANTINE_PROBATION_S = "ballista.scheduler.quarantine.probation.seconds"
# deterministic fault injection (arrow_ballista_tpu/faults/)
FAULTS_PLAN = "ballista.faults.plan"
# speculative execution (scheduler/speculation.py + execution_graph.py)
SPECULATION_ENABLED = "ballista.speculation.enabled"
SPECULATION_QUANTILE = "ballista.speculation.quantile"
SPECULATION_MULTIPLIER = "ballista.speculation.multiplier"
SPECULATION_MIN_RUNTIME_S = "ballista.speculation.min_runtime.seconds"
SPECULATION_MAX_CONCURRENT = "ballista.speculation.max_concurrent"
SPECULATION_INTERVAL_S = "ballista.speculation.interval.seconds"
# adaptive query execution (scheduler/aqe.py + execution_graph.py)
AQE_ENABLED = "ballista.aqe.enabled"
AQE_COALESCE_ENABLED = "ballista.aqe.coalesce.enabled"
AQE_COALESCE_TARGET_ROWS = "ballista.aqe.coalesce.target.rows"
AQE_COALESCE_TARGET_BYTES = "ballista.aqe.coalesce.target.bytes"
AQE_BROADCAST_ENABLED = "ballista.aqe.broadcast.enabled"
AQE_BROADCAST_THRESHOLD_ROWS = "ballista.aqe.broadcast.threshold.rows"
AQE_SKEW_ENABLED = "ballista.aqe.skew.enabled"
AQE_SKEW_FACTOR = "ballista.aqe.skew.factor"
AQE_SKEW_MIN_ROWS = "ballista.aqe.skew.min.rows"
# shuffle partition integrity (ops/shuffle.py + net/dataplane.py)
SHUFFLE_INTEGRITY = "ballista.shuffle.integrity.verify"
# shuffle transport (ops/shuffle.py + net/dataplane.py): local mmap fast
# path, streaming chunked remote fetch, and wire compression
SHUFFLE_LOCAL_HOST_MATCH = "ballista.shuffle.local.host_match"
SHUFFLE_MAX_CONCURRENT_FETCHES = "ballista.shuffle.max_concurrent_fetches"
SHUFFLE_WIRE_CHUNK_ROWS = "ballista.shuffle.wire.chunk_rows"
SHUFFLE_WIRE_COMPRESSION = "ballista.shuffle.wire.compression"
# runtime statistics observatory (obs/stats.py + scheduler sampler)
STATS_HISTORY_CAPACITY = "ballista.stats.history.capacity"
STATS_HISTORY_INTERVAL_S = "ballista.stats.history.interval.seconds"
# serving caches (scheduler/serving_cache.py): prepared-plan templates and
# completed results/subplans keyed on catalog + config versions
PLAN_CACHE_ENABLED = "ballista.plan.cache.enabled"
PLAN_CACHE_MAX_ENTRIES = "ballista.plan.cache.max.entries"
PLAN_CACHE_MAX_BYTES = "ballista.plan.cache.max.bytes"
RESULT_CACHE_ENABLED = "ballista.result.cache.enabled"
RESULT_CACHE_MAX_ENTRIES = "ballista.result.cache.max.entries"
RESULT_CACHE_MAX_BYTES = "ballista.result.cache.max.bytes"
RESULT_CACHE_MAX_ENTRY_BYTES = "ballista.result.cache.max.entry.bytes"
RESULT_CACHE_SUBPLAN = "ballista.result.cache.subplan.enabled"
# scheduler fleet HA (scheduler/kv.py + scheduler/scheduler.py): lease-based
# job ownership in the shared KV, adoption of dead shards' jobs, and the
# cross-shard registry behind client failover + /api/autoscale
FLEET_LEASE_TTL_S = "ballista.fleet.lease.ttl.seconds"
FLEET_LEASE_RENEW_S = "ballista.fleet.lease.renew.seconds"
FLEET_ADOPT_INTERVAL_S = "ballista.fleet.adopt.interval.seconds"
FLEET_REGISTRY_STALE_S = "ballista.fleet.registry.stale.seconds"
# whole-stage compiler (compile/): fuse allowlisted operator chains into
# one jitted program at stage-plan resolution time
COMPILE_ENABLED = "ballista.compile.enabled"
COMPILE_MIN_OPS = "ballista.compile.min.ops"
COMPILE_OPERATORS = "ballista.compile.operators"
COMPILE_DONATE = "ballista.compile.donate"
# live observability plane (obs/live.py + journal watch streams): in-flight
# doctor alerts on a scheduler cadence, watch-stream subscriber bounds
LIVE_ENABLED = "ballista.live.enabled"
LIVE_DOCTOR_INTERVAL_S = "ballista.live.doctor.interval.seconds"
LIVE_WATCH_QUEUE_EVENTS = "ballista.live.watch.queue.events"
LIVE_WATCH_POLL_S = "ballista.live.watch.poll.seconds"
# SLO tracker (obs/slo.py): declarative latency objective over completed
# jobs, multi-window burn rates behind /api/slo and the autoscale signal
SLO_P99_TARGET_MS = "ballista.slo.latency.p99.target.ms"
SLO_WINDOW_S = "ballista.slo.window.seconds"
# query lifecycle guardrails: server-side deadline enforcement and
# poison-query containment (scheduler/scheduler.py)
QUERY_DEADLINE_S = "ballista.query.deadline.seconds"
POISON_DISTINCT_EXECUTORS = "ballista.poison.distinct_executors"


@dataclasses.dataclass
class ConfigEntry:
    key: str
    default: Any
    parse: Callable[[str], Any]
    doc: str = ""


def env_flag(name: str) -> bool:
    """Shared truthiness rule for boolean env overrides
    (BALLISTA_REMOTE_DEVICE, BALLISTA_FORCE_HASH_COLLISIONS, ...):
    unset/''/'0'/'false'/'no' are False, anything else True.
    Returns None when the variable is unset/blank so callers can
    distinguish 'explicitly 0' from 'not set'."""
    import os

    v = os.environ.get(name)
    if v is None or v.strip() == "":
        return None
    return v.strip().lower() not in ("0", "false", "no")


def _parse_bool(s: str) -> bool:
    if str(s).lower() in ("true", "1", "yes"):
        return True
    if str(s).lower() in ("false", "0", "no"):
        return False
    raise ValueError(f"not a bool: {s!r}")


def _parse_partitions(s) -> int:
    """Shuffle partition count; 0 means 'auto' (derived at plan time from
    input row counts — the memory-control heuristic the reference leaves as
    a TODO grid, SURVEY §7 hard-parts)."""
    if str(s).lower() == "auto":
        return 0
    n = int(s)
    if n < 0:
        raise ValueError(f"partition count must be >= 0: {s!r}")
    return n


_ENTRIES: Dict[str, ConfigEntry] = {
    e.key: e
    for e in [
        ConfigEntry(SHUFFLE_PARTITIONS, 16, _parse_partitions,
                    "number of output partitions for shuffles, or 'auto' to "
                    "derive from input row counts at plan time"),
        ConfigEntry(BATCH_SIZE, 1 << 17, int, "static row capacity of a device ColumnBatch"),
        ConfigEntry(JOB_NAME, "", str, "human-readable job name"),
        ConfigEntry(REPARTITION_JOINS, True, _parse_bool,
                    "reference-parity placeholder (config.rs:34): the "
                    "distributed planner always repartitions joins; "
                    "accepted and propagated but not yet consulted"),
        ConfigEntry(REPARTITION_AGGREGATIONS, True, _parse_bool,
                    "reference-parity placeholder (config.rs:35): the "
                    "distributed planner always repartitions aggregations; "
                    "accepted and propagated but not yet consulted"),
        ConfigEntry(PARQUET_PRUNING, True, _parse_bool, "row-group pruning on parquet scans"),
        ConfigEntry(JOIN_OUTPUT_FACTOR, 2, int,
                    "mesh inner and left joins: the candidate pairs a "
                    "device may expand start at factor * a device's even "
                    "share of the smaller side's live rows (of the probe "
                    "side's in a broadcast join; a semi or anti join "
                    "expands no pair and ignores it); each side's send "
                    "bucket holds twice "
                    "its even share of that side's live rows; a bound "
                    "passed is reported and the join runs once more at "
                    "what it needed (plain joins size outputs by a count "
                    "pass)"),
        ConfigEntry(JOIN_MAX_CAPACITY, 1 << 26, int,
                    "hard ceiling for adaptive join-capacity growth (rows)"),
        ConfigEntry(COLLECT_STATISTICS, True, _parse_bool,
                    "reference-parity placeholder (config.rs:38): scans "
                    "always collect the statistics pruning needs; accepted "
                    "and propagated but not yet consulted"),
        ConfigEntry(MESH_SHUFFLE, False, _parse_bool,
                    "use ICI mesh all-to-all shuffle; a mesh aggregate's "
                    "bounds come from its input, not from a key: a device "
                    "holds as many partial groups as its shard has rows, a "
                    "send bucket twice its even share of them (a fuller "
                    "one is detected and the program runs once more at "
                    "what it needed), and owns what the buckets can "
                    "deliver"),
        ConfigEntry(MESH_HYBRID, False, _parse_bool,
                    "hybrid exchange: mesh-fused partials per host (a "
                    "device's group slots are its shard's rows), file "
                    "shuffle across hosts"),
        ConfigEntry(MESH_BROADCAST_ROWS, 1 << 18, int,
                    "mesh joins all_gather the build side instead of "
                    "all_to_all-ing both sides when its live rows fit here "
                    "(CollectLeft analog)"),
        ConfigEntry(MESH_MIN_ROWS, 8_000_000, int,
                    "adaptive transport: mesh-fuse an exchange only when "
                    "its estimated input rows reach this (on four v5e "
                    "chips SF10 q1, 15.0M estimated rows, ran 0.33 s over "
                    "the mesh against 0.77 s on one chip; below the floor "
                    "the one chip reading is SF1 q3 forced onto the mesh, "
                    "14.9 s against 13.9 s over files; between them not "
                    "measured); 0 forces mesh for every eligible exchange"),
        ConfigEntry(TASK_SLOTS, 4, int, "concurrent task slots per executor"),
        ConfigEntry(BROADCAST_THRESHOLD, 4_000_000, int,
                    "broadcast join build sides with fewer estimated rows "
                    "(4M measured best at SF10: q3 -14%, q18 -9%, SF1 "
                    "neutral — a partitioned exchange of a 60M-row probe "
                    "costs far more than probing a few-M-row build)"),
        ConfigEntry(JOB_TIMEOUT_S, 3600, int,
                    "seconds a client waits for a submitted job before giving up"),
        ConfigEntry(SCAN_CACHE_BYTES, "auto", str,
                    "device-resident scan cache budget: 'auto' (6 GiB on "
                    "accelerator backends, 1 GiB on CPU), a byte count, or "
                    "0 to disable; see utils/table_cache.py"),
        ConfigEntry(MEM_TASK_BUDGET, "auto", str,
                    "memory control: per-task device working-set budget in "
                    "bytes; joins chunk their probe side and 'auto' shuffle "
                    "partition counts scale to keep task state under it.  "
                    "'auto' = 4 GiB on accelerator backends, unlimited on "
                    "CPU; 0 = unlimited"),
        ConfigEntry(MEM_HOST_BUDGET, "0", str,
                    "memory governor: host-RSS pool budget in bytes for "
                    "operator state (join build sides, aggregation "
                    "groups).  Reservations beyond the budget are denied "
                    "and the operator spills its state to disk as Arrow "
                    "IPC runs (bit-identical results).  'auto' = 16 GiB; "
                    "0 = unlimited (governor grants everything, spill "
                    "never triggers)"),
        ConfigEntry(MEM_DEVICE_BUDGET, "0", str,
                    "memory governor: device-HBM pool budget in bytes, "
                    "checked against the device-bytes watermark sampler "
                    "(obs/device.py).  'auto' = 12 GiB on accelerator "
                    "backends, unlimited on CPU; 0 = unlimited"),
        ConfigEntry(MEM_SPILL_ENABLED, True, _parse_bool,
                    "degrade to disk spill when the governor denies a "
                    "reservation (aggs: partial runs + sort-merge "
                    "finalize; joins: partitioned build rehydrate).  "
                    "False = a denial raises retryable MemoryExhausted "
                    "instead of spilling"),
        ConfigEntry(MEM_PRESSURE_SHED, 0.95, float,
                    "executor memory pressure (reserved/budget, max over "
                    "pools, reported via heartbeat) at or above which the "
                    "scheduler stops offering that executor tasks and "
                    "admission sheds new jobs with retriable "
                    "ResourceExhausted; >= 1.0 still degrades offer "
                    "ordering but never sheds"),
        ConfigEntry(ADMISSION_TENANT, "", str,
                    "tenant identity for admission control; empty = the "
                    "session id (each session is its own tenant)"),
        ConfigEntry(ADMISSION_PRIORITY, 0, int,
                    "admission queue priority (higher runs first; FIFO "
                    "within a priority)"),
        ConfigEntry(ADMISSION_MAX_CONCURRENT_JOBS, 0, int,
                    "max jobs a tenant may have running at once; excess "
                    "submissions wait in the admission queue (0 = "
                    "unlimited)"),
        ConfigEntry(ADMISSION_MAX_QUEUED_JOBS, 0, int,
                    "max jobs a tenant may have waiting for admission; "
                    "beyond this, submissions fail immediately with a "
                    "retriable 'queue full' status (0 = unlimited)"),
        ConfigEntry(ADMISSION_QUEUE_TIMEOUT_S, 0.0, float,
                    "seconds a job may wait for admission before failing "
                    "with a retriable 'queue timeout' status (0 = wait "
                    "forever)"),
        ConfigEntry(ADMISSION_MAX_PENDING_TASKS, 0, int,
                    "load shedding: hold new jobs in the admission queue "
                    "while the scheduler's pending task count is at or "
                    "above this (0 = never shed)"),
        ConfigEntry(ADMISSION_SLOT_SHARE, 0.0, float,
                    "fraction (0..1] of the cluster's registered task "
                    "slots this tenant's running jobs may occupy at once "
                    "(0 = unlimited)"),
        ConfigEntry(OBS_TRACING, True, _parse_bool,
                    "distributed tracing: span propagation client -> "
                    "scheduler -> executor -> operator, the per-job profile "
                    "ring buffer, and the /api/job/<id>/profile|trace "
                    "endpoints (False = spans off, endpoints return 404)"),
        ConfigEntry(OBS_PROFILE_RETENTION, 64, int,
                    "finished job profiles (and their span sets) the "
                    "scheduler retains in a ring buffer for "
                    "/api/job/<id>/profile and /trace"),
        ConfigEntry(OBS_COLLECTOR, "noop", str,
                    "span export collector: 'noop' (default), 'memory' "
                    "(bounded in-process buffer), or 'otlp' (best-effort "
                    "OTLP/HTTP JSON POST to "
                    "ballista.observability.otlp.endpoint)"),
        ConfigEntry(OBS_OTLP_ENDPOINT, "", str,
                    "OTLP/HTTP endpoint (e.g. "
                    "http://localhost:4318/v1/traces) used when the 'otlp' "
                    "collector is selected"),
        ConfigEntry(OBS_DEVICE_ENABLED, True, _parse_bool,
                    "device-level observatory (obs/device.py): JIT "
                    "compile/retrace/cache-hit accounting, host<->device "
                    "transfer bytes, and memory watermarks, attributed per "
                    "operator and shipped as TaskStatus.device_stats "
                    "(False = every probe is a single predicate check)"),
        ConfigEntry(OBS_DEVICE_WATERMARKS, True, _parse_bool,
                    "sample device bytes in use and host RSS peaks at "
                    "task/operator boundaries (requires "
                    "ballista.observability.device.enabled; False drops "
                    "only the watermark sampling, keeping compile/transfer "
                    "accounting)"),
        ConfigEntry(OBS_DEVICE_ADVISOR_MIN_SAVINGS_MS, 1.0, float,
                    "fusion advisor (obs/advisor.py): drop stage operator "
                    "chains whose estimated fusion savings fall below this "
                    "many milliseconds"),
        ConfigEntry(JOURNAL_ENABLED, False, _parse_bool,
                    "flight recorder (obs/journal.py): causally-ordered "
                    "journal of every consequential scheduler/executor "
                    "decision (job lifecycle, task attempts, AQE, "
                    "speculation, cache hits, lease/quarantine "
                    "transitions, failpoint firings), feeding "
                    "GET /api/job/<id>/forensics and the query doctor "
                    "(False = every probe is a single predicate check and "
                    "the wire format is byte-identical to journal-off)"),
        ConfigEntry(JOURNAL_CAPACITY, 4096, int,
                    "events retained in the process-global journal ring "
                    "and in each per-job timeline; older events are "
                    "evicted and counted in journal_events_dropped_total"),
        ConfigEntry(JOURNAL_SPILL_PATH, "", str,
                    "append every journal event as one JSON line to this "
                    "file (durable postmortems beyond the in-memory "
                    "ring); empty = no spill"),
        ConfigEntry(LOG_FORMAT, "text", str,
                    "log record format: 'text' (classic one-line) or "
                    "'json' (structured, one JSON object per line with "
                    "job_id/trace_id/span_id correlation fields stamped "
                    "from the ambient observability scope)"),
        ConfigEntry(ADMISSION_RETRY_AFTER_S, 5, int,
                    "retry-after hint (seconds) embedded in retriable "
                    "admission failures (queue full / queue timeout)"),
        ConfigEntry(ANALYSIS_PLAN_CHECKS, True, _parse_bool,
                    "pre-launch plan sanity validation: reject an "
                    "ExecutionGraph with shuffle partition/schema "
                    "mismatches or orphan/cyclic stage dependencies before "
                    "any task launches (see "
                    "docs/developer-guide/static-analysis.md)"),
        ConfigEntry(ANALYSIS_LOCK_ORDER_RUNTIME, False, _parse_bool,
                    "debug lock-instrumentation shim: record the runtime "
                    "lock-acquisition order of every package lock and "
                    "validate it against the static concurrency model "
                    "(analysis/concurrency.py). Zero-cost when off; also "
                    "enabled by BALLISTA_LOCK_ORDER_RUNTIME=1. Intended "
                    "for the chaos/serving CI legs, not production"),
        ConfigEntry(RPC_CONNECT_TIMEOUT_S, 5.0, float,
                    "TCP connect deadline for client-side control-plane "
                    "RPCs (net/retry.py)"),
        ConfigEntry(RPC_READ_TIMEOUT_S, 60.0, float,
                    "read deadline for client-side control-plane RPCs "
                    "(net/retry.py)"),
        ConfigEntry(RPC_RETRY_BASE_S, 0.2, float,
                    "base backoff between RPC retries; doubles per attempt "
                    "(jittered, capped at ballista.rpc.retry.cap.seconds)"),
        ConfigEntry(RPC_RETRY_CAP_S, 5.0, float,
                    "upper bound on a single RPC retry backoff"),
        ConfigEntry(RPC_RETRY_DEADLINE_S, 30.0, float,
                    "give-up deadline across all retries of one RPC; on "
                    "expiry a retryable failure surfaces (executor marks "
                    "the scheduler unreachable; a failed launch becomes "
                    "ExecutorLost)"),
        ConfigEntry(CLUSTER_EXECUTOR_TIMEOUT_S, 180.0, float,
                    "seconds without a heartbeat before an executor is "
                    "declared lost (reaper -> ExecutorLost).  Work offers "
                    "stop earlier, at timeout minus a drain grace of "
                    "min(60s, timeout/2), so a slow-heartbeat executor "
                    "drains instead of receiving doomed tasks"),
        ConfigEntry(QUARANTINE_FAILURES, 5, int,
                    "consecutive retryable task failures on one executor "
                    "before it is quarantined (no new offers); 0 disables "
                    "quarantine"),
        ConfigEntry(QUARANTINE_PROBATION_S, 60.0, float,
                    "seconds a quarantined executor sits out before "
                    "probation re-admits it; one failure on probation "
                    "re-quarantines, one success clears it"),
        ConfigEntry(FAULTS_PLAN, "", str,
                    "deterministic fault-injection plan: inline JSON or "
                    "'@/path/to/plan.json' (see arrow_ballista_tpu/faults/ "
                    "and docs/user-guide/fault-tolerance.md); empty = "
                    "disabled, all failpoint sites are no-ops"),
        ConfigEntry(SPECULATION_ENABLED, False, _parse_bool,
                    "speculative execution: launch a duplicate attempt of a "
                    "straggling task on a different executor; first "
                    "successful attempt wins, the loser is cancelled and "
                    "its outputs ignored (results are identical either "
                    "way).  False = one attempt at a time, today's "
                    "behavior"),
        ConfigEntry(SPECULATION_QUANTILE, 0.75, float,
                    "duration quantile (0..1] of a stage's *completed* "
                    "attempts used as the straggler baseline"),
        ConfigEntry(SPECULATION_MULTIPLIER, 1.5, float,
                    "a running task is speculatable once its age exceeds "
                    "multiplier x the baseline quantile duration"),
        ConfigEntry(SPECULATION_MIN_RUNTIME_S, 5.0, float,
                    "never speculate a task younger than this, regardless "
                    "of the quantile math (protects short stages from "
                    "duplicate launches)"),
        ConfigEntry(SPECULATION_MAX_CONCURRENT, 2, int,
                    "max concurrent speculative attempts per stage"),
        ConfigEntry(SPECULATION_INTERVAL_S, 1.0, float,
                    "seconds between speculation-monitor scans of running "
                    "tasks"),
        ConfigEntry(AQE_ENABLED, True, _parse_bool,
                    "adaptive query execution: re-optimize not-yet-resolved "
                    "downstream stages from the observed shuffle statistics "
                    "of completed producers (dynamic partition coalescing, "
                    "shuffle-join -> broadcast switch, skew splitting).  "
                    "False freezes the plan at submit time, today's "
                    "behavior; results are identical either way (see "
                    "docs/user-guide/aqe.md)"),
        ConfigEntry(AQE_COALESCE_ENABLED, True, _parse_bool,
                    "AQE rewrite 1: merge tiny reduce partitions of an "
                    "unresolved stage up to the coalesce targets so a "
                    "many-task stage over a few thousand rows launches a "
                    "handful of tasks instead"),
        ConfigEntry(AQE_COALESCE_TARGET_ROWS, 8192, int,
                    "coalesced-partition target size in observed rows; "
                    "adjacent partitions merge while the merged group stays "
                    "at or under this (0 disables the row target)"),
        ConfigEntry(AQE_COALESCE_TARGET_BYTES, 1 << 20, int,
                    "coalesced-partition target size in observed shuffle "
                    "bytes; a merged group must also stay at or under this "
                    "(0 disables the byte target)"),
        ConfigEntry(AQE_BROADCAST_ENABLED, True, _parse_bool,
                    "AQE rewrite 2: when a completed stage's actual shuffle "
                    "output is under the broadcast threshold, flip the "
                    "downstream partitioned join that consumes it to a "
                    "broadcast join and graft away the probe side's "
                    "now-unnecessary exchange where the plan allows"),
        ConfigEntry(AQE_BROADCAST_THRESHOLD_ROWS, 4_000_000, int,
                    "observed build-side rows at or under which the "
                    "broadcast switch fires (mirrors the planner's "
                    "estimate-based ballista.join.broadcast_threshold)"),
        ConfigEntry(AQE_SKEW_ENABLED, True, _parse_bool,
                    "AQE rewrite 3: split a hot reduce partition into "
                    "several tasks, each reading a sub-range of the "
                    "producer's map outputs"),
        ConfigEntry(AQE_SKEW_FACTOR, 4.0, float,
                    "a partition is 'hot' when its observed rows exceed "
                    "factor x the mean partition rows of the stage"),
        ConfigEntry(AQE_SKEW_MIN_ROWS, 1_000_000, int,
                    "never skew-split a partition smaller than this many "
                    "observed rows (protects small stages from pointless "
                    "task fan-out)"),
        ConfigEntry(SHUFFLE_INTEGRITY, True, _parse_bool,
                    "verify the producer-recorded CRC-32 checksum of every "
                    "remotely fetched shuffle partition before "
                    "deserialization; a mismatch raises a retryable "
                    "IntegrityError (re-fetch, then lineage rollback) "
                    "instead of decoding corrupt bytes"),
        ConfigEntry(SHUFFLE_LOCAL_HOST_MATCH, True, _parse_bool,
                    "zero-copy local handoff: a reader whose executor "
                    "advertises the same host as a shuffle producer reads "
                    "the producer's IPC file directly via mmap instead of "
                    "fetching it over the data plane.  The mapped bytes are "
                    "lazily CRC-verified (when "
                    "ballista.shuffle.integrity.verify is on) and any "
                    "mismatch or missing file silently falls back to the "
                    "remote fetch path, so a stale same-named file can "
                    "never corrupt results"),
        ConfigEntry(SHUFFLE_MAX_CONCURRENT_FETCHES, 50, int,
                    "per reduce-task cap on concurrent remote shuffle "
                    "fetches (the reference's 50-permit semaphore, "
                    "shuffle_reader.rs:123); fetches run on a shared "
                    "process-level pool rather than a per-task one"),
        ConfigEntry(SHUFFLE_WIRE_CHUNK_ROWS, 1 << 16, int,
                    "rows per streamed shuffle chunk; chunk boundaries are "
                    "deterministic multiples of this so resume-from-chunk "
                    "is exact"),
        ConfigEntry(SHUFFLE_WIRE_COMPRESSION, "lz4", str,
                    "Arrow IPC buffer compression on the streaming remote "
                    "path: 'lz4' (default), 'zstd', or 'none'.  Applied "
                    "per-fetch on the network path only — local files and "
                    "mmap readers always see uncompressed bytes; an "
                    "unavailable codec silently degrades to 'none'"),
        ConfigEntry(STATS_HISTORY_CAPACITY, 512, int,
                    "ring-buffer capacity of the cluster time series behind "
                    "GET /api/cluster/history (oldest samples are evicted)"),
        ConfigEntry(STATS_HISTORY_INTERVAL_S, 5.0, float,
                    "seconds between cluster-history samples (executor "
                    "utilization, admission queue depth, event-loop lag)"),
        ConfigEntry(PLAN_CACHE_ENABLED, True, _parse_bool,
                    "prepared-plan cache: normalized SQL text (literals "
                    "extracted as bound parameters) -> validated "
                    "ExecutionGraph template.  A hit skips parse, logical "
                    "and physical planning, scalar-subquery execution and "
                    "plan validation; entries are keyed on the referenced "
                    "tables' versions (resolved file list + mtimes, or "
                    "registration generation for in-memory tables) and the "
                    "session-config fingerprint, so DDL, data changes or "
                    "config changes invalidate correctly (see "
                    "docs/user-guide/serving.md)"),
        ConfigEntry(PLAN_CACHE_MAX_ENTRIES, 256, int,
                    "max bound plan templates resident in the prepared-plan "
                    "cache (LRU beyond this)"),
        ConfigEntry(PLAN_CACHE_MAX_BYTES, 64 << 20, int,
                    "estimated-byte budget of the prepared-plan cache; "
                    "shared table data is not counted (LRU beyond this)"),
        ConfigEntry(RESULT_CACHE_ENABLED, False, _parse_bool,
                    "result/subplan cache: completed-query result bytes "
                    "(and completed shuffle-stage outputs as subplan "
                    "entries) keyed on (plan fingerprint, table versions), "
                    "served straight from the scheduler for repeat "
                    "queries.  Off by default because a hit skips "
                    "execution entirely — turn it on for serving "
                    "workloads.  Capture only happens when the result "
                    "files are readable on the scheduler host (always "
                    "true in-process); see docs/user-guide/serving.md"),
        ConfigEntry(RESULT_CACHE_MAX_ENTRIES, 512, int,
                    "max entries (results + subplans) resident in the "
                    "result cache (LRU beyond this)"),
        ConfigEntry(RESULT_CACHE_MAX_BYTES, 256 << 20, int,
                    "byte budget of the result/subplan cache (LRU beyond "
                    "this)"),
        ConfigEntry(RESULT_CACHE_MAX_ENTRY_BYTES, 32 << 20, int,
                    "results or stage outputs larger than this are never "
                    "cached (one giant answer must not wipe the working "
                    "set)"),
        ConfigEntry(RESULT_CACHE_SUBPLAN, True, _parse_bool,
                    "also cache completed shuffle-stage outputs keyed on "
                    "the stage's structural fingerprint, and pre-complete "
                    "matching stages of later submissions from the cached "
                    "bytes (in-process/shared-filesystem deployments only; "
                    "budget shared with the result cache)"),
        ConfigEntry(FLEET_LEASE_TTL_S, 15.0, float,
                    "TTL of a scheduler shard's job-ownership lease in the "
                    "shared KV; a shard that stops renewing for longer than "
                    "this has its jobs adopted by a surviving shard"),
        ConfigEntry(FLEET_LEASE_RENEW_S, 0.0, float,
                    "interval between lease renewals from the shard's lease "
                    "heartbeat thread; 0 = ttl/3"),
        ConfigEntry(FLEET_ADOPT_INTERVAL_S, 2.0, float,
                    "how often a shard scans the shared KV for expired "
                    "leases to adopt (only shards with a KV-backed job "
                    "state run the scanner)"),
        ConfigEntry(FLEET_REGISTRY_STALE_S, 30.0, float,
                    "shard-registry entries older than this are ignored "
                    "when aggregating the /api/autoscale signal and when "
                    "re-resolving a job's owner for client failover"),
        ConfigEntry(COMPILE_ENABLED, True, _parse_bool,
                    "whole-stage compiler: fuse maximal single-child "
                    "chains of allowlisted operators into one jitted "
                    "program per chain at stage-plan resolution time "
                    "(compile/; a pure performance rewrite — any doubt "
                    "leaves the stage interpreted; see "
                    "docs/user-guide/compilation.md)"),
        ConfigEntry(COMPILE_MIN_OPS, 2, int,
                    "minimum operators in an allowlisted run before the "
                    "compiler fuses it (shorter runs stay interpreted: "
                    "one operator fused alone saves nothing)"),
        ConfigEntry(COMPILE_OPERATORS, "FilterExec,ProjectionExec,"
                    "RenameExec,HashAggregateExec", str,
                    "comma-separated operator allowlist for whole-stage "
                    "fusion; operators outside the list (and host-mode / "
                    "scalar-subquery / clustered instances of listed "
                    "ones) always run interpreted"),
        ConfigEntry(COMPILE_DONATE, True, _parse_bool,
                    "donate the input column buffers of a fused row-only "
                    "program to XLA when the chain reads a shuffle (fresh "
                    "per-task buffers); a no-op on the CPU backend and "
                    "for agg-headed chains (the capacity-retry ladder "
                    "re-reads the input)"),
        ConfigEntry(LIVE_ENABLED, False, _parse_bool,
                    "live observability plane: run the in-flight doctor "
                    "scan thread against running jobs (obs/live.py) and "
                    "let the watch endpoints tail the journal; off = the "
                    "scan thread never starts and nothing changes on the "
                    "wire (docs/user-guide/live.md)"),
        ConfigEntry(LIVE_DOCTOR_INTERVAL_S, 5.0, float,
                    "cadence of the in-flight doctor scan over running "
                    "jobs (straggler / partition-skew / shuffle-hotspot / "
                    "control-plane-churn / journal-drops rules -> "
                    "alert.raised / alert.cleared journal events); <= 0 "
                    "disables the scan thread even when live is on"),
        ConfigEntry(LIVE_WATCH_QUEUE_EVENTS, 1024, int,
                    "bound of each watch subscriber's event queue; a "
                    "consumer that falls behind sheds oldest events and "
                    "receives one watch.gap event with the drop count "
                    "(emit() never blocks on a slow watcher)"),
        ConfigEntry(LIVE_WATCH_POLL_S, 0.25, float,
                    "long-poll tick of the REST watch streams and "
                    "ctx.watch(): how often a quiet stream re-checks job "
                    "state and emits progress frames"),
        ConfigEntry(SLO_P99_TARGET_MS, 0.0, float,
                    "latency SLO: 99% of completed jobs must finish "
                    "under this wall time (a failed job always counts as "
                    "a violation); 0 disables SLO tracking entirely "
                    "(null tracker, no samples kept)"),
        ConfigEntry(SLO_WINDOW_S, 300.0, float,
                    "slow burn-rate window of the SLO tracker in "
                    "seconds; the fast window is 1/12 of it (the 1h/5m "
                    "SRE ratio); served at /api/slo and summed into "
                    "/api/autoscale"),
        ConfigEntry(QUERY_DEADLINE_S, 0.0, float,
                    "server-side query deadline in seconds, measured from "
                    "submission: the scheduler fails a job that runs past "
                    "it with a DeadlineExceeded terminal status and "
                    "cancels its tasks fleet-wide.  Session-level or "
                    "per-submit (the per-request config override wins); "
                    "the absolute expiry rides the job checkpoint, so an "
                    "adopting shard keeps enforcing the original clock.  "
                    "0 disables"),
        ConfigEntry(POISON_DISTINCT_EXECUTORS, 2, int,
                    "poison-query containment: when the SAME partition "
                    "fails with equivalent errors on this many distinct "
                    "non-quarantined executors, the job is classified "
                    "poison and failed immediately — zero quarantine "
                    "strikes are charged and the remaining retry budget "
                    "is skipped, so one bad query can never blacklist "
                    "the fleet.  0 disables classification"),
    ]
}


def resolve_task_budget(cfg: "BallistaConfig") -> int:
    """MEM_TASK_BUDGET -> bytes (0 = unlimited).

    Memory-control role of the reference's spill machinery
    (reference ballista/core/src/utils.rs:176-212 write_stream_to_disk):
    a static-shape engine cannot react to pressure by spilling mid-kernel,
    so the budget is enforced *before* allocation — joins chunk their probe
    loop and 'auto' partition counts scale so no task's working set is
    planned above the budget.  Disk-tier state remains the shuffle's IPC
    files, exactly as reference shuffle files serve as its data
    checkpoints."""
    v = cfg.get(MEM_TASK_BUDGET)
    if isinstance(v, str):
        if v.strip().lower() == "auto":
            # keyed on the backend PLATFORM, not remote_device(): that
            # helper is a D2H-latency proxy with a user override
            # (BALLISTA_REMOTE_DEVICE=0 restores eager safety nets), and
            # the override must not silently lift the memory budget on
            # small-HBM accelerators
            from ..models.batch import _platform_remote

            return (4 << 30) if _platform_remote() else 0
        v = int(v)
    return int(v)


def resolve_pool_budget(cfg: "BallistaConfig", key: str) -> int:
    """MEM_HOST_BUDGET / MEM_DEVICE_BUDGET -> bytes (0 = unlimited).

    'auto' picks a conservative default: 16 GiB for the host pool, and
    for the device pool 12 GiB on accelerator backends (under every
    shipping HBM size) / unlimited on CPU, mirroring the
    resolve_task_budget platform keying."""
    v = cfg.get(key)
    if isinstance(v, str):
        if v.strip().lower() == "auto":
            if key == MEM_DEVICE_BUDGET:
                from ..models.batch import _platform_remote

                return (12 << 30) if _platform_remote() else 0
            return 16 << 30
        v = int(v)
    return int(v)


class BallistaConfig:
    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        self._settings: Dict[str, Any] = {}
        for k, v in (settings or {}).items():
            self.set(k, v)

    @staticmethod
    def builder() -> "BallistaConfigBuilder":
        return BallistaConfigBuilder()

    def set(self, key: str, value: Any) -> None:
        entry = _ENTRIES.get(key)
        if entry is None:
            raise ConfigurationError(f"unknown configuration key {key!r}")
        if isinstance(value, str) and not isinstance(entry.default, str):
            try:
                value = entry.parse(value)
            except Exception as e:
                raise ConfigurationError(f"invalid value for {key}: {e}") from e
        expected = type(entry.default)
        if expected is float and isinstance(value, int) and not isinstance(value, bool):
            value = float(value)
        if not isinstance(value, expected) or (expected is int and isinstance(value, bool)):
            raise ConfigurationError(
                f"invalid value for {key}: expected {expected.__name__}, got {type(value).__name__} ({value!r})"
            )
        self._settings[key] = value

    def get(self, key: str) -> Any:
        entry = _ENTRIES.get(key)
        if entry is None:
            raise ConfigurationError(f"unknown configuration key {key!r}")
        return self._settings.get(key, entry.default)

    # typed accessors
    @property
    def shuffle_partitions(self) -> int:
        return self.get(SHUFFLE_PARTITIONS)

    @property
    def batch_size(self) -> int:
        return self.get(BATCH_SIZE)

    @property
    def join_output_factor(self) -> int:
        return self.get(JOIN_OUTPUT_FACTOR)

    @property
    def task_slots(self) -> int:
        return self.get(TASK_SLOTS)

    @property
    def job_timeout_s(self) -> int:
        return self.get(JOB_TIMEOUT_S)

    def to_dict(self) -> Dict[str, Any]:
        d = {k: e.default for k, e in _ENTRIES.items()}
        d.update(self._settings)
        return d

    def __repr__(self):
        return f"BallistaConfig({self._settings})"


class BallistaConfigBuilder:
    def __init__(self):
        self._settings: Dict[str, Any] = {}

    def set(self, key: str, value: Any) -> "BallistaConfigBuilder":
        self._settings[key] = value
        return self

    def build(self) -> BallistaConfig:
        return BallistaConfig(self._settings)
