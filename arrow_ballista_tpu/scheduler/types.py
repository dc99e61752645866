"""Control-plane message types.

Python mirrors of the reference's protobuf contract
(reference ballista/core/proto/ballista.proto): task identity/status with
the full failure classification (ballista.proto:360-431), executor metadata and
heartbeats (284-358), and task definitions (440-463).  These are plain
dataclasses — the wire encoding for remote mode lives in
``arrow_ballista_tpu/net/wire.py`` and serializes exactly these shapes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

from ..ops.shuffle import PartitionLocation, ShuffleWritePartition

# failure classification (ballista.proto:391-431 FailedTask oneof)
EXECUTION_ERROR = "ExecutionError"      # fatal: fails the job
FETCH_PARTITION_ERROR = "FetchPartitionError"  # re-run producer stage
IO_ERROR = "IOError"                    # retryable on another executor
EXECUTOR_LOST = "ExecutorLost"          # retryable
RESULT_LOST = "ResultLost"              # retryable, outputs discarded
TASK_KILLED = "TaskKilled"              # cancellation
# memory-governor denial that could not degrade to spill: retryable
# back-pressure (ideally on a less-loaded executor) and NEVER a
# quarantine strike — an executor protecting itself from OOM is healthy
RESOURCE_EXHAUSTED = "ResourceExhausted"

# distinct terminal error markers (JobStatus.error prefix; the state stays
# 'failed' so every terminal-tuple consumer keeps working unchanged)
DEADLINE_EXCEEDED = "DeadlineExceeded"   # server-side deadline enforcement
POISON_QUERY = "PoisonQuery"             # poison-task containment


@dataclasses.dataclass
class TaskId:
    job_id: str
    stage_id: int
    partition: int
    # monotonically increasing per (stage_attempt, partition): every launch
    # — retry or speculative duplicate — gets a fresh attempt id, so the
    # scheduler can tell a winner's status from a loser's (reference
    # execution_graph.rs task-attempt bookkeeping)
    task_attempt: int = 0
    stage_attempt: int = 0
    # True for a speculative duplicate launched against a straggling
    # original attempt; first success wins either way
    speculative: bool = False


@dataclasses.dataclass
class TaskDescription:
    """A runnable task handed to an executor (parity: TaskDefinition,
    ballista.proto:440-452)."""

    task: TaskId
    plan: "object"  # ShuffleWriterExec root (encoded bytes in remote mode)
    task_internal_id: int = 0
    # job-level scalar-subquery values, shipped with every task (the
    # reference ships session props the same way, ballista.proto:446-449)
    scalars: Dict[str, object] = dataclasses.field(default_factory=dict)
    # trace propagation context ({"trace_id", "span_id"} of the job's
    # execution span); empty when tracing is disabled
    trace: Dict[str, str] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class FailedReason:
    kind: str  # one of the classification constants
    message: str = ""
    # FetchPartitionError details (ballista.proto:399-404)
    map_stage_id: int = -1
    map_partition_id: int = -1
    executor_id: str = ""

    @property
    def retryable(self) -> bool:
        return self.kind in (IO_ERROR, EXECUTOR_LOST, RESULT_LOST,
                             RESOURCE_EXHAUSTED)

    @property
    def count_to_failures(self) -> bool:
        # RESOURCE_EXHAUSTED counts toward task attempts (bounding retry
        # loops against a saturated cluster) but is exempted from
        # quarantine strikes (scheduler._record_quarantine_signals)
        return self.kind in (IO_ERROR, RESOURCE_EXHAUSTED)


@dataclasses.dataclass
class TaskStatus:
    """Executor -> scheduler task outcome (ballista.proto:360-390)."""

    task: TaskId
    executor_id: str
    state: str  # 'success' | 'failed' | 'killed'
    shuffle_writes: List[ShuffleWritePartition] = dataclasses.field(default_factory=list)
    failure: Optional[FailedReason] = None
    launch_time_ms: int = 0
    start_time_ms: int = 0
    end_time_ms: int = 0
    metrics: Dict[str, Dict[str, int]] = dataclasses.field(default_factory=dict)
    # identity of the executing PROCESS (not executor: in-proc standalone
    # executors share one process and thus one plan instance / MetricsSet;
    # stage metric aggregation must dedupe cumulative snapshots per process)
    process_id: str = ""
    # task span tree (obs.tracing.Span objects; serialized with the
    # status over the wire, empty when tracing is disabled)
    spans: List[object] = dataclasses.field(default_factory=list)
    # device-observatory fold for this task (obs/device.py task_scope):
    # jit compiles/retraces/cache hits, compile seconds, h2d/d2h
    # bytes+seconds, memory watermark peaks.  Empty dict when the
    # observatory is off — and then it serializes to NO wire key, so
    # disabled mode is byte-identical to the pre-observatory wire format
    device_stats: Dict[str, float] = dataclasses.field(default_factory=dict)
    # flight-recorder events captured during this task's run
    # (obs/journal.py task_scope; wire-ready dicts).  Same wire contract
    # as device_stats: empty list serializes to NO key, so journal-off is
    # byte-identical to the pre-journal wire format
    journal: List[Dict] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class ExecutorMetadata:
    """ballista.proto:284-300."""

    executor_id: str
    host: str = "localhost"
    port: int = 0
    task_slots: int = 1


@dataclasses.dataclass
class ExecutorHeartbeat:
    executor_id: str
    timestamp: float = dataclasses.field(default_factory=time.time)
    status: str = "active"  # 'active' | 'dead' | 'terminating'
    # carried so a restarted scheduler can auto re-register unknown
    # heartbeaters (reference heart_beat_from_executor, grpc.rs:174-241)
    metadata: Optional[ExecutorMetadata] = None
    # memory governor pressure in [0, 1] (fraction of the most-loaded
    # budgeted pool in use): degrades this executor's offer ordering and,
    # past ballista.memory.pressure.shed.threshold, feeds admission shed.
    # 0.0 (the unbudgeted default) is omitted on the wire.
    memory_pressure: float = 0.0
    # in-flight (job_id, stage_id, partition, task_attempt) tuples on this
    # executor: the scheduler diffs them against graph truth and re-issues
    # kills for zombies whose cancel RPC was lost.  Empty (the idle
    # default) is omitted on the wire.
    running: List[tuple] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class ExecutorReservation:
    """A reserved task slot, optionally job-affine (parity:
    reference scheduler state/executor_manager.rs:48-66)."""

    executor_id: str
    job_id: Optional[str] = None


# job status (ballista.proto:528-663 JobStatus oneof)
JOB_QUEUED = "queued"
JOB_RUNNING = "running"
JOB_SUCCESSFUL = "successful"
JOB_FAILED = "failed"
JOB_CANCELLED = "cancelled"


@dataclasses.dataclass
class JobStatus:
    job_id: str
    state: str
    error: str = ""
    # successful: per output-partition locations of the final stage
    locations: Dict[int, List[PartitionLocation]] = dataclasses.field(default_factory=dict)
    # failed + retriable: the failure is transient back-pressure (admission
    # queue full / timed out) — clients should back off and resubmit
    retriable: bool = False


@dataclasses.dataclass
class JobLease:
    """A scheduler shard's ownership claim on a job, stored in the shared
    KV (scheduler/kv.py JOB_LOCKS keyspace).  The epoch is the fencing
    token: it increments on every ownership change, and every fenced job
    write is guarded on (owner, epoch) — a partitioned ex-owner whose
    lease was adopted holds a stale epoch and cannot write job state
    (parity: the reference's etcd lease + sled lock in cluster/kv.rs
    try_acquire_job, hardened with epoch fencing)."""

    job_id: str
    owner: str = ""      # scheduler_id of the lease holder
    epoch: int = 0       # bumps on every ownership change, never on renewal
    ts: float = 0.0      # last acquire/renew time (unix seconds)
    endpoint: str = ""   # "host:port" the owner serves clients on
