"""Error classification.

Mirrors the reference's ``BallistaError`` retry semantics
(reference ballista/core/src/error.rs:36-58, 228-277): the *kind* of a task
failure decides whether the scheduler retries the task, re-runs the producer
stage, or fails the job:

- ``FetchFailedError``  -> not task-retryable, but triggers producer-stage
  re-run (shuffle lineage recovery).
- ``IOError`` / transient -> task retryable (counts against task attempts).
- ``ExecutionError``    -> fatal for the job (deterministic query error).
- ``CancelledError``    -> job/task cancellation, never retried.
"""
from __future__ import annotations


class BallistaError(Exception):
    """Base class; ``retryable`` drives scheduler retry policy."""

    retryable = False
    fail_stage = False


class ExecutionError(BallistaError):
    """Deterministic failure while executing a plan: fails the job."""


class PlanningError(BallistaError):
    """SQL/logical/physical planning failure."""


class InternalError(BallistaError):
    pass


class PlanValidationError(PlanningError):
    """Pre-launch plan sanity validation rejected an ExecutionGraph.

    Raised by ``analysis.plan_checks.validate_graph`` before any task of the
    job launches; carries every violated invariant, not just the first."""

    def __init__(self, job_id: str, errors):
        self.job_id = job_id
        self.errors = list(errors)
        detail = "; ".join(self.errors)
        super().__init__(f"plan validation failed for job {job_id}: {detail}")


class ConfigurationError(BallistaError):
    pass


class IOError_(BallistaError):
    """Transient I/O failure: the task is retried (≤ task max attempts)."""

    retryable = True


class CancelledError(BallistaError):
    pass


class ResourceExhausted(BallistaError):
    """Admission control shed the job (tenant queue full, or the queue
    timeout expired before capacity freed up).  Transient back-pressure,
    not a query error: back off and resubmit — the message carries a
    ``retry after N s`` hint."""

    retryable = True


class MemoryExhausted(BallistaError):
    """The memory governor (arrow_ballista_tpu/memory/) denied a
    reservation and the operator could not degrade to spill (spill
    disabled, or the denial hit a non-spillable allocation).

    Retryable back-pressure, **never** an executor fault: the scheduler
    retries the task (ideally on a less-loaded executor) and the
    quarantine tracker is explicitly exempted — an executor that protects
    itself by denying memory must not be blamed into quarantine for it.
    Pickle-safe (crosses the executor -> scheduler boundary)."""

    retryable = True

    def __init__(self, pool: str, requested: int, available: int,
                 message: str = ""):
        super().__init__(pool, requested, available, message)
        self.pool = pool
        self.requested = requested
        self.available = available
        self.message = message

    def __str__(self):
        return (
            f"memory exhausted on pool {self.pool!r}: requested "
            f"{self.requested} bytes, {self.available} available"
            + (f" ({self.message})" if self.message else ""))


class FetchFailedError(BallistaError):
    """A shuffle fetch from ``executor_id`` failed.

    Not retryable at task level: the scheduler rolls back the consuming
    stage and re-runs the producing map stage (reference
    ballista/scheduler/src/state/execution_graph.rs:270-657).

    This error crosses process boundaries (executor -> scheduler), so it
    must round-trip pickling: ``args`` carries the constructor fields.
    """

    fail_stage = True

    def __init__(self, executor_id: str, map_stage_id: int, map_partition_id: int, message: str = ""):
        super().__init__(executor_id, map_stage_id, map_partition_id, message)
        self.executor_id = executor_id
        self.map_stage_id = map_stage_id
        self.map_partition_id = map_partition_id
        self.message = message

    def __str__(self):
        return (
            f"fetch failed from executor {self.executor_id} "
            f"(map stage {self.map_stage_id} partition {self.map_partition_id}): {self.message}"
        )


class IntegrityError(BallistaError):
    """Payload failed an integrity check (checksum mismatch or an
    undecodable frame) at a named site — corruption detected *before* bad
    bytes turn into wrong results or an opaque decode traceback.

    Retryable: a re-fetch usually heals transient wire corruption; when it
    doesn't, the caller escalates to ``FetchFailedError`` so shuffle
    lineage recovery re-runs the producer.  Pickle-safe (crosses the
    executor -> scheduler boundary inside failure messages).
    """

    retryable = True

    def __init__(self, site: str, detail: str = "", **context):
        super().__init__(site, detail, context)
        self.site = site
        self.detail = detail
        self.context = context

    def __str__(self):
        ctx = " ".join(f"{k}={v}" for k, v in sorted(self.context.items()))
        return f"integrity check failed at {self.site}: {self.detail}" + (
            f" [{ctx}]" if ctx else "")


class ExecutorKilled(BallistaError):
    """The ``faults`` kill action is abruptly stopping this executor.

    Raised in the task thread so the in-flight task unwinds as ``killed``
    (never reported as a job failure — the executor is simulating SIGKILL;
    the scheduler learns of the death via heartbeat timeout / launch
    failure, exactly as it would for a real crash)."""


class CapacityError(ExecutionError):
    """Static output capacity exceeded (join fan-out / agg groups).

    The fix is a config bump (e.g. ``ballista.join.output_factor``); the
    message says which knob.
    """
