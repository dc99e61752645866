"""Executor: runs query-stage tasks and reports status.

Parity: reference ballista/executor/src/executor.rs:56-166 (task execution
with cancellation + metrics) and lib.rs:36-102 (result -> TaskStatus
mapping with the failure classification).  The reference's DedicatedExecutor
(separate runtime for CPU-bound work) maps to a ThreadPoolExecutor here:
XLA dispatch releases the GIL, so pool threads genuinely overlap host IO
with device compute.
"""
from __future__ import annotations

import logging
import threading
import time
import traceback
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Set

from .. import faults
from ..memory import MemoryGovernor
from ..ops.physical import TaskContext
from ..utils.config import BallistaConfig
from ..utils.errors import (CancelledError, ExecutorKilled, FetchFailedError,
                            IntegrityError, IOError_, MemoryExhausted,
                            ResourceExhausted)
from ..scheduler.types import (
    EXECUTION_ERROR,
    FETCH_PARTITION_ERROR,
    IO_ERROR,
    RESOURCE_EXHAUSTED,
    TASK_KILLED,
    ExecutorMetadata,
    FailedReason,
    TaskDescription,
    TaskStatus,
)
from .execution_engine import DefaultExecutionEngine, ExecutionEngine

log = logging.getLogger(__name__)

# one id per executor PROCESS: in-proc standalone executors share plan
# instances (and so cumulative MetricsSets) — stage metric aggregation keys
# snapshots by this, not by executor_id
PROCESS_ID = __import__("uuid").uuid4().hex[:12]


def remove_job_data(work_dir: str, job_id: str) -> None:
    """Delete ``<work_dir>/<job_id>`` (path-traversal guarded) and drop the
    job's cached broadcast build tables.  Shared by the executor server's
    remove_job_data RPC, its TTL janitor, and the standalone launcher's
    scheduler-driven cleanup (reference executor_server.rs remove_job_data
    with is_subdirectory guard)."""
    import os
    import shutil

    from ..ops.operators import clear_job_build_caches

    root = os.path.realpath(work_dir)
    job_dir = os.path.realpath(os.path.join(work_dir, job_id))
    if job_dir != root and os.path.commonpath([job_dir, root]) == root \
            and os.path.isdir(job_dir):
        shutil.rmtree(job_dir, ignore_errors=True)
    clear_job_build_caches(job_id)


class Executor:
    def __init__(self, metadata: ExecutorMetadata, work_dir: str,
                 config: Optional[BallistaConfig] = None,
                 engine: Optional[ExecutionEngine] = None,
                 concurrent_tasks: int = 4):
        self.metadata = metadata
        self.work_dir = work_dir
        self.config = config or BallistaConfig()
        self.engine = engine or DefaultExecutionEngine()
        self.pool = ThreadPoolExecutor(max_workers=concurrent_tasks,
                                       thread_name_prefix=f"task-{metadata.executor_id}")
        # job-level cancel flags (reference abort_handles, executor.rs:93-111;
        # python threads can't be killed, so in-flight operators run to
        # completion and the *result* is dropped as 'killed').  Bounded so a
        # long-lived executor doesn't accumulate ids forever.
        self._cancelled_jobs: "OrderedDict[str, None]" = OrderedDict()
        # single-attempt cancel flags, keyed (job, stage, partition, attempt):
        # the scheduler reaps the losing duplicate of a speculative race
        # without touching the job's other tasks
        self._cancelled_tasks: "OrderedDict[tuple, None]" = OrderedDict()
        self._max_cancelled = 1024
        self._lock = threading.Lock()
        self._active = 0
        # in-flight registry: (job, stage, partition, attempt) -> the
        # attempt's cooperative CancelToken.  Feeds the heartbeat's
        # running-task set (zombie reconciliation) and lets cancel fanout
        # flip tokens so a cancel lands at the next batch boundary even in
        # contexts without a wired probe
        self._inflight: Dict[tuple, object] = {}
        # prometheus-style process counters (served by ExecutorServer's
        # /metrics listener; always collected — they are a few ints)
        from .metrics import ExecutorMetrics

        self.metrics = ExecutorMetrics()
        # memory governor: operators holding unbounded state (join builds,
        # agg state) reserve through this before materializing and spill
        # on denial; its pressure() rides heartbeats into the scheduler
        self.governor = MemoryGovernor.from_config(self.config)
        from ..utils.config import (OBS_DEVICE_ENABLED, OBS_DEVICE_WATERMARKS,
                                    OBS_TRACING)

        self._tracing = bool(self.config.get(OBS_TRACING))
        # device observatory switches are process-global (the jit wrappers
        # and transfer sites it instruments are process-wide); every
        # executor in the process shares one config in practice
        from ..obs import device as device_obs

        device_obs.set_enabled(bool(self.config.get(OBS_DEVICE_ENABLED)))
        device_obs.set_watermarks(bool(self.config.get(OBS_DEVICE_WATERMARKS)))
        # flight recorder: enable-only (never force-off — in-proc standalone
        # executors share the scheduler's process-global journal, and a
        # default-config executor must not stomp a test's explicit enable)
        from ..obs import journal
        from ..utils.config import (JOURNAL_CAPACITY, JOURNAL_ENABLED,
                                    JOURNAL_SPILL_PATH, env_flag)

        if env_flag("BALLISTA_JOURNAL") \
                or bool(self.config.get(JOURNAL_ENABLED)):
            journal.set_enabled(True)
            journal.configure(
                capacity=int(self.config.get(JOURNAL_CAPACITY)),
                spill_path=str(self.config.get(JOURNAL_SPILL_PATH)))
        if journal.enabled() and not journal.actor():
            journal.set_actor(metadata.executor_id)

    # --- task execution --------------------------------------------------
    def run_task(self, task: TaskDescription,
                 launch_ns: Optional[int] = None) -> TaskStatus:
        """Execute one task synchronously (callers use ``submit_task`` for
        pool execution; ``launch_ns`` is when the launch reached it).

        This wrapper owns observability — the task span tree (parented on
        the job's execution span via ``task.trace``) and the process
        counters; ``_run_task_inner`` owns execution and the failure
        classification.  Spans attach to every outcome, so failed tasks profile
        too."""
        tid = task.task
        start_ns = time.time_ns()
        launch_ns = launch_ns or start_ns
        launch_ms = launch_ns // 1_000_000
        recorder = None
        if self._tracing:
            from ..obs.tracing import TaskSpanRecorder

            trace = task.trace or {}
            # launch_ns -> start_ns is the wait for a slot: the launch
            # arrived, a pool thread picked it up
            recorder = TaskSpanRecorder(
                trace.get("trace_id"), trace.get("span_id", ""),
                name=f"task {tid.job_id}/{tid.stage_id}/{tid.partition}",
                kind="executor",
                attrs={"job_id": tid.job_id, "stage_id": tid.stage_id,
                       "partition": tid.partition,
                       "task_attempt": tid.task_attempt,
                       "executor_id": self.metadata.executor_id,
                       "launch_ns": launch_ns, "start_ns": start_ns,
                       "actor": f"executor {self.metadata.executor_id}",
                       "lane": f"stage {tid.stage_id} / p{tid.partition}"})
        try:
            status = self._run_task_observed(task, launch_ms, recorder)
        except BaseException:
            if recorder is not None:
                recorder.finish("error")  # leave the thread's stack clean
            raise
        if recorder is not None:
            if status.shuffle_writes:
                recorder.annotate(
                    rows_written=int(sum(w.num_rows
                                         for w in status.shuffle_writes)),
                    bytes_shuffled=int(sum(w.num_bytes
                                           for w in status.shuffle_writes)),
                    output_partitions=len(status.shuffle_writes))
            status.spans = recorder.finish(
                "ok" if status.state == "success" else status.state)
        self.metrics.record_task(status,
                                 (time.time_ns() - start_ns) / 1e9)
        return status

    def _run_task_observed(self, task: TaskDescription, launch_ms: int,
                           recorder) -> TaskStatus:
        """The log, device-accounting and flight-recorder scopes around
        one task."""
        tid = task.task
        from ..obs import device as device_obs
        from ..obs import journal
        from ..utils.logsetup import log_scope

        _trace = task.trace or {}
        with log_scope(job_id=tid.job_id,
                       trace_id=str(_trace.get("trace_id") or ""),
                       span_id=str(_trace.get("span_id") or "")), \
                device_obs.task_scope() as dev_acc, \
                journal.task_scope() as jbuf:
            if jbuf is not None:
                journal.emit("task.run", job_id=tid.job_id,
                             stage_id=tid.stage_id, partition=tid.partition,
                             attempt=tid.task_attempt,
                             executor_id=self.metadata.executor_id,
                             speculative=tid.speculative)
            status = self._run_task_inner(task, launch_ms, recorder)
            if (status.state == "killed"
                    and tid.job_id in self._cancelled_jobs):
                # a task that slipped past its cancel checkpoints (e.g. a
                # single-batch partition) can write shuffle files AFTER
                # the scheduler's cleanup fanout already ran — the last
                # dying task of a cancelled job sweeps the job's data so
                # the workspace never leaks what nothing registered
                remove_job_data(self.work_dir, tid.job_id)
        if dev_acc is not None:
            status.device_stats = dev_acc.snapshot()
        if jbuf:
            # ship the task's flight-record buffer piggyback on the status
            # (merged into the job timeline scheduler-side); empty buffer =
            # no wire key, same contract as device_stats
            status.journal = jbuf
        return status

    def _run_task_inner(self, task: TaskDescription, launch_ms: int,
                        recorder) -> TaskStatus:
        from ..ops.physical import CancelToken, install_cancel_token

        tid = task.task
        key = (tid.job_id, tid.stage_id, tid.partition, tid.task_attempt)
        token = CancelToken()
        with self._lock:
            self._active += 1
            self._inflight[key] = token
        # thread-local install: TaskContext.check_cancelled (and the free
        # checkpoint()) consult the token between batch iterations and
        # fused-kernel invocations, so cancel/deadline lands in seconds
        install_cancel_token(token)
        if self._is_cancelled(tid):
            token.cancel()  # cancel arrived before launch
        try:
            if self._is_cancelled(tid):
                return TaskStatus(tid, self.metadata.executor_id, "killed")
            faults.inject("executor.task.before_run",
                          executor_id=self.metadata.executor_id,
                          job_id=tid.job_id, stage_id=tid.stage_id,
                          partition=tid.partition,
                          task_attempt=tid.task_attempt)
            stage_exec = self.engine.create_query_stage_exec(
                tid.job_id, tid.stage_id, task.plan, self.work_dir)
            ctx = TaskContext(config=self.config, scalars=dict(task.scalars),
                              work_dir=self.work_dir, job_id=tid.job_id,
                              stage_id=tid.stage_id,
                              executor_id=self.metadata.executor_id,
                              executor_host=self.metadata.host,
                              cancelled=lambda: self._is_cancelled(tid),
                              span_recorder=recorder,
                              governor=self.governor)
            start_ms = int(time.time() * 1000)
            # deterministic straggler: a 'delay' rule here stalls the task
            # mid-run, which is what the speculation monitor watches for
            faults.inject("executor.task.slow",
                          executor_id=self.metadata.executor_id,
                          job_id=tid.job_id, stage_id=tid.stage_id,
                          partition=tid.partition,
                          task_attempt=tid.task_attempt,
                          speculative=tid.speculative)
            writes = stage_exec.execute_query_stage(tid.partition, ctx)
            end_ms = int(time.time() * 1000)
            if self._is_cancelled(tid):
                return TaskStatus(tid, self.metadata.executor_id, "killed")
            return TaskStatus(tid, self.metadata.executor_id, "success",
                              shuffle_writes=writes,
                              launch_time_ms=launch_ms,
                              start_time_ms=start_ms, end_time_ms=end_ms,
                              metrics=stage_exec.collect_plan_metrics(),
                              # key = plan INSTANCE: cumulative MetricsSets
                              # are monotone per decoded plan object, and a
                              # process can host several instances of one
                              # stage (fetch-failure re-resolve changes the
                              # plan blob; LRU eviction re-decodes) — see
                              # ExecutionStage.aggregate_metrics
                              process_id=f"{PROCESS_ID}-{id(task.plan):x}")
        except CancelledError:
            # the operator noticed the job's cancel flag between batches
            # (reference abortable execution, executor.rs:114-144): the
            # slot frees without waiting out the plan
            return TaskStatus(tid, self.metadata.executor_id, "killed")
        except ExecutorKilled:
            # faults kill action: this executor is simulating SIGKILL.  The
            # task unwinds as 'killed' (the graph ignores it); the scheduler
            # learns of the death via heartbeat timeout / launch failures.
            return TaskStatus(tid, self.metadata.executor_id, "killed")
        except FetchFailedError as e:
            return TaskStatus(tid, self.metadata.executor_id, "failed",
                              failure=FailedReason(
                                  FETCH_PARTITION_ERROR, str(e),
                                  map_stage_id=e.map_stage_id,
                                  map_partition_id=e.map_partition_id,
                                  executor_id=e.executor_id))
        except (MemoryExhausted, ResourceExhausted) as e:
            # governor-caught denial that could not degrade to spill:
            # retryable back-pressure, exempt from quarantine strikes —
            # never an executor fault
            return TaskStatus(tid, self.metadata.executor_id, "failed",
                              failure=FailedReason(RESOURCE_EXHAUSTED,
                                                   str(e)))
        except (OSError, IOError_, IntegrityError) as e:
            # IntegrityError covers spill-run read-back CRC mismatches:
            # the retry recomputes from the (immutable) shuffle inputs —
            # lineage recovery, not data corruption
            return TaskStatus(tid, self.metadata.executor_id, "failed",
                              failure=FailedReason(IO_ERROR, str(e)))
        except Exception as e:  # noqa: BLE001 — anything else is fatal
            log.debug("task %s failed:\n%s", tid, traceback.format_exc())
            return TaskStatus(tid, self.metadata.executor_id, "failed",
                              failure=FailedReason(EXECUTION_ERROR,
                                                   f"{type(e).__name__}: {e}"))
        finally:
            install_cancel_token(None)
            with self._lock:
                self._active -= 1
                self._inflight.pop(key, None)

    def submit_task(self, task: TaskDescription,
                    on_done: Callable[[TaskStatus], None]) -> None:
        launch_ns = time.time_ns()

        def run():
            on_done(self.run_task(task, launch_ns))

        self.pool.submit(run)

    # --- cancellation ----------------------------------------------------
    def _is_cancelled(self, tid) -> bool:
        return (tid.job_id in self._cancelled_jobs
                or (tid.job_id, tid.stage_id, tid.partition,
                    tid.task_attempt) in self._cancelled_tasks)

    def cancel_job_tasks(self, job_id: str) -> None:
        self._cancelled_jobs[job_id] = None
        while len(self._cancelled_jobs) > self._max_cancelled:
            self._cancelled_jobs.popitem(last=False)
        # flip the in-flight tokens too: the thread-local checkpoint fires
        # at the next batch boundary even where no probe was wired
        with self._lock:
            for key, token in self._inflight.items():
                if key[0] == job_id:
                    token.cancel()

    def cancel_task(self, task_id) -> None:
        """Cancel ONE attempt (a speculative race's loser): the flag is
        checked between batches and before the result is reported, so the
        attempt unwinds as 'killed' and its outputs are discarded."""
        key = (task_id.job_id, task_id.stage_id, task_id.partition,
               task_id.task_attempt)
        self._cancelled_tasks[key] = None
        while len(self._cancelled_tasks) > self._max_cancelled:
            self._cancelled_tasks.popitem(last=False)
        with self._lock:
            token = self._inflight.get(key)
        if token is not None:
            token.cancel()

    def active_tasks(self) -> int:
        with self._lock:
            return self._active

    def running_task_ids(self) -> List[tuple]:
        """(job, stage, partition, attempt) of in-flight tasks — the
        heartbeat's running-task set (zombie reconciliation).  Empty for
        an idle executor, so the heartbeat wire shape is unchanged."""
        with self._lock:
            return sorted(self._inflight)

    def active_job_ids(self) -> Set[str]:
        """Jobs with at least one in-flight task here (the shuffle
        janitor's live-job guard)."""
        with self._lock:
            return {key[0] for key in self._inflight}

    def shutdown(self) -> None:
        self.pool.shutdown(wait=True)
