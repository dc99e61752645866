"""Standalone mode: scheduler + executors in one process.

Parity: reference ballista/scheduler/src/standalone.rs +
ballista/executor/src/standalone.rs + BallistaContext::standalone
(client context.rs:142-212) — the full stage-DAG machinery, shuffle files,
and fault-tolerance paths run in-process with no RPC, which is also the
test configuration (SURVEY.md §4).
"""
from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time
from typing import Dict, List, Optional

from ..executor.executor import Executor
from ..models.batch import ColumnBatch
from ..models.ipc import read_ipc_files
from ..obs.tracing import current_context, span
from ..ops.physical import TaskContext
from ..utils.config import BallistaConfig
from ..utils.errors import ExecutionError
from .scheduler import (
    SchedulerConfig,
    SchedulerServer,
    TaskLauncher,
    random_job_id,
)
from .types import ExecutorHeartbeat, ExecutorMetadata, TaskDescription


class InProcessTaskLauncher(TaskLauncher):
    """Launch seam wired directly to in-proc Executor objects."""

    def __init__(self):
        self.executors: Dict[str, Executor] = {}
        self.scheduler: Optional[SchedulerServer] = None

    def launch_tasks(self, executor_id: str, tasks: List[TaskDescription]) -> None:
        executor = self.executors[executor_id]
        for task in tasks:
            executor.submit_task(
                task,
                lambda st: self.scheduler.update_task_status(executor_id, [st]))

    def cancel_tasks(self, executor_id: str, job_id: str) -> None:
        from .. import faults

        # same lost-cancel failpoint as NetTaskLauncher: the fanout is the
        # scheduler's to lose whatever the transport — heartbeat zombie
        # reconciliation must reap whatever this drop leaks
        if faults.dropped("scheduler.cancel.fanout",
                          executor_id=executor_id, job_id=job_id):
            return
        self.executors[executor_id].cancel_job_tasks(job_id)

    def cancel_task(self, executor_id: str, task) -> None:
        from .. import faults

        if faults.dropped("scheduler.cancel.fanout",
                          executor_id=executor_id, job_id=task.job_id):
            return
        ex = self.executors.get(executor_id)
        if ex is not None:
            ex.cancel_task(task)

    def clean_job_data(self, executor_id: str, job_id: str) -> None:
        from ..executor.executor import remove_job_data

        remove_job_data(self.executors[executor_id].work_dir, job_id)

    def stop(self) -> None:
        for ex in self.executors.values():
            ex.shutdown()


class StandaloneCluster:
    """In-proc scheduler + N executors sharing a work_dir tree."""

    def __init__(self, config: Optional[BallistaConfig] = None,
                 concurrent_tasks: int = 4, num_executors: int = 1,
                 work_dir: Optional[str] = None,
                 scheduler_config: Optional[SchedulerConfig] = None):
        self.config = config or BallistaConfig()
        # arm failpoints (no-op unless a plan is configured) — standalone
        # runs the same instrumented task/shuffle paths as remote mode
        from .. import faults

        faults.configure(self.config)
        self.work_dir = work_dir or tempfile.mkdtemp(prefix="ballista-tpu-")
        self._owns_work_dir = work_dir is None
        from ..obs import JobObservability

        self.launcher = InProcessTaskLauncher()
        if scheduler_config is None:
            # honour the session's ballista.speculation.* and
            # ballista.live./slo.* keys (remote deployments do the same
            # via SchedulerNetService)
            from ..utils.config import (LIVE_DOCTOR_INTERVAL_S,
                                        LIVE_ENABLED,
                                        POISON_DISTINCT_EXECUTORS,
                                        QUERY_DEADLINE_S,
                                        SLO_P99_TARGET_MS,
                                        SLO_WINDOW_S,
                                        SPECULATION_ENABLED,
                                        SPECULATION_INTERVAL_S,
                                        SPECULATION_MAX_CONCURRENT,
                                        SPECULATION_MIN_RUNTIME_S,
                                        SPECULATION_MULTIPLIER,
                                        SPECULATION_QUANTILE)

            scheduler_config = SchedulerConfig(
                speculation_enabled=bool(self.config.get(SPECULATION_ENABLED)),
                speculation_quantile=float(self.config.get(SPECULATION_QUANTILE)),
                speculation_multiplier=float(self.config.get(SPECULATION_MULTIPLIER)),
                speculation_min_runtime_s=float(
                    self.config.get(SPECULATION_MIN_RUNTIME_S)),
                speculation_max_concurrent=int(
                    self.config.get(SPECULATION_MAX_CONCURRENT)),
                speculation_interval_s=float(
                    self.config.get(SPECULATION_INTERVAL_S)),
                live_enabled=bool(self.config.get(LIVE_ENABLED)),
                live_doctor_interval_s=float(
                    self.config.get(LIVE_DOCTOR_INTERVAL_S)),
                slo_p99_target_ms=float(self.config.get(SLO_P99_TARGET_MS)),
                slo_window_s=float(self.config.get(SLO_WINDOW_S)),
                query_deadline_s=float(self.config.get(QUERY_DEADLINE_S)),
                poison_distinct_executors=int(
                    self.config.get(POISON_DISTINCT_EXECUTORS)))
        self.scheduler = SchedulerServer(
            self.launcher, scheduler_config,
            observability=JobObservability.from_config(self.config))
        self.launcher.scheduler = self.scheduler
        self.scheduler.init()
        self.last_job_id: Optional[str] = None
        self.executors: List[Executor] = []
        for i in range(num_executors):
            meta = ExecutorMetadata(executor_id=f"executor-{i}",
                                    task_slots=concurrent_tasks)
            ex = Executor(meta, self.work_dir, self.config,
                          concurrent_tasks=concurrent_tasks)
            self.executors.append(ex)
            self.launcher.executors[meta.executor_id] = ex
            self.scheduler.register_executor(meta)
        self._hb_stop = threading.Event()
        self._hb_thread = threading.Thread(target=self._heartbeat_loop,
                                           name="standalone-heartbeat",
                                           daemon=True)
        self._hb_thread.start()

    def _heartbeat_loop(self) -> None:
        # reference executors heartbeat every 60 s (executor_server.rs:465)
        while not self._hb_stop.wait(10.0):
            for ex in self.executors:
                self.scheduler.heartbeat(ExecutorHeartbeat(
                    ex.metadata.executor_id,
                    memory_pressure=ex.governor.pressure(),
                    running=ex.running_task_ids()))

    # --- query execution -------------------------------------------------
    def execute_sql(self, sql_text: str, catalog,
                    config: Optional[BallistaConfig] = None,
                    statement=None) -> List[ColumnBatch]:
        """Serving path: SQL text in, batches out, through the scheduler's
        prepared-plan / result / subplan caches (scheduler/serving.py).  A
        result-cache hit returns decoded bytes without planning or running
        anything; ``execute`` below stays cache-free for pre-planned
        queries (EXPLAIN ANALYZE, chaos/fault harnesses)."""
        from ..models.ipc import read_ipc_buffers
        from .serving import prepare_sql_submission

        config = config or self.config
        job_id = random_job_id()
        trace = current_context()
        with span("submit", "client", job_id=job_id) as sp:
            cached, plan_fn, serving = prepare_sql_submission(
                self.scheduler, sql_text, catalog, config, job_id,
                subplan_ok=True, work_dir=self.work_dir, statement=statement)
            sp.set(cached=cached is not None)
            if cached is None:
                self._submit(job_id, plan_fn, config, trace,
                             serving=serving)
        if cached is not None:
            batches: List[ColumnBatch] = []
            with span("fetch", "client", job_id=job_id):
                for _part, blobs in cached["partitions"]:
                    batches.extend(read_ipc_buffers(
                        blobs, cached["schema"], capacity=config.batch_size))
            return batches
        # the schema is known once the job has been planned
        return self._await_result(job_id, config, lambda: serving.schema)

    def execute(self, planned) -> List[ColumnBatch]:
        """Run a PlannedQuery through the distributed machinery and fetch
        the final-stage output files (the client side of
        DistributedQueryExec, reference distributed_query.rs:226-329)."""
        from ..client.context import extract_scalar

        job_id = random_job_id()
        trace = current_context()
        with span("submit", "client", job_id=job_id):
            # scalar subqueries run first, host-side (they are tiny by
            # construction: single-row reductions)
            scalar_ctx = TaskContext(config=self.config,
                                     work_dir=self.work_dir, job_id="scalars")
            scalars: Dict[str, object] = {}
            for sid, splan in planned.scalars:
                scalar_ctx.scalars = scalars
                scalars[sid] = extract_scalar(splan, scalar_ctx)
            self._submit(job_id, lambda: (planned.plan, scalars),
                         self.config, trace)
        return self._await_result(job_id, self.config,
                                  lambda: planned.plan.schema)

    def _submit(self, job_id: str, plan_fn, config: BallistaConfig,
                trace: Dict[str, str], **kwargs) -> None:
        from ..admission import AdmissionRequest

        # remembered so explain_analyze can find the job's retained graph
        # (and its RuntimeStatsStore) after execute() returns
        self.last_job_id = job_id
        # ``trace``: the client span the job span hangs under
        # (client.collect; {} = a trace of its own, for a direct caller)
        self.scheduler.submit_job(
            job_id, plan_fn, admission=AdmissionRequest.from_config(config),
            trace=trace, config=config, **kwargs)

    def _await_result(self, job_id: str, config: BallistaConfig,
                      schema_of) -> List[ColumnBatch]:
        """The client's half after submission: ``wait`` ends when this
        thread learns of the terminal status (``wait.end - job.end`` is
        what noticing cost), ``fetch`` reads the final stage's files."""
        # deadline is config-driven (round-2 failure mode: a slow first-compile
        # TPU run blew through a hard-coded 300 s wait and "failed" a job that
        # would have finished)
        with span("wait", "client", job_id=job_id, polls=0):
            status = self.scheduler.wait_for_job(
                job_id, timeout=float(config.job_timeout_s))
        if status.state == "failed":
            if status.retriable:
                from ..utils.errors import ResourceExhausted

                raise ResourceExhausted(f"job {job_id} shed: {status.error}")
            raise ExecutionError(f"job {job_id} failed: {status.error}")
        if status.state != "successful":
            raise ExecutionError(f"job {job_id} ended as {status.state}")
        batches: List[ColumnBatch] = []
        with span("fetch", "client", job_id=job_id) as sp:
            nbytes = 0
            for part in sorted(status.locations):
                locs = [loc for loc in status.locations[part] if loc.num_rows]
                nbytes += sum(loc.num_bytes for loc in locs)
                batches.extend(read_ipc_files([loc.path for loc in locs],
                                              schema_of(),
                                              capacity=config.batch_size))
            sp.set(bytes=nbytes)
        return batches

    def shutdown(self) -> None:
        self._hb_stop.set()
        self._hb_thread.join(timeout=5.0)
        self.scheduler.shutdown()
        if self._owns_work_dir:
            shutil.rmtree(self.work_dir, ignore_errors=True)
