"""SchedulerServer: the event-driven scheduler state machine.

Parity with the reference scheduler
(reference ballista/scheduler/src/scheduler_server/):
- event set mirrors QueryStageSchedulerEvent (event.rs:14-57):
  JobQueued -> (async planning) -> JobSubmitted | JobPlanningFailed,
  ReservationOffering, TaskUpdating, ExecutorLost, JobCancel, JobFinished;
- all state transitions run on one EventLoop (query_stage_scheduler.rs);
- push scheduling via slot reservations: free slots are reserved
  atomically, filled with tasks from active jobs, and launched through the
  ``TaskLauncher`` seam (state/task_manager.rs:59-119) — the seam is what
  lets tests fabricate a whole cluster in-process (SURVEY.md §4);
- a reaper thread expires dead executors (scheduler_server/mod.rs:224-305).
"""
from __future__ import annotations

import dataclasses
import logging
import queue
import random
import string
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Tuple

from .. import faults
from ..admission import AdmissionController, AdmissionRequest
from ..analysis.plan_checks import validate_graph
from ..compile.fuse import CompilePolicy, fuse_resolved_stages
from ..obs import journal
from ..utils.config import ANALYSIS_PLAN_CHECKS
from .aqe import AqePolicy
from .cluster import ClusterState, JobState
from .event_loop import EventLoop
from .execution_graph import ExecutionGraph
from .quarantine import ExecutorQuarantine
from .speculation import SpeculationPolicy, find_candidates
from .types import (
    DEADLINE_EXCEEDED,
    FETCH_PARTITION_ERROR,
    POISON_QUERY,
    RESOURCE_EXHAUSTED,
    ExecutorHeartbeat,
    ExecutorMetadata,
    ExecutorReservation,
    JobStatus,
    TaskDescription,
    TaskId,
    TaskStatus,
)

log = logging.getLogger(__name__)


def random_job_id() -> str:
    """7-char alphanumeric job ids (reference task_manager.rs generates the
    same shape)."""
    return "".join(random.choices(string.ascii_lowercase + string.digits, k=7))


class TaskLauncher:
    """Launch seam (reference TaskLauncher trait, task_manager.rs:59-67)."""

    def launch_tasks(self, executor_id: str, tasks: List[TaskDescription]) -> None:
        raise NotImplementedError

    def cancel_tasks(self, executor_id: str, job_id: str) -> None:
        """Best-effort cancellation of a job's running tasks."""

    def cancel_task(self, executor_id: str, task: TaskId) -> None:
        """Best-effort cancellation of ONE running attempt — used to reap
        the losing duplicate once a speculative race has a winner."""

    def clean_job_data(self, executor_id: str, job_id: str) -> None:
        """Best-effort removal of a finished job's shuffle data on one
        executor (reference ExecutorGrpc.remove_job_data fanout,
        executor_manager.rs:231-253)."""

    def stop(self) -> None:
        pass


# --- events (reference scheduler_server/event.rs) -------------------------
@dataclasses.dataclass
class JobQueued:
    job_id: str
    plan_fn: Callable[[], Tuple[object, Dict[str, object]]]
    # plan_fn() -> (root physical plan, scalar values) — planning runs inside
    # the event loop worker, failures become JobPlanningFailed


@dataclasses.dataclass
class JobPlanned:
    job_id: str
    graph: Optional[ExecutionGraph]
    error: str = ""


@dataclasses.dataclass
class TaskUpdating:
    executor_id: str
    # None = drain the executor's status inbox (coalesced intake: many
    # update_task_status calls fold into one event); a non-None list is
    # processed verbatim (direct posts from tests/chaos harnesses)
    statuses: Optional[List[TaskStatus]]


@dataclasses.dataclass
class ExecutorLost:
    executor_id: str
    reason: str = ""


@dataclasses.dataclass
class JobCancel:
    job_id: str


@dataclasses.dataclass
class JobDeadline:
    """Posted by the deadline scan thread when a job's wall clock expired;
    the handler re-checks on the event loop (scan and completion race) and
    fails the job with the DeadlineExceeded terminal status."""

    job_id: str


@dataclasses.dataclass
class Offer:
    """Try to hand out tasks (reference ReservationOffering)."""


@dataclasses.dataclass
class SpeculationTick:
    """Periodic straggler scan: posted by the speculation monitor thread so
    all graph reads/mutations stay on the event loop (the thread itself
    never touches a graph)."""


@dataclasses.dataclass
class PollWork:
    """Pull-mode work request (reference SchedulerGrpc.poll_work,
    grpc.rs:57-136): absorb statuses, then fill the executor's free slots.
    The reply travels back through ``reply`` (filled on the event loop)."""

    executor_id: str
    num_free_slots: int
    statuses: List[TaskStatus]
    reply: "queue.Queue"


class SchedulerConfig:
    def __init__(self, task_distribution: str = "bias",
                 executor_timeout_s: Optional[float] = None,
                 reaper_interval_s: float = 15.0,
                 event_buffer_size: int = 10000,
                 policy: str = "push",
                 job_data_cleanup_delay_s: float = 30.0,
                 quarantine_failures: Optional[int] = None,
                 quarantine_probation_s: Optional[float] = None,
                 speculation_enabled: Optional[bool] = None,
                 speculation_quantile: Optional[float] = None,
                 speculation_multiplier: Optional[float] = None,
                 speculation_min_runtime_s: Optional[float] = None,
                 speculation_max_concurrent: Optional[int] = None,
                 speculation_interval_s: Optional[float] = None,
                 stats_history_capacity: Optional[int] = None,
                 stats_history_interval_s: Optional[float] = None,
                 fleet_lease_ttl_s: Optional[float] = None,
                 fleet_lease_renew_s: Optional[float] = None,
                 fleet_adopt_interval_s: Optional[float] = None,
                 fleet_registry_stale_s: Optional[float] = None,
                 live_enabled: Optional[bool] = None,
                 live_doctor_interval_s: Optional[float] = None,
                 slo_p99_target_ms: Optional[float] = None,
                 slo_window_s: Optional[float] = None,
                 memory_shed_threshold: Optional[float] = None,
                 query_deadline_s: Optional[float] = None,
                 poison_distinct_executors: Optional[int] = None,
                 deadline_scan_interval_s: float = 1.0):
        from ..utils.config import (BallistaConfig,
                                    CLUSTER_EXECUTOR_TIMEOUT_S,
                                    FLEET_ADOPT_INTERVAL_S,
                                    FLEET_LEASE_RENEW_S,
                                    FLEET_LEASE_TTL_S,
                                    FLEET_REGISTRY_STALE_S,
                                    LIVE_DOCTOR_INTERVAL_S,
                                    LIVE_ENABLED,
                                    MEM_PRESSURE_SHED,
                                    POISON_DISTINCT_EXECUTORS,
                                    QUARANTINE_FAILURES,
                                    QUARANTINE_PROBATION_S,
                                    QUERY_DEADLINE_S,
                                    SLO_P99_TARGET_MS,
                                    SLO_WINDOW_S,
                                    SPECULATION_ENABLED,
                                    SPECULATION_INTERVAL_S,
                                    SPECULATION_MAX_CONCURRENT,
                                    SPECULATION_MIN_RUNTIME_S,
                                    SPECULATION_MULTIPLIER,
                                    SPECULATION_QUANTILE,
                                    STATS_HISTORY_CAPACITY,
                                    STATS_HISTORY_INTERVAL_S)

        assert policy in ("push", "pull")  # reference TaskSchedulingPolicy
        defaults = BallistaConfig()
        self.task_distribution = task_distribution
        # one key drives both "stop offering" (minus the drain grace, see
        # cluster.alive_cutoff_s) and "declare lost" (the reaper):
        # ballista.cluster.executor_timeout_s
        self.executor_timeout_s = float(
            executor_timeout_s if executor_timeout_s is not None
            else defaults.get(CLUSTER_EXECUTOR_TIMEOUT_S))
        self.quarantine_failures = int(
            quarantine_failures if quarantine_failures is not None
            else defaults.get(QUARANTINE_FAILURES))
        self.quarantine_probation_s = float(
            quarantine_probation_s if quarantine_probation_s is not None
            else defaults.get(QUARANTINE_PROBATION_S))
        # straggler mitigation (scheduler/speculation.py): knobs default
        # from the ballista.speculation.* config-registry entries
        self.speculation = SpeculationPolicy(
            enabled=bool(speculation_enabled
                         if speculation_enabled is not None
                         else defaults.get(SPECULATION_ENABLED)),
            quantile=float(speculation_quantile
                           if speculation_quantile is not None
                           else defaults.get(SPECULATION_QUANTILE)),
            multiplier=float(speculation_multiplier
                             if speculation_multiplier is not None
                             else defaults.get(SPECULATION_MULTIPLIER)),
            min_runtime_s=float(speculation_min_runtime_s
                                if speculation_min_runtime_s is not None
                                else defaults.get(SPECULATION_MIN_RUNTIME_S)),
            max_concurrent=int(speculation_max_concurrent
                               if speculation_max_concurrent is not None
                               else defaults.get(SPECULATION_MAX_CONCURRENT)),
            interval_s=float(speculation_interval_s
                             if speculation_interval_s is not None
                             else defaults.get(SPECULATION_INTERVAL_S)))
        # cluster time-series sampler (obs/stats.py ClusterHistory): knobs
        # default from the ballista.stats.* config-registry entries
        self.stats_history_capacity = int(
            stats_history_capacity if stats_history_capacity is not None
            else defaults.get(STATS_HISTORY_CAPACITY))
        self.stats_history_interval_s = float(
            stats_history_interval_s if stats_history_interval_s is not None
            else defaults.get(STATS_HISTORY_INTERVAL_S))
        self.reaper_interval_s = reaper_interval_s
        self.event_buffer_size = event_buffer_size
        self.policy = policy
        # delay before the remove_job_data fanout for a finished job: long
        # enough for the client to fetch final-stage partitions, short
        # enough that shuffle files don't pile up (reference delayed
        # clean_up_job_data, executor_manager.rs:231-253).  <0 disables;
        # in daemon deployments the executor TTL janitor remains as
        # backstop, in standalone mode the work dir dies with the cluster
        # (StandaloneCluster.shutdown).
        self.job_data_cleanup_delay_s = job_data_cleanup_delay_s
        # scheduler fleet HA (ballista.fleet.*): job-ownership lease TTL,
        # renewal cadence (0 = ttl/3), expired-lease adoption scan interval
        # and shard-registry freshness (client failover + /api/autoscale)
        self.fleet_lease_ttl_s = float(
            fleet_lease_ttl_s if fleet_lease_ttl_s is not None
            else defaults.get(FLEET_LEASE_TTL_S))
        self.fleet_lease_renew_s = float(
            fleet_lease_renew_s if fleet_lease_renew_s is not None
            else defaults.get(FLEET_LEASE_RENEW_S))
        self.fleet_adopt_interval_s = float(
            fleet_adopt_interval_s if fleet_adopt_interval_s is not None
            else defaults.get(FLEET_ADOPT_INTERVAL_S))
        self.fleet_registry_stale_s = float(
            fleet_registry_stale_s if fleet_registry_stale_s is not None
            else defaults.get(FLEET_REGISTRY_STALE_S))
        # live observability plane (ballista.live.* / ballista.slo.*): the
        # in-flight doctor cadence and the latency-SLO objective
        self.live_enabled = bool(
            live_enabled if live_enabled is not None
            else defaults.get(LIVE_ENABLED))
        self.live_doctor_interval_s = float(
            live_doctor_interval_s if live_doctor_interval_s is not None
            else defaults.get(LIVE_DOCTOR_INTERVAL_S))
        self.slo_p99_target_ms = float(
            slo_p99_target_ms if slo_p99_target_ms is not None
            else defaults.get(SLO_P99_TARGET_MS))
        self.slo_window_s = float(
            slo_window_s if slo_window_s is not None
            else defaults.get(SLO_WINDOW_S))
        # memory backpressure (ballista.memory.pressure.shed.threshold):
        # when every alive executor heartbeats governor pressure at or
        # above this, admission queues/sheds new jobs with a retriable
        # ResourceExhausted instead of piling work onto a fleet about
        # to spill or OOM.  <= 0 disables the admission feed.
        self.memory_shed_threshold = float(
            memory_shed_threshold if memory_shed_threshold is not None
            else defaults.get(MEM_PRESSURE_SHED))
        # query lifecycle guardrails (ballista.query.* / ballista.poison.*):
        # scheduler-wide deadline default (a job's session/per-submit config
        # overrides it), the distinct-executor threshold for poison
        # classification, and the deadline scan cadence
        self.query_deadline_s = float(
            query_deadline_s if query_deadline_s is not None
            else defaults.get(QUERY_DEADLINE_S))
        self.poison_distinct_executors = int(
            poison_distinct_executors if poison_distinct_executors is not None
            else defaults.get(POISON_DISTINCT_EXECUTORS))
        self.deadline_scan_interval_s = float(deadline_scan_interval_s)


class SchedulerServer:
    def __init__(self, launcher: TaskLauncher,
                 config: Optional[SchedulerConfig] = None,
                 metrics: Optional["SchedulerMetricsCollector"] = None,
                 job_backend=None, scheduler_id: Optional[str] = None,
                 cluster_state=None, observability=None):
        import uuid

        from ..obs import ClusterHistory, JobObservability
        from .metrics import InMemoryMetricsCollector

        self.config = config or SchedulerConfig()
        # pluggable: in-memory (single scheduler) or KV-backed (N schedulers
        # sharing one cluster, scheduler/kv.py KvClusterState)
        self.cluster = cluster_state or ClusterState(self.config.task_distribution)
        self.jobs = JobState()
        self.launcher = launcher
        self.metrics = metrics if metrics is not None else InMemoryMetricsCollector()
        # tracing + profile retention (arrow_ballista_tpu/obs/): phase
        # spans per job, task span intake, /api/job/<id>/profile|trace
        self.obs = observability if observability is not None \
            else JobObservability()
        # optional persistence: checkpoint graphs on every transition so a
        # restarted/sibling scheduler can adopt them (reference JobState
        # backends + try_acquire_job)
        self.job_backend = job_backend
        self.scheduler_id = scheduler_id or f"scheduler-{uuid.uuid4().hex[:8]}"
        # flight recorder (obs/journal.py): enable-only switch — a shard
        # never force-disables a journal a test/session already turned on
        # (standalone runs share one process-global journal across the
        # scheduler and its in-proc executors)
        from ..utils.config import (BallistaConfig, JOURNAL_CAPACITY,
                                    JOURNAL_ENABLED, JOURNAL_SPILL_PATH,
                                    env_flag)
        _defaults = BallistaConfig()
        if env_flag("BALLISTA_JOURNAL") or bool(_defaults.get(JOURNAL_ENABLED)):
            journal.set_enabled(True)
        if journal.enabled():
            journal.configure(
                capacity=int(_defaults.get(JOURNAL_CAPACITY)),
                spill_path=str(_defaults.get(JOURNAL_SPILL_PATH)))
            if not journal.actor():
                # first process identity wins (in-proc fleets share one
                # journal; lease events carry scheduler_id explicitly)
                journal.set_actor(self.scheduler_id)
        # delta base for sync_journal_metrics (journal counters are
        # process-global; this collector folds only deltas it hasn't seen)
        self._journal_last = (0, 0)  # ballista: guarded-by=none
        # fleet HA: lease-capable backends (KvJobStateBackend) get epoch-
        # fenced TTL ownership; file/legacy backends keep the PR-4 lock path
        self._lease_capable = job_backend is not None \
            and hasattr(job_backend, "acquire_lease")
        # "host:port" this shard serves clients on, published in the lease
        # and the shard registry for client failover; set by the net
        # service once its RPC port is known, before init()
        self.client_endpoint = ""  # ballista: guarded-by=none
        # _lease_lock guards _leases (job_id -> held lease epoch): written
        # by event-loop handlers (checkpoint/terminal release), the lease-
        # renewal thread and the adoption scanner
        self._lease_lock = threading.Lock()
        self._leases: Dict[str, int] = {}
        # _meta_lock guards the per-job bookkeeping dicts below
        # (_queued_at_ms, _job_configs, _serving_info): they are touched
        # from submit threads, admission callbacks (sweeper thread), event
        # -loop handlers and planning closures.  Scope is always one dict
        # op — never held across a call that takes another lock
        self._meta_lock = threading.Lock()
        self._queued_at_ms: Dict[str, int] = {}
        # job_id -> submitting session's BallistaConfig (popped at planning
        # or terminal shed/cancel; entries are only written before JobQueued)
        self._job_configs: Dict[str, object] = {}
        # serving caches (scheduler/serving_cache.py): plan templates +
        # result/subplan entries, shared by every session; per-session
        # enable knobs are honoured at submit by the serving entry points
        from ..utils.config import BallistaConfig
        from .serving_cache import caches_from_config

        self.plan_cache, self.result_cache = caches_from_config(
            BallistaConfig(), metrics=self.metrics)
        # job_id -> ServingJobInfo for SQL jobs on the serving path (popped
        # at capture on success, or by the terminal-status backstop)
        self._serving_info: Dict[str, object] = {}
        # status-report coalescing: executors append under the lock; the
        # event loop drains an executor's whole inbox in ONE TaskUpdating,
        # so a flood of single-status reports costs one event, not N
        self._status_lock = threading.Lock()
        self._status_inbox: Dict[str, List[TaskStatus]] = {}
        self._event_loop = EventLoop("scheduler-events", self._on_event,
                                     self.config.event_buffer_size,
                                     on_error=self._on_event_error)
        self._launch_pool = ThreadPoolExecutor(max_workers=8,
                                               thread_name_prefix="launch")
        # loop threads: written once by init() before any concurrency on
        # them, read only by shutdown() (init happens-before shutdown)
        self._reaper: Optional[threading.Thread] = None  # ballista: guarded-by=none
        self._spec_monitor: Optional[threading.Thread] = None  # ballista: guarded-by=none
        self._history_sampler: Optional[threading.Thread] = None  # ballista: guarded-by=none
        self._lease_thread: Optional[threading.Thread] = None  # ballista: guarded-by=none
        self._adopt_thread: Optional[threading.Thread] = None  # ballista: guarded-by=none
        self._live_doctor_thread: Optional[threading.Thread] = None  # ballista: guarded-by=none
        self._deadline_thread: Optional[threading.Thread] = None  # ballista: guarded-by=none
        # live observability plane: in-flight doctor state machine (scan
        # thread starts in init() only when ballista.live.enabled) and the
        # latency-SLO tracker (null object when no target is configured)
        from ..obs.live import LiveDoctor
        from ..obs.slo import NullSloTracker, SloPolicy, SloTracker

        self.live_doctor = LiveDoctor()
        if self.config.slo_p99_target_ms > 0:
            self.slo = SloTracker(SloPolicy(self.config.slo_p99_target_ms,
                                            self.config.slo_window_s))
        else:
            self.slo = NullSloTracker()
        # cluster time series behind GET /api/cluster/history: periodic
        # utilization / queue-depth / event-loop-lag samples in a bounded
        # ring buffer (obs/stats.py)
        self.history = ClusterHistory(self.config.stats_history_capacity,
                                      self.config.stats_history_interval_s)
        self._stopped = threading.Event()
        self._cleanup_timers: Dict[str, threading.Timer] = {}
        self._cleanup_lock = threading.Lock()
        # quarantine: executors racking up consecutive retryable failures
        # stop receiving offers until probation re-admits them
        self.quarantine = ExecutorQuarantine(
            threshold=self.config.quarantine_failures,
            probation_s=self.config.quarantine_probation_s)
        # poison-query containment: (job, stage, partition) -> per-executor
        # failure signatures, plus jobs whose classification completed this
        # intake round.  Event-loop-only state (written by
        # _record_quarantine_signals, drained by _absorb_statuses)
        self._poison_evidence: Dict[Tuple[str, int, int], Dict[str, str]] = {}
        self._poison_suspects: set = set()
        # admission gate between submit_job and JobQueued planning; with no
        # ballista.admission.* limits configured this is pass-through
        self.admission = AdmissionController(
            admit_cb=self._admission_admit,
            fail_cb=self._admission_reject,
            pending_tasks_fn=self.pending_task_count,
            total_slots_fn=self.cluster.total_slots,
            memory_pressure_fn=self._fleet_memory_pressure,
            memory_shed_threshold=self.config.memory_shed_threshold,
            metrics=self.metrics)
        # terminal transitions release the tenant's concurrency reservation
        # and pull the next admissible job out of the wait queue
        self.jobs.subscribe(self._on_job_terminal)

    # --- lifecycle -------------------------------------------------------
    def init(self, start_reaper: bool = True) -> None:
        self._event_loop.start()
        if start_reaper:
            self._reaper = threading.Thread(target=self._reap_loop,
                                            name="executor-reaper", daemon=True)
            self._reaper.start()
            if self.config.deadline_scan_interval_s > 0:
                # finer-grained than the executor reaper: a deadline must
                # land within seconds of expiry, not a reaper interval
                self._deadline_thread = threading.Thread(
                    target=self._deadline_loop, name="deadline-reaper",
                    daemon=True)
                self._deadline_thread.start()
        if self.config.speculation.enabled:
            self._spec_monitor = threading.Thread(
                target=self._speculation_loop, name="speculation-monitor",
                daemon=True)
            self._spec_monitor.start()
        self._history_sampler = threading.Thread(
            target=self._history_loop, name="cluster-history-sampler",
            daemon=True)
        self._history_sampler.start()
        if start_reaper and self._lease_capable:
            self._lease_thread = threading.Thread(
                target=self._lease_loop, name="lease-renewal", daemon=True)
            self._lease_thread.start()
            if self.config.fleet_adopt_interval_s > 0:
                self._adopt_thread = threading.Thread(
                    target=self._adopt_loop, name="lease-adoption",
                    daemon=True)
                self._adopt_thread.start()
        if self.config.live_enabled \
                and self.config.live_doctor_interval_s > 0:
            self._live_doctor_thread = threading.Thread(
                target=self._live_doctor_loop, name="live-doctor",
                daemon=True)
            self._live_doctor_thread.start()

    def shutdown(self, withdraw: bool = True) -> None:
        # withdraw=False is the chaos harness's crash-simulation: skip the
        # registry goodbye so the shard vanishes exactly like kill -9
        # (its entry ages out of scheduler_registry at the stale cutoff)
        # order matters: stop the event loop BEFORE closing the launch pool,
        # so no event handler can race a _launch_pool.submit against
        # pool.shutdown (round-2 bench crash: "cannot schedule new futures
        # after shutdown" killed the event loop mid-run)
        self._stopped.set()
        self.admission.stop()
        # bounded joins: every loop waits on _stopped (already set), so
        # each returns within one in-flight iteration; the timeout keeps a
        # wedged iteration from hanging shutdown (daemons regardless)
        if self._reaper is not None:
            self._reaper.join(timeout=5.0)
        if self._spec_monitor is not None:
            self._spec_monitor.join(timeout=5.0)
        if self._history_sampler is not None:
            self._history_sampler.join(timeout=5.0)
        if self._lease_thread is not None:
            self._lease_thread.join(timeout=5.0)
        if self._adopt_thread is not None:
            self._adopt_thread.join(timeout=5.0)
        if self._live_doctor_thread is not None:
            self._live_doctor_thread.join(timeout=5.0)
        if self._deadline_thread is not None:
            self._deadline_thread.join(timeout=5.0)
        # clean shutdown deliberately does NOT release job leases: a
        # shard stopping mid-job should look exactly like a crash so a
        # sibling adopts its jobs after one TTL.  Only the registry entry
        # (client routing hint) is withdrawn.
        if self._lease_capable and withdraw:
            store = getattr(self.job_backend, "store", None)
            if store is not None:
                try:
                    from .kv import remove_scheduler
                    remove_scheduler(store, self.scheduler_id)
                except Exception:  # noqa: BLE001 — KV may already be gone
                    log.info("shard registry withdrawal failed",
                             exc_info=True)
        with self._cleanup_lock:
            timers = list(self._cleanup_timers.values())
            self._cleanup_timers.clear()
        for t in timers:
            t.cancel()
        self._event_loop.stop()
        self._launch_pool.shutdown(wait=False)
        self.launcher.stop()
        self.result_cache.close()

    def _submit_work(self, fn, *args) -> None:
        """Submit to the launch pool, tolerating shutdown races."""
        if self._stopped.is_set():
            return
        try:
            self._launch_pool.submit(fn, *args)
        except RuntimeError:  # pool closed between the check and the submit
            log.info("dropping work submitted during shutdown")

    # --- public API (the SchedulerGrpc surface, ballista.proto:665-689) --
    def register_executor(self, meta: ExecutorMetadata) -> None:
        self.cluster.register_executor(meta)
        self._event_loop.post(Offer())

    def heartbeat(self, hb: ExecutorHeartbeat) -> None:
        known = self.cluster.get_executor(hb.executor_id) is not None
        self.cluster.save_heartbeat(hb)
        if not known:
            if hb.metadata is not None:
                # auto re-register: heals push-mode executors after a
                # scheduler restart (reference grpc.rs:174-241)
                log.info("re-registering unknown heartbeater %s", hb.executor_id)
                self.register_executor(hb.metadata)
                # registration installs a fresh 'active' heartbeat; re-apply
                # the REPORTED status so a terminating executor stays
                # unschedulable through its re-registration
                self.cluster.save_heartbeat(hb)
            else:
                log.info("heartbeat from unknown executor %s", hb.executor_id)
        if hb.running:
            # zombie-task reconciliation: the executor's in-flight set is
            # ground truth for "still burning cycles"; diff it against the
            # scheduler's job states and re-issue kills for tasks whose job
            # is terminal or unknown (closes the lost-cancel-RPC leak —
            # NetTaskLauncher.cancel_tasks logs and swallows delivery
            # failures, so without this a dropped fanout leaks the task
            # until it finishes on its own)
            self._reconcile_running(hb.executor_id, hb.running)

    def _reconcile_running(self, executor_id: str,
                           running: List[tuple]) -> None:
        by_job: Dict[str, int] = {}
        for entry in running:
            job_id = entry[0]
            by_job[job_id] = by_job.get(job_id, 0) + 1
        reaped = 0
        for job_id, count in sorted(by_job.items()):
            if not self._job_is_zombie(job_id):
                continue
            reaped += count
            log.warning("reaping %d zombie task(s) of job %s on %s",
                        count, job_id, executor_id)
            if journal.enabled():
                journal.emit_job("zombie.reaped", job_id,
                                 executor_id=executor_id, tasks=str(count))
            self._submit_work(self.launcher.cancel_tasks, executor_id, job_id)
        if reaped:
            self.metrics.record_zombies_reaped(reaped)

    def _job_is_zombie(self, job_id: str) -> bool:
        """A running task is a zombie when its job can no longer use the
        result: the job is terminal here, or nobody in the fleet knows it."""
        st = self.jobs.get_status(job_id)
        if st is not None:
            return st.state in ("successful", "failed", "cancelled")
        # unknown locally: in a fleet another shard may own the job, so
        # consult the shared backend before declaring it dead
        if self.job_backend is not None:
            try:
                obj = self.job_backend.load_job(job_id)
            except Exception:  # noqa: BLE001 — backend hiccup
                log.warning("zombie check: job backend load failed for %s"
                            " — sparing the task", job_id, exc_info=True)
                return False  # don't kill on bad data
            if obj is not None:
                return False  # some shard still tracks it
        return True

    def executor_stopped(self, executor_id: str, reason: str = "") -> None:
        self._event_loop.post(ExecutorLost(executor_id, reason))

    def submit_job(self, job_id: str,
                   plan_fn: Callable[[], Tuple[object, Dict[str, object]]],
                   admission: Optional[AdmissionRequest] = None,
                   trace: Optional[Dict[str, str]] = None,
                   config: Optional[object] = None,
                   serving: Optional[object] = None) -> None:
        """``config``: the submitting session's BallistaConfig — consulted
        at planning time for ``ballista.analysis.plan_checks`` (None = all
        defaults).  Stashed here because the admission queue only carries
        (job_id, plan_fn).  ``serving``: ServingJobInfo for SQL jobs going
        through the serving caches (scheduler/serving.py) — drives template
        storage, validation skipping, subplan preload and result capture."""
        self.jobs.accept_job(job_id)
        self.obs.on_submitted(job_id, trace)
        if journal.enabled():
            journal.emit_job("job.submitted", job_id)
        with self._meta_lock:
            if config is not None:
                self._job_configs[job_id] = config
            if serving is not None:
                self._serving_info[job_id] = serving
            self._queued_at_ms[job_id] = int(time.time() * 1000)
        self.admission.submit(job_id, plan_fn, admission)

    # --- admission callbacks (see arrow_ballista_tpu/admission/) ---------
    def _admission_admit(self, job_id: str, plan_fn: Callable) -> None:
        if self._stopped.is_set():
            return
        self.obs.on_admitted(job_id)
        if journal.enabled():
            journal.emit_job("job.admitted", job_id)
        self._event_loop.post(JobQueued(job_id, plan_fn))

    def _admission_reject(self, job_id: str, message: str) -> None:
        """Shed (queue full / queue timeout): a *retriable* failure — the
        client should back off and resubmit, not treat it as a query
        error."""
        with self._meta_lock:
            self._queued_at_ms.pop(job_id, None)
            self._job_configs.pop(job_id, None)
        if journal.enabled():
            journal.emit_job("job.shed", job_id, reason=message)
        self.jobs.set_status(JobStatus(job_id, "failed", error=message,
                                       retriable=True))
        self.metrics.record_failed(job_id)

    def _on_job_terminal(self, status: JobStatus) -> None:
        if status.state in ("successful", "failed", "cancelled"):
            self.admission.release(status.job_id)
            # fleet: completion releases the ownership lease (the terminal
            # checkpoint is already durable) so the lock never lingers as
            # an adoptable expired lease
            self._release_lease(status.job_id)
            # backstop: success pops this at capture time; failed/cancelled
            # (and crashed-handler) paths release the serving info here
            with self._meta_lock:
                self._serving_info.pop(status.job_id, None)
            # finalize the job's trace/profile off the retained graph —
            # one hook covers success, failure, cancel and admission shed
            try:
                self.obs.on_finished(status,
                                     self.jobs.get_graph(status.job_id))
            except Exception:  # noqa: BLE001 — observability is best-effort
                log.exception("profile finalization failed for %s",
                              status.job_id)

    def update_task_status(self, executor_id: str,
                           statuses: List[TaskStatus]) -> None:
        # coalesce: append to the executor's inbox, and post a drain event
        # only when the inbox was empty — N reports landing while one event
        # is in flight are absorbed together by that single event
        with self._status_lock:
            box = self._status_inbox.setdefault(executor_id, [])
            was_empty = not box
            box.extend(statuses)
        if was_empty:
            self._event_loop.post(TaskUpdating(executor_id, None))

    def cancel_job(self, job_id: str) -> None:
        self._event_loop.post(JobCancel(job_id))

    def get_job_status(self, job_id: str) -> Optional[JobStatus]:
        return self.jobs.get_status(job_id)

    def wait_for_job(self, job_id: str, timeout: float = 300.0) -> JobStatus:
        return self.jobs.wait_for_completion(job_id, timeout)

    def pending_task_count(self) -> int:
        return sum(g.available_task_count() for g in self.jobs.active_graphs())

    # --- event machine ---------------------------------------------------
    def _on_event_error(self, event: object, exc: BaseException) -> None:
        """A handler crash must not strand the affected job in 'running'
        forever — clients poll status, and without this they wait out the
        full job deadline on a job no handler will ever touch again."""
        job_ids = set()
        jid = getattr(event, "job_id", None)
        if jid:
            job_ids.add(jid)
        # TaskUpdating has no job_id field; its affected jobs ride in the
        # statuses' task ids.  A drain event (statuses=None) crashed before
        # emptying its inbox — pull the unprocessed reports out now, or the
        # jobs they belong to hang until the job deadline
        statuses = getattr(event, "statuses", None)
        if statuses is None and isinstance(event, TaskUpdating):
            with self._status_lock:
                statuses = self._status_inbox.pop(event.executor_id, [])
        for st in statuses or []:
            task = getattr(st, "task", None)
            if task is not None and getattr(task, "job_id", None):
                job_ids.add(task.job_id)
        for job_id in job_ids:
            st = self.jobs.get_status(job_id)
            if st is not None and st.state in ("successful", "failed",
                                               "cancelled"):
                continue
            # stop the graph too, or the scheduler keeps launching its
            # remaining tasks and a late 'job_successful' event would
            # overwrite the failed status the client already saw
            graph = self.jobs.get_graph(job_id)
            if graph is not None and graph.status == "running":
                graph.status = "failed"
            with self._meta_lock:
                self._queued_at_ms.pop(job_id, None)
            self.jobs.set_status(JobStatus(
                job_id, "failed",
                error=f"scheduler event handler crashed: "
                      f"{type(exc).__name__}: {exc}"))
            self.metrics.record_failed(job_id)

    def _on_event(self, event: object) -> None:
        # log <-> trace correlation: job-scoped events stamp their job id
        # onto every record the handler emits (utils/logsetup.ContextFilter)
        job_id = getattr(event, "job_id", "")
        if job_id:
            from ..utils.logsetup import log_scope

            with log_scope(job_id=job_id):
                self._dispatch_event(event)
        else:
            self._dispatch_event(event)

    def _dispatch_event(self, event: object) -> None:
        if isinstance(event, JobQueued):
            self._on_job_queued(event)
        elif isinstance(event, JobPlanned):
            self._on_job_planned(event)
        elif isinstance(event, TaskUpdating):
            self._on_task_updating(event)
        elif isinstance(event, ExecutorLost):
            self._on_executor_lost(event)
        elif isinstance(event, JobCancel):
            self._on_job_cancel(event)
        elif isinstance(event, JobDeadline):
            self._on_job_deadline(event)
        elif isinstance(event, Offer):
            self._offer()
        elif isinstance(event, SpeculationTick):
            self._on_speculation_tick()
        elif isinstance(event, PollWork):
            self._on_poll_work(event)
        else:
            log.warning("unknown scheduler event %r", event)

    def _on_job_queued(self, ev: JobQueued) -> None:
        # planning (incl. scalar subquery evaluation) can take seconds —
        # run it off the event loop so scheduling stays responsive
        # (reference spawns planning too, query_stage_scheduler.rs:106-148)
        def plan():
            try:
                with self._meta_lock:
                    cfg = self._job_configs.pop(ev.job_id, None)
                    serving = self._serving_info.get(ev.job_id)
                plan, scalars = ev.plan_fn()
                graph = ExecutionGraph.build(ev.job_id, plan)
                if serving is not None and serving.prevalidated:
                    # template hit: the plan validated at template creation
                    # and any scan-layout change would have invalidated the
                    # template (table-version fingerprint), so skip
                    pass
                elif cfg is None or cfg.get(ANALYSIS_PLAN_CHECKS):
                    # pre-launch sanity validation (analysis/plan_checks.py):
                    # reject broken stage wiring before any task runs
                    validate_graph(graph)
                if serving is not None and serving.pending_template is not None:
                    # only a plan whose graph built (and validated) above
                    # may become a reusable template
                    self.plan_cache.store(serving.pending_template)
                    serving.pending_template = None
                # runtime re-optimization knobs for this job's lifetime
                # (ballista.aqe.*, defaults apply when no session config)
                graph.aqe = AqePolicy.from_config(cfg)
                # whole-stage compiler (ballista.compile.*): the policy
                # arms revive()-time fusion for downstream stages; the
                # leaf stages that resolved during graph build are fused
                # here, after validation and before any task launches
                graph.compiler = CompilePolicy.from_config(cfg)
                fuse_resolved_stages(graph)
                graph.scalars = scalars
                graph.addr_resolver = self._resolve_addr
                # server-side deadline: a positive session/per-submit
                # ballista.query.deadline.seconds overrides the scheduler
                # default; the clock runs from SUBMISSION (queued time
                # counts), and the absolute expiry rides the checkpoint
                deadline_s = self.config.query_deadline_s
                if cfg is not None:
                    from ..utils.config import QUERY_DEADLINE_S

                    v = float(cfg.get(QUERY_DEADLINE_S))
                    if v > 0:
                        deadline_s = v
                if deadline_s > 0:
                    with self._meta_lock:
                        queued_at = self._queued_at_ms.get(ev.job_id, 0)
                    start = queued_at / 1000.0 if queued_at else time.time()
                    graph.deadline_s = deadline_s
                    graph.deadline_ts = start + deadline_s
                if serving is not None and serving.subplan:
                    self._preload_subplans(graph, serving)
                self._event_loop.post(JobPlanned(ev.job_id, graph))
            except Exception as e:  # noqa: BLE001 — planning failure fails the job
                log.exception("planning failed for job %s", ev.job_id)
                self._event_loop.post(JobPlanned(ev.job_id, None,
                                                 f"planning error: {e}"))

        self._submit_work(plan)

    def _preload_subplans(self, graph: ExecutionGraph, serving) -> None:
        """Fingerprint every non-final stage and complete those whose
        shuffle output is already cached (serving subplan cache).  Runs on
        the planning worker BEFORE the graph is published to the event
        loop, so graph access is single-threaded; cached bytes are spooled
        to scheduler-local files that port-0 locations point at."""
        from ..ops.shuffle import ShuffleWritePartition
        from .serving_cache import stage_fingerprint, subplan_cache_key

        for sid, stage in graph.stages.items():
            if not stage.output_links:
                continue  # final stage: the result cache's domain
            if stage.producer_ids:
                # only LEAF stages: a leaf's fingerprint fully determines
                # its computation, while a downstream stage's plan sees its
                # inputs only as UnresolvedShuffleExec stubs — two queries
                # with different upstream filters would fingerprint alike
                continue
            try:
                serving.stage_fps[sid] = stage_fingerprint(stage.plan)
            except Exception:  # noqa: BLE001 — unfingerprintable plan shape
                log.warning("stage fingerprint failed for job %s stage %d",
                            graph.job_id, sid, exc_info=True)
        # ascending stage ids are topological (the planner numbers stages
        # bottom-up), so producers complete before consumers resolve
        for sid in sorted(serving.stage_fps):
            key = subplan_cache_key(serving.stage_fps[sid],
                                    serving.config_fp, serving.table_fp)
            payload = self.result_cache.get(key)
            if payload is None:
                continue
            outputs = {}
            for map_part, _executor_id, rows in payload["outputs"]:
                writes = []
                for i, (out_part, num_rows, num_bytes, crc, data) in \
                        enumerate(rows):
                    path = self.result_cache.spool(
                        graph.job_id, sid, f"{map_part}-{i}.arrow", data)
                    writes.append(ShuffleWritePartition(
                        out_part, path, num_rows, num_bytes, crc))
                outputs[map_part] = ("subplan-cache", writes)
            if graph.preload_stage(sid, outputs):
                serving.preloaded.add(sid)

    def _capture_serving(self, graph: ExecutionGraph, locations,
                         serving) -> None:
        """Copy a successful job's result (and completed non-preloaded
        stage outputs) into the result cache.  Runs on a worker thread
        right after the terminal status — well inside the
        job-data-cleanup delay, after which the source files vanish."""
        from .serving_cache import (
            capture_result_payload,
            capture_stage_payload,
            subplan_cache_key,
        )

        try:
            if serving.capture_result and serving.result_key is not None \
                    and serving.schema is not None:
                cap = capture_result_payload(
                    locations, serving.schema,
                    self.result_cache.max_entry_bytes)
                if cap is not None:
                    self.result_cache.put(serving.result_key, cap[0], cap[1])
                    if serving.tables:
                        # key[1:4] = (norm_text, params, config_fp)
                        self.result_cache.remember_tables(
                            tuple(serving.result_key[1:4]), serving.tables)
            if serving.subplan:
                for sid, fp in serving.stage_fps.items():
                    if sid in serving.preloaded:
                        continue
                    stage = graph.stages.get(sid)
                    if stage is None or stage.state != "successful":
                        continue
                    cap = capture_stage_payload(
                        stage, self.result_cache.max_entry_bytes)
                    if cap is not None:
                        self.result_cache.put(
                            subplan_cache_key(fp, serving.config_fp,
                                              serving.table_fp),
                            cap[0], cap[1], kind="subplan")
        except Exception:  # noqa: BLE001 — capture is best-effort
            log.exception("serving-cache capture failed for job %s",
                          graph.job_id)

    def _on_job_planned(self, ev: JobPlanned) -> None:
        if ev.graph is None:
            if journal.enabled():
                journal.emit_job("job.plan_failed", ev.job_id, error=ev.error)
            self.jobs.set_status(JobStatus(ev.job_id, "failed", error=ev.error))
            self.metrics.record_failed(ev.job_id)
            with self._meta_lock:
                self._queued_at_ms.pop(ev.job_id, None)
            return
        self.obs.on_planned(ev.job_id)
        if journal.enabled():
            journal.emit_job("job.planned", ev.job_id,
                             stages=len(ev.graph.stages))
        # hand the execution span's context to every task of this job
        ev.graph.start_trace(self.obs.task_parent(ev.job_id))
        self.jobs.submit_job(ev.job_id, ev.graph)
        with self._meta_lock:
            queued_at = self._queued_at_ms.get(ev.job_id, 0)
        self.metrics.record_submitted(ev.job_id, queued_at,
                                      int(time.time() * 1000))
        self._checkpoint(ev.graph)
        self._offer()

    def _checkpoint(self, graph: ExecutionGraph) -> bool:
        """Persist the graph.  Returns False only when this shard lost the
        job's lease (another shard adopted it) — the caller must stop
        driving the job; plain persistence failures stay best-effort."""
        if self.job_backend is None:
            return True
        if journal.enabled():
            # the checkpoint carries the job's merged timeline, so the
            # flight record survives failover (the adopter seeds from it)
            graph.journal = journal.job_timeline(graph.job_id)
        if not self._lease_capable:
            try:
                self.job_backend.try_acquire_job(graph.job_id,
                                                 self.scheduler_id)
                self.job_backend.save_job(graph)
            except Exception:  # noqa: BLE001 — persistence is best-effort
                log.exception("job checkpoint failed for %s", graph.job_id)
            return True
        from .kv import LeaseLost

        try:
            epoch = self._acquire_job_lease(graph.job_id)
            if epoch is None:
                self._on_lease_lost(graph.job_id,
                                    "lease held by another shard")
                return False
            self.job_backend.save_job(graph, owner=self.scheduler_id,
                                      epoch=epoch)
            return True
        except LeaseLost as e:
            self._on_lease_lost(graph.job_id, str(e))
            return False
        except Exception:  # noqa: BLE001 — persistence is best-effort
            log.exception("job checkpoint failed for %s", graph.job_id)
            return True

    def _acquire_job_lease(self, job_id: str) -> Optional[int]:
        """The epoch this shard holds the job's lease at, acquiring the
        lease on first use (fresh jobs claim at first checkpoint)."""
        with self._lease_lock:
            epoch = self._leases.get(job_id)
        if epoch is not None:
            return epoch
        lease = self.job_backend.acquire_lease(
            job_id, self.scheduler_id, endpoint=self.client_endpoint,
            ttl_s=self.config.fleet_lease_ttl_s)
        if lease is None:
            return None
        with self._lease_lock:
            self._leases[job_id] = lease.epoch
        if journal.enabled():
            journal.set_job_epoch(job_id, lease.epoch)
            journal.emit_job("lease.acquire", job_id, epoch=lease.epoch,
                             scheduler_id=self.scheduler_id)
        return lease.epoch

    def _release_lease(self, job_id: str) -> None:
        if not self._lease_capable:
            return
        with self._lease_lock:
            held = self._leases.pop(job_id, None)
        if held is None:
            return
        try:
            self.job_backend.release_lease(job_id, self.scheduler_id)
        except Exception:  # noqa: BLE001 — lease will expire regardless
            log.exception("lease release failed for %s", job_id)

    def _on_lease_lost(self, job_id: str, why: str) -> None:
        """Fencing kicked in: another shard owns the job now.  Drop every
        local trace of it; the adopter relaunches what was in flight and
        records all further state.  No cancel goes to the executors: a
        cancel names the job, not this shard's attempts, so it would kill
        the tasks the adopter has already launched there, mark the job
        cancelled for every later one and sweep its shuffle files.  Our
        in-flight attempts run out as they do after a crashed owner."""
        with self._lease_lock:
            self._leases.pop(job_id, None)
        if self.jobs.get_status(job_id) is None:
            return
        log.warning("lost lease on job %s (%s): abandoning local drive",
                    job_id, why)
        if journal.enabled():
            # emitted BEFORE the epoch clears, so the stand-down is stamped
            # with the fenced-off epoch this shard last held
            journal.emit_job("lease.stand_down", job_id, why=why,
                             scheduler_id=self.scheduler_id)
            journal.set_job_epoch(job_id, 0)
        # retain this shard's half of the job trace with a stand-down
        # marker before the job is dropped locally (the adopter's spans
        # continue the same trace_id via the checkpointed context)
        self.obs.on_stand_down(job_id, why)
        self.jobs.remove_job(job_id)
        with self._meta_lock:
            self._queued_at_ms.pop(job_id, None)
            self._serving_info.pop(job_id, None)
        self.admission.release(job_id)

    def recover_jobs(self) -> List[str]:
        """Adopt persisted unfinished jobs (reference try_acquire_job,
        cluster/mod.rs:347-350).  Call after init() once executors have a
        chance to re-register."""
        if self.job_backend is None:
            return []
        adopted = []
        for job_id in self.job_backend.list_jobs():
            if self.jobs.get_status(job_id) is not None:
                continue
            if self._lease_capable:
                if self._adopt_one(job_id):
                    adopted.append(job_id)
                continue
            if not self.job_backend.try_acquire_job(job_id, self.scheduler_id):
                continue
            graph = self.job_backend.load_job(job_id)
            if graph is None or graph.status != "running":
                continue
            graph.addr_resolver = self._resolve_addr
            self.jobs.accept_job(job_id)
            self.jobs.submit_job(job_id, graph)
            adopted.append(job_id)
            log.info("adopted persisted job %s", job_id)
        if adopted:
            self._event_loop.post(Offer())
        return adopted

    # --- fleet HA: lease renewal + adoption ------------------------------
    def _lease_loop(self) -> None:
        """Lease heartbeat: renew every held job lease and refresh this
        shard's registry entry.  Not an event handler — blocking KV calls
        are fine here (same idiom as ``_reap_loop``)."""
        ttl = self.config.fleet_lease_ttl_s
        interval = self.config.fleet_lease_renew_s or ttl / 3.0
        while not self._stopped.wait(interval):
            with self._lease_lock:
                held = dict(self._leases)
            for job_id, epoch in held.items():
                try:
                    faults.inject("scheduler.lease.renew", job_id=job_id,
                                  scheduler_id=self.scheduler_id)
                except Exception as e:  # noqa: BLE001 — injected partition
                    log.warning("lease renewal suppressed for %s: %s",
                                job_id, e)
                    continue
                try:
                    if self.job_backend.renew_lease(
                            job_id, self.scheduler_id, epoch) is None:
                        self._on_lease_lost(job_id, "renewal refused")
                    elif journal.enabled():
                        journal.emit("lease.renew", job_id=job_id,
                                     epoch=epoch,
                                     scheduler_id=self.scheduler_id)
                except Exception:  # noqa: BLE001 — KV blip; TTL still runs
                    log.exception("lease renewal failed for %s", job_id)
            self._publish_registry()

    def _publish_registry(self) -> None:
        store = getattr(self.job_backend, "store", None)
        if store is None:
            return
        from .kv import publish_scheduler

        try:
            publish_scheduler(store, self.scheduler_id, self.client_endpoint,
                              sample=self._registry_sample())
        except Exception:  # noqa: BLE001 — registry is advisory
            log.exception("shard registry publish failed")

    _REGISTRY_KEYS = ("pending_tasks", "active_jobs",
                      "admission_queue_depth", "utilization", "total_slots",
                      "available_slots", "executors_alive")

    def _registry_sample(self) -> Dict:
        s = self.cluster_sample()
        out = {k: s[k] for k in self._REGISTRY_KEYS}
        # SLO piggyback: raw (count, violations) pairs per burn window so
        # any shard can merge a fleet-wide burn rate by summation (empty
        # for the null tracker — wire shape unchanged when SLO is off)
        out.update(self.slo.sample())
        return out

    def _adopt_loop(self) -> None:
        while not self._stopped.wait(self.config.fleet_adopt_interval_s):
            try:
                self.adopt_expired_jobs()
                # the slots are shared: another shard (a fenced ex-owner
                # absorbing its last statuses) can free them, and no event
                # of ours says so.  An offer that came up empty is made
                # again here, or an adopted job would wait for ever
                if self.pending_task_count() > 0:
                    self._event_loop.post(Offer())
            except Exception:  # noqa: BLE001 — scan again next interval
                log.exception("lease adoption scan failed")

    def adopt_expired_jobs(self) -> List[str]:
        """Scan the shared KV for jobs whose owner stopped renewing (crash,
        partition, kill -9) and adopt them: take the lease over — bumping
        the fencing epoch — reload the graph from its last checkpoint, and
        resume driving it."""
        if not self._lease_capable or self._stopped.is_set():
            return []
        adopted: List[str] = []
        for stale in self.job_backend.expired_leases(
                self.config.fleet_lease_ttl_s):
            if stale.owner == self.scheduler_id:
                continue  # our own expiry: the renewal loop handles it
            if self.jobs.get_status(stale.job_id) is not None:
                continue
            if self._adopt_one(stale.job_id, prev_owner=stale.owner):
                adopted.append(stale.job_id)
        if adopted:
            self._event_loop.post(Offer())
        return adopted

    def _adopt_one(self, job_id: str, prev_owner: str = "") -> bool:
        lease = self.job_backend.acquire_lease(
            job_id, self.scheduler_id, endpoint=self.client_endpoint,
            ttl_s=self.config.fleet_lease_ttl_s)
        if lease is None:
            return False  # the owner came back, or another shard won
        faults.inject("scheduler.adopt.before_resume", job_id=job_id,
                      scheduler_id=self.scheduler_id)
        graph = self.job_backend.load_job(job_id)
        if graph is None or graph.status != "running":
            # the ex-owner finished the job (adoption raced completion) or
            # it never reached a running checkpoint: nothing to drive —
            # drop the claim so the lock doesn't linger as expired
            self.job_backend.release_lease(job_id, self.scheduler_id)
            return False
        with self._lease_lock:
            self._leases[job_id] = lease.epoch
        graph.addr_resolver = self._resolve_addr
        self.jobs.accept_job(job_id)
        self.jobs.submit_job(job_id, graph)
        if journal.enabled():
            # continue the ex-owner's flight record under the same job id
            # (the checkpoint carried its timeline), then mark the
            # ownership change at the new fencing epoch
            journal.seed_job(job_id,
                             list(getattr(graph, "journal", []) or []))
            journal.set_job_epoch(job_id, lease.epoch)
            journal.emit_job("lease.adopt", job_id, epoch=lease.epoch,
                             prev_owner=prev_owner,
                             scheduler_id=self.scheduler_id)
        # trace continuity across the failover: open this shard's side of
        # the job trace (same trace_id as the ex-owner when the checkpoint
        # carried it) with the fencing epoch annotated, then re-parent the
        # relaunched tasks under the adopter's execution phase
        self.obs.on_adopted(job_id, lease.epoch, prev_owner=prev_owner,
                            scheduler_id=self.scheduler_id,
                            trace=dict(getattr(graph, "trace", {}) or {}))
        graph.start_trace(self.obs.task_parent(job_id))
        log.info("adopted job %s at lease epoch %d", job_id, lease.epoch)
        return True

    def _on_task_updating(self, ev: TaskUpdating) -> None:
        statuses = ev.statuses
        if statuses is None:
            with self._status_lock:
                statuses = self._status_inbox.pop(ev.executor_id, [])
        if not statuses:
            # a sibling event already drained this inbox
            return
        self.cluster.free_slots(ev.executor_id, len(statuses))
        self._absorb_statuses(ev.executor_id, statuses)
        self._offer()

    def _on_executor_lost(self, ev: ExecutorLost) -> None:
        log.info("executor %s lost: %s", ev.executor_id, ev.reason)
        self.cluster.remove_executor(ev.executor_id)
        self.quarantine.remove(ev.executor_id)
        for graph in self.jobs.active_graphs():
            graph.executor_lost(ev.executor_id)
            # rolled-back stages re-resolve inside executor_lost, which may
            # re-apply AQE rewrites — surface their metric events too
            self._drain_aqe_events(graph)
        self._offer()

    def _on_job_cancel(self, ev: JobCancel) -> None:
        graph = self.jobs.get_graph(ev.job_id)
        if graph is None or graph.status != "running":
            # the job may still be waiting in the admission queue: pull it
            # out so it never plans, and free its tenant's queue slot
            if self.admission.take_queued(ev.job_id):
                with self._meta_lock:
                    self._queued_at_ms.pop(ev.job_id, None)
                    self._job_configs.pop(ev.job_id, None)
                if journal.enabled():
                    journal.emit_job("job.cancelled", ev.job_id, queued=True)
                self.jobs.set_status(JobStatus(ev.job_id, "cancelled"))
                self.metrics.record_cancelled(ev.job_id)
            return
        if journal.enabled():
            journal.emit_job("job.cancelled", ev.job_id)
        graph.cancel()
        self.jobs.set_status(JobStatus(ev.job_id, "cancelled"))
        self.metrics.record_cancelled(ev.job_id)
        with self._meta_lock:
            self._queued_at_ms.pop(ev.job_id, None)
        self._drop_poison_evidence(ev.job_id)
        self._cancel_running(graph)
        self._schedule_job_data_cleanup(graph)

    # --- job-data cleanup ------------------------------------------------
    def _schedule_job_data_cleanup(self, graph: ExecutionGraph) -> None:
        """Schedule a delayed remove_job_data fanout to every executor
        holding shuffle output for this finished job (reference
        clean_up_job_data, executor_manager.rs:231-253).  The TTL janitor
        on each executor remains the backstop for fanouts that miss."""
        delay = self.config.job_data_cleanup_delay_s
        if delay < 0 or self._stopped.is_set():
            return
        executors = {eid for stage in graph.stages.values()
                     for (eid, _w) in stage.outputs.values()}
        status = self.jobs.get_status(graph.job_id)
        if status is None or status.state != "successful":
            # a cancelled/expired/poisoned job can have stalled tasks that
            # wake AFTER the terminal verdict and write shuffle files no
            # stage ever registered — fan the remove to the whole fleet,
            # not just the executors with recorded outputs
            executors |= {m.executor_id for m in self.cluster.executors()}
        executors = sorted(executors)
        if not executors:
            return
        job_id = graph.job_id

        def fanout():
            with self._cleanup_lock:
                self._cleanup_timers.pop(job_id, None)
            if self._stopped.is_set():
                return
            # subplan spool files rehydrated for this job die with it
            self.result_cache.cleanup_job(job_id)
            for eid in executors:
                try:
                    self.launcher.clean_job_data(eid, job_id)
                except Exception:  # noqa: BLE001 — best effort
                    log.warning("clean_job_data on %s failed", eid,
                                exc_info=True)

        timer = threading.Timer(delay, fanout)
        timer.daemon = True
        with self._cleanup_lock:
            old = self._cleanup_timers.pop(job_id, None)
            self._cleanup_timers[job_id] = timer
        if old is not None:
            old.cancel()
        timer.start()

    def _cancel_one(self, executor_id: str, task_id: TaskId) -> None:
        try:
            self.launcher.cancel_task(executor_id, task_id)
        except Exception:  # noqa: BLE001 — best effort
            log.warning("cancel_task on %s failed for %s", executor_id,
                        task_id, exc_info=True)

    def _cancel_running(self, graph: ExecutionGraph) -> None:
        executors = {eid for _, _, eid in graph.running_tasks()}
        for eid in executors:
            try:
                self.launcher.cancel_tasks(eid, graph.job_id)
            except Exception:  # noqa: BLE001
                log.exception("cancel_tasks failed for %s", eid)

    def poll_work(self, executor_id: str, num_free_slots: int,
                  statuses: List[TaskStatus],
                  timeout: float = 10.0) -> List[TaskDescription]:
        """Pull-mode entry (blocking): returns up to num_free_slots tasks."""
        reply: "queue.Queue" = queue.Queue(maxsize=1)
        self._event_loop.post(PollWork(executor_id, num_free_slots,
                                       statuses, reply))
        try:
            return reply.get(timeout=timeout)
        except queue.Empty:
            return []

    def _on_poll_work(self, ev: PollWork) -> None:
        tasks: List[TaskDescription] = []
        try:
            # timestamp-only refresh: a poll from a draining executor must
            # not flip its 'terminating' status back to active
            self.cluster.touch_heartbeat(ev.executor_id)
            if ev.statuses:
                self._absorb_statuses(ev.executor_id, ev.statuses)
            if self.quarantine.is_quarantined(ev.executor_id):
                return  # reply with no tasks (finally still runs)
            graphs = self.jobs.active_graphs()
            # retry anti-affinity context (see pop_next_task)
            alive = set(self.quarantine.filter(
                self.cluster.alive_executors(self.config.executor_timeout_s)))
            gate = self.admission.slot_gate(
                lambda: {g.job_id: len(g.running_tasks()) for g in graphs})
            while len(tasks) < ev.num_free_slots:
                task = None
                for graph in graphs:
                    if gate is not None and not gate.allows(graph.job_id):
                        continue
                    task = graph.pop_next_task(ev.executor_id, alive=alive)
                    if task is not None:
                        if gate is not None:
                            gate.took(graph.job_id)
                        break
                if task is None:
                    break
                tasks.append(task)
        finally:
            ev.reply.put(tasks)

    def _absorb_statuses(self, executor_id: str,
                         statuses: List[TaskStatus]) -> None:
        """Shared status intake (used by push TaskUpdating and pull
        PollWork)."""
        self._record_quarantine_signals(executor_id, statuses)
        by_job: Dict[str, List[TaskStatus]] = {}
        for st in statuses:
            if st.device_stats:
                # fleet-wide device-observatory fold: each status carries
                # the task's own delta, so summing on intake is exact
                self.metrics.record_device_stats(st.device_stats)
            if st.journal:
                # executor flight-record piggyback: merge into the job's
                # timeline (wire contract mirrors device_stats)
                journal.absorb(st.task.job_id, st.journal)
            if journal.enabled():
                journal.emit("task.finish", job_id=st.task.job_id,
                             parent_key=("task", st.task.job_id,
                                         st.task.stage_id,
                                         st.task.partition,
                                         st.task.task_attempt),
                             stage_id=st.task.stage_id,
                             partition=st.task.partition,
                             attempt=st.task.task_attempt,
                             state=st.state,
                             executor_id=st.executor_id or executor_id)
            by_job.setdefault(st.task.job_id, []).append(st)
        for job_id, sts in by_job.items():
            graph = self.jobs.get_graph(job_id)
            if graph is None:
                continue
            if job_id in self._poison_suspects:
                # containment beats retry: fail the job NOW, before the
                # graph's retry bookkeeping re-launches the poison
                # partition and burns another executor's slot
                self._poison_suspects.discard(job_id)
                try:
                    self._fail_poisoned(job_id, graph)
                except Exception:  # noqa: BLE001 — scope the blast radius
                    log.exception("poison containment crashed for job %s",
                                  job_id)
                continue
            try:
                self._absorb_job_statuses(job_id, graph, sts)
            except Exception as e:  # noqa: BLE001 — scope the blast radius
                # a crash absorbing ONE job's statuses must not fail the
                # other jobs in the batch (their updates were already
                # applied, or will be, independently)
                log.exception("status absorption crashed for job %s", job_id)
                st = self.jobs.get_status(job_id)
                if st is not None and st.state in ("successful", "failed",
                                                   "cancelled"):
                    # the crash happened AFTER a terminal status was
                    # published (e.g. in metrics/cleanup scheduling) —
                    # don't overwrite what clients already saw
                    continue
                if graph.status == "running":
                    graph.status = "failed"
                with self._meta_lock:
                    self._queued_at_ms.pop(job_id, None)
                # durable before visible, same as the success path below
                self._checkpoint(graph)
                self.jobs.set_status(JobStatus(
                    job_id, "failed",
                    error=f"status absorption crashed: "
                          f"{type(e).__name__}: {e}"))
                self.metrics.record_failed(job_id)

    def _fleet_memory_pressure(self) -> float:
        """Fleet-wide memory-pressure floor (admission's shed signal);
        0.0 for cluster backends without pressure tracking."""
        fn = getattr(self.cluster, "min_alive_pressure", None)
        return fn(self.config.executor_timeout_s) if fn is not None else 0.0

    def _record_quarantine_signals(self, executor_id: str,
                                   statuses: List[TaskStatus]) -> None:
        """Feed the quarantine counter: a success clears the reporting
        executor's streak; a *retryable* failure (IOError/ExecutorLost/
        ResultLost) extends it.  Fetch failures blame the producer's data
        and fatal ExecutionErrors fail the job outright — neither says this
        executor is sick, so neither counts.  ResourceExhausted is
        retryable but ALSO exempt: a governor denial means the executor
        protected itself from OOM — blaming it into quarantine would
        quarantine the whole fleet exactly when memory is tight."""
        for st in statuses:
            eid = st.executor_id or executor_id
            if st.state == "success":
                self.quarantine.record_success(eid)
            elif (st.state == "failed" and st.failure is not None
                  and st.failure.kind == RESOURCE_EXHAUSTED):
                # no strike, no streak reset: memory back-pressure says
                # nothing about this executor's health either way
                pass
            elif (st.state == "failed" and st.failure is not None
                  and st.failure.kind == FETCH_PARTITION_ERROR
                  and "integrity check failed" in st.failure.message):
                # a checksum/decode failure that survived the fetcher's
                # in-loop retries: the PRODUCER's data is damaged — count
                # the producing executor, not the reporting fetcher, so a
                # host serving corrupt partitions gets quarantined
                self.metrics.record_integrity_failure(st.failure.executor_id)
                if st.failure.executor_id and self.quarantine.record_failure(
                        st.failure.executor_id):
                    log.warning(
                        "executor %s quarantined: served corrupt shuffle "
                        "data (%s)", st.failure.executor_id,
                        st.failure.message)
                    self.metrics.record_quarantined(st.failure.executor_id)
                    if journal.enabled():
                        journal.emit("quarantine.enter",
                                     job_id=st.task.job_id,
                                     executor_id=st.failure.executor_id,
                                     reason="corrupt shuffle data")
            elif (st.state == "failed" and st.failure is not None
                  and st.failure.retryable):
                if self._note_poison_evidence(eid, st):
                    # a DIFFERENT executor already failed this exact
                    # partition the same way: the evidence points at the
                    # query, not this host — corroborating failures carry
                    # no quarantine strike
                    continue
                if self.quarantine.record_failure(eid):
                    log.warning(
                        "executor %s quarantined after %d consecutive "
                        "retryable task failures (probation in %.0fs)", eid,
                        self.quarantine.threshold,
                        self.quarantine.probation_s)
                    self.metrics.record_quarantined(eid)
                    if journal.enabled():
                        journal.emit("quarantine.enter",
                                     job_id=st.task.job_id,
                                     executor_id=eid,
                                     reason="consecutive retryable failures")
        self.metrics.set_quarantined_executors(self.quarantine.count())

    # --- poison-query containment ----------------------------------------
    def _note_poison_evidence(self, eid: str, st: TaskStatus) -> bool:
        """Record one retryable failure as poison evidence.  Returns True
        when the quarantine strike should be SUPPRESSED because another
        executor already failed the same partition with an equivalent
        error (the query is the prime suspect, not this host).  Once the
        same signature lands on ``poison_distinct_executors`` distinct
        non-quarantined executors, the job is queued for containment.
        Event-loop only (push TaskUpdating and pull PollWork both absorb
        on the loop)."""
        k = self.config.poison_distinct_executors
        if k <= 0 or st.failure is None:
            return False
        key = (st.task.job_id, st.task.stage_id, st.task.partition)
        sig = f"{st.failure.kind}: {st.failure.message[:160]}"
        ev = self._poison_evidence.setdefault(key, {})
        corroborated = any(e != eid and s == sig for e, (s, _w) in ev.items())
        # a witness counts if it was healthy when it FIRST testified —
        # judged at record time, because the poison query's own strikes
        # may quarantine an executor before the Kth failure lands, and a
        # host the query itself knocked out is still a valid witness
        if eid in ev:
            ev[eid] = (sig, ev[eid][1])
        else:
            ev[eid] = (sig, not self.quarantine.is_quarantined(eid))
        distinct = {e for e, (s, w) in ev.items() if s == sig and w}
        if len(distinct) >= k:
            self._poison_suspects.add(st.task.job_id)
        return corroborated

    def _drop_poison_evidence(self, job_id: str) -> None:
        """Forget a terminal job's poison bookkeeping (event-loop only)."""
        self._poison_suspects.discard(job_id)
        for key in [k for k in self._poison_evidence if k[0] == job_id]:
            del self._poison_evidence[key]

    def _fail_poisoned(self, job_id: str, graph) -> None:
        """Containment: the same partition failed with equivalent errors on
        K distinct executors — the query is the culprit.  Fail it
        immediately (skipping the per-task retry budget), refund every
        implicated executor's quarantine streak, and attach a forensics
        bundle so the failure is diagnosable post-mortem."""
        if graph.status != "running":
            self._drop_poison_evidence(job_id)
            return
        k = self.config.poison_distinct_executors
        evidence: Dict[str, Dict[str, str]] = {}
        implicated = set()
        for (jid, sid, p), ev in self._poison_evidence.items():
            if jid != job_id:
                continue
            evidence[f"{sid}/{p}"] = {e: s for e, (s, _w) in ev.items()}
            implicated.update(ev)
        # zero quarantine strikes: the poison query burned healthy hosts,
        # so wipe the streaks it charged them (forced poison queries must
        # end with an empty quarantine set)
        for eid in sorted(implicated):
            self.quarantine.record_success(eid)
        self.metrics.set_quarantined_executors(self.quarantine.count())
        message = (f"{POISON_QUERY}: same partition failed with equivalent "
                   f"errors on {k}+ distinct executors — job classified "
                   f"poison, retries abandoned")
        if journal.enabled():
            # before the checkpoint, so the terminal event (with its
            # per-executor evidence) rides the persisted timeline
            journal.emit_job("job.poisoned", job_id,
                             distinct_executors=str(k),
                             evidence=evidence)
        graph.status = "failed"
        graph.error = message
        with self._meta_lock:
            queued_at = self._queued_at_ms.pop(job_id, None)
        self._drop_poison_evidence(job_id)
        if not self._checkpoint(graph):
            return  # lease lost: the adopter owns this job now
        self.jobs.set_status(JobStatus(job_id, "failed", error=message,
                                       retriable=False))
        self.metrics.record_failed(job_id)
        self.metrics.record_poisoned(job_id)
        self.slo.record(
            int(time.time() * 1000) - queued_at if queued_at else 0.0,
            ok=False)
        log.warning("job %s classified poison: %s", job_id, message)
        self._cancel_running(graph)
        self._schedule_job_data_cleanup(graph)
        try:
            from ..obs.doctor import assemble_forensics
            graph.forensics = assemble_forensics(self, job_id)
        except Exception:  # noqa: BLE001 — forensics are best-effort
            log.warning("forensics assembly failed for %s", job_id,
                        exc_info=True)

    def _absorb_job_statuses(self, job_id: str, graph,
                             sts: List[TaskStatus]) -> None:
        checkpointed = False
        for kind, payload in graph.update_task_status(sts):
            if kind == "speculative_win":
                stage_id, partition = payload
                log.info("speculative attempt won: job %s stage %d "
                         "partition %d", job_id, stage_id, partition)
                self.metrics.record_speculative_win(job_id)
                if journal.enabled():
                    journal.emit("speculation.win", job_id=job_id,
                                 stage_id=stage_id, partition=partition)
            elif kind == "cancel_task":
                # first result won the race: reap the losing duplicate so
                # it stops burning a slot (its late status is discarded by
                # the graph's attempt bookkeeping either way)
                executor_id, task_id = payload
                if journal.enabled():
                    journal.emit("task.cancel", job_id=job_id,
                                 stage_id=task_id.stage_id,
                                 partition=task_id.partition,
                                 attempt=task_id.task_attempt,
                                 executor_id=executor_id)
                self._submit_work(self._cancel_one, executor_id, task_id)
            elif kind == "job_successful":
                # terminal state must be durable BEFORE waiters wake:
                # set_status releases wait_for_job, and a restarted
                # scheduler must never see a completed job as running
                if journal.enabled():
                    # before the checkpoint, so the terminal event is IN
                    # the persisted timeline
                    journal.emit_job("job.successful", job_id)
                if not self._checkpoint(graph):
                    return  # lease lost: the adopter owns this job now
                checkpointed = True
                with self._meta_lock:
                    serving = self._serving_info.pop(job_id, None)
                if serving is not None and (serving.capture_result
                                            or serving.subplan):
                    self._submit_work(self._capture_serving, graph, payload,
                                      serving)
                self.jobs.set_status(
                    JobStatus(job_id, "successful", locations=payload))
                with self._meta_lock:
                    queued_at = self._queued_at_ms.pop(job_id, 0)
                done_ms = int(time.time() * 1000)
                self.metrics.record_completed(job_id, queued_at, done_ms)
                if queued_at:
                    # SLO sample: queue-to-done wall time, the latency a
                    # waiting client observed (no-op on the null tracker)
                    self.slo.record(done_ms - queued_at, ok=True)
                self._drop_poison_evidence(job_id)
                self._schedule_job_data_cleanup(graph)
            elif kind == "job_failed":
                if journal.enabled():
                    journal.emit_job("job.failed", job_id,
                                     error=str(payload))
                if not self._checkpoint(graph):
                    return  # lease lost: the adopter owns this job now
                checkpointed = True
                self.jobs.set_status(
                    JobStatus(job_id, "failed", error=str(payload)))
                self.metrics.record_failed(job_id)
                with self._meta_lock:
                    queued_at = self._queued_at_ms.pop(job_id, None)
                # a failed job always burns SLO budget, whatever its wall time
                self.slo.record(
                    int(time.time() * 1000) - queued_at if queued_at else 0.0,
                    ok=False)
                self._drop_poison_evidence(job_id)
                self._cancel_running(graph)
                self._schedule_job_data_cleanup(graph)
        self._drain_aqe_events(graph)
        if not checkpointed:
            self._checkpoint(graph)  # False = abandoned; nothing more to do

    def _drain_aqe_events(self, graph) -> None:
        """Fold the graph's buffered AQE rewrite events into the metrics
        collector (rewrites happen inside graph mutation, which has no
        collector handle; the scheduler drains after every absorb)."""
        events = getattr(graph, "aqe_events", None)
        if not events:
            return
        for kind, n in events:
            if journal.enabled():
                journal.emit("aqe.rewrite", job_id=graph.job_id,
                             rewrite=kind, partitions=n)
            if kind == "coalesce":
                self.metrics.record_aqe_coalesce(n)
            elif kind == "broadcast":
                self.metrics.record_aqe_broadcast_switch(n)
            elif kind == "skew":
                self.metrics.record_aqe_skew_split(n)
        events.clear()

    def _resolve_addr(self, executor_id: str):
        meta = self.cluster.get_executor(executor_id)
        return (meta.host, meta.port) if meta is not None else ("", 0)

    # --- push scheduling -------------------------------------------------
    def _offer(self) -> None:
        """Reserve free slots and fill them with tasks (reference
        state/mod.rs:195-233 offer_reservation + fill_reservations)."""
        pending = self.pending_task_count()
        self.metrics.set_pending_tasks_queue_size(pending)
        # every scheduling round re-evaluates the admission queue against
        # live signals (completions, executor registrations/losses all
        # funnel through here)
        self.admission.pump()
        if self.config.policy != "push":
            return  # pull mode: executors come to us via poll_work
        alive = set(self.quarantine.filter(
            self.cluster.alive_executors(self.config.executor_timeout_s)))
        if pending == 0 or not alive:
            return
        reservations = self.cluster.reserve_slots(pending, sorted(alive))
        if not reservations:
            return
        assignments: Dict[str, List[TaskDescription]] = {}
        unused: List[ExecutorReservation] = []
        graphs = self.jobs.active_graphs()
        if self._lease_capable:
            # slots go only to jobs whose lease THIS shard holds: a job we
            # were fenced off of is the adopter's to drive, even if its
            # local teardown hasn't landed yet
            with self._lease_lock:
                owned = set(self._leases)
            graphs = [g for g in graphs if g.job_id in owned]
        gate = self.admission.slot_gate(
            lambda: {g.job_id: len(g.running_tasks()) for g in graphs})

        def fill(rs: List[ExecutorReservation]) -> List[ExecutorReservation]:
            leftovers: List[ExecutorReservation] = []
            for r in rs:
                task = None
                for graph in graphs:
                    if gate is not None and not gate.allows(graph.job_id):
                        continue
                    task = graph.pop_next_task(r.executor_id, alive=alive)
                    if task is not None:
                        if gate is not None:
                            gate.took(graph.job_id)
                        break
                if task is None:
                    leftovers.append(r)
                else:
                    assignments.setdefault(r.executor_id, []).append(task)
            return leftovers

        unused = fill(reservations)
        if unused:
            # Retry anti-affinity can veto every reserved executor while a
            # DIFFERENT alive executor could legally run the pending task
            # (a retried partition is steered away from executors that
            # already failed it).  Once an idle fleet's offer round comes
            # up empty no further event re-triggers it, so convert the
            # veto into a steer with one bounded second pass over the
            # executors the first reservation round never tried.
            vetoed = {r.executor_id for r in unused}
            self.cluster.cancel_reservations(unused)
            unused = []
            retry_pool = sorted(alive - vetoed)
            if retry_pool:
                unused = fill(self.cluster.reserve_slots(
                    len(vetoed), retry_pool))
                if unused:
                    self.cluster.cancel_reservations(unused)
        for executor_id, tasks in assignments.items():
            self._submit_work(self._launch, executor_id, tasks)

    def _launch(self, executor_id: str, tasks: List[TaskDescription]) -> None:
        try:
            self.launcher.launch_tasks(executor_id, tasks)
        except Exception as e:  # noqa: BLE001 — treat as executor failure
            log.exception("launch on %s failed", executor_id)
            self.cluster.free_slots(executor_id, len(tasks))
            self._event_loop.post(ExecutorLost(executor_id, f"launch failed: {e}"))

    # --- speculative execution (straggler mitigation) --------------------
    def _speculation_loop(self) -> None:
        """Monitor thread: periodically posts a tick; the straggler scan
        itself runs on the event loop (single-threaded graph access)."""
        while not self._stopped.wait(self.config.speculation.interval_s):
            self._event_loop.post(SpeculationTick())

    def _on_speculation_tick(self) -> None:
        policy = self.config.speculation
        alive = set(self.quarantine.filter(
            self.cluster.alive_executors(self.config.executor_timeout_s)))
        if len(alive) < 2:
            return  # a duplicate must land on a DIFFERENT executor
        now = time.monotonic()
        for graph in self.jobs.active_graphs():
            for stage_id, partition, running_on in find_candidates(
                    graph, now, policy):
                pool = sorted(alive - {running_on})
                if not pool:
                    continue
                reservations = self.cluster.reserve_slots(1, pool)
                if not reservations:
                    continue
                executor_id = reservations[0].executor_id
                task = graph.launch_speculative(stage_id, partition,
                                                executor_id)
                if task is None:
                    self.cluster.cancel_reservations(reservations)
                    continue
                log.info(
                    "speculative attempt %d: job %s stage %d partition %d "
                    "on %s (original still running on %s)",
                    task.task.task_attempt, graph.job_id, stage_id,
                    partition, executor_id, running_on)
                self.metrics.record_speculative_launched(graph.job_id)
                if journal.enabled():
                    journal.emit("speculation.launch", job_id=graph.job_id,
                                 stage_id=stage_id, partition=partition,
                                 attempt=task.task.task_attempt,
                                 executor_id=executor_id,
                                 running_on=running_on)
                self._submit_work(self._launch, executor_id, [task])

    # --- cluster time series (obs/stats.py ClusterHistory) ---------------
    def cluster_sample(self) -> Dict:
        """One utilization/saturation sample (pure read — also served fresh
        as the ``now`` field of GET /api/cluster/history)."""
        total = self.cluster.total_slots()
        available = self.cluster.total_available()
        ev = self._event_loop.stats()
        return {
            "ts": round(time.time(), 3),
            "executors_alive": len(self.cluster.alive_executors(
                self.config.executor_timeout_s)),
            "executors_total": len(self.cluster.executors()),
            "total_slots": total,
            "available_slots": available,
            "utilization": round((total - available) / total, 4)
            if total else 0.0,
            "pending_tasks": self.pending_task_count(),
            "active_jobs": len(self.jobs.active_graphs()),
            "admission_queue_depth": self.admission.queue_depth(),
            "event_queue_depth": ev["queue_depth"],
            "event_loop_lag_s": ev["last_lag_s"],
            "event_loop_max_lag_s": ev["max_lag_s"],
            "event_handler_seconds_mean": ev["handler_seconds_mean"],
            "slow_events": ev["slow_events"],
        }

    def autoscale_signal(self) -> Dict:
        """KEDA-style scaling signal behind GET /api/autoscale: pending
        work, utilization and queue depths — aggregated across every live
        shard via the shared-KV shard registry when one exists, so any
        shard answers for the whole fleet (reference external_scaler.rs
        generalized from one scheduler to N)."""
        local = self.cluster_sample()
        shards = [{"scheduler_id": self.scheduler_id,
                   "endpoint": self.client_endpoint,
                   **{k: local[k] for k in self._REGISTRY_KEYS}}]
        store = getattr(self.job_backend, "store", None) \
            if self._lease_capable else None
        if store is not None:
            from .kv import scheduler_registry

            try:
                reg = scheduler_registry(store,
                                         self.config.fleet_registry_stale_s)
            except Exception:  # noqa: BLE001 — fall back to local-only
                log.exception("shard registry read failed")
                reg = {}
            for sid in sorted(reg):
                if sid == self.scheduler_id:
                    continue
                obj = reg[sid]
                sample = obj.get("sample") or {}
                shards.append({"scheduler_id": sid,
                               "endpoint": obj.get("endpoint", ""),
                               **{k: sample.get(k, 0)
                                  for k in self._REGISTRY_KEYS}})
        # flow is per-shard (each shard owns distinct jobs) so it sums;
        # capacity is the SHARED executor pool seen by every shard through
        # the common KV (executors multi-register), so summing would
        # multiply it by the shard count — take the freshest full view
        out = {k: sum(s.get(k, 0) for s in shards)
               for k in ("pending_tasks", "active_jobs",
                         "admission_queue_depth")}
        out.update({k: max(s.get(k, 0) for s in shards)
                    for k in ("total_slots", "available_slots",
                              "executors_alive")})
        total, avail = out["total_slots"], out["available_slots"]
        out["utilization"] = round((total - avail) / total, 4) if total else 0.0
        # slots needed for everything runnable now, in executor units at
        # the fleet's current mean slots-per-executor
        backlog = out["pending_tasks"] + out["admission_queue_depth"] \
            + (total - avail)
        per_exec = max(1.0, total / max(1, out["executors_alive"]))
        out["desired_executors"] = int(-(-backlog // per_exec))
        if self.slo.enabled:
            # SLO-aware term: a burn rate above 1.0 means the latency
            # budget is being consumed faster than it refills — ask for
            # extra executors proportional to the overshoot even when the
            # raw backlog alone would not scale (queueing shows up in
            # latency before it shows up in slot arithmetic)
            snap = self.slo.snapshot(
                shard_samples=self._sibling_slo_samples())
            burn = max(snap["windows"]["fast"]["burn_rate"],
                       snap["windows"]["slow"]["burn_rate"])
            # ceil(burn - 1), capped: a cold window with one slow job can
            # read burn=100x, which must not demand 99 extra executors
            boost = min(int(-(-(burn - 1.0) // 1)), 4) if burn > 1.0 else 0
            out["desired_executors"] += boost
            out["slo"] = {"burn_rate": burn, "scale_boost": boost,
                          "windows": snap["windows"]}
        out["inflight_tasks"] = out["pending_tasks"]  # /api/scaler parity
        out["shards"] = shards
        return out

    def _sibling_slo_samples(self) -> List[Dict]:
        """Sibling shards' SLO (count, violations) pairs from the shard
        registry — the fleet half of every burn-rate merge."""
        store = getattr(self.job_backend, "store", None) \
            if self._lease_capable else None
        if store is None:
            return []
        from .kv import scheduler_registry

        try:
            reg = scheduler_registry(store,
                                     self.config.fleet_registry_stale_s)
        except Exception:  # noqa: BLE001 — fall back to local-only
            log.exception("shard registry read failed")
            return []
        return [{k: v for k, v in (obj.get("sample") or {}).items()
                 if k.startswith("slo_")}
                for sid, obj in reg.items() if sid != self.scheduler_id]

    def slo_report(self) -> Dict:
        """GET /api/slo: the fleet-merged burn-rate report (or
        ``{"enabled": false}`` when no objective is configured)."""
        return self.slo.snapshot(shard_samples=self._sibling_slo_samples())

    def _history_loop(self) -> None:
        """Sampler thread: appends a cluster sample to the ring buffer and
        refreshes the event-loop gauges.  Not an event handler — blocking
        waits are fine here (same idiom as ``_reap_loop``)."""
        while not self._stopped.wait(self.config.stats_history_interval_s):
            try:
                sample = self.cluster_sample()
            except Exception:  # noqa: BLE001 — sampling must outlive one bad read
                log.exception("cluster history sampling failed")
                continue
            self.history.record(sample)
            self.metrics.set_event_queue_depth(sample["event_queue_depth"])
            self.metrics.set_event_loop_lag(sample["event_loop_lag_s"])
            self.sync_journal_metrics()
            if self.slo.enabled:
                # shard-local burn gauges (fleet merge happens at
                # /api/slo; prometheus sums/maxes across shards itself)
                snap = self.slo.snapshot()
                self.metrics.set_slo_burn_rate(
                    "fast", snap["windows"]["fast"]["burn_rate"])
                self.metrics.set_slo_burn_rate(
                    "slow", snap["windows"]["slow"]["burn_rate"])

    def _live_doctor_loop(self) -> None:
        """In-flight doctor cadence (obs/live.py): evaluate the live rule
        subset over running jobs, raise/clear journal alerts with
        hysteresis, refresh the alerts_active gauge.  A sampler-style
        thread (blocking waits allowed), never an event handler."""
        while not self._stopped.wait(self.config.live_doctor_interval_s):
            try:
                self.live_doctor.scan(self)
            except Exception:  # noqa: BLE001 — scan again next interval
                log.exception("live doctor scan failed")
            self.metrics.set_alerts_active(self.live_doctor.alerts_active())

    def sync_journal_metrics(self) -> None:
        """Fold the process-global journal counters into this collector as
        deltas (called by the history sampler and the REST /api/metrics
        handler; cheap and idempotent)."""
        tot, drop = journal.counters()
        last_tot, last_drop = self._journal_last
        if tot > last_tot:
            self.metrics.record_journal_events(tot - last_tot)
        if drop > last_drop:
            self.metrics.record_journal_dropped(drop - last_drop)
        self._journal_last = (tot, drop)

    def cluster_history(self) -> Dict:
        """Fleet-aware GET /api/cluster/history: this shard's sample ring
        plus a live per-shard breakdown and fleet rollup when a shard
        registry exists (same merge discipline as ``autoscale_signal``:
        per-shard flow sums, shared capacity takes the freshest full
        view)."""
        out = self.history.snapshot()
        out["now"] = self.cluster_sample()
        shards = [{"scheduler_id": self.scheduler_id,
                   "endpoint": self.client_endpoint, "local": True,
                   **{k: out["now"][k] for k in self._REGISTRY_KEYS}}]
        store = getattr(self.job_backend, "store", None) \
            if self._lease_capable else None
        if store is not None:
            from .kv import scheduler_registry

            try:
                reg = scheduler_registry(store,
                                         self.config.fleet_registry_stale_s)
            except Exception:  # noqa: BLE001 — fall back to local-only
                log.exception("shard registry read failed")
                reg = {}
            for sid in sorted(reg):
                if sid == self.scheduler_id:
                    continue
                obj = reg[sid]
                sample = obj.get("sample") or {}
                shards.append({"scheduler_id": sid,
                               "endpoint": obj.get("endpoint", ""),
                               "local": False,
                               **{k: sample.get(k, 0)
                                  for k in self._REGISTRY_KEYS}})
            fleet = {k: sum(s.get(k, 0) for s in shards)
                     for k in ("pending_tasks", "active_jobs",
                               "admission_queue_depth")}
            fleet.update({k: max(s.get(k, 0) for s in shards)
                          for k in ("total_slots", "available_slots",
                                    "executors_alive")})
            total, avail = fleet["total_slots"], fleet["available_slots"]
            fleet["utilization"] = round((total - avail) / total, 4) \
                if total else 0.0
            out["fleet"] = fleet
        out["shards"] = shards
        return out

    # --- failure detection ----------------------------------------------
    def _reap_loop(self) -> None:
        """Dead-executor reaper (reference expire_dead_executors,
        scheduler_server/mod.rs:224-305)."""
        while not self._stopped.wait(self.config.reaper_interval_s):
            for eid in self.cluster.expired_executors(self.config.executor_timeout_s):
                self._event_loop.post(ExecutorLost(eid, "heartbeat timeout"))

    # --- server-side deadlines -------------------------------------------
    def _deadline_loop(self) -> None:
        """Deadline scan: posts JobDeadline for any active job whose
        absolute expiry passed.  Read-only off the loop — the handler
        re-checks graph state ON the loop before acting, so a job that
        finished between scan and dispatch is untouched."""
        while not self._stopped.wait(self.config.deadline_scan_interval_s):
            now = time.time()
            for graph in self.jobs.active_graphs():
                ts = getattr(graph, "deadline_ts", 0.0)
                if ts and now >= ts:
                    self._event_loop.post(JobDeadline(graph.job_id))

    def _on_job_deadline(self, ev: JobDeadline) -> None:
        graph = self.jobs.get_graph(ev.job_id)
        if (graph is None or graph.status != "running"
                or not getattr(graph, "deadline_ts", 0.0)
                or time.time() < graph.deadline_ts):
            return  # finished/cancelled in flight, or a stale scan
        budget = getattr(graph, "deadline_s", 0.0)
        message = (f"{DEADLINE_EXCEEDED}: job exceeded its "
                   f"{budget:.1f}s deadline")
        if journal.enabled():
            # before the checkpoint, so the terminal event is IN the
            # persisted timeline
            journal.emit_job("job.deadline_exceeded", ev.job_id,
                             deadline_s=f"{budget:.3f}", retriable="false")
        graph.status = "failed"
        graph.error = message
        with self._meta_lock:
            queued_at = self._queued_at_ms.pop(ev.job_id, None)
        self._drop_poison_evidence(ev.job_id)
        # durable before visible: a restarted/adopting scheduler must see
        # the deadline verdict, never resurrect the job past its budget
        if not self._checkpoint(graph):
            return  # lease lost: the adopter owns this job now
        self.jobs.set_status(JobStatus(ev.job_id, "failed", error=message,
                                       retriable=False))
        self.metrics.record_failed(ev.job_id)
        self.metrics.record_deadline_exceeded(ev.job_id)
        # a deadline miss always burns SLO budget, whatever its wall time
        self.slo.record(
            int(time.time() * 1000) - queued_at if queued_at else 0.0,
            ok=False)
        log.warning("job %s cancelled fleet-wide: %s", ev.job_id, message)
        self._cancel_running(graph)
        self._schedule_job_data_cleanup(graph)
