"""Executor network service: task intake + shuffle data plane.

Parity: reference ballista/executor/src/executor_server.rs (push-mode gRPC:
launch_multi_task / cancel_tasks / remove_job_data / stop_executor, status
batching back to the scheduler, 60 s heartbeats) + flight_service.rs
(do_get FetchPartition with IPC streaming).  Both services share one RPC
port here; the path-traversal guard mirrors is_subdirectory
(executor_server.rs:839-876).
"""
from __future__ import annotations

import logging
import os
import queue
import shutil
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

from .. import faults, serde
from ..net import wire
from ..net.rpc import RpcServer
from ..net.retry import RetryPolicy, call_with_retry
from ..scheduler.types import ExecutorHeartbeat, ExecutorMetadata, TaskStatus
from ..utils.config import BallistaConfig
from ..utils.errors import ExecutionError
from ..utils.logsetup import ThrottledLogger
from .executor import Executor

log = logging.getLogger(__name__)

HEARTBEAT_INTERVAL_S = 60.0
# interval-class for throttled retry-loop logging: one record per loop kind
# per this many seconds, suppressed occurrences counted (satellite: the
# reporter used to warn once per second for as long as the scheduler was
# down)
RETRY_LOG_INTERVAL_S = 60.0


class StagePlanCache:
    """Tasks of one stage share ONE decoded plan instance so operators'
    lazily-built XLA programs compile once per stage, not once per task
    (the reference decodes a MultiTaskDefinition's stage plan once,
    executor_server.rs:613-697).  Keyed by plan CONTENT, not just
    (job, stage): a stage re-run after lineage rollback carries new shuffle
    locations and must not reuse the stale instance."""

    def __init__(self, max_entries: int = 64):
        import collections

        self._cache = collections.OrderedDict()
        self._max = max_entries
        self._lock = threading.Lock()

    def decode(self, t: dict):
        import hashlib
        import json

        from ..scheduler.types import TaskDescription, TaskId

        tid = t.get("task", {})
        blob = json.dumps(t.get("plan"), sort_keys=True,
                          separators=(",", ":")).encode()
        key = (tid.get("job_id"), tid.get("stage_id"),
               hashlib.sha256(blob).hexdigest())
        with self._lock:
            cached = self._cache.get(key)
            if cached is not None:
                self._cache.move_to_end(key)
        if cached is not None:
            # cache hit: only the cheap task envelope is decoded
            return TaskDescription(TaskId(**t["task"]), cached,
                                   t.get("internal_id", 0),
                                   dict(t.get("scalars", {})),
                                   trace=dict(t.get("trace", {})))
        td = serde.task_from_obj(t)
        with self._lock:
            # re-check: a racing decode of the same stage wins ties
            now = self._cache.get(key)
            if now is not None:
                td.plan = now
            else:
                self._cache[key] = td.plan
                while len(self._cache) > self._max:
                    self._cache.popitem(last=False)
        return td


class SchedulerClient:
    """Executor -> scheduler control-plane client.

    Every call goes through ``net.retry.call_with_retry``: connect/read
    deadlines plus capped jittered backoff bounded by the policy's give-up
    deadline, after which :class:`net.retry.GiveUpError` (retryable at the
    caller) surfaces instead of a hung socket."""

    def __init__(self, host: str, port: int,
                 policy: Optional[RetryPolicy] = None):
        self.host, self.port = host, port
        self.policy = policy or RetryPolicy()

    def _call(self, method: str, payload: dict) -> dict:
        resp, _ = call_with_retry(self.host, self.port, method, payload,
                                  policy=self.policy)
        return resp

    def register_executor(self, meta: ExecutorMetadata) -> None:
        self._call("register_executor",
                   {"meta": serde.executor_metadata_to_obj(meta)})

    def heartbeat(self, executor_id: str, status: str = "active",
                  meta: Optional[ExecutorMetadata] = None,
                  pressure: float = 0.0,
                  running: Optional[List[tuple]] = None) -> None:
        if faults.dropped("executor.heartbeat.send", executor_id=executor_id,
                          status=status):
            raise ConnectionError(
                "failpoint executor.heartbeat.send dropped the heartbeat")
        payload = {"executor_id": executor_id, "status": status}
        if meta is not None:
            payload["meta"] = serde.executor_metadata_to_obj(meta)
        # memory-governor pressure: 0.0 (unbudgeted) omits the key so the
        # wire format is unchanged for unconstrained fleets
        if pressure:
            payload["memory_pressure"] = pressure
        # in-flight (job, stage, partition, attempt) set for zombie-task
        # reconciliation; idle executors omit the key (wire-silent)
        if running:
            payload["running"] = [list(t) for t in running]
        self._call("heartbeat", payload)

    def update_task_status(self, executor_id: str,
                           statuses: List[TaskStatus]) -> None:
        # the drop fires BEFORE the retrying transport so the report is
        # lost outright and the reporter loop's own retry path must redeem
        # it (the chaos suite's dropped-status-report scenario)
        if faults.dropped("executor.status.report", executor_id=executor_id,
                          count=len(statuses)):
            raise ConnectionError(
                "failpoint executor.status.report dropped the payload")
        self._call("update_task_status",
                   {"executor_id": executor_id,
                    "statuses": [serde.status_to_obj(s) for s in statuses]})

    def poll_work(self, executor_id: str, num_free_slots: int,
                  statuses: List[TaskStatus], decode=serde.task_from_obj):
        # single-shot ON PURPOSE: the server POPS tasks into the reply, so a
        # transport-level retry after a lost response would leak the popped
        # tasks.  The poll loop itself retries (re-queueing statuses); only
        # the policy's deadlines apply here.
        payload, _ = wire.call(self.host, self.port, "poll_work", {
            "executor_id": executor_id, "num_free_slots": num_free_slots,
            "statuses": [serde.status_to_obj(s) for s in statuses]},
            timeout=self.policy.read_timeout_s,
            connect_timeout=self.policy.connect_timeout_s)
        from ..scheduler.netservice import ungroup_tasks

        return [decode(t) for t in ungroup_tasks(payload)]

    def executor_stopped(self, executor_id: str, reason: str = "") -> None:
        self._call("executor_stopped",
                   {"executor_id": executor_id, "reason": reason})


class ExecutorServer:
    def __init__(self, scheduler_host: str, scheduler_port: int,
                 host: str = "127.0.0.1", port: int = 0,
                 work_dir: Optional[str] = None, concurrent_tasks: int = 4,
                 executor_id: Optional[str] = None,
                 config: Optional[BallistaConfig] = None,
                 external_host: Optional[str] = None,
                 policy: str = "push",
                 job_data_ttl_s: float = 3600.0,
                 janitor_interval_s: float = 300.0,
                 flight_port: int = -1,
                 metrics_port: int = -1,
                 heartbeat_interval_s: float = HEARTBEAT_INTERVAL_S,
                 scheduler_endpoints: Optional[List[Tuple[str, int]]] = None,
                 profile_dir: Optional[str] = None):
        import socket as socketmod
        import tempfile
        import uuid

        faults.configure(config)
        self.work_dir = work_dir or tempfile.mkdtemp(prefix="ballista-exec-")
        executor_id = executor_id or f"exec-{uuid.uuid4().hex[:8]}"
        self.rpc = RpcServer(host, port)
        # advertised address: what peers dial for shuffle fetch (reference
        # executor's external_host flag).  Binding 0.0.0.0 is not routable,
        # so fall back to the machine hostname there.
        if external_host is None:
            external_host = host if host not in ("0.0.0.0", "::") \
                else socketmod.gethostname()
        # shared-secret auth of partition fetches (reference issues bearer
        # tokens at Flight handshake, flight_service.rs:136-157)
        self._dp_token = os.environ.get("BALLISTA_DATA_PLANE_TOKEN", "")
        # one port: control RPCs and the chunked partition stream
        self.metadata = ExecutorMetadata(
            executor_id=executor_id, host=external_host, port=self.rpc.port,
            task_slots=concurrent_tasks)
        self.executor = Executor(self.metadata, self.work_dir, config,
                                 concurrent_tasks=concurrent_tasks)
        self.retry_policy = RetryPolicy.from_config(config) \
            if config is not None else RetryPolicy()
        self.scheduler = SchedulerClient(scheduler_host, scheduler_port,
                                         policy=self.retry_policy)
        # fleet mode: one control-plane client per scheduler shard.  The
        # primary (index 0 / scheduler_host:port) keeps the single-scheduler
        # surface (self.scheduler, _scheduler_down) intact; extra shards get
        # registration + heartbeats so the shared-KV heartbeat row keeps
        # refreshing even after the primary dies, and task statuses route
        # back to whichever shard LAUNCHED the task (see _route_client —
        # a broadcast would double-free shared slot accounting).
        self._route_lock = threading.Lock()
        primary = (scheduler_host, scheduler_port)
        self._clients: Dict[Tuple[str, int], SchedulerClient] = \
            {primary: self.scheduler}  # ballista: guarded-by=_route_lock
        for ep in (scheduler_endpoints or []):
            ep = (ep[0], int(ep[1]))
            if ep not in self._clients:
                self._clients[ep] = SchedulerClient(
                    ep[0], ep[1], policy=self.retry_policy)
        # job -> launching shard endpoint, learned from launch payloads;
        # LRU-bounded (routes die with the job's data cleanup anyway)
        self._job_routes: "OrderedDict[str, Tuple[str, int]]" = \
            OrderedDict()  # ballista: guarded-by=_route_lock
        self._max_job_routes = 512
        assert policy in ("push", "pull")
        self.policy = policy
        self.heartbeat_interval_s = heartbeat_interval_s
        self._stop = threading.Event()
        # monotonic False->True flip written by drain_and_stop() (RPC/main
        # thread) and read by the poll loop + /health route; CPython bool
        # loads are atomic and readers tolerate one stale iteration
        self._draining = False  # ballista: guarded-by=none
        # _teardown_lock serializes stop() vs kill(): chaos fault injection
        # kills from a pool thread while a fixture teardown stops — without
        # it both pass the None-checks and double-stop obs_http
        self._teardown_lock = threading.Lock()
        self._killed = False
        # satellite: bounded/throttled retry loops.  One transition log when
        # the scheduler becomes unreachable (a call blew its give-up
        # deadline); on the next successful call we re-register so a
        # restarted scheduler relearns our metadata immediately.
        self._sched_state_lock = threading.Lock()
        self._scheduler_down = False
        self._log_throttle = ThrottledLogger(log,
                                             interval_s=RETRY_LOG_INTERVAL_S)
        faults.register_kill_target(self.metadata.executor_id, self.kill)
        # loop threads: written once by start() before any of them runs,
        # read only by _join_threads() during shutdown (start happens-before
        # stop), so no lock is needed
        self._hb_thread: Optional[threading.Thread] = None  # ballista: guarded-by=none
        self._poll_thread: Optional[threading.Thread] = None  # ballista: guarded-by=none
        self._reporter_thread: Optional[threading.Thread] = None  # ballista: guarded-by=none
        self._status_queue: "queue.Queue[TaskStatus]" = queue.Queue()
        self.job_data_ttl_s = job_data_ttl_s
        self.janitor_interval_s = janitor_interval_s
        self._janitor_thread: Optional[threading.Thread] = None  # ballista: guarded-by=none
        self._plan_cache = StagePlanCache()

        # optional standard Arrow Flight door (reference
        # flight_service.rs:82-120): any stock Arrow client can do_get a
        # shuffle partition; peers keep using the RPC port
        self.flight = None
        if flight_port >= 0:
            from .flight_service import ExecutorFlightServer

            self.flight = ExecutorFlightServer(self.work_dir, self._dp_token,
                                               host, flight_port)

        # observability listener mirroring the scheduler's exposition:
        # prometheus /metrics + /health (-1 = disabled, 0 = ephemeral port).
        # Claimed-and-nulled under _teardown_lock in stop()/kill(); start()
        # reads it before any other thread exists
        self.obs_http = None  # ballista: guarded-by=_teardown_lock
        if metrics_port >= 0:
            import json as jsonmod

            from ..obs.http import PROM_CTYPE, ObsHttpServer

            def _metrics():
                return (self.executor.metrics.gather(
                    self.executor.active_tasks()), PROM_CTYPE)

            def _health():
                return (jsonmod.dumps({
                    "status": "draining" if self._draining else "ok",
                    "executor_id": self.metadata.executor_id,
                    "policy": self.policy,
                    "task_slots": self.metadata.task_slots,
                    "active_tasks": self.executor.active_tasks(),
                }), "application/json")

            self.obs_http = ObsHttpServer(host, metrics_port,
                                          {"/metrics": _metrics,
                                           "/health": _health})

        # a profiler session around task execution, written by stop() and
        # by profile.write() (the daemon's SIGUSR2); None = no session
        self.profile = None
        if profile_dir:
            from ..obs.device import ProfilerSession

            self.profile = ProfilerSession(profile_dir)

        self.rpc.register("launch_multi_task", self._launch_multi_task)
        self.rpc.register("cancel_tasks", self._cancel_tasks)
        self.rpc.register("cancel_task", self._cancel_task)
        self.rpc.register_stream("fetch_partition_stream",
                                 self._fetch_partition_stream)
        self.rpc.register("remove_job_data", self._remove_job_data)
        self.rpc.register("stop_executor", self._stop_executor)
        self.rpc.register("ping", lambda p, b: ({"executor_id": executor_id}, b""))

    # --- lifecycle -------------------------------------------------------
    def start(self, register: bool = True) -> None:
        if self.profile is not None:
            self.profile.start()
        self.rpc.start()
        if self.flight is not None:
            self.flight.start()
        if self.obs_http is not None:
            self.obs_http.start()
        if register:
            self.scheduler.register_executor(self.metadata)
            # extra shards are best-effort: a shard that is down now learns
            # us later from the metadata riding on every heartbeat
            for ep, client in self._extra_clients():
                try:
                    client.register_executor(self.metadata)
                except Exception:  # noqa: BLE001 — heartbeat re-registers
                    log.warning("register to scheduler shard %s:%d failed "
                                "(heartbeats will retry)", ep[0], ep[1])
        self._hb_thread = threading.Thread(target=self._heartbeat_loop,
                                           name="executor-heartbeat", daemon=True)
        self._hb_thread.start()
        if self.policy == "pull":
            self._poll_thread = threading.Thread(target=self._poll_loop,
                                                 name="executor-poll", daemon=True)
            self._poll_thread.start()
        else:
            self._reporter_thread = threading.Thread(
                target=self._reporter_loop, name="status-reporter", daemon=True)
            self._reporter_thread.start()
        self._janitor_thread = threading.Thread(target=self._janitor_loop,
                                                name="shuffle-janitor",
                                                daemon=True)
        self._janitor_thread.start()

    def _janitor_loop(self) -> None:
        """Shuffle-data TTL janitor (reference clean_shuffle_data_loop,
        executor_process.rs:245-273): delete job dirs untouched for longer
        than the TTL."""
        while not self._stop.wait(self.janitor_interval_s):
            try:
                now = time.time()
                live = self.executor.active_job_ids()
                for entry in os.scandir(self.work_dir):
                    if not entry.is_dir():
                        continue
                    if entry.name in live:
                        # a job with a task RUNNING here is alive whatever
                        # its files' mtimes say — a long-running producer
                        # that wrote stage 1 output hours ago must not
                        # lose it mid-query to the TTL scan
                        continue
                    newest = entry.stat().st_mtime
                    for root, _dirs, files in os.walk(entry.path):
                        for fn in files:
                            try:
                                newest = max(newest, os.stat(
                                    os.path.join(root, fn)).st_mtime)
                            except OSError:
                                pass
                    if now - newest > self.job_data_ttl_s:
                        log.info("janitor removing stale job data %s", entry.path)
                        from .executor import remove_job_data

                        remove_job_data(self.work_dir, entry.name)
            except Exception:  # noqa: BLE001 — janitor must survive
                log.exception("shuffle janitor iteration failed")

    def _poll_loop(self) -> None:
        """Pull-mode work loop (reference execution_loop.rs:49-133):
        report drained statuses, ask for as many tasks as there are free
        slots, idle-sleep 100 ms when nothing came back."""
        while not self._stop.is_set():
            statuses: List[TaskStatus] = []
            while True:
                try:
                    statuses.append(self._status_queue.get_nowait())
                except queue.Empty:
                    break
            # draining: keep polling to drain statuses, but take no new work
            free = 0 if self._draining else \
                self.metadata.task_slots - self.executor.active_tasks()
            try:
                tasks = self.scheduler.poll_work(self.metadata.executor_id,
                                                 max(0, free), statuses,
                                                 decode=self._plan_cache.decode)
            except Exception:  # noqa: BLE001 — scheduler briefly unreachable
                self._mark_scheduler_down("poll_work")
                self._log_throttle.warning("poll", "poll_work failed",
                                           exc_info=True)
                # re-queue unreported statuses for the next poll
                for st in statuses:
                    self._status_queue.put(st)
                self._stop.wait(1.0)
                continue
            self._mark_scheduler_up()
            for task in tasks:
                self.executor.submit_task(task, self._status_queue.put)
            if not tasks and not statuses:
                self._stop.wait(0.1)

    def drain_and_stop(self, grace_s: float = 30.0) -> None:
        """Graceful shutdown (reference executor_process.rs:309-320):
        Terminating heartbeat -> scheduler stops assigning -> wait for
        in-flight tasks (bounded by ``grace_s``) -> notify -> exit.
        Pull mode additionally stops asking for new work (the poll loop
        keeps running to drain statuses)."""
        self._draining = True
        try:
            self.scheduler.heartbeat(self.metadata.executor_id,
                                     status="terminating", meta=self.metadata)
        # drain proceeds regardless; the scheduler may already be gone
        # ballista: allow=recovery-path-logging — best-effort terminating ping
        except Exception:  # noqa: BLE001 — scheduler may already be gone
            pass
        deadline = time.monotonic() + grace_s
        while self.executor.active_tasks() > 0 and time.monotonic() < deadline:
            time.sleep(0.1)
        # give the status reporter one last chance to flush results
        for _ in range(20):
            if self._status_queue.empty():
                break
            time.sleep(0.1)
        self.stop(notify=True)

    def stop(self, notify: bool = True) -> None:
        with self._teardown_lock:
            if self._killed:
                # kill() already tore the sockets down abruptly; a later
                # fixture teardown must not double-stop or notify
                self._stop.set()
                return
            self._stop.set()
            # claim the shared resource under the lock so a racing kill()
            # cannot stop it a second time (or trip over the None)
            obs_http, self.obs_http = self.obs_http, None
        faults.unregister_kill_target(self.metadata.executor_id)
        if notify:
            try:
                self.scheduler.executor_stopped(self.metadata.executor_id, "shutdown")
            # best-effort goodbye on shutdown; the scheduler may be gone
            # ballista: allow=recovery-path-logging — outcome needs no trace
            except Exception:  # noqa: BLE001 — scheduler may be gone
                pass
        self.executor.shutdown()
        if self.profile is not None:
            self.profile.write(reopen=False)
        self.rpc.stop()
        if self.flight is not None:
            self.flight.stop()
        if obs_http is not None:
            obs_http.stop()
        self._join_threads()

    def _join_threads(self) -> None:
        """Bounded join of the long-lived loops: _stop is already set, so
        each exits within one poll interval; the timeout keeps a wedged
        loop from hanging shutdown (the threads are daemons regardless).
        Skip the current thread: the reporter's final flush can be the one
        calling stop() via _stop_executor."""
        cur = threading.current_thread()
        if self._hb_thread is not None and self._hb_thread is not cur:
            self._hb_thread.join(timeout=5.0)
        if self._poll_thread is not None and self._poll_thread is not cur:
            self._poll_thread.join(timeout=5.0)
        if self._reporter_thread is not None and self._reporter_thread is not cur:
            self._reporter_thread.join(timeout=5.0)
        if self._janitor_thread is not None and self._janitor_thread is not cur:
            self._janitor_thread.join(timeout=5.0)

    def kill(self) -> None:
        """Abrupt death for chaos tests (the ``faults`` kill action):
        simulate SIGKILL as closely as one process allows — drop off the
        network NOW.  No Terminating heartbeat, no executor_stopped notify,
        no final status flush; in-flight tasks unwind as ``killed`` and are
        never reported.  The scheduler must discover the death the hard
        way: launch failures, fetch failures, heartbeat timeout."""
        with self._teardown_lock:
            if self._killed:
                return
            self._killed = True
            self._stop.set()
            obs_http, self.obs_http = self.obs_http, None
        faults.unregister_kill_target(self.metadata.executor_id)
        log.warning("executor %s killed by fault injection",
                    self.metadata.executor_id)
        self.rpc.stop()
        if self.flight is not None:
            self.flight.stop()
        if obs_http is not None:
            obs_http.stop()
        # wait=False: this may run on a pool thread (the task that tripped
        # the failpoint); a joining shutdown would deadlock on itself
        self.executor.pool.shutdown(wait=False)

    def _mark_scheduler_down(self, what: str) -> None:
        with self._sched_state_lock:
            if self._scheduler_down:
                return
            self._scheduler_down = True
        log.warning(
            "scheduler unreachable (%s failed past the %.1fs give-up "
            "deadline); will re-register on reconnect", what,
            self.retry_policy.give_up_after_s)

    def _mark_scheduler_up(self) -> None:
        """First successful call after an outage: re-register, because the
        scheduler may have restarted (or expired us) while unreachable."""
        with self._sched_state_lock:
            if not self._scheduler_down:
                return
            self._scheduler_down = False
        log.info("scheduler reachable again; re-registering executor %s",
                 self.metadata.executor_id)
        try:
            self.scheduler.register_executor(self.metadata)
        except Exception:  # noqa: BLE001 — the next loop pass re-detects
            self._log_throttle.warning(
                "re-register", "re-register after reconnect failed",
                exc_info=True)
            with self._sched_state_lock:
                self._scheduler_down = True

    def _heartbeat_loop(self) -> None:
        while not self._stop.wait(self.heartbeat_interval_s):
            # memory-governor pressure rides every beat: the scheduler
            # degrades this executor's offer ordering with it, and the
            # fleet-wide floor feeds admission shed
            pressure = self.executor.governor.pressure()
            # in-flight task set: the scheduler diffs it against job truth
            # and re-issues kills for zombies (lost cancel fanouts)
            running = self.executor.running_task_ids()
            try:
                # metadata rides along so a restarted scheduler re-registers
                # us (reference heart_beat_from_executor, grpc.rs:174-241)
                self.scheduler.heartbeat(self.metadata.executor_id,
                                         meta=self.metadata,
                                         pressure=pressure,
                                         running=running)
                self._mark_scheduler_up()
            except Exception:  # noqa: BLE001 — retried next interval
                self._mark_scheduler_down("heartbeat")
                self._log_throttle.warning(
                    "heartbeat", "heartbeat to scheduler failed",
                    exc_info=True)
            # fleet: every shard gets a beat so the shared heartbeat row
            # keeps refreshing through ANY live shard — an executor must
            # not be reaped just because its primary shard died
            for ep, client in self._extra_clients():
                try:
                    client.heartbeat(self.metadata.executor_id,
                                     meta=self.metadata,
                                     pressure=pressure,
                                     running=running)
                except Exception:  # noqa: BLE001 — that shard may be dead
                    self._log_throttle.warning(
                        f"heartbeat-{ep[0]}:{ep[1]}",
                        "heartbeat to scheduler shard %s:%d failed",
                        ep[0], ep[1], exc_info=True)

    # --- fleet routing ---------------------------------------------------
    #: consecutive failed reporter rounds against one shard before its
    #: statuses fail over to a sibling (each round already spends the
    #: client's full in-call retry deadline, so 2 rounds ≈ several seconds
    #: of continuous unreachability — a dead shard, not a blip)
    REROUTE_AFTER = 2

    def _extra_clients(self):
        with self._route_lock:
            return [(ep, c) for ep, c in self._clients.items()
                    if c is not self.scheduler]

    def _primary_endpoint(self) -> Optional[Tuple[str, int]]:
        # an injected in-process scheduler (tests, embedded standalone mode)
        # has no endpoint; routing then collapses to the single-scheduler
        # path: every status goes straight through self.scheduler
        host = getattr(self.scheduler, "host", None)
        port = getattr(self.scheduler, "port", None)
        if host is None or port is None:
            return None
        return (host, int(port))

    def _client_for(self, ep: Optional[Tuple[str, int]]) -> SchedulerClient:
        if ep is None:
            return self.scheduler
        with self._route_lock:
            client = self._clients.get(ep)
            if client is None:
                client = SchedulerClient(ep[0], ep[1],
                                         policy=self.retry_policy)
                self._clients[ep] = client
            return client

    def _route_endpoint(self, job_id: str) -> Optional[Tuple[str, int]]:
        """The shard that most recently launched tasks for this job: task
        statuses must go back to the shard DRIVING the job.  A broadcast
        would double-free the shared slot accounting, and pinning the
        primary would strand statuses after an adoption re-homes the job
        (the adopter's launches overwrite the route).  ``None`` means the
        in-process injected scheduler (no endpoint to route by)."""
        with self._route_lock:
            return self._job_routes.get(job_id) or self._primary_endpoint()

    def _route_client(self, job_id: str) -> SchedulerClient:
        return self._client_for(self._route_endpoint(job_id))

    def _reroute_jobs(self, job_ids, dead_ep: Optional[Tuple[str, int]],
                      attempt: int) -> Optional[Tuple[str, int]]:
        """Re-home these jobs' statuses to a sibling shard: their routed
        shard stayed unreachable for REROUTE_AFTER reporter rounds (killed
        or partitioned away).  Delivering to ANY live shard frees the
        shared slot accounting — without this, slots reserved by a dead
        shard's in-flight tasks leak and the adopter can never relaunch —
        and once the adopter launches, its payload overwrites the route
        with itself.  Continued failure walks the candidate list."""
        if dead_ep is None:
            # the injected in-process scheduler has no siblings; rerouting
            # to a networked endpoint would strand the statuses instead
            return None
        with self._route_lock:
            candidates = [e for e in self._clients if e != dead_ep]
            if not candidates:
                return None
            fallback = candidates[attempt % len(candidates)]
            for job_id in job_ids:
                self._job_routes[job_id] = fallback
                self._job_routes.move_to_end(job_id)
        return fallback

    def _learn_routes(self, payload: dict, tasks) -> None:
        sched = payload.get("scheduler")
        if not sched:
            return
        ep = (sched["host"], int(sched["port"]))
        with self._route_lock:
            for task in tasks:
                self._job_routes[task.task.job_id] = ep
                self._job_routes.move_to_end(task.task.job_id)
            while len(self._job_routes) > self._max_job_routes:
                self._job_routes.popitem(last=False)

    # --- RPC handlers ----------------------------------------------------
    def _launch_multi_task(self, payload: dict, _bin: bytes):
        from ..scheduler.netservice import ungroup_tasks

        # MultiTaskDefinition shape (one plan + N task envelopes)
        tasks = [self._decode_task(t) for t in ungroup_tasks(payload)]
        self._learn_routes(payload, tasks)
        for task in tasks:
            self.executor.submit_task(task, self._report_status)
        return {"accepted": len(tasks)}, b""

    def _decode_task(self, t: dict):
        return self._plan_cache.decode(t)

    def _report_status(self, status: TaskStatus) -> None:
        # push mode routes through the batching reporter loop so a transient
        # scheduler-connection failure can never lose a TaskStatus (the
        # reference batches + retries the same way, executor_server.rs
        # TaskRunnerPool reporter loop; pull mode re-queues in _poll_loop)
        self._status_queue.put(status)

    def _reporter_loop(self) -> None:
        pending: List[TaskStatus] = []
        # consecutive failed rounds per shard endpoint; reaching
        # REROUTE_AFTER re-homes that shard's statuses to a sibling
        route_fails: Dict[Tuple[str, int], int] = {}
        while not self._stop.is_set():
            try:
                pending.append(self._status_queue.get(timeout=0.2))
            except queue.Empty:
                pass
            while True:
                try:
                    pending.append(self._status_queue.get_nowait())
                except queue.Empty:
                    break
            if not pending:
                continue
            # fleet: group by the shard that launched each job's tasks and
            # flush per shard — one dead shard must not dam statuses bound
            # for live ones.  Routes are re-resolved on every attempt, so
            # statuses stranded toward a dead shard drain to the adopter as
            # soon as its first launch overwrites the job's route.
            groups: Dict[Tuple[str, int], List[TaskStatus]] = {}
            for st in pending:
                groups.setdefault(self._route_endpoint(st.task.job_id),
                                  []).append(st)
            primary = self._primary_endpoint()
            still_pending: List[TaskStatus] = []
            for ep, sts in groups.items():
                client = self._client_for(ep)
                try:
                    client.update_task_status(self.metadata.executor_id,
                                              list(sts))
                    route_fails.pop(ep, None)
                    if ep == primary:
                        self._mark_scheduler_up()
                except Exception:  # noqa: BLE001 — keep and retry next round
                    fails = route_fails.get(ep, 0) + 1
                    route_fails[ep] = fails
                    if ep == primary:
                        self._mark_scheduler_down("status report")
                    ep_label = "%s:%d" % ep if ep else "in-process"
                    if fails >= self.REROUTE_AFTER:
                        fallback = self._reroute_jobs(
                            {st.task.job_id for st in sts}, ep,
                            fails - self.REROUTE_AFTER)
                        if fallback is not None:
                            log.warning(
                                "shard %s unreachable for %d status "
                                "rounds; rerouting %d status(es) to %s:%d",
                                ep_label, fails, len(sts),
                                fallback[0], fallback[1])
                    self._log_throttle.warning(
                        "status-report",
                        "status report to %s failed (%d pending, will "
                        "retry)", ep_label, len(sts), exc_info=True)
                    still_pending.extend(sts)
            pending = still_pending
            if pending:
                self._stop.wait(1.0)
        # final best-effort flush on shutdown — but NOT after kill():
        # a SIGKILLed executor reports nothing
        with self._teardown_lock:
            killed = self._killed
        if pending and not killed:
            flush: Dict[int, List[TaskStatus]] = {}
            fclients: Dict[int, SchedulerClient] = {}
            for st in pending:
                client = self._route_client(st.task.job_id)
                fclients[id(client)] = client
                flush.setdefault(id(client), []).append(st)
            for key, sts in flush.items():
                try:
                    fclients[key].update_task_status(
                        self.metadata.executor_id, list(sts))
                # last-gasp flush on shutdown; nothing listens to a failure
                # ballista: allow=recovery-path-logging — best effort
                except Exception:  # noqa: BLE001
                    pass

    def _cancel_tasks(self, payload: dict, _bin: bytes):
        self.executor.cancel_job_tasks(payload["job_id"])
        return {}, b""

    def _cancel_task(self, payload: dict, _bin: bytes):
        # single-attempt cancel: the losing duplicate of a speculative race
        self.executor.cancel_task(serde.taskid_from_obj(payload["task"]))
        return {}, b""

    def _is_under_work_dir(self, path: str) -> bool:
        base = os.path.realpath(self.work_dir)
        target = os.path.realpath(path)
        return os.path.commonpath([base, target]) == base

    def _fetch_partition_stream(self, payload: dict, _bin: bytes, send):
        """Chunked partition fetch: auth + work-dir path guard, then the
        framing is delegated to the shared data-plane server half
        (net/dataplane.stream_partition)."""
        from ..net.dataplane import stream_partition

        if self._dp_token and payload.get("token", "") != self._dp_token:
            raise ExecutionError("data plane auth failed")
        path = payload["path"]
        if not self._is_under_work_dir(path):
            raise ExecutionError(f"path {path!r} escapes the work dir")
        if not os.path.exists(path):
            raise ExecutionError(f"no such shuffle file: {path}")
        stream_partition(path, payload, send)

    def _remove_job_data(self, payload: dict, _bin: bytes):
        from .executor import remove_job_data

        remove_job_data(self.work_dir, payload["job_id"])
        return {}, b""

    def _stop_executor(self, payload: dict, _bin: bytes):
        threading.Thread(target=self.stop, kwargs={"notify": False},
                         daemon=True).start()
        return {}, b""
