"""Scheduler network service: the SchedulerGrpc surface over the wire.

Parity: reference ballista/scheduler/src/scheduler_server/grpc.rs — the 10
RPC handlers (execute_query, get_job_status, register_executor,
heart_beat_from_executor, update_task_status, executor_stopped, cancel_job,
clean_job_data, …) — plus table registration (the reference client ships
CREATE EXTERNAL TABLE inside the logical plan, context.rs:358-530; here the
scheduler owns the catalog and clients register tables by RPC).

Launching goes through ``NetTaskLauncher`` -> executor launch_multi_task,
i.e. push scheduling (TaskSchedulingPolicy::PushStaged).
"""
from __future__ import annotations

import logging
import threading
from typing import Dict, List, Optional

from .. import faults, serde
from ..catalog import CsvTable, MemoryTable, ParquetTable, SchemaCatalog
from ..models.schema import Field, Schema
from ..net.rpc import RpcServer
from ..net.retry import RetryPolicy, call_with_retry
from ..utils.config import BallistaConfig
from ..utils.errors import ExecutionError, PlanningError
from .scheduler import SchedulerConfig, SchedulerServer, TaskLauncher, random_job_id
from .types import ExecutorHeartbeat, ExecutorMetadata, TaskDescription

log = logging.getLogger(__name__)

# guards plan encoding (see serialize_tasks_or_fail)
_ENCODE_LOCK = threading.Lock()


def serialize_tasks_or_fail(scheduler, executor_id: str,
                            tasks: List[TaskDescription]) -> List[dict]:
    """Serialize tasks PER TASK; a task whose plan cannot serialize fails
    identically on every executor, so report it as a fatal task failure
    (fails its job fast) instead of letting launch retry forever —
    WITHOUT killing unrelated jobs' tasks sharing the batch.  Shared by
    the push launcher and the pull poll_work response.

    Same-stage tasks share one plan instance, so the (expensive) plan
    encoding runs once per stage per batch and is reused across its tasks
    (reference: MultiTaskDefinition's stage plan is encoded once,
    task_manager.rs:583-650)."""
    objs: List[dict] = []
    failed = []
    plan_cache: dict = {}
    for t in tasks:
        try:
            plan_obj = plan_cache.get(id(t.plan))
            if plan_obj is None:
                # ONE encode at a time process-wide: two launch-pool
                # threads serializing the same plan concurrently segfaulted
                # inside pyarrow's IPC writer (same MemoryScanExec table
                # from two threads); encoding is cheap host work, so the
                # lock costs nothing measurable
                with _ENCODE_LOCK:
                    plan_obj = serde.plan_to_obj(t.plan)
                plan_cache[id(t.plan)] = plan_obj
            objs.append(serde.task_to_obj(t, plan_obj=plan_obj))
        except Exception as e:  # noqa: BLE001 — deterministic plan defect
            from .types import EXECUTION_ERROR, FailedReason, TaskStatus

            log.exception("task %s failed to serialize", t.task)
            failed.append(TaskStatus(t.task, executor_id, "failed",
                                     failure=FailedReason(
                                         EXECUTION_ERROR,
                                         f"plan serialization failed: {e}")))
    if failed:
        scheduler.update_task_status(executor_id, failed)
    return objs


def group_tasks_by_plan(objs: List[dict]) -> List[dict]:
    """Flat task objects -> MultiTaskDefinition groups (one plan dict + N
    task envelopes).  Same-stage tasks share the plan OBJECT, so identity
    grouping is exact and the plan is JSON-encoded onto the wire once."""
    groups: dict = {}
    for o in objs:
        g = groups.setdefault(id(o["plan"]), {"plan": o["plan"], "tasks": []})
        g["tasks"].append({"task": o["task"],
                           "internal_id": o["internal_id"],
                           "scalars": o["scalars"],
                           "trace": o.get("trace", {})})
    return list(groups.values())


def ungroup_tasks(payload: dict) -> List[dict]:
    """Inverse of group_tasks_by_plan."""
    if "stages" not in payload:
        raise ExecutionError("task payload has no 'stages'")
    out = []
    for st in payload["stages"]:
        for env in st["tasks"]:
            out.append({"task": env["task"], "plan": st["plan"],
                        "internal_id": env.get("internal_id", 0),
                        "scalars": env.get("scalars", {}),
                        "trace": env.get("trace", {})})
    return out


class NetTaskLauncher(TaskLauncher):
    """Pushes tasks to executors over the wire (reference
    DefaultTaskLauncher -> ExecutorGrpc.LaunchMultiTask,
    state/task_manager.rs:69-119)."""

    def __init__(self, policy: Optional[RetryPolicy] = None):
        self.scheduler: Optional[SchedulerServer] = None
        # deadline + bounded-backoff policy for every scheduler->executor
        # call; a launch that exhausts the give-up deadline raises a
        # ConnectionError subclass, which _launch turns into ExecutorLost —
        # the retryable path that re-runs the tasks elsewhere without
        # charging task retry budgets
        self.policy = policy or RetryPolicy()
        # (host, port) this scheduler serves RPC on; rides in every launch
        # payload so multi-registered executors report task statuses back
        # to the shard that LAUNCHED the task (fleet mode: a status
        # broadcast to every shard would double-free shared slot accounting)
        self.endpoint: Optional[tuple] = None

    def _addr(self, executor_id: str):
        meta = self.scheduler.cluster.get_executor(executor_id)
        if meta is None:
            raise PlanningError(f"unknown executor {executor_id}")
        return meta.host, meta.port

    def launch_tasks(self, executor_id: str, tasks: List[TaskDescription]) -> None:
        objs = serialize_tasks_or_fail(self.scheduler, executor_id, tasks)
        if not objs:
            return
        # MultiTaskDefinition wire shape (reference ballista.proto:440-463 +
        # task_manager.rs:583-650): one encoded stage plan + N task
        # envelopes, so the plan crosses the wire once per stage, not once
        # per task
        host, port = self._addr(executor_id)
        payload = {"stages": group_tasks_by_plan(objs)}
        if self.endpoint is not None:
            payload["scheduler"] = {"host": self.endpoint[0],
                                    "port": self.endpoint[1]}
        call_with_retry(host, port, "launch_multi_task", payload,
                        policy=self.policy)

    def cancel_tasks(self, executor_id: str, job_id: str) -> None:
        if faults.dropped("scheduler.cancel.fanout",
                          executor_id=executor_id, job_id=job_id):
            # chaos: simulate the lost cancel RPC this method otherwise
            # swallows below — heartbeat zombie reconciliation must reap
            return
        try:
            host, port = self._addr(executor_id)
            call_with_retry(host, port, "cancel_tasks", {"job_id": job_id},
                            policy=self.policy)
        except Exception:  # noqa: BLE001 — best effort: delivery failures
            # are logged and swallowed; the executor's heartbeat `running`
            # set lets the scheduler re-issue the kill (zombie reaping)
            log.warning("cancel_tasks on %s failed", executor_id, exc_info=True)

    def cancel_task(self, executor_id: str, task) -> None:
        if faults.dropped("scheduler.cancel.fanout",
                          executor_id=executor_id, job_id=task.job_id):
            return
        try:
            host, port = self._addr(executor_id)
            call_with_retry(host, port, "cancel_task",
                            {"task": serde.taskid_to_obj(task)},
                            policy=self.policy)
        except Exception:  # noqa: BLE001 — best effort (the loser's late
            # result is discarded by the graph's attempt bookkeeping anyway)
            log.warning("cancel_task on %s failed", executor_id, exc_info=True)

    def clean_job_data(self, executor_id: str, job_id: str) -> None:
        host, port = self._addr(executor_id)
        call_with_retry(host, port, "remove_job_data", {"job_id": job_id},
                        policy=self.policy)


class SchedulerNetService:
    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 config: Optional[BallistaConfig] = None,
                 scheduler_config: Optional[SchedulerConfig] = None,
                 rest_port: Optional[int] = None,
                 state_dir: Optional[str] = None,
                 cluster_url: Optional[str] = None,
                 flight_port: Optional[int] = None):
        self.config = config or BallistaConfig()
        # arm the failpoint plan (no-op unless ballista.faults.plan or
        # BALLISTA_FAULTS_PLAN is set) before any instrumented site runs
        faults.configure(self.config)
        # flight recorder: honour the session config here — SchedulerServer
        # itself only sees process defaults/env.  Enable-only (a journal a
        # test already turned on stays on), and before SchedulerServer is
        # built so its init names the actor.
        from ..utils.config import (JOURNAL_CAPACITY, JOURNAL_ENABLED,
                                    JOURNAL_SPILL_PATH)

        if bool(self.config.get(JOURNAL_ENABLED)):
            from ..obs import journal

            journal.set_enabled(True)
            journal.configure(
                capacity=int(self.config.get(JOURNAL_CAPACITY)),
                spill_path=str(self.config.get(JOURNAL_SPILL_PATH)))
        if scheduler_config is None:
            # honour the session config's cluster keys when the caller did
            # not hand us an explicit SchedulerConfig — one timeout key
            # (ballista.cluster.executor_timeout_s) governs offers, the
            # reaper, and the REST summary alike
            from ..utils.config import (
                CLUSTER_EXECUTOR_TIMEOUT_S,
                FLEET_ADOPT_INTERVAL_S,
                FLEET_LEASE_RENEW_S,
                FLEET_LEASE_TTL_S,
                FLEET_REGISTRY_STALE_S,
                LIVE_DOCTOR_INTERVAL_S,
                LIVE_ENABLED,
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
            )

            scheduler_config = SchedulerConfig(
                executor_timeout_s=float(
                    self.config.get(CLUSTER_EXECUTOR_TIMEOUT_S)),
                fleet_lease_ttl_s=float(
                    self.config.get(FLEET_LEASE_TTL_S)),
                fleet_lease_renew_s=float(
                    self.config.get(FLEET_LEASE_RENEW_S)),
                fleet_adopt_interval_s=float(
                    self.config.get(FLEET_ADOPT_INTERVAL_S)),
                fleet_registry_stale_s=float(
                    self.config.get(FLEET_REGISTRY_STALE_S)),
                quarantine_failures=int(
                    self.config.get(QUARANTINE_FAILURES)),
                quarantine_probation_s=float(
                    self.config.get(QUARANTINE_PROBATION_S)),
                speculation_enabled=bool(
                    self.config.get(SPECULATION_ENABLED)),
                speculation_quantile=float(
                    self.config.get(SPECULATION_QUANTILE)),
                speculation_multiplier=float(
                    self.config.get(SPECULATION_MULTIPLIER)),
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
        self.catalog = SchemaCatalog()
        launcher = NetTaskLauncher(RetryPolicy.from_config(self.config))
        job_backend = None
        cluster_state = None
        if cluster_url:
            # shared KV backend: job checkpoints AND slot accounting go
            # through one store so sibling schedulers cooperate (kv.py)
            from .kv import KvClusterState, KvJobStateBackend
            from .kv_remote import open_remote_or_local

            sc = scheduler_config or SchedulerConfig()
            # kv://host:port -> networked KV service (multi-host HA);
            # memory:// / sqlite:/// -> embedded
            store = open_remote_or_local(cluster_url)
            job_backend = KvJobStateBackend(store,
                                            lease_ttl_s=sc.fleet_lease_ttl_s)
            cluster_state = KvClusterState(store, sc.task_distribution)
        elif state_dir:
            from .persistence import FileJobStateBackend

            job_backend = FileJobStateBackend(state_dir)
        from ..obs import JobObservability

        self.server = SchedulerServer(
            launcher, scheduler_config,
            job_backend=job_backend,
            cluster_state=cluster_state,
            observability=JobObservability.from_config(self.config))
        launcher.scheduler = self.server
        self.rpc = RpcServer(host, port)
        self.host, self.port = self.rpc.host, self.rpc.port
        # published to the shard registry + job leases so a surviving shard
        # (and redirected clients) can name where this scheduler serves;
        # launch payloads carry it so executors route statuses back here
        self.server.client_endpoint = f"{self.host}:{self.port}"
        launcher.endpoint = (self.host, self.port)
        # job -> result schema, LRU-bounded: clients fetch results right
        # after completion, so old entries are dead weight in a long-running
        # daemon
        from collections import OrderedDict

        self._final_schemas: "OrderedDict[str, Schema]" = OrderedDict()
        self._max_schemas = 1024
        self._lock = threading.Lock()
        self._default_prepared: Dict[str, tuple] = {}
        # result-cache hits parked for one fetch_result round-trip: the
        # execute_query reply stays a tiny job handle either way, and the
        # client pulls the bytes exactly once (entries are popped)
        self._cached_results: "OrderedDict[str, dict]" = OrderedDict()
        self._max_cached_results = 64

        # per-session isolation (reference session_manager.rs:27-57; the
        # Flight-SQL-analog surface below opens one session per client)
        from .session import SessionManager

        self.sessions = SessionManager(self.config, self.catalog)

        r = self.rpc.register
        r("create_session", self._create_session)
        r("update_session", self._update_session)
        r("remove_session", self._remove_session)
        r("prepare", self._prepare)
        r("explain", self._explain)
        r("execute_query", self._execute_query)
        r("get_job_status", self._get_job_status)
        r("watch_job", self._watch_job)
        r("fetch_result", self._fetch_result)
        r("cancel_job", self._cancel_job)
        r("register_executor", self._register_executor)
        r("heartbeat", self._heartbeat)
        r("update_task_status", self._update_task_status)
        r("poll_work", self._poll_work)
        r("executor_stopped", self._executor_stopped)
        r("register_table", self._register_table)
        r("register_external_table", self._register_external_table)
        r("get_file_metadata", self._get_file_metadata)
        r("list_tables", self._list_tables)
        r("table_schema", self._table_schema)
        r("deregister_table", self._deregister_table)
        r("ping", lambda p, b: ({}, b""))

        self.rest = None
        if rest_port is not None:
            from .rest import RestApi

            self.rest = RestApi(self.server, host, rest_port)

        # Arrow Flight (SQL) front door (reference flight_sql.rs:83-911)
        self.flight = None
        if flight_port is not None:
            from .flight_service import BallistaFlightServer

            self.flight = BallistaFlightServer(self, host, flight_port)

    def start(self) -> None:
        import time as _time

        self.server._started_at = int(_time.time())
        self.server.init()
        self.rpc.start()
        if self.rest is not None:
            self.rest.start()
        if self.flight is not None:
            self.flight.start()
        if self.server.job_backend is not None:
            self.server.recover_jobs()

    def stop(self) -> None:
        self.server.shutdown()
        self.rpc.stop()
        if self.rest is not None:
            self.rest.stop()
        if self.flight is not None:
            self.flight.stop()

    def kill(self) -> None:
        """Crash-simulate this shard inside one process (chaos harness):
        tear the RPC listener and background threads down WITHOUT the
        goodbyes a clean stop performs — no registry withdrawal, no lease
        release.  Held job leases simply stop renewing, exactly like
        kill -9, so a sibling shard must adopt them through lease expiry
        (the registry entry ages out at the stale cutoff the same way)."""
        self.server.shutdown(withdraw=False)
        self.rpc.stop()
        if self.rest is not None:
            self.rest.stop()
        if self.flight is not None:
            self.flight.stop()

    # --- sessions (the Flight SQL handshake analog) -----------------------
    def _session_ctx(self, payload: dict):
        """Resolve (catalog, config) for a request: its session's when a
        session_id is given, the shared defaults otherwise; per-request
        config overrides apply on top either way."""
        session = self.sessions.get(payload.get("session_id"))
        base_catalog = session.catalog if session else self.catalog
        base_settings = (session.config if session else self.config)._settings
        overrides = payload.get("config", {})
        config = BallistaConfig({**base_settings, **overrides}) \
            if overrides or session else self.config
        return session, base_catalog, config

    def _create_session(self, payload: dict, _bin: bytes):
        s = self.sessions.create_session(payload.get("settings"))
        return {"session_id": s.id,
                "settings": dict(s.config._settings)}, b""

    def _update_session(self, payload: dict, _bin: bytes):
        s = self.sessions.update_session(payload["session_id"],
                                         payload.get("settings", {}))
        return {"settings": dict(s.config._settings)}, b""

    def _remove_session(self, payload: dict, _bin: bytes):
        self.sessions.remove_session(payload["session_id"])
        return {}, b""

    def _prepare(self, payload: dict, _bin: bytes):
        """Prepared statement: validate + plan once, return the result
        schema (reference FlightSqlServiceImpl prepared statements,
        flight_sql.rs:483-560).  Execute later via execute_query with
        {"statement_id": ...}."""
        import uuid as uuidmod

        from ..sql.optimizer import optimize
        from ..sql.parser import parse_sql
        from ..sql.planner import SqlToRel

        session, catalog, _config = self._session_ctx(payload)
        sql = payload["sql"]
        logical = optimize(SqlToRel(catalog).plan(parse_sql(sql)))
        stmt_id = f"stmt-{uuidmod.uuid4().hex[:12]}"
        holder = session.prepared if session else self._default_prepared
        holder[stmt_id] = (sql, logical.schema)
        while len(holder) > 256:
            holder.pop(next(iter(holder)))
        return {"statement_id": stmt_id,
                "schema": serde.schema_to_obj(logical.schema)}, b""

    def _explain(self, payload: dict, _bin: bytes):
        """EXPLAIN over the wire: the scheduler owns the catalog in remote
        deployments, so planning happens here; clients get plan rows."""
        from ..scheduler.physical_planner import explain_rows
        from ..sql import ast as sqlast
        from ..sql.parser import parse_sql

        _session, catalog, config = self._session_ctx(payload)
        stmt = parse_sql(payload["sql"])
        verbose = False
        if isinstance(stmt, sqlast.Explain):
            if stmt.analyze:
                raise PlanningError(
                    "EXPLAIN ANALYZE is not supported over the wire: run "
                    "the query, then read GET /api/job/<id>/stats on the "
                    "scheduler's REST API for the same report")
            verbose = stmt.verbose
            stmt = stmt.statement
        return {"rows": explain_rows(catalog, config, stmt, verbose)}, b""

    # --- query handling --------------------------------------------------
    def _execute_query(self, payload: dict, _bin: bytes):
        session, catalog, session_config = self._session_ctx(payload)
        if "statement_id" in payload:
            holder = session.prepared if session else self._default_prepared
            entry = holder.get(payload["statement_id"])
            if entry is None:
                raise PlanningError(
                    f"unknown prepared statement {payload['statement_id']!r}")
            sql = entry[0]
        else:
            sql = payload["sql"]
        job_id = random_job_id()

        from .serving import prepare_sql_submission

        def schema_cb(schema):
            with self._lock:
                self._final_schemas[job_id] = schema
                while len(self._final_schemas) > self._max_schemas:
                    self._final_schemas.popitem(last=False)

        # subplan_ok=False: spooled stage files are served by filesystem
        # path (port-0 locations), which networked executors cannot reach
        cached, plan_fn, serving = prepare_sql_submission(
            self.server, sql, catalog, session_config, job_id,
            subplan_ok=False, schema_cb=schema_cb)
        if cached is not None:
            with self._lock:
                self._cached_results[job_id] = cached
                while len(self._cached_results) > self._max_cached_results:
                    self._cached_results.popitem(last=False)
            return {"job_id": job_id, "cached": True}, b""

        # tenant identity + quotas ride on the session config (plus any
        # per-request overrides already merged into session_config)
        if session is not None:
            request = session.admission_request(session_config)
        else:
            from ..admission import AdmissionRequest

            request = AdmissionRequest.from_config(session_config)
        self.server.submit_job(job_id, plan_fn, admission=request,
                               trace=payload.get("trace"),
                               config=session_config, serving=serving)
        return {"job_id": job_id}, b""

    def _get_job_status(self, payload: dict, _bin: bytes):
        job_id = payload["job_id"]
        with self._lock:
            cached = self._cached_results.get(job_id)
        if cached is not None:
            return {"state": "successful", "cached": True,
                    "schema": serde.schema_to_obj(cached["schema"])}, b""
        status = self.server.get_job_status(job_id)
        if status is None:
            return self._resolve_foreign_status(job_id), b""
        out = {"state": status.state, "error": status.error,
               "retriable": status.retriable}
        if status.state == "successful":
            out["locations"] = {
                str(part): [serde.location_to_obj(l) for l in locs]
                for part, locs in status.locations.items()}
            with self._lock:
                schema = self._final_schemas.get(job_id)
            if schema is None:
                # adopted job: the submit-time schema cache lives on the
                # shard that PLANNED it — re-derive from the final stage
                graph = self.server.jobs.get_graph(job_id)
                if graph is not None:
                    final = graph.stages[graph.final_stage_id]
                    schema = (final.resolved_plan or final.plan).schema
            if schema is not None:
                out["schema"] = serde.schema_to_obj(schema)
        return out, b""

    def _watch_job(self, payload: dict, _bin: bytes):
        """One long-poll watch frame: the job's journal events past
        ``cursor`` plus a live progress snapshot and the current state.
        The client's ``ctx.watch()`` stitches frames into a single stream
        and follows lease adoption (PR 11): when the answering shard
        changes it resets the cursor to 0 — the adopted shard re-seeded
        its timeline from the checkpoint, so indices restart — and dedups
        replayed events on (actor, seq).  Blocking here is fine: the RPC
        server is one thread per connection."""
        import time as _time

        from ..obs import journal
        from ..obs.progress import job_progress

        job_id = payload["job_id"]
        cursor = max(0, int(payload.get("cursor", 0)))
        timeout_s = min(max(float(payload.get("timeout_s", 0.25)), 0.0), 5.0)
        deadline = _time.monotonic() + timeout_s
        while True:
            with self._lock:
                cached = job_id in self._cached_results
            if cached:
                return {"state": "successful", "cached": True,
                        "scheduler_id": self.server.scheduler_id,
                        "cursor": cursor, "events": [],
                        "progress": None}, b""
            status = self.server.get_job_status(job_id)
            if status is None:
                # foreign job: same redirect shape as get_job_status —
                # the reply names the owning shard's endpoint
                return self._resolve_foreign_status(job_id), b""
            timeline = journal.job_timeline(job_id)
            if cursor > len(timeline):
                cursor = 0  # timeline restarted (adoption re-seed)
            events = timeline[cursor:]
            terminal = status.state in ("successful", "failed", "cancelled")
            if events or terminal or _time.monotonic() >= deadline:
                graph = self.server.jobs.get_graph(job_id)
                progress = job_progress(graph) if graph is not None else None
                return {"state": status.state, "error": status.error,
                        "scheduler_id": self.server.scheduler_id,
                        "cursor": cursor + len(events),
                        "events": events, "progress": progress}, b""
            _time.sleep(0.05)

    def _resolve_foreign_status(self, job_id: str) -> dict:
        """A job this shard is not driving: consult the shared KV so
        clients polling the wrong shard after a failover either get
        redirected (lease held by a sibling — the reply names the owner's
        endpoint) or served directly (the job finished and its lease was
        released: the checkpointed graph is the source of truth, and the
        result schema is re-derived from the final stage's plan because
        ``_final_schemas`` is shard-local)."""
        backend = self.server.job_backend
        if backend is None or not hasattr(backend, "get_lease"):
            return {"state": "not_found"}
        try:
            lease = backend.get_lease(job_id)
            if lease is not None and lease.owner != self.server.scheduler_id:
                return {"state": "not_found", "owner": lease.owner,
                        "endpoint": lease.endpoint}
            graph = backend.load_job(job_id)
        except Exception:  # noqa: BLE001 — KV blip: look lost, not failed
            log.exception("foreign-status resolution failed for %s", job_id)
            return {"state": "not_found"}
        if graph is None or graph.status not in ("successful", "failed"):
            return {"state": "not_found"}
        if graph.status == "failed":
            return {"state": "failed", "error": graph.error,
                    "retriable": False}
        graph.addr_resolver = self.server._resolve_addr
        final = graph.stages[graph.final_stage_id]
        locations = final.output_locations(graph.addr_resolver)
        return {"state": "successful", "error": "", "retriable": False,
                "locations": {
                    str(part): [serde.location_to_obj(l) for l in locs]
                    for part, locs in locations.items()},
                "schema": serde.schema_to_obj(
                    (final.resolved_plan or final.plan).schema)}

    def _fetch_result(self, payload: dict, _bin: bytes):
        """One-shot pull of a parked result-cache hit: the reply payload
        lists ``[partition, [blob_len, ...]]`` per partition and the binary
        channel carries the Arrow IPC file blobs concatenated in that
        order (same bytes the executors wrote, so decode is bit-identical
        to the uncached fetch path)."""
        job_id = payload["job_id"]
        with self._lock:
            cached = self._cached_results.pop(job_id, None)
        if cached is None:
            raise PlanningError(f"no cached result parked for job {job_id}")
        parts = []
        blob = bytearray()
        for part, blobs in cached["partitions"]:
            parts.append([part, [len(b) for b in blobs]])
            for b in blobs:
                blob.extend(b)
        return {"partitions": parts,
                "schema": serde.schema_to_obj(cached["schema"])}, bytes(blob)

    def _cancel_job(self, payload: dict, _bin: bytes):
        self.server.cancel_job(payload["job_id"])
        return {}, b""

    # --- executor control ------------------------------------------------
    def _register_executor(self, payload: dict, _bin: bytes):
        self.server.register_executor(
            serde.executor_metadata_from_obj(payload["meta"]))
        return {}, b""

    def _heartbeat(self, payload: dict, _bin: bytes):
        # failpoint: the heartbeat reached the scheduler but is discarded
        # before it touches cluster state — the executor ages toward the
        # offer cutoff / reaper timeout exactly as if the packet was lost
        if faults.dropped("scheduler.heartbeat.receive",
                          executor_id=payload.get("executor_id")):
            return {}, b""
        meta = payload.get("meta")
        self.server.heartbeat(ExecutorHeartbeat(
            payload["executor_id"], status=payload.get("status", "active"),
            metadata=serde.executor_metadata_from_obj(meta) if meta else None,
            memory_pressure=float(payload.get("memory_pressure", 0.0)),
            running=[tuple(t) for t in payload.get("running", [])]))
        return {}, b""

    def _update_task_status(self, payload: dict, _bin: bytes):
        if faults.dropped("scheduler.status.receive",
                          executor_id=payload.get("executor_id"),
                          count=len(payload.get("statuses", []))):
            # swallow the report: the executor's reporter loop keeps the
            # statuses pending and must redeem them on a later attempt
            raise ConnectionError(
                "failpoint scheduler.status.receive dropped the report")
        statuses = [serde.status_from_obj(s) for s in payload["statuses"]]
        # a status report is proof of life: refresh the heartbeat timestamp
        # (without clobbering status) so a busy executor whose heartbeat
        # thread is starved is not reaped while actively reporting work
        self.server.cluster.touch_heartbeat(payload["executor_id"])
        self.server.update_task_status(payload["executor_id"], statuses)
        return {}, b""

    def _poll_work(self, payload: dict, _bin: bytes):
        statuses = [serde.status_from_obj(s) for s in payload.get("statuses", [])]
        executor_id = payload["executor_id"]
        tasks = self.server.poll_work(executor_id,
                                      payload.get("num_free_slots", 0), statuses)
        # per-task guard: an unserializable plan must fail its job, not
        # strand already-popped tasks as running forever.  Grouped shape:
        # the stage plan is wire-encoded once, not once per task.
        objs = serialize_tasks_or_fail(self.server, executor_id, tasks)
        return {"stages": group_tasks_by_plan(objs)}, b""

    def _executor_stopped(self, payload: dict, _bin: bytes):
        self.server.executor_stopped(payload["executor_id"],
                                     payload.get("reason", ""))
        return {}, b""

    # --- catalog (session-scoped when a session_id is supplied) -----------
    def _register_table(self, payload: dict, binary: bytes):
        import io

        import pyarrow.ipc as ipc

        _session, catalog, _ = self._session_ctx(payload)
        table = ipc.open_stream(io.BytesIO(binary)).read_all()
        catalog.register(MemoryTable(payload["name"], table))
        return {}, b""

    def _register_external_table(self, payload: dict, _bin: bytes):
        _session, catalog, _ = self._session_ctx(payload)
        name, fmt, path = payload["name"], payload["format"], payload["path"]
        schema = serde.schema_from_obj(payload["schema"]) if payload.get("schema") else None
        if fmt == "parquet":
            catalog.register(ParquetTable(name, path, schema))
        elif fmt == "csv":
            catalog.register(CsvTable(
                name, path, schema, payload.get("delimiter", ","),
                payload.get("has_header", True)))
        elif fmt == "json":
            from ..catalog import JsonTable

            catalog.register(JsonTable(name, path, schema))
        elif fmt == "avro":
            from ..catalog import AvroTable

            catalog.register(AvroTable(name, path, schema))
        else:
            raise PlanningError(f"unsupported format {fmt!r}")
        return {}, b""

    def _get_file_metadata(self, payload: dict, _bin: bytes):
        """Schema inference for a file path (reference
        SchedulerGrpc.get_file_metadata, grpc.rs:271-325)."""
        from ..catalog import AvroTable, CsvTable, JsonTable, ParquetTable

        path = payload["path"]
        fmt = payload.get("format") or (
            "parquet" if path.endswith(".parquet") else
            "avro" if path.endswith(".avro") else
            "json" if path.endswith((".json", ".jsonl", ".ndjson")) else "csv")
        provider = {"parquet": ParquetTable, "csv": CsvTable,
                    "json": JsonTable, "avro": AvroTable}[fmt]
        schema = provider("__meta", path).schema
        return {"format": fmt, "schema": serde.schema_to_obj(schema)}, b""

    def _list_tables(self, payload: dict, _bin: bytes):
        _session, catalog, _ = self._session_ctx(payload)
        return {"tables": catalog.table_names()}, b""

    def _table_schema(self, payload: dict, _bin: bytes):
        _session, catalog, _ = self._session_ctx(payload)
        schema = catalog.table_schema(payload["name"])
        return {"schema": serde.schema_to_obj(schema)}, b""

    def _deregister_table(self, payload: dict, _bin: bytes):
        _session, catalog, _ = self._session_ctx(payload)
        catalog.deregister(payload["name"])
        return {}, b""
