"""Remote cluster client: the DistributedQueryExec role.

Parity: reference core/src/execution_plans/distributed_query.rs — submit
the query to the scheduler, poll GetJobStatus every 100 ms (:262), then
open data-plane streams to the executors holding the final-stage
partitions (:305-329, via BallistaClient::fetch_partition).
"""
from __future__ import annotations

import io
import time
from typing import Dict, List, Optional, Tuple

from .. import serde
from ..models.batch import ColumnBatch
from ..net import wire
from ..obs.tracing import current_context, span
from ..utils.config import BallistaConfig
from ..utils.errors import ExecutionError, ResourceExhausted

POLL_INTERVAL_S = 0.1  # reference: 100 ms


class RemoteCluster:
    """Scheduler client with fleet failover.

    Single-scheduler callers keep the old surface: ``RemoteCluster(host,
    port, config)`` binds one endpoint and transport errors surface raw.
    Fleet callers pass ``endpoints=[(h1, p1), (h2, p2), ...]``: calls stick
    to one shard until it dies, then rotate down the ordered list; sessions
    are shard-local so one is created per endpoint on first use, and
    catalog mutations are broadcast (plus replayed on session creation) so
    any shard can plan this client's queries after a failover.  A poll that
    lands on a non-owning shard is redirected via the lease record the
    shards keep in their shared KV (netservice._resolve_foreign_status)."""

    def __init__(self, host: Optional[str] = None, port: Optional[int] = None,
                 config: Optional[BallistaConfig] = None,
                 endpoints: Optional[List[Tuple[str, int]]] = None):
        self.config = config or BallistaConfig()
        eps = [(h, int(p)) for h, p in (endpoints or [])]
        if host is not None and (host, port) not in eps:
            eps.insert(0, (host, port))
        if not eps:
            raise ValueError("RemoteCluster needs host/port or endpoints")
        self._endpoints = eps
        self._primary = 0
        self.host, self.port = eps[0]
        # shard-local sessions, created lazily per endpoint; catalog
        # mutations are logged for replay so a session created AFTER a
        # registration (failover to a lazily-dialed shard) still sees the
        # client's tables
        self._sessions: Dict[Tuple[str, int], str] = {}
        self._catalog_log: List[tuple] = []
        # how long a fleet client keeps polling through "not_found" before
        # declaring the job lost: one lease TTL (the owner must miss that
        # many renewals before expiry) + two adoption scans + slack
        from ..utils.config import FLEET_ADOPT_INTERVAL_S, FLEET_LEASE_TTL_S

        self._adoption_grace_s = (
            float(self.config.get(FLEET_LEASE_TTL_S))
            + 2.0 * float(self.config.get(FLEET_ADOPT_INTERVAL_S)) + 2.0)
        # one scheduler session per client context: private table namespace
        # + this client's config (reference: ExecuteQuery with no query
        # creates the server-side session, context.rs:80-140)
        self.session_id = self._session_for(eps[0])

    def close(self) -> None:
        for ep, sid in list(self._sessions.items()):
            try:
                wire.call(ep[0], ep[1], "remove_session", {"session_id": sid})
            except Exception:  # noqa: BLE001 — scheduler may be gone
                pass
        self._sessions.clear()
        self.session_id = None

    # --- endpoint walking ------------------------------------------------
    def _session_for(self, ep: Tuple[str, int]) -> str:
        sid = self._sessions.get(ep)
        if sid is not None:
            return sid
        payload, _ = wire.call(ep[0], ep[1], "create_session",
                               {"settings": dict(self.config._settings)})
        sid = payload["session_id"]
        self._sessions[ep] = sid
        # catch the new session up on this client's catalog (idempotent:
        # registration overwrites by name)
        for method, p, binary in self._catalog_log:
            q = dict(p)
            q["session_id"] = sid
            wire.call(ep[0], ep[1], method, q, binary)
        return sid

    def _rotate(self, failed_ep: Tuple[str, int]) -> None:
        # the dead shard's session dies with it: a restarted shard would
        # not recognise the id, so re-create (and replay) on reconnect
        self._sessions.pop(failed_ep, None)
        self._primary = (self._primary + 1) % len(self._endpoints)
        self.host, self.port = self._endpoints[self._primary]
        self.session_id = self._sessions.get(self._endpoints[self._primary])

    def _point_primary(self, endpoint: str) -> None:
        """Re-stick to the shard a not_found redirect named as the job's
        current lease owner ("host:port")."""
        host, _, port = endpoint.rpartition(":")
        ep = (host, int(port))
        if ep not in self._endpoints:
            self._endpoints.append(ep)
        self._primary = self._endpoints.index(ep)
        self.host, self.port = ep
        self.session_id = self._sessions.get(ep)

    def _call(self, method: str, payload: dict = None, binary: bytes = b""):
        payload = dict(payload or {})
        last: Optional[Exception] = None
        # iterate a snapshot: concurrent callers (a watch generator and a
        # status poller share this client) may re-point _primary mid-loop,
        # which must not make this loop retry a dead shard while a live
        # one exists
        eps = list(self._endpoints)
        start = self._primary if self._primary < len(eps) else 0
        for i in range(len(eps)):
            ep = eps[(start + i) % len(eps)]
            try:
                sid = self._session_for(ep)
                p = dict(payload)
                p.setdefault("session_id", sid)
                return wire.call(ep[0], ep[1], method, p, binary)
            except (ConnectionError, OSError) as e:
                if len(eps) == 1:
                    raise  # single-scheduler surface: raw transport error
                last = e
                self._rotate(ep)
        raise ConnectionError(
            f"no scheduler endpoint reachable for {method}: {last}") from last

    # --- catalog ---------------------------------------------------------
    def _broadcast_catalog(self, method: str, payload: dict,
                           binary: bytes = b"") -> None:
        """Catalog mutations go to EVERY shard (sessions — and therefore
        table namespaces — are shard-local): the current primary must
        succeed, siblings are best-effort and get caught up by the replay
        log when their session is next created."""
        self._catalog_log.append((method, dict(payload), binary))
        self._call(method, payload, binary)
        current = self._endpoints[self._primary]
        for ep in list(self._endpoints):
            if ep == current:
                continue
            try:
                sid = self._session_for(ep)
                p = dict(payload)
                p["session_id"] = sid
                wire.call(ep[0], ep[1], method, p, binary)
            except (ConnectionError, OSError):
                # shard down: the replay log catches it up on reconnect
                self._sessions.pop(ep, None)

    def register_table(self, name: str, table) -> None:
        import pyarrow.ipc as ipc

        buf = io.BytesIO()
        with ipc.new_stream(buf, table.schema) as w:
            w.write_table(table)
        self._broadcast_catalog("register_table", {"name": name},
                                buf.getvalue())

    def register_external_table(self, name: str, fmt: str, path: str,
                                schema=None, delimiter: str = ",",
                                has_header: bool = True) -> None:
        self._broadcast_catalog("register_external_table", {
            "name": name, "format": fmt, "path": path,
            "schema": serde.schema_to_obj(schema) if schema is not None else None,
            "delimiter": delimiter, "has_header": has_header})

    def list_tables(self) -> List[str]:
        payload, _ = self._call("list_tables")
        return payload["tables"]

    def table_schema(self, name: str):
        payload, _ = self._call("table_schema", {"name": name})
        return serde.schema_from_obj(payload["schema"])

    def deregister_table(self, name: str) -> None:
        self._broadcast_catalog("deregister_table", {"name": name})

    def explain(self, sql: str) -> List[dict]:
        payload, _ = self._call("explain", {"sql": sql})
        return payload["rows"]

    def update_session(self, settings: dict) -> dict:
        payload, _ = self._call("update_session", {"settings": settings})
        return payload["settings"]

    # --- query execution -------------------------------------------------
    def execute_sql(self, sql: str, timeout: Optional[float] = None) -> List[ColumnBatch]:
        if timeout is None:
            timeout = float(self.config.job_timeout_s)
        deadline = time.monotonic() + timeout
        # fleet: a job that dies with its shard BEFORE the first checkpoint
        # leaves no lease and no graph in the KV — nothing for a sibling to
        # adopt — so the client resubmits the query once (SQL reads are
        # safe to re-run; at worst a partitioned-but-unreachable ex-owner
        # wastes work, which lease fencing already makes harmless)
        tries = 2 if len(self._endpoints) > 1 else 1
        for attempt in range(tries):
            batches = self._execute_once(sql, deadline,
                                         final=attempt == tries - 1)
            if batches is not None:
                return batches
        raise ExecutionError(
            "query lost across scheduler failover (resubmitted once)")

    def _execute_once(self, sql: str, deadline: float,
                      final: bool) -> Optional[List[ColumnBatch]]:
        """One submit+poll+fetch round.  Returns the batches, or None when
        the job was lost without a trace in the fleet's shared KV and the
        caller should resubmit (never when ``final``: then it raises)."""
        # the client owns the trace root: the scheduler parents its job
        # span on client.collect, executors parent task spans below that
        trace = current_context()
        with span("submit", "client") as sp:
            payload, _ = self._call("execute_query",
                                    {"sql": sql,
                                     "config": dict(self.config._settings),
                                     "trace": trace})
            job_id = payload["job_id"]
            sp.set(job_id=job_id, cached=bool(payload.get("cached")))
        if payload.get("cached"):
            # result-cache hit: no job ran; pull the parked bytes in one
            # round-trip instead of polling
            return self._fetch_cached(job_id)
        # ``wait`` ends when this client learns of the terminal status, so
        # ``wait.end - job.end`` is what the poll cost
        with span("wait", "client", job_id=job_id) as sp:
            status = self._poll(job_id, deadline, final, sp)
        if status is None:
            return None
        if status.get("cached"):
            return self._fetch_cached(job_id)
        # result collection rides the same chunked protocol as
        # executor-to-executor shuffle
        from ..net.dataplane import fetch_partition

        schema = serde.schema_from_obj(status["schema"])
        batches: List[ColumnBatch] = []
        with span("fetch", "client", job_id=job_id) as sp:
            nbytes = 0
            for part in sorted(status["locations"], key=int):
                for obj in status["locations"][part]:
                    loc = serde.location_from_obj(obj)
                    if not loc.num_rows:
                        continue
                    nbytes += loc.num_bytes
                    batches.extend(
                        fetch_partition(loc, schema, self.config)[0])
            sp.set(bytes=nbytes)
        return batches

    def _poll(self, job_id: str, deadline: float, final: bool,
              sp) -> Optional[dict]:
        """Poll ``get_job_status`` until the job is terminal: the
        successful status, or None where the job was lost and the caller
        should resubmit.  ``sp`` (the ``wait`` span) counts the polls."""
        lost_since: Optional[float] = None
        polls = 0
        while True:
            status, _ = self._call("get_job_status", {"job_id": job_id})
            polls += 1
            sp.set(polls=polls)
            state = status["state"]
            if state == "successful":
                return status
            if state == "not_found" and len(self._endpoints) > 1:
                if status.get("owner") and status.get("endpoint"):
                    # a sibling named the current lease owner: re-stick
                    # there and keep polling (sticky routing survives the
                    # submitting shard's death)
                    self._point_primary(status["endpoint"])
                    lost_since = None
                    time.sleep(POLL_INTERVAL_S)
                    continue
                # no owner yet: adoption may be mid-flight (the lease must
                # expire first) — keep polling for one grace window
                lost_since = lost_since if lost_since is not None \
                    else time.monotonic()
                if (time.monotonic() - lost_since < self._adoption_grace_s
                        and time.monotonic() < deadline):
                    time.sleep(POLL_INTERVAL_S)
                    continue
                if not final:
                    return None  # lost pre-checkpoint: resubmit once
            if state in ("failed", "cancelled", "not_found"):
                if status.get("retriable"):
                    # admission shed (queue full / timeout): transient
                    # back-pressure, surfaced distinctly so callers retry
                    raise ResourceExhausted(
                        f"job {job_id} shed: {status.get('error', '')}")
                raise ExecutionError(
                    f"job {job_id} {state}: {status.get('error', '')}")
            if time.monotonic() > deadline:
                self._call("cancel_job", {"job_id": job_id})
                raise ExecutionError(f"job {job_id} timed out")
            time.sleep(POLL_INTERVAL_S)

    # --- lifecycle control -----------------------------------------------
    def cancel_job(self, job_id: str) -> None:
        """Ask the scheduler to cancel ``job_id`` fleet-wide: running tasks
        get a cancel fanout (cooperative checkpoints land it in seconds), a
        still-queued job is pulled from the admission queue, and every
        leaked remnant — slot reservations, admission permits, speculation
        state — is released with the terminal status."""
        self._call("cancel_job", {"job_id": job_id})

    # --- live watch ------------------------------------------------------
    def watch(self, job_id: str, timeout: Optional[float] = None):
        """Generator of live watch frames for ``job_id`` — dicts tagged
        ``{"t": "event"|"progress"|"end"}``, the same shape the REST
        NDJSON stream carries.  Long-polls the owning shard's watch_job
        RPC and follows lease adoption (PR 11): a not_found redirect
        re-sticks to the named owner, a change of answering shard resets
        the cursor to 0 (the adopted timeline was re-seeded from the
        checkpoint) and the (actor, seq) dedup set drops the replayed
        prefix — so a SIGKILL failover yields ONE continuous timeline
        with the ``lease.adopt`` marker in-band, no duplicates, and the
        terminal frame intact."""
        from ..obs.progress import monotonic_fraction
        from ..utils.config import LIVE_WATCH_POLL_S

        if timeout is None:
            timeout = float(self.config.job_timeout_s)
        poll_s = float(self.config.get(LIVE_WATCH_POLL_S))
        deadline = time.monotonic() + timeout
        cursor = 0
        shard: Optional[str] = None
        seen: set = set()
        floor = 0.0
        lost_since: Optional[float] = None
        while time.monotonic() < deadline:
            try:
                payload, _ = self._call("watch_job",
                                        {"job_id": job_id, "cursor": cursor,
                                         "timeout_s": poll_s})
            except (ConnectionError, OSError):
                if len(self._endpoints) == 1:
                    raise
                # whole fleet unreachable this instant (mid-failover):
                # keep trying for the adoption grace window
                lost_since = lost_since if lost_since is not None \
                    else time.monotonic()
                if time.monotonic() - lost_since > self._adoption_grace_s:
                    raise
                time.sleep(POLL_INTERVAL_S)
                continue
            state = payload.get("state")
            if state == "not_found":
                if payload.get("owner") and payload.get("endpoint"):
                    # the named owner may be a corpse whose lease has not
                    # expired yet: pace the redirect loop like the status
                    # poller does instead of hammering it
                    self._point_primary(payload["endpoint"])
                    lost_since = None
                    time.sleep(POLL_INTERVAL_S)
                    continue
                lost_since = lost_since if lost_since is not None \
                    else time.monotonic()
                if time.monotonic() - lost_since < self._adoption_grace_s:
                    time.sleep(POLL_INTERVAL_S)
                    continue
                raise ExecutionError(
                    f"job {job_id} lost: no shard owns or remembers it")
            lost_since = None
            sid = payload.get("scheduler_id")
            if shard is None:
                shard = sid
            elif sid != shard:
                # failover: replay the adopted shard's timeline from the
                # start; dedup below drops everything already shown
                shard = sid
                cursor = 0
                continue
            for ev in payload.get("events", []):
                key = (ev.get("actor"), ev.get("seq"))
                # watch.gap markers carry seq=0 and must never dedup
                if ev.get("kind") != "watch.gap":
                    if key in seen:
                        continue
                    seen.add(key)
                yield {"t": "event", "event": ev}
            cursor = int(payload.get("cursor", cursor))
            prog = payload.get("progress")
            if prog:
                floor = monotonic_fraction(prog, floor)
                prog["fraction"] = floor
                yield {"t": "progress", "progress": prog, "state": state}
            if state in ("successful", "failed", "cancelled"):
                yield {"t": "end", "state": state,
                       "error": payload.get("error", "")}
                return
        raise ExecutionError(f"watch of job {job_id} timed out")

    def _fetch_cached(self, job_id: str) -> List[ColumnBatch]:
        """Decode a fetch_result reply: the payload lists per-partition
        blob lengths, the binary channel is those Arrow IPC files
        concatenated — the same bytes the uncached path reads from
        executors, so results are bit-identical."""
        from ..models.ipc import read_ipc_buffers

        payload, blob = self._call("fetch_result", {"job_id": job_id})
        schema = serde.schema_from_obj(payload["schema"])
        batches: List[ColumnBatch] = []
        off = 0
        for _part, lens in sorted(payload["partitions"], key=lambda p: p[0]):
            blobs = []
            for n in lens:
                blobs.append(blob[off:off + n])
                off += n
            batches.extend(read_ipc_buffers(blobs, schema,
                                            capacity=self.config.batch_size))
        return batches
