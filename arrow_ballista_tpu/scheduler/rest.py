"""REST API for the scheduler: cluster state, jobs, stages, dot, metrics.

Parity: reference ballista/scheduler/src/api/ (warp routes under /api,
api/mod.rs:85-137 + handlers.rs):

    GET  /api/state            cluster summary
    GET  /api/executors        executor metadata + heartbeats
    GET  /api/jobs             job list with status + progress
    GET  /api/job/<id>         job detail incl. per-task attempt history
    GET  /api/job/<id>/stages  per-stage task progress
    GET  /api/job/<id>/dot     graphviz of the execution graph
    PATCH /api/job/<id>        cancel (body ignored)
    GET  /api/metrics          prometheus text exposition

Beyond the reference surface:

    GET  /api/admission        admission-control queue state per tenant
    GET  /api/quarantine       quarantined/probation executors + counters
    GET  /api/job/<id>/profile per-stage -> per-task -> per-operator profile
    GET  /api/job/<id>/trace   Chrome trace-event JSON (Perfetto-loadable)
    GET  /api/job/<id>/stats   EXPLAIN ANALYZE report: per-stage skew /
                               histograms / duration quantiles + annotated
                               operator tree (obs/stats.py)
    GET  /api/job/<id>/advise  stage-fusion advisor: operator chains ranked
                               by estimated fusion savings (obs/advisor.py)
    GET  /api/cluster/history  ring-buffer time series of cluster samples
                               (utilization, queue depths, event-loop lag),
                               fleet-aware: per-shard breakdown + rollup
                               via the shared-KV shard registry
    GET  /api/job/<id>/forensics  self-contained postmortem bundle: flight-
                               recorder timeline + stage stats + device
                               stats + spans + metrics (obs/doctor.py)
    GET  /api/job/<id>/doctor  automated pathology diagnosis over the
                               forensics bundle: ranked findings with
                               cited metric evidence + config remedies
    GET  /api/plan-cache       prepared-plan cache: hit/miss/eviction
                               counters, budgets, recent templates
    GET  /api/result-cache     result/subplan cache counters + budgets
    GET  /api/autoscale        KEDA-style fleet scaling signal: pending
                               tasks / utilization / queue depths summed
                               across shards via the shared-KV registry
    GET  /api/slo              latency SLO snapshot: policy, fast/slow
                               window counts and burn rates, fleet-merged
                               across shards via the shared-KV registry
    GET  /api/job/<id>/watch   live chunked-NDJSON stream: journal events
                               + progress frames + one terminal frame
                               (docs/user-guide/live.md for the schema)
    GET  /api/cluster/watch    live chunked-NDJSON stream of every journal
                               event on this shard (no terminal frame;
                               close the connection to stop)
"""
from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from ..obs import journal
from ..obs.advisor import advise_graph
from ..obs.doctor import assemble_forensics, diagnose
from ..obs.progress import job_progress, monotonic_fraction
from ..obs.stats import explain_analyze_report
from ..utils.config import (
    BallistaConfig,
    LIVE_WATCH_POLL_S,
    LIVE_WATCH_QUEUE_EVENTS,
)
from .graph_dot import graph_to_dot
from .scheduler import SchedulerServer

#: job states that end a watch stream
_TERMINAL = ("successful", "failed", "cancelled")


class RestApi:
    def __init__(self, server: SchedulerServer, host: str = "127.0.0.1",
                 port: int = 0):
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def _send(self, code: int, body: str, ctype="application/json"):
                data = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                try:
                    outer._route_get(self)
                # the error is returned to the HTTP client as the 500 body;
                # logging every probe of a bad route lets clients spam the log
                # ballista: allow=recovery-path-logging — surfaced in the 500
                except Exception as e:  # noqa: BLE001
                    self._send(500, json.dumps({"error": str(e)}))

            def do_PATCH(self):
                parts = self.path.strip("/").split("/")
                if len(parts) == 3 and parts[0] == "api" and parts[1] == "job":
                    outer.server.cancel_job(parts[2])
                    self._send(200, json.dumps({"cancelled": parts[2]}))
                else:
                    self._send(404, json.dumps({"error": "not found"}))

        self.server = server
        # watch streams poll this so stop() does not hang on a client that
        # keeps its NDJSON connection open  ballista: guarded-by=none
        self._stopping = False
        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self.host, self.port = self._httpd.server_address
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name=f"rest-{self.port}", daemon=True)

    def start(self):
        self._thread.start()

    def stop(self):
        self._stopping = True
        if self._thread.is_alive():
            self._httpd.shutdown()
            self._thread.join(timeout=5.0)
        self._httpd.server_close()

    # --- routing ---------------------------------------------------------
    def _route_get(self, h) -> None:
        parts = h.path.strip("/").split("/")
        if parts in ([""], ["ui"], ["index.html"]):
            # the web dashboard (reference ships a React app over the same
            # /api surface, ui/src/components/*.tsx)
            from .webui import INDEX_HTML

            h._send(200, INDEX_HTML, ctype="text/html; charset=utf-8")
            return
        if parts[:1] != ["api"]:
            h._send(404, json.dumps({"error": "not found"}))
            return
        rest = parts[1:]
        if rest == ["state"]:
            h._send(200, json.dumps(self._state()))
        elif rest == ["executors"]:
            h._send(200, json.dumps(self._executors()))
        elif rest == ["jobs"]:
            h._send(200, json.dumps(self._jobs()))
        elif len(rest) == 2 and rest[0] == "job":
            job = self._job_detail(rest[1])
            if job is None:
                h._send(404, json.dumps({"error": "no such job"}))
            else:
                h._send(200, json.dumps(job))
        elif len(rest) == 3 and rest[0] == "job" and rest[2] == "stages":
            h._send(200, json.dumps(self._stages(rest[1])))
        elif len(rest) == 3 and rest[0] == "job" and rest[2] == "watch":
            if self.server.jobs.get_status(rest[1]) is None:
                h._send(404, json.dumps({"error": "no such job"}))
            else:
                self._stream_watch(h, rest[1])
        elif rest == ["cluster", "watch"]:
            self._stream_watch(h, None)
        elif rest == ["slo"]:
            h._send(200, json.dumps(self.server.slo_report()))
        elif len(rest) == 3 and rest[0] == "job" and rest[2] == "profile":
            prof = self.server.obs.get_profile(
                rest[1], self.server.jobs.get_graph(rest[1]),
                self.server.jobs.get_status(rest[1]))
            if prof is None:
                h._send(404, json.dumps({"error": "no profile for job"}))
            else:
                h._send(200, json.dumps(prof))
        elif len(rest) == 3 and rest[0] == "job" and rest[2] == "trace":
            trace = self.server.obs.get_trace(
                rest[1], self.server.jobs.get_graph(rest[1]))
            if trace is None:
                h._send(404, json.dumps({"error": "no trace for job"}))
            else:
                h._send(200, json.dumps(trace))
        elif len(rest) == 3 and rest[0] == "job" and rest[2] == "stats":
            graph = self.server.jobs.get_graph(rest[1])
            if graph is None:
                h._send(404, json.dumps({"error": "no such job"}))
            else:
                h._send(200, json.dumps(explain_analyze_report(graph)))
        elif len(rest) == 3 and rest[0] == "job" and rest[2] == "advise":
            graph = self.server.jobs.get_graph(rest[1])
            if graph is None:
                h._send(404, json.dumps({"error": "no such job"}))
            else:
                h._send(200, json.dumps(advise_graph(graph)))
        elif len(rest) == 3 and rest[0] == "job" and rest[2] == "forensics":
            bundle = assemble_forensics(self.server, rest[1])
            if bundle is None:
                h._send(404, json.dumps({"error": "no such job"}))
            else:
                h._send(200, json.dumps(bundle, default=str))
        elif len(rest) == 3 and rest[0] == "job" and rest[2] == "doctor":
            bundle = assemble_forensics(self.server, rest[1])
            if bundle is None:
                h._send(404, json.dumps({"error": "no such job"}))
            else:
                h._send(200, json.dumps(diagnose(bundle), default=str))
        elif rest == ["cluster", "history"]:
            h._send(200, json.dumps(self.server.cluster_history()))
        elif len(rest) == 3 and rest[0] == "job" and rest[2] == "dot":
            graph = self.server.jobs.get_graph(rest[1])
            if graph is None:
                h._send(404, json.dumps({"error": "no such job"}))
            else:
                h._send(200, graph_to_dot(graph), ctype="text/vnd.graphviz")
        elif rest == ["metrics"]:
            # fold the latest journal counter deltas in before exposition
            # (the history sampler also does this on its own cadence)
            self.server.sync_journal_metrics()
            h._send(200, self.server.metrics.gather(), ctype="text/plain")
        elif rest == ["admission"]:
            h._send(200, json.dumps(self.server.admission.snapshot()))
        elif rest == ["plan-cache"]:
            h._send(200, json.dumps(self.server.plan_cache.snapshot()))
        elif rest == ["result-cache"]:
            h._send(200, json.dumps(self.server.result_cache.snapshot()))
        elif rest == ["quarantine"]:
            h._send(200, json.dumps(self.server.quarantine.snapshot()))
        elif rest == ["scaler"]:
            # KEDA-scaler-shaped endpoint (reference external_scaler.rs:14-60
            # reports inflight_tasks = pending task count); consumed by a
            # metrics-api trigger (deploy/helm templates/hpa.yaml)
            h._send(200, json.dumps(
                {"inflight_tasks": self.server.pending_task_count()}))
        elif rest == ["autoscale"]:
            # fleet-wide scaling signal: /api/scaler's successor — pending
            # work, queue depths and utilization summed over every live
            # shard via the shared-KV shard registry (docs/user-guide/
            # metrics.md), plus a desired_executors suggestion
            h._send(200, json.dumps(self.server.autoscale_signal()))
        else:
            h._send(404, json.dumps({"error": "not found"}))

    # --- watch streams ---------------------------------------------------
    def _stream_watch(self, h, job_id: Optional[str]) -> None:
        """Chunk NDJSON frames at the client until the job ends (job watch)
        or the connection drops (cluster watch).  Frames are one JSON
        object per line, tagged ``{"t": "event"|"progress"|"end"}``; no
        Content-Length — the stream is close-delimited.  The journal
        subscription is bounded and never blocks ``emit()``: a slow
        reader sees a ``watch.gap`` event instead of backpressure."""
        defaults = BallistaConfig()
        poll_s = float(defaults.get(LIVE_WATCH_POLL_S))
        capacity = int(defaults.get(LIVE_WATCH_QUEUE_EVENTS))
        h.send_response(200)
        h.send_header("Content-Type", "application/x-ndjson")
        h.send_header("Cache-Control", "no-cache")
        h.end_headers()

        def frame(obj: dict) -> bool:
            try:
                h.wfile.write((json.dumps(obj) + "\n").encode())
                h.wfile.flush()
                return True
            except (BrokenPipeError, ConnectionResetError, OSError):
                return False

        floor = 0.0
        with journal.subscribe(job_id=job_id, capacity=capacity) as sub:
            # subscribe BEFORE snapshotting the retained timeline, then
            # dedup on (actor, seq): no event emitted during the handoff
            # is lost, none is shown twice
            replayed = set()
            if job_id is not None:
                for ev in journal.job_timeline(job_id):
                    replayed.add((ev.get("actor"), ev.get("seq")))
                    if not frame({"t": "event", "event": ev}):
                        return
            while not self._stopping:
                for ev in sub.poll(timeout=poll_s):
                    key = (ev.get("actor"), ev.get("seq"))
                    # watch.gap markers carry seq=0 and must never dedup
                    if ev.get("kind") != "watch.gap" and key in replayed:
                        continue
                    if not frame({"t": "event", "event": ev}):
                        return
                if replayed:
                    replayed.clear()  # only the handoff window needs it
                if job_id is None:
                    continue
                st = self.server.jobs.get_status(job_id)
                graph = self.server.jobs.get_graph(job_id)
                if graph is not None:
                    prog = job_progress(graph)
                    floor = monotonic_fraction(prog, floor)
                    prog["fraction"] = floor
                    if not frame({"t": "progress", "progress": prog,
                                  "state": st.state if st else None}):
                        return
                if st is not None and st.state in _TERMINAL:
                    frame({"t": "end", "state": st.state,
                           "error": st.error})
                    return

    # --- payloads --------------------------------------------------------
    def _state(self) -> dict:
        cluster = self.server.cluster
        return {
            "executors": len(cluster.executors()),
            "alive_executors": len(cluster.alive_executors(
                self.server.config.executor_timeout_s)),
            "quarantined_executors": self.server.quarantine.count(),
            "available_task_slots": cluster.total_available(),
            "pending_tasks": self.server.pending_task_count(),
            "started_at": getattr(self.server, "_started_at", 0),
        }

    def _executors(self) -> list:
        cluster = self.server.cluster
        out = []
        for meta in cluster.executors():
            hb = cluster._heartbeats.get(meta.executor_id)
            out.append({
                "executor_id": meta.executor_id, "host": meta.host,
                "port": meta.port,
                "task_slots": meta.task_slots,
                "last_seen_s_ago": round(time.time() - hb.timestamp, 1) if hb else None,
                "status": hb.status if hb else "unknown",
                "quarantined": self.server.quarantine.is_quarantined(
                    meta.executor_id),
            })
        return out

    def _jobs(self) -> list:
        out = []
        with self.server.jobs._lock:
            statuses = dict(self.server.jobs._status)
        for job_id, st in statuses.items():
            entry = {"job_id": job_id, "state": st.state, "error": st.error}
            graph = self.server.jobs.get_graph(job_id)
            if graph is not None:
                # one computation for every surface: REST, watch frames and
                # EXPLAIN ANALYZE all report obs/progress.py's fraction
                prog = job_progress(graph)
                entry["stages"] = len(graph.stages)
                entry["tasks_completed"] = prog["tasks_completed"]
                entry["tasks_total"] = prog["tasks_total"]
                entry["progress"] = prog["fraction"]
                entry["eta_s"] = prog["eta_s"]
            out.append(entry)
        return out

    def _job_detail(self, job_id: str) -> Optional[dict]:
        """Job status + the full per-task attempt history: every launch
        (original, retry, or speculative duplicate) with its executor,
        terminal state and duration — the audit trail for straggler
        mitigation ("did speculation fire, and who won?")."""
        st = self.server.jobs.get_status(job_id)
        if st is None:
            return None
        out = {"job_id": job_id, "state": st.state, "error": st.error}
        graph = self.server.jobs.get_graph(job_id)
        if graph is None:
            return out
        out["progress"] = job_progress(graph)
        stages = {}
        for sid in sorted(graph.stages):
            s = graph.stages[sid]
            stages[str(sid)] = {
                "state": s.state,
                "stage_attempt": s.stage_attempt,
                "attempts": [
                    {"partition": e["partition"], "attempt": e["attempt"],
                     "stage_attempt": e["stage_attempt"],
                     "executor_id": e["executor_id"],
                     "speculative": e["speculative"], "state": e["state"],
                     "duration_s": (round(e["duration_s"], 3)
                                    if e["duration_s"] is not None else None)}
                    for e in s.attempt_log],
            }
        out["stages"] = stages
        return out

    def _stages(self, job_id: str) -> list:
        graph = self.server.jobs.get_graph(job_id)
        if graph is None:
            return []
        # per-stage fractions come from the same obs/progress.py fold the
        # job-level surfaces use, so the numbers always agree
        prog = {s["stage_id"]: s for s in job_progress(graph)["stages"]}
        out = []
        for sid in sorted(graph.stages):
            s = graph.stages[sid]
            agg = {k: round(v, 3) for k, v in s.aggregate_metrics().items()}
            out.append({
                "stage_id": sid, "state": s.state,
                "partitions": s.partitions,
                "completed": prog[sid]["tasks_completed"],
                "fraction": prog[sid]["fraction"],
                "attempt": s.stage_attempt,
                "producers": s.producer_ids,
                "consumers": s.output_links,
                "plan": (s.resolved_plan or s.plan).display(),
                "metrics": agg,
            })
        return out
