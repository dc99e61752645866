"""ExecutionGraph: per-job DAG of shuffle stages + fault tolerance.

Parity with the reference's scheduler core
(reference ballista/scheduler/src/state/execution_graph.rs:61-1211 and
execution_graph/execution_stage.rs): stages move through

    UNRESOLVED -> RESOLVED/RUNNING -> SUCCESSFUL
         ^                |
         └── rollback ────┘        (FetchPartitionError / executor lost)

``update_task_status`` implements the same lineage-aware recovery
(execution_graph.rs:270-657): a fetch failure rolls the consumer stage back
to UNRESOLVED and re-opens the producer's poisoned map partition; retryable
task errors reset the task; execution errors fail the job.  Retry budgets
mirror task_manager.rs:55-57 (TASK_MAX_FAILURES=4, STAGE_MAX_FAILURES=4).

Design deviation from the reference: consumer input locations are *derived*
from producer stage outputs at resolve time instead of being incrementally
pushed — a stage resolves only when every producer is SUCCESSFUL, at which
point producer outputs are final, so the derived view is equivalent and
removes a whole class of partial-update states.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Dict, List, Optional, Tuple

from ..ops.shuffle import (
    PartitionLocation,
    ShuffleReaderExec,
    ShuffleWritePartition,
    ShuffleWriterExec,
)
from ..obs import journal
from ..obs.stats import RuntimeStatsStore
from ..utils.errors import InternalError
from .aqe import AqePolicy, maybe_broadcast_switch, rewrite_resolved_stage
from .planner import (
    DistributedPlanner,
    QueryStage,
    collect_nodes,
    remove_unresolved_shuffles,
)
from ..ops.shuffle import UnresolvedShuffleExec
from .types import (
    EXECUTION_ERROR,
    FETCH_PARTITION_ERROR,
    TASK_KILLED,
    FailedReason,
    TaskDescription,
    TaskId,
    TaskStatus,
)

TASK_MAX_FAILURES = 4
STAGE_MAX_FAILURES = 4

UNRESOLVED = "unresolved"
RUNNING = "running"
SUCCESSFUL = "successful"
FAILED = "failed"


@dataclasses.dataclass
class TaskInfo:
    partition: int
    executor_id: str
    state: str  # 'running' | 'success'
    # last TaskStatus for observability: per-operator metrics + launch/end
    # timestamps survive absorption (reference keeps the full status stream
    # in ExecutionGraph for the UI's stage metrics)
    status: object = None
    # attempt id this info belongs to (matches TaskId.task_attempt), so a
    # status from a cancelled duplicate can be told apart from the winner's
    attempt: int = 0
    speculative: bool = False
    # monotonic launch time; age drives the speculation policy
    started_at: float = 0.0


class ExecutionStage:
    def __init__(self, stage_id: int, plan: ShuffleWriterExec):
        self.stage_id = stage_id
        self.plan = plan  # with UnresolvedShuffleExec leaves
        self.partitions = plan.output_partition_count()
        self.producer_ids = sorted(
            {u.stage_id for u in collect_nodes(plan, UnresolvedShuffleExec)})
        self.output_links: List[int] = []
        self.state = UNRESOLVED
        # stage_attempt is a monotonic *epoch*: it identifies which attempt a
        # task belongs to, so late statuses from rolled-back attempts can be
        # dropped.  failures is the *budget* counter checked against
        # STAGE_MAX_FAILURES — rollbacks that aren't the query's fault
        # (executor loss) bump the epoch but not the budget.
        self.stage_attempt = 0
        self.failures = 0
        self.resolved_plan: Optional[ShuffleWriterExec] = None
        self.task_infos: List[Optional[TaskInfo]] = [None] * self.partitions
        self.task_failures: List[int] = [0] * self.partitions
        # next attempt id per partition: every launch — retry duplicate or
        # speculative duplicate — draws a fresh id (keeps planned length,
        # like task_failures, across adaptive coalescing)
        self.task_attempts: List[int] = [0] * self.partitions
        # partition -> in-flight speculative duplicate of a straggling task
        self.speculative_tasks: Dict[int, TaskInfo] = {}
        # partition -> executors where it failed retryably (this stage
        # attempt): retry anti-affinity steers the next attempt to a FRESH
        # executor when one is alive, so a task-level fault either clears
        # (executor was sick) or accumulates the distinct-executor evidence
        # poison containment needs (query is sick)
        self.failed_on: Dict[int, set] = {}
        # completed-attempt durations (s), the speculation-policy baseline
        self.durations: List[float] = []
        # append-only per-attempt history for /api/job/<id> (survives
        # rollbacks: entries carry their stage_attempt epoch)
        self.attempt_log: List[dict] = []
        self._attempt_index: Dict[Tuple[int, int, int], dict] = {}
        # map partition -> (executor_id, [ShuffleWritePartition])
        self.outputs: Dict[int, Tuple[str, List[ShuffleWritePartition]]] = {}
        # AQE rewrite records applied to this stage (scheduler/aqe.py);
        # append-only, entries carry their stage_attempt epoch
        # the open "stage <id>" span of this attempt (ExecutionGraph
        # _open_stage_span; None when tracing is off or the stage is done)
        self.span = None
        self.aqe_rewrites: List[dict] = []
        # whole-stage-fusion decisions for this stage (compile/fuse.py):
        # one record per detected chain — fused or rejected, with reasons;
        # append-only, entries carry their stage_attempt epoch
        self.fusion_rewrites: List[dict] = []

    # --- attempt bookkeeping ---------------------------------------------
    def new_attempt(self, partition: int, executor_id: str,
                    speculative: bool = False) -> TaskInfo:
        """Mint the next attempt id for ``partition`` and log it."""
        import time as _time

        attempt = self.task_attempts[partition]
        self.task_attempts[partition] = attempt + 1
        info = TaskInfo(partition, executor_id, "running", attempt=attempt,
                        speculative=speculative,
                        started_at=_time.monotonic())
        entry = {"partition": partition, "attempt": attempt,
                 "stage_attempt": self.stage_attempt,
                 "executor_id": executor_id, "speculative": speculative,
                 "state": "running", "duration_s": None}
        self.attempt_log.append(entry)
        self._attempt_index[(partition, attempt, self.stage_attempt)] = entry
        return info

    def close_attempt(self, st: TaskStatus, state: str) -> None:
        """Record an attempt's terminal state + duration in the log."""
        import time as _time

        entry = self._attempt_index.get(
            (st.task.partition, st.task.task_attempt, st.task.stage_attempt))
        if entry is None or entry["state"] != "running":
            return
        entry["state"] = state
        for info in (self.task_infos[st.task.partition],
                     self.speculative_tasks.get(st.task.partition)):
            if info is not None and info.attempt == st.task.task_attempt \
                    and info.started_at:
                entry["duration_s"] = round(_time.monotonic() - info.started_at, 3)
                break

    def operator_metrics(self) -> Dict[str, Dict[str, float]]:
        """Fold completed tasks' per-operator metrics into a
        per-operator dict keyed by the ``collect_plan_metrics`` path key
        (e.g. ``'0.1:HashAggregateExec'``) — the structured view behind
        the profile endpoint and the dot annotations
        (``aggregate_metrics`` flattens it for the legacy stage view).

        Same-stage tasks in one executor process share operator instances,
        so each task status snapshots the *cumulative* counters at its
        completion time — summing snapshots would overcount quadratically
        (observed: a 6M-row scan reported as 49M).  The stage total is the
        LAST snapshot per PLAN INSTANCE (statuses carry a
        process+instance id; counters are monotone per decoded plan
        object), summed across instances — correct across processes,
        in-proc multi-executor standalone mode, fetch-failure re-resolves
        and plan-cache evictions alike (id() reuse after GC could in
        principle alias two instances; metrics are observability, not
        correctness)."""
        per_exec: Dict[str, Dict[Tuple[str, str], float]] = {}
        for t in self.task_infos:
            st = getattr(t, "status", None)
            if st is None:
                continue
            # attempt-aware dedup: only the recorded winner's own status
            # counts — a terminal status absorbed from a cancelled
            # speculative loser carries a different task_attempt and must
            # not add its (cumulative) snapshot to the fold
            st_att = getattr(getattr(st, "task", None), "task_attempt", None)
            if st_att is not None and st_att != getattr(t, "attempt", st_att):
                continue
            dst = per_exec.setdefault(
                getattr(st, "process_id", "") or getattr(t, "executor_id", ""),
                {})
            for op, mm in (st.metrics or {}).items():
                for k, v in mm.items():
                    if v > dst.get((op, k), float("-inf")):
                        dst[(op, k)] = v
        agg: Dict[str, Dict[str, float]] = {}
        for mm in per_exec.values():
            for (op, k), v in mm.items():
                d = agg.setdefault(op, {})
                d[k] = d.get(k, 0.0) + v
        return agg

    def aggregate_metrics(self) -> Dict[str, float]:
        """Flattened '<op>.<metric>' -> total view of
        ``operator_metrics`` (the REST stage view)."""
        return {f"{op}.{k}": v
                for op, mm in self.operator_metrics().items()
                for k, v in mm.items()}

    # --- queries ---------------------------------------------------------
    @property
    def planned_partitions(self) -> int:
        """The partition count the planner asked for, regardless of
        adaptive coalescing (observability/tests read this)."""
        return getattr(self, "_orig_partitions", None) or self.partitions

    def pending_partitions(self) -> List[int]:
        if self.state != RUNNING:
            return []
        return [p for p in range(self.partitions) if self.task_infos[p] is None]

    def all_successful(self) -> bool:
        return all(t is not None and t.state == "success" for t in self.task_infos)

    def output_locations(self, addr_resolver=None) -> Dict[int, List[PartitionLocation]]:
        """output partition -> locations across all map tasks.
        ``addr_resolver(executor_id) -> (host, port)`` stamps the owning
        executor's address for remote fetch (None in purely local
        deployments)."""
        locs: Dict[int, List[PartitionLocation]] = {}
        for map_part, (executor_id, writes) in sorted(self.outputs.items()):
            host, port = addr_resolver(executor_id) \
                if addr_resolver is not None else ("", 0)
            for w in writes:
                locs.setdefault(w.output_partition, []).append(
                    PartitionLocation(executor_id, map_part, w.output_partition,
                                      w.path, w.num_rows, w.num_bytes,
                                      host, port, checksum=w.checksum,
                                      format="arrow_file"))
        return locs

    # --- adaptive exchange coalescing ------------------------------------
    # When the producers' REAL output is tiny, running the planned N reduce
    # tasks is pure overhead (q1: 46 final-agg tasks over 48 partial rows
    # cost ~1.9 s of launch/fetch/dispatch).  The scheduler knows the exact
    # shuffle sizes before launching the consumer — a static planner never
    # does — so the stage collapses to one task reading every bucket.
    # Correct for any hash exchange: the union of buckets is the full
    # input, and aggregates/joins re-group/re-match within the task.
    COALESCE_INPUT_ROWS = 8192

    def maybe_coalesce(self) -> None:
        if self.partitions <= 1 or self.resolved_plan is None:
            return
        leaves = []

        def walk(p):
            kids = p.children()
            if not kids:
                leaves.append(p)
            for c in kids:
                walk(c)

        walk(self.resolved_plan)
        readers = [p for p in leaves if isinstance(p, ShuffleReaderExec)]
        if len(readers) != len(leaves):
            return  # a scan leaf owns the partition count; leave it alone
        total = sum(loc.num_rows for r in readers
                    for locs in r.locations.values() for loc in locs)
        if total > self.COALESCE_INPUT_ROWS:
            return
        for r in readers:
            merged = [loc for q in sorted(r.locations)
                      for loc in r.locations[q]]
            r.locations = {0: merged}
            # remember the planned count: resolve mutates the plan tree in
            # place, and a rollback rebuilds UnresolvedShuffleExec from
            # this reader — it must restore the ORIGINAL partitioning
            r._orig_partition_count = r.partition_count
            r.partition_count = 1
        self._orig_partitions = self.partitions
        self.partitions = 1
        self.task_infos = [None]
        # task_failures/task_attempts keep their planned length: only
        # index 0 is touched while coalesced, and rollback restores the
        # full partition count with per-partition budgets intact

    # --- transitions -----------------------------------------------------
    def rollback(self, count_failure: bool = True) -> None:
        """RUNNING/RESOLVED -> UNRESOLVED (reference execution_stage.rs
        rollback arrows); outputs are discarded, tasks forgotten.

        ``remove_unresolved_shuffles`` resolves in place (each stage owns
        its subtree), so the inverse walk here restores the
        UnresolvedShuffleExec leaves — without it a re-resolve would keep
        the *previous* attempt's partition locations (dead paths)."""
        from .planner import rollback_resolved_shuffles

        self.plan = rollback_resolved_shuffles(self.plan)
        self.state = UNRESOLVED
        self.resolved_plan = None
        # undo adaptive coalescing: the fresh resolve re-decides from the
        # new attempt's real shuffle sizes
        if getattr(self, "_orig_partitions", None):
            self.partitions = self._orig_partitions
            self._orig_partitions = None
        self.task_infos = [None] * self.partitions
        self.speculative_tasks.clear()
        self.failed_on.clear()
        self.outputs.clear()
        self.stage_attempt += 1
        if count_failure:
            self.failures += 1

    def reopen_partitions(self, partitions: List[int], count_attempt: bool = True) -> None:
        """SUCCESSFUL/RUNNING -> RUNNING with the given map partitions
        pending again (reference SuccessfulStage::to_running).  Partitions
        already pending or re-running (reported lost twice, e.g. by two
        reducer tasks that both failed to fetch) are left alone."""
        reopened = False
        for p in partitions:
            info = self.task_infos[p]
            if p not in self.outputs and (info is None or info.state != "success"):
                continue  # already re-opened; a re-run may be in flight
            self.outputs.pop(p, None)
            self.task_infos[p] = None
            self.speculative_tasks.pop(p, None)
            reopened = True
        if reopened and self.state == SUCCESSFUL:
            self.state = RUNNING
            self.stage_attempt += 1  # new epoch either way
            if count_attempt:
                self.failures += 1

    def __repr__(self):
        done = sum(1 for t in self.task_infos if t and t.state == "success")
        return (f"Stage(id={self.stage_id}, {self.state}, "
                f"{done}/{self.partitions} tasks, attempt={self.stage_attempt})")


class ExecutionGraph:
    """Parity: reference state/execution_graph.rs ExecutionGraph."""

    def __init__(self, job_id: str, stages: List[QueryStage]):
        self.job_id = job_id
        self.stages: Dict[int, ExecutionStage] = {
            s.stage_id: ExecutionStage(s.stage_id, s.plan) for s in stages}
        # link producers -> consumers (reference ExecutionStageBuilder,
        # execution_graph.rs:1441-1543)
        for stage in self.stages.values():
            for pid in stage.producer_ids:
                if pid not in self.stages:
                    raise InternalError(f"stage {stage.stage_id} references "
                                        f"unknown producer {pid}")
                self.stages[pid].output_links.append(stage.stage_id)
        finals = [s for s in self.stages.values() if not s.output_links]
        if len(finals) != 1:
            raise InternalError(f"expected exactly one final stage, got {finals}")
        self.final_stage_id = finals[0].stage_id
        self.status = "running"
        self.error = ""
        self.scalars: Dict[str, object] = {}
        # server-side deadline (ballista.query.deadline.seconds): absolute
        # wall-clock expiry + the configured budget, stamped at planning
        # from the submitter's clock and checkpointed so an adopting shard
        # keeps enforcing the original deadline.  0.0 = no deadline.
        self.deadline_ts = 0.0
        self.deadline_s = 0.0
        # trace propagation context handed to every task of this job
        # ({"trace_id", "span_id"}; empty when tracing is off), set by
        # start_trace; and one "stage <id>" span per stage attempt, open
        # from the stage turning runnable to its last status absorbed
        self.trace: Dict[str, str] = {}
        self.stage_spans: List[object] = []
        # executor_id -> (host, port) of the data plane; None = local-only
        self.addr_resolver = None
        # live per-stage runtime summaries (skew, histograms, duration
        # quantiles) — refolded on every task success, read by EXPLAIN
        # ANALYZE, /api/job/<id>/stats, and future AQE.  Not checkpointed
        # (serde.graph_to_obj is field-explicit): a recovered graph starts
        # with an empty store and refills as its re-run stages complete.
        self.stats = RuntimeStatsStore(job_id)
        # adaptive query execution (scheduler/aqe.py): per-job policy (the
        # scheduler overwrites it from the session config right after
        # build), the flat rewrite log (REST/serde), and the pending
        # metric events the scheduler drains into its collector
        self.aqe = AqePolicy()
        self.aqe_log: List[dict] = []
        self.aqe_events: List[Tuple[str, int]] = []
        # whole-stage compiler (compile/fuse.py): per-job policy installed
        # by the scheduler AFTER build (None = fusion off, so the leaf
        # stages resolved by the revive() below stay interpreted until the
        # scheduler decides), plus the flat decision log (REST/serde)
        self.compiler = None
        self.compile_log: List[dict] = []
        self._task_id_gen = itertools.count()
        self.revive()

    @staticmethod
    def build(job_id: str, plan) -> "ExecutionGraph":
        stages = DistributedPlanner().plan_query_stages(job_id, plan)
        return ExecutionGraph(job_id, stages)

    # --- tracing ---------------------------------------------------------
    def start_trace(self, trace: Dict[str, str]) -> None:
        """Adopt the job's execution-span context.  The graph is built (and
        its leaf stages resolved) before the scheduler has one to give, so
        the stages already runnable get their spans here."""
        self.trace = dict(trace or {})
        for stage in self.stages.values():
            if stage.state == RUNNING:
                self._open_stage_span(stage)

    def _open_stage_span(self, stage: ExecutionStage) -> None:
        if not self.trace:
            return
        from ..obs.tracing import span

        self._close_stage_span(stage, "superseded")
        stage.span = span(f"stage {stage.stage_id}", "scheduler", self.trace,
                          job_id=self.job_id, stage_id=stage.stage_id,
                          stage_attempt=stage.stage_attempt,
                          actor="scheduler", lane=f"job {self.job_id}",
                          launched_at={}).begin()
        self.stage_spans.append(stage.span)

    @staticmethod
    def _close_stage_span(stage: ExecutionStage, status: str = "ok") -> None:
        if stage.span is not None:
            stage.span.end(status)
            stage.span = None

    # --- scheduling ------------------------------------------------------
    def revive(self) -> bool:
        """Resolve every UNRESOLVED stage whose producers are all
        SUCCESSFUL (reference execution_graph.rs:242-266)."""
        changed = False
        for stage in self.stages.values():
            if stage.state != UNRESOLVED:
                continue
            if all(self.stages[p].state == SUCCESSFUL for p in stage.producer_ids):
                locations = {p: self.stages[p].output_locations(self.addr_resolver)
                             for p in stage.producer_ids}
                stage.resolved_plan = remove_unresolved_shuffles(stage.plan, locations) \
                    if stage.producer_ids else stage.plan
                if stage.producer_ids:
                    if self.aqe.enabled:
                        # dynamic coalescing + skew splitting off the
                        # observed shuffle sizes (subsumes the static
                        # heuristic below, which stays byte-identical for
                        # ballista.aqe.enabled=false)
                        rewrite_resolved_stage(self, stage, self.aqe)
                    else:
                        stage.maybe_coalesce()
                stage.state = RUNNING
                changed = True
                self._open_stage_span(stage)
                if self.compiler is not None and self.compiler.enabled:
                    # whole-stage fusion rides the resolve: applied to the
                    # freshly resolved plan (after AQE), before any task
                    # launches — so rollbacks re-resolve AND re-fuse, and
                    # speculative duplicates share the fused kernel
                    from ..compile.fuse import fuse_stage

                    fuse_stage(self, stage)
                if journal.enabled():
                    journal.emit("stage.resolved", job_id=self.job_id,
                                 stage_id=stage.stage_id,
                                 partitions=stage.partitions,
                                 producers=list(stage.producer_ids))
        return changed

    def preload_stage(self, stage_id: int,
                      outputs: Dict[int, Tuple[str, List["ShuffleWritePartition"]]]
                      ) -> bool:
        """Complete a stage from cached shuffle output without running any
        of its tasks (serving subplan cache, scheduler/serving_cache.py).
        Only a stage that is already resolved (RUNNING) and untouched is
        eligible — resolution must run normally so fetch-failure recovery
        keeps working on preloaded stages (reopen_partitions requires
        resolved_plan).  The final stage is never preloaded: its output is
        the result cache's domain."""
        stage = self.stages.get(stage_id)
        if stage is None or stage.state != RUNNING:
            return False
        if not stage.output_links:
            return False
        if any(t is not None for t in stage.task_infos):
            return False
        if sorted(outputs) != list(range(stage.partitions)):
            return False  # adaptive rewrites changed the task shape
        stage.outputs = dict(outputs)
        for p in range(stage.partitions):
            stage.task_infos[p] = TaskInfo(p, "subplan-cache", "success")
        stage.state = SUCCESSFUL
        self.revive()
        return True

    def available_task_count(self) -> int:
        if self.status != "running":
            return 0
        return sum(len(s.pending_partitions()) for s in self.stages.values())

    def pop_next_task(self, executor_id: str,
                      alive: Optional[set] = None) -> Optional[TaskDescription]:
        """Hand out one pending task (reference execution_graph.rs:834-935).

        ``alive``: the scheduler's current alive+healthy executor set,
        enabling retry anti-affinity — a partition that already failed
        retryably on ``executor_id`` is skipped HERE as long as some other
        alive executor could still take it (no deadlock: when every alive
        executor has failed it, anyone may retry it and the failure budget
        decides).  ``alive=None`` (tests, direct drivers) disables the
        steering."""
        if self.status != "running":
            return None
        for stage in sorted(self.stages.values(), key=lambda s: s.stage_id):
            for p in stage.pending_partitions():
                failed_on = stage.failed_on.get(p)
                if (failed_on and executor_id in failed_on
                        and alive is not None and (alive - failed_on)):
                    continue  # steer this retry toward a fresh executor
                info = stage.new_attempt(p, executor_id)
                stage.task_infos[p] = info
                return self._describe(stage, info)
        return None

    def _describe(self, stage: ExecutionStage, info: TaskInfo) -> TaskDescription:
        tid = TaskId(self.job_id, stage.stage_id, info.partition,
                     task_attempt=info.attempt,
                     stage_attempt=stage.stage_attempt,
                     speculative=info.speculative)
        if journal.enabled():
            # the single mint point for every launch (normal + speculative):
            # registers the causal key the scheduler's task.finish event
            # chains back to
            journal.emit("task.launch", job_id=self.job_id,
                         causal_key=("task", self.job_id, stage.stage_id,
                                     info.partition, info.attempt),
                         stage_id=stage.stage_id, partition=info.partition,
                         attempt=info.attempt,
                         executor_id=info.executor_id,
                         speculative=info.speculative)
        if stage.span is not None:
            # when each task was handed out: the first entry less the
            # producers' last status is the stage hand-off
            stage.span.attrs["launched_at"][
                f"{info.partition}.{info.attempt}"] = time.time_ns()
        return TaskDescription(tid, stage.resolved_plan,
                               task_internal_id=next(self._task_id_gen),
                               scalars=self.scalars,
                               trace=dict(self.trace))

    def launch_speculative(self, stage_id: int, partition: int,
                           executor_id: str) -> Optional[TaskDescription]:
        """Mint a speculative duplicate attempt for a straggling running
        task, to be placed on ``executor_id`` (the caller guarantees it is
        a *different* executor than the original's).  Returns None when the
        partition is no longer a candidate (finished, rolled back, or
        already speculated) — the monitor races task completion by design."""
        if self.status != "running":
            return None
        stage = self.stages.get(stage_id)
        if stage is None or stage.state != RUNNING:
            return None
        if partition in stage.speculative_tasks:
            return None
        primary = stage.task_infos[partition]
        if primary is None or primary.state != "running" \
                or primary.executor_id == executor_id:
            return None
        info = stage.new_attempt(partition, executor_id, speculative=True)
        stage.speculative_tasks[partition] = info
        return self._describe(stage, info)

    # --- status intake ---------------------------------------------------
    def update_task_status(self, statuses: List[TaskStatus]) -> List[Tuple[str, object]]:
        """Absorb executor task outcomes; returns job-level events:
        ('job_successful', locations) | ('job_failed', message).
        Parity: reference execution_graph.rs:270-657."""
        events: List[Tuple[str, object]] = []
        if self.status != "running":
            # a terminal job still absorbs attempt BOOKKEEPING: a cancelled
            # speculative loser often reports "killed" after the job has
            # already succeeded, and without this its audit-log entry would
            # read "running" forever
            for st in statuses:
                stage = self.stages.get(st.task.stage_id)
                if stage is not None \
                        and st.task.stage_attempt == stage.stage_attempt:
                    stage.close_attempt(st, st.state)
            return events
        for st in statuses:
            stage = self.stages.get(st.task.stage_id)
            if stage is None:
                continue
            if st.task.stage_attempt != stage.stage_attempt:
                # late message from a rolled-back attempt — drop it
                # (reference handles these via attempt checks)
                continue
            if st.state == "success":
                self._on_task_success(stage, st, events)
            elif st.state == "failed":
                self._on_task_failed(stage, st, events)
            elif st.state == "killed":
                # job-level cancel, or a cancelled speculative loser: free
                # the duplicate's slot bookkeeping, nothing else to do
                stage.close_attempt(st, "killed")
                spec = stage.speculative_tasks.get(st.task.partition)
                if spec is not None and spec.attempt == st.task.task_attempt:
                    stage.speculative_tasks.pop(st.task.partition, None)
            if self.status != "running":
                break
        return events

    def _on_task_success(self, stage: ExecutionStage, st: TaskStatus,
                         events: List[Tuple[str, object]]) -> None:
        import time as _time

        p = st.task.partition
        info = stage.task_infos[p]
        spec = stage.speculative_tasks.get(p)
        att = st.task.task_attempt
        stage.close_attempt(st, "success")
        if info is not None and info.state == "success":
            # first-result-wins dedup: the loser of a speculative race (or
            # any duplicate report) finished after the winner — its outputs
            # are ignored, the recorded ones stay authoritative
            if spec is not None and spec.attempt == att:
                stage.speculative_tasks.pop(p, None)
            return
        # which in-flight attempt does this status belong to?
        winner: Optional[TaskInfo] = None
        if info is not None and info.state == "running" and info.attempt == att:
            winner = info
        elif spec is not None and spec.attempt == att:
            winner = spec
            events.append(("speculative_win", (stage.stage_id, p)))
        # cancel the losing duplicate (first success wins either way)
        loser = spec if winner is info else info
        if spec is not None and loser is not None and loser is not winner \
                and loser.state == "running":
            events.append(("cancel_task",
                           (loser.executor_id,
                            TaskId(self.job_id, stage.stage_id, p,
                                   task_attempt=loser.attempt,
                                   stage_attempt=stage.stage_attempt,
                                   speculative=loser.speculative))))
        stage.speculative_tasks.pop(p, None)
        started = winner.started_at if winner is not None else 0.0
        if started:
            stage.durations.append(_time.monotonic() - started)
        stage.task_infos[p] = TaskInfo(p, st.executor_id, "success", st,
                                       attempt=att,
                                       speculative=st.task.speculative,
                                       started_at=started)
        stage.outputs[p] = (st.executor_id, list(st.shuffle_writes))
        completed = stage.all_successful() and stage.state == RUNNING
        if completed:
            stage.state = SUCCESSFUL
            self._close_stage_span(stage)
        # refold AFTER the state transition (the final summary must record
        # the stage as successful) and BEFORE downstream stages resolve:
        # the AQE passes read the completed stage's folded stats
        self.stats.fold_stage(stage)
        if completed:
            if stage.stage_id == self.final_stage_id:
                self.status = "successful"
                events.append(("job_successful",
                               stage.output_locations(self.addr_resolver)))
            else:
                # broadcast-switch pass first: a flipped join changes what
                # revive() resolves (and may graft away an exchange whose
                # cancellations ride out on ``events``)
                maybe_broadcast_switch(self, stage, events, self.aqe)
                self.revive()

    def _on_task_failed(self, stage: ExecutionStage, st: TaskStatus,
                        events: List[Tuple[str, object]]) -> None:
        p = st.task.partition
        info = stage.task_infos[p]
        spec = stage.speculative_tasks.get(p)
        att = st.task.task_attempt
        reason = st.failure or FailedReason(EXECUTION_ERROR, "unknown failure")
        stage.close_attempt(st, "killed" if reason.kind == TASK_KILLED
                            else "failed")

        # a cancelled/crashed loser must never disturb a completed
        # partition: the winner's outputs are already recorded
        if info is not None and info.state == "success":
            if spec is not None and spec.attempt == att:
                stage.speculative_tasks.pop(p, None)
            return

        if reason.kind == EXECUTION_ERROR:
            self._fail_job(f"task {st.task.job_id}/{stage.stage_id}/{p}: "
                           f"{reason.message}", events)
            return

        if reason.kind == TASK_KILLED:
            if spec is not None and spec.attempt == att:
                stage.speculative_tasks.pop(p, None)
            return

        if reason.kind == FETCH_PARTITION_ERROR:
            self._on_fetch_failure(stage, reason, events)
            return

        # retryable (IOError / ExecutorLost / ResultLost)
        if spec is not None and spec.attempt == att:
            # the speculative duplicate died while the original is still
            # running: just drop the duplicate — no budget charge, no reset
            stage.speculative_tasks.pop(p, None)
            return
        if reason.count_to_failures:
            stage.task_failures[p] += 1
        if stage.task_failures[p] >= TASK_MAX_FAILURES:
            self._fail_job(
                f"task {st.task.job_id}/{stage.stage_id}/{p} failed "
                f"{TASK_MAX_FAILURES} times: {reason.message}", events)
            return
        # remember WHERE it failed so the retry steers to a fresh executor
        # (and poison containment can count distinct witnesses)
        eid = st.executor_id or (info.executor_id if info is not None else "")
        if eid:
            stage.failed_on.setdefault(p, set()).add(eid)
        if spec is not None:
            # the original died but a speculative duplicate is in flight:
            # promote it to primary instead of launching a third attempt
            stage.task_infos[p] = stage.speculative_tasks.pop(p)
        else:
            stage.task_infos[p] = None  # back to pending

    def _on_fetch_failure(self, stage: ExecutionStage, reason: FailedReason,
                          events: List[Tuple[str, object]]) -> None:
        """Shuffle-lineage retry (execution_graph.rs: fetch failures remove
        poisoned inputs, roll back the reducer, re-run the producer)."""
        producer = self.stages.get(reason.map_stage_id)
        if producer is None:
            self._fail_job(f"fetch failure names unknown stage "
                           f"{reason.map_stage_id}", events)
            return
        stage.rollback()
        if stage.failures >= STAGE_MAX_FAILURES:
            # keep the ORIGINAL transport cause in the job error: "budget
            # exhausted" alone is undebuggable once the executor is gone
            self._fail_job(
                f"stage {stage.stage_id} exceeded {STAGE_MAX_FAILURES} "
                f"attempts after fetch failures (last: {reason.message})",
                events)
            return
        producer.reopen_partitions([reason.map_partition_id])
        if producer.failures >= STAGE_MAX_FAILURES:
            self._fail_job(
                f"stage {producer.stage_id} exceeded {STAGE_MAX_FAILURES} "
                f"re-runs (last fetch failure: {reason.message})", events)
            return
        self.revive()

    # --- executor loss ---------------------------------------------------
    def executor_lost(self, executor_id: str) -> None:
        """Reset tasks and roll back stages whose outputs lived on the lost
        executor (reference execution_graph.rs:950-1095).  Does not count
        toward stage attempt budgets: losing a node is not the query's
        fault."""
        if self.status != "running":
            return
        # 1. forget running tasks on the executor (a surviving speculative
        #    duplicate is promoted to primary rather than relaunching)
        for stage in self.stages.values():
            if stage.state != RUNNING:
                continue
            for p, spec in list(stage.speculative_tasks.items()):
                if spec.executor_id == executor_id:
                    stage.speculative_tasks.pop(p, None)
            for p, info in enumerate(stage.task_infos):
                if info is not None and info.state == "running" \
                        and info.executor_id == executor_id:
                    spec = stage.speculative_tasks.pop(p, None)
                    stage.task_infos[p] = spec
        # 2. re-open map partitions whose outputs are gone
        poisoned: List[int] = []
        for stage in self.stages.values():
            lost = [p for p, (ex, _) in stage.outputs.items() if ex == executor_id]
            if lost:
                stage.reopen_partitions(lost, count_attempt=False)
                poisoned.append(stage.stage_id)
        # 3. roll back non-successful consumers of poisoned stages
        #    (they may hold resolved plans pointing at dead locations);
        #    consumers that are already SUCCESSFUL keep their outputs.
        #    No recursion needed: a consumer-of-a-consumer can only be
        #    RUNNING if its producer was SUCCESSFUL, whose lost outputs
        #    step 2 already handles directly.
        for sid in poisoned:
            for cid in self.stages[sid].output_links:
                consumer = self.stages[cid]
                if consumer.state == RUNNING:
                    consumer.rollback(count_failure=False)
        self.revive()

    # --- job level -------------------------------------------------------
    def _fail_job(self, message: str, events: List[Tuple[str, object]]) -> None:
        self.status = "failed"
        self.error = message
        events.append(("job_failed", message))

    def cancel(self) -> None:
        self.status = "cancelled"

    def running_tasks(self) -> List[Tuple[int, int, str]]:
        """(stage_id, partition, executor_id) of in-flight tasks,
        speculative duplicates included."""
        out = []
        for stage in self.stages.values():
            if stage.state != RUNNING:
                continue
            for info in stage.task_infos:
                if info is not None and info.state == "running":
                    out.append((stage.stage_id, info.partition, info.executor_id))
            for info in stage.speculative_tasks.values():
                out.append((stage.stage_id, info.partition, info.executor_id))
        return out

    def __repr__(self):
        lines = [f"ExecutionGraph(job={self.job_id}, status={self.status})"]
        for sid in sorted(self.stages):
            lines.append("  " + repr(self.stages[sid]))
        return "\n".join(lines)
