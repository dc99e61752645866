"""BallistaContext: the user entry point.

Parity with the reference client (reference ballista/client/src/context.rs):
``standalone()`` runs scheduler+executor machinery in-process
(context.rs:142-212), ``sql()`` handles DDL client-side and plans SELECTs
(context.rs:358-530), ``register_parquet/csv/table`` mirror register_*
(context.rs:214-352).  ``remote()`` connects to a scheduler over gRPC.

Execution engines:
- ``local``: single-process operator tree walk (RepartitionExec materializes
  exchanges in memory) — the fast path for one host / one TPU chip.
- ``standalone``: in-process scheduler + executor objects exercising the full
  stage DAG, shuffle files, and fault-tolerance machinery.
"""
from __future__ import annotations

import os
import tempfile
import uuid
from typing import Dict, List, Optional

import numpy as np

from ..catalog import CsvTable, MemoryTable, ParquetTable, SchemaCatalog, TableProvider
from ..models import logical as L
from ..models.batch import ColumnBatch
from ..models.schema import Field, Schema
from ..obs.tracing import ROOT, span
from ..ops.physical import ExecutionPlan, TaskContext
from ..scheduler.physical_planner import PhysicalPlanner, PlannedQuery
from ..sql import ast
from ..sql.optimizer import optimize
from ..sql.parser import parse_sql
from ..sql.planner import SqlToRel, parse_type_name
from ..utils.config import BallistaConfig
from ..utils.errors import PlanningError


def _collect(frame, decode=None):
    """``client.collect``: execute, fetch and (with ``decode``, under a
    ``decode`` span) convert one statement's batches, in the trace
    ``ctx.sql`` began; the scheduler's ``job`` span is its child.  The
    shared null context where that trace is empty (tracing off)."""
    trace = frame._trace
    with span("client.collect", "client",
              {"trace_id": trace["trace_id"]} if trace else None):
        batches = frame._execute()
        if decode is None:
            return batches
        with span("decode", "client"):
            return decode(batches)


def _to_pandas(batches):
    import pandas as pd

    frames = [b.to_pandas() for b in batches]
    return pd.concat(frames, ignore_index=True) if frames else pd.DataFrame()


class BallistaDataFrame:
    """A planned query, lazily executed (parity: DataFusion DataFrame as
    returned by BallistaContext::sql).  ``static`` carries an immediate
    result for statements with no plan to execute (SET / DDL / EXPLAIN),
    mirroring RemoteDataFrame."""

    def __init__(self, ctx: "BallistaContext", logical: Optional[L.LogicalPlan],
                 static=None, sql_text: Optional[str] = None):
        self.ctx = ctx
        self.logical = logical
        self._static = static
        # the trace ``ctx.sql`` began ({} = tracing off): ``client.collect``
        # and everything under it continue it
        self._trace: Dict[str, str] = {}
        # original statement text for pristine sql() SELECTs: lets the
        # standalone engine route through the serving caches (plan/result
        # reuse keyed on normalized text); None for DDL/EXPLAIN/derived
        # frames, which execute the logical plan directly
        self._sql_text = sql_text

    @property
    def schema(self) -> Schema:
        if self.logical is None:
            return Schema([])
        return self.logical.schema

    def explain(self) -> str:
        if self.logical is None:
            return ""
        return optimize(self.logical).display()

    def _execute(self) -> List[ColumnBatch]:
        if self.logical is None:
            return []
        return self.ctx._execute_logical(self.logical, self._sql_text)

    def collect(self) -> List[ColumnBatch]:
        return _collect(self)

    def to_arrow(self):
        import pyarrow as pa

        if self._static is not None:
            return pa.Table.from_pandas(self._static)

        def decode(batches):
            tables = [b.to_arrow() for b in batches if b.num_rows > 0]
            if not tables:
                return batches[0].to_arrow() if batches else pa.table({})
            return pa.concat_tables(tables)

        return _collect(self, decode)

    def to_pandas(self):
        if self._static is not None:
            return self._static
        return _collect(self, _to_pandas)


class RemoteDataFrame:
    """Lazy remote query (collect polls the scheduler, then fetches the
    final-stage partitions from executors)."""

    def __init__(self, ctx: "BallistaContext", sql: Optional[str], static=None):
        self.ctx = ctx
        self._sql = sql
        self._static = static  # pre-computed frame (SHOW …)
        self._trace: Dict[str, str] = {}  # as BallistaDataFrame._trace

    def _execute(self) -> List[ColumnBatch]:
        if self._sql is None:
            return []  # DDL / SHOW
        return self.ctx._remote.execute_sql(self._sql)

    def collect(self) -> List[ColumnBatch]:
        return _collect(self)

    def to_pandas(self):
        if self._static is not None:
            return self._static
        return _collect(self, _to_pandas)

    def to_arrow(self):
        import pyarrow as pa

        if self._static is not None:
            return pa.Table.from_pandas(self._static)

        def decode(batches):
            tables = [b.to_arrow() for b in batches if b.num_rows > 0]
            return pa.concat_tables(tables) if tables else pa.table({})

        return _collect(self, decode)


class BallistaContext:
    def __init__(self, config: Optional[BallistaConfig] = None, engine: str = "local",
                 work_dir: Optional[str] = None):
        self.config = config or BallistaConfig()
        self.engine = engine
        self.catalog = SchemaCatalog()
        self.work_dir = work_dir or os.path.join(tempfile.gettempdir(), "ballista_tpu")
        self._standalone = None
        self._remote = None
        # per-session parsed-AST memo: hot clients resubmitting the same
        # statement text skip the parser entirely (LRU, text -> AST)
        from collections import OrderedDict

        self._ast_memo: "OrderedDict[str, object]" = OrderedDict()

    def _parse_cached(self, sql: str):
        stmt = self._ast_memo.get(sql)
        if stmt is not None:
            self._ast_memo.move_to_end(sql)
            return stmt
        stmt = parse_sql(sql)
        self._ast_memo[sql] = stmt
        while len(self._ast_memo) > 256:
            self._ast_memo.popitem(last=False)
        return stmt

    # --- constructors (parity: context.rs:80-212) -----------------------
    @staticmethod
    def local(config: Optional[BallistaConfig] = None) -> "BallistaContext":
        return BallistaContext(config, engine="local")

    @staticmethod
    def standalone(config: Optional[BallistaConfig] = None,
                   concurrent_tasks: int = 4,
                   num_executors: int = 1) -> "BallistaContext":
        ctx = BallistaContext(config, engine="standalone")
        from ..scheduler.standalone import StandaloneCluster

        ctx._standalone = StandaloneCluster(ctx.config, concurrent_tasks,
                                            num_executors)
        return ctx

    def shutdown(self) -> None:
        if self._standalone is not None:
            self._standalone.shutdown()
            self._standalone = None
        if self._remote is not None:
            self._remote.close()
        self._remote = None

    @staticmethod
    def remote(host: Optional[str] = None, port: Optional[int] = None,
               config: Optional[BallistaConfig] = None,
               endpoints=None) -> "BallistaContext":
        """Connect to a scheduler daemon (parity: BallistaContext::remote,
        reference client context.rs:80-140).  SQL text ships to the
        scheduler; results stream back from executor data planes.

        ``endpoints=[(host, port), ...]`` connects to a scheduler FLEET:
        calls stick to the first reachable shard and fail over down the
        list when it dies (docs/user-guide/ha.md).

        A process that has started no jax backend yet is pinned to the
        CPU platform here: the client runs no stage, and the chip belongs
        to the executor.  One that wants a standalone engine on the device
        as well creates that context first."""
        from ..models.batch import pin_to_host

        pin_to_host()
        ctx = BallistaContext(config, engine="remote")
        from .remote import RemoteCluster

        ctx._remote = RemoteCluster(host, port, ctx.config,
                                    endpoints=endpoints)
        return ctx

    # --- registration ---------------------------------------------------
    def register_table(self, name: str, table) -> None:
        if self._remote is not None:
            import pyarrow as pa

            if not isinstance(table, pa.Table):
                table = pa.Table.from_pandas(table)
            self._remote.register_table(name, table)
            return
        self.catalog.register(MemoryTable(name, table))

    def register_parquet(self, name: str, path, schema: Optional[Schema] = None) -> None:
        if self._remote is not None:
            self._remote.register_external_table(name, "parquet", path, schema)
            return
        self.catalog.register(ParquetTable(name, path, schema))

    def register_csv(self, name: str, path, schema: Optional[Schema] = None,
                     delimiter: str = ",", has_header: bool = True) -> None:
        if self._remote is not None:
            self._remote.register_external_table(name, "csv", path, schema,
                                                 delimiter, has_header)
            return
        self.catalog.register(CsvTable(name, path, schema, delimiter, has_header))

    def register_json(self, name: str, path, schema: Optional[Schema] = None) -> None:
        """Newline-delimited JSON (reference register_json, context.rs)."""
        if self._remote is not None:
            self._remote.register_external_table(name, "json", path, schema)
            return
        from ..catalog import JsonTable

        self.catalog.register(JsonTable(name, path, schema))

    def register_avro(self, name: str, path, schema: Optional[Schema] = None) -> None:
        """Avro object container files (reference register_avro)."""
        if self._remote is not None:
            self._remote.register_external_table(name, "avro", path, schema)
            return
        from ..catalog import AvroTable

        self.catalog.register(AvroTable(name, path, schema))

    def deregister_table(self, name: str) -> None:
        if self._remote is not None:
            self._remote.deregister_table(name)
            return
        self.catalog.deregister(name)

    # --- SQL ------------------------------------------------------------
    def sql(self, sql: str) -> "BallistaDataFrame":
        """Parse and plan one statement.  ``client.sql`` is the root of the
        statement's trace; the frame returned carries it to ``collect``."""
        from ..utils.config import OBS_TRACING

        with span("client.sql", "client",
                  ROOT if self.config.get(OBS_TRACING) else None) as sp:
            df = self._remote_sql(sql) if self._remote is not None \
                else self._local_sql(sql)
            df._trace = sp.context()
            return df

    def _local_sql(self, sql: str) -> "BallistaDataFrame":
        stmt = self._parse_cached(sql)
        if isinstance(stmt, ast.SetVariable):
            self.config.set(stmt.key, stmt.value)
            return self._empty_df()
        if isinstance(stmt, ast.ShowSettings):
            return self._show_settings(stmt.key, self.config.to_dict())
        if isinstance(stmt, ast.Explain):
            return self._explain(stmt)
        if isinstance(stmt, ast.CreateExternalTable):
            return self._create_external_table(stmt)
        if isinstance(stmt, ast.ShowTables):
            import pyarrow as pa

            t = pa.table({"table_name": self.catalog.table_names()})
            name = f"__show_{uuid.uuid4().hex[:6]}"
            self.register_table(name, t)
            return self.sql(f"select table_name from {name}")
        if isinstance(stmt, ast.ShowColumns):
            import pyarrow as pa

            schema = self.catalog.table_schema(stmt.table)
            t = pa.table({
                "column_name": [f.name for f in schema],
                "data_type": [str(f.dtype) for f in schema],
            })
            name = f"__cols_{uuid.uuid4().hex[:6]}"
            self.register_table(name, t)
            return self.sql(f"select column_name, data_type from {name}")
        logical = SqlToRel(self.catalog).plan(stmt)
        return BallistaDataFrame(self, logical,
                                 sql_text=sql if isinstance(stmt, ast.Select)
                                 else None)

    def _remote_sql(self, sql: str) -> "RemoteDataFrame":
        # DDL and SHOW are handled via scheduler RPCs; SELECT ships verbatim
        import pandas as pd

        stmt = self._parse_cached(sql)
        if isinstance(stmt, ast.SetVariable):
            # validate locally, then update BOTH ends: the scheduler plans
            # with the session config, the client uses its copy for
            # deadlines etc.
            self.config.set(stmt.key, stmt.value)
            self._remote.update_session({stmt.key: stmt.value})
            return RemoteDataFrame(self, None, static=pd.DataFrame())
        if isinstance(stmt, ast.ShowSettings):
            # the client config mirrors every SET (both ends update), so
            # SHOW answers locally — no RPC
            df = self._show_settings(stmt.key, self.config.to_dict())
            return RemoteDataFrame(self, None, static=df.to_pandas())
        if isinstance(stmt, ast.Explain):
            rows = self._remote.explain(sql)
            return RemoteDataFrame(self, None, static=pd.DataFrame(rows))
        if isinstance(stmt, ast.CreateExternalTable):
            schema = None
            if stmt.columns:
                schema = Schema(Field(n, parse_type_name(t)) for n, t in stmt.columns)
            self._remote.register_external_table(
                stmt.name, stmt.file_format, stmt.location, schema,
                delimiter=stmt.delimiter, has_header=stmt.has_header)
            return RemoteDataFrame(self, None)
        if isinstance(stmt, ast.ShowTables):
            return RemoteDataFrame(self, None, static=pd.DataFrame(
                {"table_name": sorted(self._remote.list_tables())}))
        if isinstance(stmt, ast.ShowColumns):
            schema = self._remote.table_schema(stmt.table)
            return RemoteDataFrame(self, None, static=pd.DataFrame({
                "column_name": [f.name for f in schema],
                "data_type": [str(f.dtype) for f in schema]}))
        return RemoteDataFrame(self, sql)

    def _empty_df(self) -> BallistaDataFrame:
        """DDL-style statements: nothing to collect."""
        import pandas as pd

        return BallistaDataFrame(self, None, static=pd.DataFrame())

    def _show_settings(self, key: str, settings: Dict[str, object]) -> BallistaDataFrame:
        import pandas as pd

        if key:
            self.config.get(key)  # raises ConfigurationError on unknown keys
            settings = {key: settings[key]}
        rows = sorted(settings.items())
        return BallistaDataFrame(self, None, static=pd.DataFrame(
            {"name": [k for k, _ in rows],
             "value": [str(v) for _, v in rows]}))

    def _explain(self, stmt: "ast.Explain") -> BallistaDataFrame:
        """EXPLAIN [ANALYZE] [VERBOSE] <select>: plan rows,
        DataFusion-shaped (plan_type, plan); VERBOSE adds the distributed
        stage split, ANALYZE runs the query and appends a row with the
        runtime-annotated plan (obs/stats.py).  Parity: the reference gets
        EXPLAIN from DataFusion through ballista-cli; here the physical
        row shows the exchange/mesh decisions this engine makes (SURVEY §1
        ENGINE layer).  The result is a static frame — nothing is
        registered in the catalog."""
        import pandas as pd

        from ..scheduler.physical_planner import explain_rows

        rows = explain_rows(self.catalog, self.config, stmt.statement,
                            verbose=stmt.verbose)
        if stmt.analyze:
            report = self._explain_analyze_statement(stmt.statement)
            rows = rows + [{"plan_type": "explain_analyze",
                            "plan": report["text"]}]
        return BallistaDataFrame(
            self, None,
            static=pd.DataFrame(rows, columns=["plan_type", "plan"]))

    def explain_analyze(self, sql: str) -> Dict:
        """Run ``sql`` and return the EXPLAIN ANALYZE report: the physical
        plan annotated with observed rows/bytes/wall-time per operator and
        skew/duration quantiles per stage.  The returned dict is the JSON
        form (same shape as ``GET /api/job/<id>/stats``); its ``"text"``
        key holds the rendered report.  Accepts either a bare SELECT or a
        full ``EXPLAIN ANALYZE <select>`` statement."""
        if self._remote is not None:
            raise PlanningError(
                "explain_analyze is not supported over a remote connection; "
                "run the query and read GET /api/job/<id>/stats on the "
                "scheduler's REST API instead")
        stmt = parse_sql(sql)
        if isinstance(stmt, ast.Explain):
            stmt = stmt.statement
        if not isinstance(stmt, ast.Select):
            raise PlanningError("explain_analyze requires a SELECT query")
        return self._explain_analyze_statement(stmt)

    def advise(self, sql: str) -> Dict:
        """Run ``sql`` and return the stage-fusion advisor report
        (obs/advisor.py): operator chains ranked by the materialization +
        recompilation overhead a fused program would eliminate, with
        estimated savings.  Same JSON shape as ``GET
        /api/job/<id>/advise``; the ``"text"`` key holds the rendered
        report.  Requires the device observatory
        (``ballista.observability.device.enabled``) for non-zero
        numbers."""
        from ..obs.advisor import advise_report
        from ..utils.config import OBS_DEVICE_ADVISOR_MIN_SAVINGS_MS

        return advise_report(
            self.explain_analyze(sql),
            min_savings_ms=float(
                self.config.get(OBS_DEVICE_ADVISOR_MIN_SAVINGS_MS)))

    def forensics(self, job_id: Optional[str] = None) -> Dict:
        """Assemble the self-contained forensics bundle for ``job_id``
        (default: the last job this session ran): flight-recorder
        timeline, stage stats, device stats, spans, AQE/speculation
        records and scheduler metrics in one JSON artifact.  Same shape
        as ``GET /api/job/<id>/forensics``.  Standalone engine only —
        remote sessions read the scheduler's REST endpoint."""
        from ..obs.doctor import assemble_forensics

        if self._standalone is None:
            raise PlanningError(
                "forensics requires a standalone session; over a remote "
                "connection read GET /api/job/<id>/forensics on the "
                "scheduler's REST API instead")
        job_id = job_id or self._standalone.last_job_id
        if not job_id:
            raise PlanningError("no job has run in this session yet")
        bundle = assemble_forensics(self._standalone.scheduler, job_id)
        if bundle is None:
            raise PlanningError(f"job {job_id!r} is not known to the "
                                "scheduler (or has aged out of retention)")
        return bundle

    def doctor(self, job_id: Optional[str] = None) -> Dict:
        """Run the query doctor (obs/doctor.py) over ``job_id``'s
        forensics bundle: ranked pathology findings with cited metric
        evidence and config-knob remedies.  The ``"text"`` key holds the
        rendered diagnosis.  Same shape as ``GET /api/job/<id>/doctor``."""
        from ..obs.doctor import diagnose

        return diagnose(self.forensics(job_id))

    def cancel(self, job_id: Optional[str] = None) -> None:
        """Cancel ``job_id`` (default: the last job this session ran)
        fleet-wide.  The scheduler pulls a still-queued job out of the
        admission queue; for a running job it fans a cancel out to every
        executor holding its tasks — cooperative cancellation checkpoints
        between operator batches and fused-kernel invocations land the
        kill in seconds, and heartbeat zombie reconciliation re-issues any
        fanout the network lost.  All job state (admission permits, slot
        reservations, speculation bookkeeping) is released with the
        terminal status.  Idempotent: cancelling a finished or already
        cancelled job is a no-op."""
        if self._remote is not None:
            if not job_id:
                raise PlanningError("remote cancel needs an explicit job id")
            self._remote.cancel_job(job_id)
            return
        if self._standalone is None:
            raise PlanningError(
                "cancel requires a standalone or remote session")
        job_id = job_id or self._standalone.last_job_id
        if not job_id:
            raise PlanningError("no job has run in this session yet")
        self._standalone.scheduler.cancel_job(job_id)

    def watch(self, job_id: Optional[str] = None,
              timeout: Optional[float] = None):
        """Live watch stream for ``job_id`` (default: the last job this
        session ran): a generator of frames, dicts tagged ``{"t":
        "event"|"progress"|"end"}`` — journal events as they happen,
        progress snapshots (monotonically non-decreasing ``fraction``,
        rows/s, quantile ETA) on the watch poll cadence, and one terminal
        frame.  Remote sessions long-poll the scheduler's watch_job RPC
        and follow lease adoption across a shard failover
        (docs/user-guide/live.md); standalone sessions subscribe to the
        in-process journal directly.  Event frames require the flight
        recorder (``ballista.journal.enabled``); progress and terminal
        frames flow either way."""
        if self._remote is not None:
            if not job_id:
                raise PlanningError("remote watch needs an explicit job id")
            return self._remote.watch(job_id, timeout=timeout)
        if self._standalone is None:
            raise PlanningError(
                "watch requires a standalone or remote session")
        job_id = job_id or self._standalone.last_job_id
        if not job_id:
            raise PlanningError("no job has run in this session yet")
        return self._watch_standalone(job_id, timeout)

    def _watch_standalone(self, job_id: str, timeout: Optional[float]):
        import time

        from ..obs import journal
        from ..obs.progress import job_progress, monotonic_fraction
        from ..utils.config import LIVE_WATCH_POLL_S, LIVE_WATCH_QUEUE_EVENTS

        sched = self._standalone.scheduler
        if sched.jobs.get_status(job_id) is None:
            raise PlanningError(f"job {job_id!r} is not known to the "
                                "scheduler (or has aged out of retention)")
        poll_s = float(self.config.get(LIVE_WATCH_POLL_S))
        capacity = int(self.config.get(LIVE_WATCH_QUEUE_EVENTS))
        deadline = time.monotonic() + (
            timeout if timeout is not None
            else float(self.config.job_timeout_s))
        floor = 0.0
        with journal.subscribe(job_id=job_id, capacity=capacity) as sub:
            # subscribe BEFORE snapshotting the retained timeline, then
            # dedup on (actor, seq): nothing emitted during the handoff is
            # lost, nothing is shown twice
            replayed = set()
            for ev in journal.job_timeline(job_id):
                replayed.add((ev.get("actor"), ev.get("seq")))
                yield {"t": "event", "event": ev}
            while time.monotonic() < deadline:
                for ev in sub.poll(timeout=poll_s):
                    key = (ev.get("actor"), ev.get("seq"))
                    if ev.get("kind") != "watch.gap" and key in replayed:
                        continue
                    yield {"t": "event", "event": ev}
                if replayed:
                    replayed.clear()  # only the handoff window needs it
                st = sched.jobs.get_status(job_id)
                graph = sched.jobs.get_graph(job_id)
                if graph is not None:
                    prog = job_progress(graph)
                    floor = monotonic_fraction(prog, floor)
                    prog["fraction"] = floor
                    yield {"t": "progress", "progress": prog,
                           "state": st.state if st else None}
                if st is not None and st.state in ("successful", "failed",
                                                   "cancelled"):
                    yield {"t": "end", "state": st.state, "error": st.error}
                    return
        from ..utils.errors import ExecutionError

        raise ExecutionError(f"watch of job {job_id} timed out")

    def _explain_analyze_statement(self, stmt: "ast.Node") -> Dict:
        """Plan + run one SELECT and build the annotated report.  The
        standalone engine reads the retained ExecutionGraph's stats store
        (identical numbers to the profile endpoint); the local engine
        reads metrics straight off the executed operator instances."""
        import time

        from ..obs.stats import explain_analyze_report, local_explain_report

        logical = SqlToRel(self.catalog).plan(stmt)
        planner = PhysicalPlanner(self.catalog, self.config)
        planned = planner.plan_query(optimize(logical))
        t0 = time.monotonic()
        if self.engine == "local":
            from ..obs import device as device_obs

            with device_obs.task_scope() as dev_acc:
                batches = self._execute_local(planned)
            wall_ms = (time.monotonic() - t0) * 1000.0
            return local_explain_report(
                planned.plan, wall_ms,
                rows_returned=sum(b.num_rows for b in batches),
                device_stats=dev_acc.snapshot() if dev_acc else None)
        batches = self._standalone.execute(planned)
        wall_ms = (time.monotonic() - t0) * 1000.0
        graph = self._standalone.scheduler.jobs.get_graph(
            self._standalone.last_job_id)
        if graph is None:
            raise PlanningError(
                f"job {self._standalone.last_job_id} graph is no longer "
                "retained; cannot build the EXPLAIN ANALYZE report")
        return explain_analyze_report(
            graph, wall_ms, rows_returned=sum(b.num_rows for b in batches))

    def _create_external_table(self, stmt: ast.CreateExternalTable) -> BallistaDataFrame:
        schema = None
        if stmt.columns:
            schema = Schema(Field(n, parse_type_name(t)) for n, t in stmt.columns)
        if stmt.file_format == "parquet":
            self.register_parquet(stmt.name, stmt.location, schema)
        elif stmt.file_format == "csv":
            self.register_csv(stmt.name, stmt.location, schema,
                              delimiter=stmt.delimiter, has_header=stmt.has_header)
        else:
            raise PlanningError(f"unsupported format {stmt.file_format}")
        return self._empty_df()

    # --- execution ------------------------------------------------------
    def _execute_logical(self, logical: L.LogicalPlan,
                         sql_text: Optional[str] = None) -> List[ColumnBatch]:
        if self.engine == "standalone" and sql_text is not None:
            # serving path: the scheduler's plan/result caches key on the
            # statement text; a hit skips (re-)planning entirely
            return self._standalone.execute_sql(
                sql_text, self.catalog, self.config,
                statement=self._parse_cached(sql_text))
        optimized = optimize(logical)
        planner = PhysicalPlanner(self.catalog, self.config)
        planned = planner.plan_query(optimized)
        if self.engine == "local":
            return self._execute_local(planned)
        return self._standalone.execute(planned)

    def _execute_local(self, planned: PlannedQuery) -> List[ColumnBatch]:
        from ..obs import device as device_obs
        from ..utils.config import OBS_DEVICE_ENABLED, OBS_DEVICE_WATERMARKS

        device_obs.set_enabled(bool(self.config.get(OBS_DEVICE_ENABLED)))
        device_obs.set_watermarks(
            bool(self.config.get(OBS_DEVICE_WATERMARKS)))
        from ..memory import MemoryGovernor

        ctx = TaskContext(config=self.config, work_dir=self.work_dir,
                          job_id=uuid.uuid4().hex[:7],
                          governor=MemoryGovernor.from_config(self.config))
        for sid, splan in planned.scalars:
            ctx.scalars[sid] = extract_scalar(splan, ctx)
        out: List[ColumnBatch] = []
        for p in range(planned.plan.output_partition_count()):
            out.extend(planned.plan.execute(p, ctx))
        return out


def extract_scalar(plan: ExecutionPlan, ctx: TaskContext):
    """Run a scalar-subquery plan to a single python value (raw physical
    repr: decimals stay scaled ints; _substitute_scalars rescales)."""
    vals = []
    for p in range(plan.output_partition_count()):
        for b in plan.execute(p, ctx):
            if b.num_rows:
                mask = np.asarray(b.mask)
                col = np.asarray(b.columns[b.schema.fields[0].name])
                vals.extend(col[mask].tolist())
    if len(vals) > 1:
        raise PlanningError("scalar subquery returned more than one row")
    if not vals:
        return 0
    return vals[0]
