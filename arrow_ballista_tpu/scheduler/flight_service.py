"""Arrow Flight front door on the scheduler, speaking enough Flight SQL
for JDBC-class clients.

Parity: the reference exposes Arrow Flight SQL on the scheduler
(reference ballista/scheduler/src/flight_sql.rs:83-911 — handshake,
CommandStatementQuery/getFlightInfo, prepared statements, do_get with
TicketStatementQuery; it powers the Arrow Flight SQL JDBC driver) and an
Arrow Flight data plane on executors (flight_service.rs:82-120).  Here one
`pyarrow.flight.FlightServerBase` fronts the scheduler's existing
session/prepare/execute/fetch machinery:

- a STOCK ``pyarrow.flight`` client can run SQL end-to-end:
  ``get_flight_info(FlightDescriptor.for_command(b"select ..."))`` then
  ``do_get(endpoint.ticket)``;
- Flight SQL's simple-query and prepared-statement flows are understood at
  the wire level: ``google.protobuf.Any``-wrapped ``CommandStatementQuery``
  / ``TicketStatementQuery`` / ``ActionCreatePreparedStatementRequest`` /
  ``CommandPreparedStatementQuery`` messages are parsed/emitted with a
  minimal protobuf codec (every field involved is length-delimited), so no
  protobuf toolchain is needed.

Results stream as plain (non-dictionary) arrow arrays: one stable stream
schema regardless of per-batch dictionaries.
"""
from __future__ import annotations

import logging
import os
import threading
from typing import Dict, List, Optional, Tuple

log = logging.getLogger(__name__)

_SQL_NS = "type.googleapis.com/arrow.flight.protocol.sql."


def like_pattern(pattern: str):
    """SQL LIKE filter pattern -> compiled regex (Flight SQL
    CommandGetTables): ``%`` -> ``.*``, ``_`` -> ``.``, and a backslash
    escapes the next character (``\\%`` / ``\\_`` match literal ``%`` /
    ``_`` — re.escape alone would turn ``\\%`` into an escaped backslash
    followed by a live wildcard)."""
    import re as _re

    out = []
    i = 0
    while i < len(pattern):
        c = pattern[i]
        if c == "\\" and i + 1 < len(pattern):
            out.append(_re.escape(pattern[i + 1]))
            i += 2
            continue
        if c == "%":
            out.append(".*")
        elif c == "_":
            out.append(".")
        else:
            out.append(_re.escape(c))
        i += 1
    return _re.compile("^" + "".join(out) + "$", _re.IGNORECASE)


# --------------------------------------------------------------------------
# minimal protobuf (length-delimited fields only)
# --------------------------------------------------------------------------


def _read_varint(data: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = data[i]
        out |= (b & 0x7F) << shift
        i += 1
        if not b & 0x80:
            return out, i
        shift += 7


def _write_varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def pb_decode(data: bytes) -> Dict[int, List]:
    """field number -> list of values: raw bytes for length-delimited
    fields, int for varint fields (bools like include_schema arrive as
    wire-type 0 — skipping them loses real driver flags).  64/32-bit
    fixed fields are skipped (none of the messages we speak use them)."""
    out: Dict[int, List] = {}
    i = 0
    while i < len(data):
        key, i = _read_varint(data, i)
        field, wire = key >> 3, key & 7
        if wire == 2:  # length-delimited
            n, i = _read_varint(data, i)
            out.setdefault(field, []).append(data[i:i + n])
            i += n
        elif wire == 0:  # varint
            v, i = _read_varint(data, i)
            out.setdefault(field, []).append(v)
        elif wire == 1:  # 64-bit — skip
            i += 8
        elif wire == 5:  # 32-bit — skip
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
    return out


def pb_field(field: int, payload: bytes) -> bytes:
    return _write_varint(field << 3 | 2) + _write_varint(len(payload)) + payload


def any_wrap(type_name: str, value: bytes) -> bytes:
    return pb_field(1, (_SQL_NS + type_name).encode()) + pb_field(2, value)


def any_unwrap(data: bytes) -> Tuple[str, bytes]:
    """(short type name, value) from a google.protobuf.Any; raises
    ValueError when the bytes aren't an Any we understand."""
    fields = pb_decode(data)
    if 1 not in fields:
        raise ValueError("not a protobuf Any")
    url = fields[1][0].decode("utf-8", "strict")
    if "/" not in url:
        raise ValueError(f"unexpected Any type url {url!r}")
    value = fields[2][0] if 2 in fields else b""
    return url.rsplit(".", 1)[1], value


# --------------------------------------------------------------------------
# schema mapping
# --------------------------------------------------------------------------


def logical_arrow_schema(schema):
    """Our Schema -> the (stable) pyarrow schema Flight streams use:
    strings as plain utf8 (not per-batch dictionaries), decimals as
    decimal128(38, scale) — matching ColumnBatch.to_arrow after the
    dictionary cast.  One mapping for the whole engine
    (Schema.to_arrow_schema)."""
    return schema.to_arrow_schema()


# --------------------------------------------------------------------------
# the server
# --------------------------------------------------------------------------


class BallistaFlightServer:
    """Flight (SQL) service over a SchedulerNetService.  Lazily imports
    pyarrow.flight so deployments without the Flight door never pay for
    grpc."""

    def __init__(self, svc, host: str = "127.0.0.1", port: int = 0):
        import pyarrow.flight as fl

        self.svc = svc
        outer = self

        class _Server(fl.FlightServerBase):
            def __init__(self):
                super().__init__(location=f"grpc://{host}:{port}")

            def get_flight_info(self, context, descriptor):
                return outer._get_flight_info(descriptor)

            def get_schema(self, context, descriptor):
                kind, payload = outer._command_kind(bytes(descriptor.command))
                if kind == "meta":
                    return fl.SchemaResult(outer._meta_table(*payload).schema)
                return fl.SchemaResult(outer._plan_schema(payload))

            def do_get(self, context, ticket):
                return outer._do_get(bytes(ticket.ticket))

            def do_action(self, context, action):
                return outer._do_action(action.type, bytes(action.body))

            def list_actions(self, context):
                return [("CreatePreparedStatement",
                         "Flight SQL prepared statement"),
                        ("ClosePreparedStatement",
                         "drop a prepared statement handle")]

        self._fl = fl
        self._server = _Server()
        self.host = host
        self.port = self._server.port
        self._prepared: Dict[bytes, str] = {}
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None

    # --- lifecycle -------------------------------------------------------
    def start(self) -> None:
        self._thread = threading.Thread(target=self._server.serve,
                                        name=f"flight-{self.port}",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        try:
            self._server.shutdown()
        except Exception:  # noqa: BLE001 — shutdown is best-effort
            log.debug("flight server shutdown", exc_info=True)
        if self._thread is not None:
            self._thread.join(timeout=5.0)

    # --- metadata commands (the JDBC/ADBC connect sequence) --------------
    # Every Flight SQL driver issues these on connect, before any query
    # (reference flight_sql.rs get_flight_info_sql_info/_catalogs/
    # _schemas/_tables/_table_types); the standard result schemas are
    # fixed by the Flight SQL spec.
    _META_COMMANDS = ("CommandGetSqlInfo", "CommandGetCatalogs",
                      "CommandGetDbSchemas", "CommandGetTables",
                      "CommandGetTableTypes")
    CATALOG_NAME = "ballista"
    DB_SCHEMA_NAME = "public"

    def _meta_table(self, name: str, value: bytes):
        import pyarrow as pa

        if name == "CommandGetCatalogs":
            return pa.table({"catalog_name": pa.array([self.CATALOG_NAME],
                                                      type=pa.string())})
        if name == "CommandGetDbSchemas":
            return pa.table({
                "catalog_name": pa.array([self.CATALOG_NAME], type=pa.string()),
                "db_schema_name": pa.array([self.DB_SCHEMA_NAME],
                                           type=pa.string())})
        if name == "CommandGetTableTypes":
            return pa.table({"table_type": pa.array(["TABLE"],
                                                    type=pa.string())})
        if name == "CommandGetTables":
            # FlightSql.proto CommandGetTables: catalog=1,
            # db_schema_filter_pattern=2, table_name_filter_pattern=3,
            # table_types=4 (repeated string), include_schema=5 (bool)
            f = pb_decode(value)
            _like = like_pattern
            names = sorted(self.svc.catalog.table_names())
            catalog = f[1][0].decode("utf-8") if 1 in f else None
            if catalog not in (None, "", self.CATALOG_NAME):
                names = []
            if 2 in f and not _like(f[2][0].decode("utf-8")).match(
                    self.DB_SCHEMA_NAME):
                names = []
            if 3 in f:
                rx = _like(f[3][0].decode("utf-8"))
                names = [n for n in names if rx.match(n)]
            if 4 in f:  # repeated table-type filter
                types = {t.decode("utf-8").upper() for t in f[4]}
                if "TABLE" not in types:
                    names = []
            include_schema = bool(f[5][0]) if 5 in f else False
            cols = {
                "catalog_name": pa.array([self.CATALOG_NAME] * len(names),
                                         type=pa.string()),
                "db_schema_name": pa.array([self.DB_SCHEMA_NAME] * len(names),
                                           type=pa.string()),
                "table_name": pa.array(names, type=pa.string()),
                "table_type": pa.array(["TABLE"] * len(names),
                                       type=pa.string()),
            }
            if include_schema:
                blobs = []
                for n in names:
                    sch = logical_arrow_schema(
                        self.svc.catalog.provider(n).schema)
                    blobs.append(sch.serialize().to_pybytes())
                cols["table_schema"] = pa.array(blobs, type=pa.binary())
            return pa.table(cols)
        if name == "CommandGetSqlInfo":
            # spec schema: info_name uint32, value dense_union of
            # (string, bool, int64, int32, list<utf8>, map<int32,list<int32>>)
            from .. import __version__ as _ver

            info = {
                0: "arrow-ballista-tpu",          # FLIGHT_SQL_SERVER_NAME
                1: str(_ver),                     # FLIGHT_SQL_SERVER_VERSION
                2: pa.__version__,                # FLIGHT_SQL_SERVER_ARROW_VERSION
            }
            f = pb_decode(value)
            # requested info ids: packed (one LEN payload of varints) or
            # unpacked repeated uint32 (ints straight from the decoder)
            wanted = None
            if 1 in f:
                wanted = set()
                for payload in f[1]:
                    if isinstance(payload, int):
                        wanted.add(payload)
                        continue
                    i = 0
                    while i < len(payload):
                        v, i = _read_varint(payload, i)
                        wanted.add(v)
            rows = [(k, v) for k, v in sorted(info.items())
                    if wanted is None or k in wanted]
            union_type = pa.dense_union([
                pa.field("string_value", pa.string()),
                pa.field("bool_value", pa.bool_()),
                pa.field("bigint_value", pa.int64()),
                pa.field("int32_bitmask", pa.int32()),
                pa.field("string_list", pa.list_(pa.string())),
                pa.field("int32_to_int32_list_map",
                         pa.map_(pa.int32(), pa.list_(pa.int32()))),
            ])
            types = pa.array([0] * len(rows), type=pa.int8())
            offsets = pa.array(range(len(rows)), type=pa.int32())
            strings = pa.array([v for _, v in rows], type=pa.string())
            empty = [pa.array([], type=t.type) for t in list(union_type)[1:]]
            union = pa.UnionArray.from_dense(types, offsets,
                                             [strings, *empty],
                                             [t.name for t in union_type])
            return pa.table({
                "info_name": pa.array([k for k, _ in rows], type=pa.uint32()),
                "value": union})
        raise self._fl.FlightServerError(f"unsupported metadata command {name}")

    # --- command parsing -------------------------------------------------
    def _command_kind(self, cmd: bytes):
        """(kind, payload): ('meta', (name, value)) for metadata commands,
        ('sql', text) for query commands."""
        try:
            name, value = any_unwrap(cmd)
        # ballista: allow=recovery-path-logging — expected dual-format parse
        except Exception:  # noqa: BLE001 — not protobuf: plain SQL bytes
            return "sql", cmd.decode("utf-8")
        if name in self._META_COMMANDS:
            return "meta", (name, value)
        if name == "CommandStatementQuery":
            return "sql", pb_decode(value)[1][0].decode("utf-8")
        if name == "CommandPreparedStatementQuery":
            handle = pb_decode(value)[1][0]
            with self._lock:
                sql = self._prepared.get(handle)
            if sql is None:
                raise self._fl.FlightServerError(
                    f"unknown prepared statement handle {handle!r}")
            return "sql", sql
        raise self._fl.FlightServerError(
            f"unsupported Flight SQL command {name}")

    def _sql_of_command(self, cmd: bytes) -> str:
        """SQL text from a descriptor command: an Any-wrapped Flight SQL
        message, or raw SQL bytes (the stock-pyarrow-client path)."""
        kind, payload = self._command_kind(cmd)
        if kind != "sql":
            raise self._fl.FlightServerError(
                f"metadata command {payload[0]} carries no SQL")
        return payload

    def _sql_of_ticket(self, raw: bytes) -> str:
        try:
            name, value = any_unwrap(raw)
        # ballista: allow=recovery-path-logging — expected dual-format parse
        except Exception:  # noqa: BLE001 — plain SQL ticket
            return raw.decode("utf-8")
        if name == "TicketStatementQuery":
            # statement_handle carries the SQL we stamped in get_flight_info
            return pb_decode(value)[1][0].decode("utf-8")
        # tickets for prepared statements carry the command itself
        return self._sql_of_command(raw)

    # --- planning / execution -------------------------------------------
    _DDL_TYPES = ("CreateExternalTable", "SetVariable", "ShowTables",
                  "ShowSettings", "ShowColumns", "Explain")

    def _parse(self, sql: str):
        """Parse once; returns (stmt, is_ddl) where is_ddl marks the
        utility statements (CREATE EXTERNAL TABLE / SET / SHOW / DESCRIBE
        / EXPLAIN) the Flight door executes directly — JDBC clients issue
        them like any statement (same set the CLI/client dispatch covers,
        context.py:255-283)."""
        from ..sql.parser import parse_sql

        stmt = parse_sql(sql)
        return stmt, type(stmt).__name__ in self._DDL_TYPES

    def _run_ddl(self, stmt):
        """Execute a DDL/utility statement; returns the result pa.Table."""
        import pyarrow as pa

        from ..sql import ast as sqlast

        if isinstance(stmt, sqlast.CreateExternalTable):
            from ..models.schema import Field as EField, Schema as ESchema
            from ..sql.planner import parse_type_name

            from .. import serde

            payload = {"name": stmt.name, "format": stmt.file_format,
                       "path": stmt.location, "has_header": stmt.has_header,
                       "delimiter": stmt.delimiter}
            if stmt.columns:  # declared column types win over inference
                payload["schema"] = serde.schema_to_obj(ESchema(
                    EField(n, parse_type_name(t)) for n, t in stmt.columns))
            self.svc._register_external_table(payload, b"")
            return pa.table({"result": pa.array([], type=pa.string())})
        if isinstance(stmt, sqlast.SetVariable):
            # sessionless Flight SET mutates the shared default config
            self.svc.config.set(stmt.key, stmt.value)
            return pa.table({"result": pa.array([], type=pa.string())})
        if isinstance(stmt, sqlast.ShowSettings):
            settings = self.svc.config.to_dict()
            if stmt.key:
                self.svc.config.get(stmt.key)  # unknown key -> error
                settings = {stmt.key: settings[stmt.key]}
            rows = sorted(settings.items())
            return pa.table({
                "name": pa.array([k for k, _ in rows], type=pa.string()),
                "value": pa.array([str(v) for _, v in rows], type=pa.string())})
        if isinstance(stmt, sqlast.ShowColumns):
            schema = self.svc.catalog.provider(stmt.table).schema
            return pa.table({
                "column_name": pa.array([f.name for f in schema],
                                        type=pa.string()),
                "data_type": pa.array([str(f.dtype) for f in schema],
                                      type=pa.string())})
        if isinstance(stmt, sqlast.Explain):
            from .physical_planner import explain_rows

            rows = explain_rows(self.svc.catalog, self.svc.config,
                                stmt.statement, stmt.verbose)
            return pa.table({
                "plan_type": pa.array([r["plan_type"] for r in rows],
                                      type=pa.string()),
                "plan": pa.array([r["plan"] for r in rows],
                                 type=pa.string())})
        # ShowTables
        names = sorted(self.svc.catalog.table_names())
        return pa.table({"table_name": pa.array(names, type=pa.string())})

    def _plan_schema(self, sql: str):
        stmt, is_ddl = self._parse(sql)
        if is_ddl:
            return self._run_ddl(stmt).schema
        # plan directly (the _prepare RPC would store a statement in the
        # sessionless prepared holder — leaking one entry per Flight
        # schema probe and evicting real RPC-prepared statements)
        from ..sql.optimizer import optimize
        from ..sql.planner import SqlToRel

        logical = optimize(SqlToRel(self.svc.catalog).plan(stmt))
        return logical_arrow_schema(logical.schema)

    def _get_flight_info(self, descriptor):
        fl = self._fl
        cmd = bytes(descriptor.command)
        kind, payload = self._command_kind(cmd)
        if kind == "meta":
            # metadata flows: the ticket is the command itself, round-tripped
            # verbatim (exactly how the JDBC driver replays it to do_get)
            schema = self._meta_table(*payload).schema
            ticket = fl.Ticket(cmd)
        else:
            sql = payload
            schema = self._plan_schema(sql)
            # the ticket round-trips through the client verbatim (JDBC sends
            # it back as-is): Any(TicketStatementQuery{statement_handle=sql})
            ticket = fl.Ticket(any_wrap(
                "TicketStatementQuery", pb_field(1, sql.encode())))
        endpoint = fl.FlightEndpoint(ticket, [
            fl.Location.for_grpc_tcp(self.host, self.port)])
        return fl.FlightInfo(schema, descriptor, [endpoint], -1, -1)

    def _do_get(self, raw_ticket: bytes):
        fl = self._fl
        try:
            name, value = any_unwrap(raw_ticket)
        # ballista: allow=recovery-path-logging — expected dual-format parse
        except Exception:  # noqa: BLE001
            name = value = None
        if name in self._META_COMMANDS:
            return fl.RecordBatchStream(self._meta_table(name, value))
        sql = self._sql_of_ticket(raw_ticket)
        table = self._execute_to_table(sql)
        return fl.RecordBatchStream(table)

    def _execute_to_table(self, sql: str):
        import pyarrow as pa

        stmt, is_ddl = self._parse(sql)
        if is_ddl:
            return self._run_ddl(stmt)

        from .. import serde
        from ..models.batch import ColumnBatch
        from ..models.ipc import read_ipc_files
        from ..net.dataplane import fetch_partition
        from ..utils.errors import ExecutionError

        payload, _ = self.svc._execute_query({"sql": sql}, b"")
        job_id = payload["job_id"]
        status = self.svc.server.wait_for_job(
            job_id, float(self.svc.config.job_timeout_s))
        if status.state != "successful":
            raise ExecutionError(f"job {job_id} {status.state}: {status.error}")
        with self.svc._lock:
            schema = self.svc._final_schemas.get(job_id)
        if schema is None:  # LRU-evicted under heavy concurrent load
            raise ExecutionError(
                f"result schema for job {job_id} no longer cached; re-run "
                f"the query")
        target = logical_arrow_schema(schema)
        batches: List[ColumnBatch] = []
        for part in sorted(status.locations):
            for loc in status.locations[part]:
                if not loc.num_rows:
                    continue
                if os.path.exists(loc.path):
                    batches.extend(read_ipc_files([loc.path], schema))
                else:
                    batches.extend(fetch_partition(
                        loc, schema, self.svc.config)[0])
        tables = [b.to_arrow().cast(target) for b in batches]
        return pa.concat_tables(tables) if tables \
            else target.empty_table()

    # --- actions (prepared statements) ----------------------------------
    def _do_action(self, action_type: str, body: bytes):
        fl = self._fl
        if action_type == "CreatePreparedStatement":
            try:
                _name, value = any_unwrap(body)
            # ballista: allow=recovery-path-logging — expected dual-format parse
            except Exception:  # noqa: BLE001 — raw request body
                value = body
            sql = pb_decode(value)[1][0].decode("utf-8")
            schema = self._plan_schema(sql)
            handle = os.urandom(12)
            with self._lock:
                self._prepared[handle] = sql
                while len(self._prepared) > 256:
                    self._prepared.pop(next(iter(self._prepared)))
            result = (pb_field(1, handle)
                      + pb_field(2, schema.serialize().to_pybytes()))
            return [any_wrap("ActionCreatePreparedStatementResult", result)]
        if action_type == "ClosePreparedStatement":
            try:
                _name, value = any_unwrap(body)
            # ballista: allow=recovery-path-logging — expected dual-format parse
            except Exception:  # noqa: BLE001
                value = body
            handle = pb_decode(value)[1][0]
            with self._lock:
                self._prepared.pop(handle, None)
            return []
        raise self._fl.FlightServerError(f"unknown action {action_type!r}")
