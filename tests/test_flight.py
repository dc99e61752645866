"""Arrow Flight (SQL) front door: a STOCK pyarrow.flight client runs SQL
end-to-end against the scheduler, and the Flight SQL wire shapes a JDBC
driver uses (Any-wrapped CommandStatementQuery / prepared statements) are
understood (reference flight_sql.rs:83-911)."""
import numpy as np
import pyarrow as pa
import pyarrow.flight as fl
import pytest

from arrow_ballista_tpu.scheduler.flight_service import (
    any_unwrap,
    any_wrap,
    pb_decode,
    pb_field,
)
from arrow_ballista_tpu.utils.config import BallistaConfig


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    from arrow_ballista_tpu.catalog import MemoryTable
    from arrow_ballista_tpu.executor.server import ExecutorServer
    from arrow_ballista_tpu.scheduler.netservice import SchedulerNetService

    svc = SchedulerNetService(
        "127.0.0.1", 0,
        config=BallistaConfig({"ballista.shuffle.partitions": "2"}),
        flight_port=0)
    svc.start()
    work = str(tmp_path_factory.mktemp("flight-exec"))
    ex = ExecutorServer("127.0.0.1", svc.port, "127.0.0.1", 0,
                        work_dir=work, concurrent_tasks=2,
                        executor_id="flight-exec")
    ex.start()

    rng = np.random.default_rng(11)
    svc.catalog.register(MemoryTable("t", pa.table({
        "g": pa.array(rng.integers(0, 3, 1000).astype(np.int64)),
        "v": pa.array(rng.integers(0, 100, 1000).astype(np.int64)),
        "s": pa.array([f"name-{i % 7}" for i in range(1000)]),
    })))
    yield svc
    ex.stop(notify=False)
    svc.stop()


@pytest.fixture(scope="module")
def client(cluster):
    return fl.connect(f"grpc://127.0.0.1:{cluster.flight.port}")


def test_stock_pyarrow_client_select(client):
    sql = b"select g, sum(v) as s, count(*) as n from t group by g order by g"
    info = client.get_flight_info(fl.FlightDescriptor.for_command(sql))
    assert [f.name for f in info.schema] == ["g", "s", "n"]
    table = client.do_get(info.endpoints[0].ticket).read_all()
    assert table.num_rows == 3
    assert sum(table.column("n").to_pylist()) == 1000
    assert table.column("g").to_pylist() == [0, 1, 2]


def test_strings_stream_as_plain_utf8(client):
    info = client.get_flight_info(fl.FlightDescriptor.for_command(
        b"select s, count(*) as n from t group by s order by s"))
    table = client.do_get(info.endpoints[0].ticket).read_all()
    assert table.schema.field("s").type == pa.string()
    assert table.num_rows == 7
    assert table.column("s").to_pylist()[0] == "name-0"


def test_flight_sql_command_statement_query(client):
    """The JDBC simple-query wire shape: Any(CommandStatementQuery)."""
    cmd = any_wrap("CommandStatementQuery",
                   pb_field(1, b"select count(*) as n from t"))
    info = client.get_flight_info(fl.FlightDescriptor.for_command(cmd))
    # the ticket is Any(TicketStatementQuery) — echoed back verbatim
    name, _ = any_unwrap(info.endpoints[0].ticket.ticket)
    assert name == "TicketStatementQuery"
    table = client.do_get(info.endpoints[0].ticket).read_all()
    assert table.column("n").to_pylist() == [1000]


def test_flight_sql_prepared_statement(client):
    """JDBC executeQuery flow: CreatePreparedStatement action ->
    getFlightInfo(CommandPreparedStatementQuery) -> do_get."""
    req = any_wrap("ActionCreatePreparedStatementRequest",
                   pb_field(1, b"select g, max(v) as m from t group by g order by g"))
    results = list(client.do_action(fl.Action("CreatePreparedStatement", req)))
    name, value = any_unwrap(results[0].body.to_pybytes())
    assert name == "ActionCreatePreparedStatementResult"
    fields = pb_decode(value)
    handle = fields[1][0]
    schema = pa.ipc.read_schema(pa.BufferReader(fields[2][0]))
    assert [f.name for f in schema] == ["g", "m"]

    cmd = any_wrap("CommandPreparedStatementQuery", pb_field(1, handle))
    info = client.get_flight_info(fl.FlightDescriptor.for_command(cmd))
    table = client.do_get(info.endpoints[0].ticket).read_all()
    assert table.num_rows == 3

    client.do_action(fl.Action(
        "ClosePreparedStatement",
        any_wrap("ActionClosePreparedStatementRequest", pb_field(1, handle))))


def test_get_schema_and_errors(client):
    res = client.get_schema(fl.FlightDescriptor.for_command(
        b"select g from t"))
    assert [f.name for f in res.schema] == ["g"]
    with pytest.raises(fl.FlightError):
        info = client.get_flight_info(
            fl.FlightDescriptor.for_command(b"select nope from missing"))


def test_ddl_and_example_script(cluster, tmp_path):
    """DDL through the Flight door (JDBC clients issue CREATE/SET/SHOW
    like any statement) + the stock-client example script end-to-end."""
    import os
    import subprocess
    import sys

    import pyarrow.parquet as pq

    data = tmp_path / "nums.parquet"
    pq.write_table(pa.table({"v": pa.array(range(50), type=pa.int64())}),
                   str(data))
    client = fl.connect(f"grpc://127.0.0.1:{cluster.flight.port}")
    info = client.get_flight_info(fl.FlightDescriptor.for_command(
        f"create external table nums stored as parquet location '{data}'"
        .encode()))
    client.do_get(info.endpoints[0].ticket).read_all()
    info = client.get_flight_info(fl.FlightDescriptor.for_command(b"show tables"))
    shown = client.do_get(info.endpoints[0].ticket).read_all()
    assert "nums" in shown.column("table_name").to_pylist()
    info = client.get_flight_info(fl.FlightDescriptor.for_command(
        b"select sum(v) as s from nums"))
    assert client.do_get(info.endpoints[0].ticket).read_all() \
        .column("s").to_pylist() == [sum(range(50))]

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "examples/flight_sql_client.py",
         "127.0.0.1", str(cluster.flight.port),
         "select count(*) as n from nums"],
        capture_output=True, text=True, timeout=120, cwd=repo,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-1500:]
    assert "50" in out.stdout


def test_flight_utility_statements(cluster):
    """SHOW ALL / DESCRIBE / EXPLAIN through the Flight door."""
    client = fl.connect(f"grpc://127.0.0.1:{cluster.flight.port}")

    def run(sql):
        info = client.get_flight_info(fl.FlightDescriptor.for_command(sql))
        return client.do_get(info.endpoints[0].ticket).read_all()

    settings = run(b"show all")
    assert "ballista.shuffle.partitions" in settings.column("name").to_pylist()
    cols = run(b"show columns from t")
    assert "g" in cols.column("column_name").to_pylist()
    plan = run(b"explain select g, sum(v) s from t group by g")
    assert plan.column("plan_type").to_pylist() == [
        "logical_plan", "physical_plan"]
    assert "HashAggregateExec" in plan.column("plan").to_pylist()[1]


def _cmd(name: str, value: bytes = b"") -> fl.FlightDescriptor:
    return fl.FlightDescriptor.for_command(any_wrap(name, value))


def _fetch(client, descriptor):
    info = client.get_flight_info(descriptor)
    return client.do_get(info.endpoints[0].ticket).read_all()


def test_jdbc_connect_sequence_metadata(client):
    """The exact metadata flow the Flight SQL JDBC/ADBC drivers issue on
    connect (reference flight_sql.rs get_flight_info_sql_info/_catalogs/
    _schemas/_tables/_table_types), with the spec's fixed result schemas."""
    # 1. GetSqlInfo (no filter -> all advertised infos)
    t = _fetch(client, _cmd("CommandGetSqlInfo"))
    assert t.schema.field("info_name").type == pa.uint32()
    names = dict(zip(t.column("info_name").to_pylist(),
                     [v for v in t.column("value").to_pylist()]))
    assert names[0] == "arrow-ballista-tpu"  # FLIGHT_SQL_SERVER_NAME
    # 2. GetCatalogs / GetDbSchemas / GetTableTypes
    t = _fetch(client, _cmd("CommandGetCatalogs"))
    assert t.column("catalog_name").to_pylist() == ["ballista"]
    t = _fetch(client, _cmd("CommandGetDbSchemas"))
    assert t.column("db_schema_name").to_pylist() == ["public"]
    t = _fetch(client, _cmd("CommandGetTableTypes"))
    assert t.column("table_type").to_pylist() == ["TABLE"]
    # 3. GetTables, spec field numbers (FlightSql.proto CommandGetTables:
    # catalog=1, db_schema_filter_pattern=2, table_name_filter_pattern=3,
    # table_types=4 repeated, include_schema=5 varint) — the exact message
    # a JDBC driver sends on getTables(null, null, "t", ["TABLE"])
    body = (pb_field(3, b"t") + pb_field(4, b"TABLE")
            + b"\x28\x01")  # field 5 varint true
    t = _fetch(client, _cmd("CommandGetTables", body))
    assert "t" in t.column("table_name").to_pylist()
    blob = t.column("table_schema").to_pylist()[
        t.column("table_name").to_pylist().index("t")]
    sch = pa.ipc.read_schema(pa.BufferReader(blob))
    assert set(sch.names) == {"g", "v", "s"}
    # pattern that matches nothing; unknown table type filters everything
    t = _fetch(client, _cmd("CommandGetTables", pb_field(3, b"zz%")))
    assert t.num_rows == 0
    t = _fetch(client, _cmd("CommandGetTables", pb_field(4, b"VIEW")))
    assert t.num_rows == 0
    # include_schema=false -> no table_schema column
    t = _fetch(client, _cmd("CommandGetTables", pb_field(3, b"t")))
    assert "table_schema" not in t.schema.names
    # 4. get_schema probe (JDBC PreparedStatement.getMetaData path)
    res = client.get_schema(_cmd("CommandGetTables", b""))
    assert "table_name" in res.schema.names


def test_adbc_driver_session(cluster):
    """End-to-end with the REAL adbc_driver_flightsql wheel when present;
    this image cannot install it (zero egress), so the protocol-sequence
    test above covers the same RPC flow at the wire level."""
    pytest.importorskip("adbc_driver_flightsql")
    import adbc_driver_flightsql.dbapi as dbapi  # pragma: no cover

    with dbapi.connect(  # pragma: no cover — needs the optional wheel
            f"grpc://127.0.0.1:{cluster.flight.port}") as conn:
        with conn.cursor() as cur:
            cur.execute("select g, count(*) as n from t group by g order by g")
            rows = cur.fetchall()
            assert len(rows) == 3
            assert sum(r[1] for r in rows) == 1000


def test_like_pattern_escape_sequences():
    """SQL LIKE escapes in CommandGetTables filters: ``\\%`` / ``\\_``
    match literal chars, bare ``%`` / ``_`` stay wildcards."""
    from arrow_ballista_tpu.scheduler.flight_service import like_pattern

    assert like_pattern("t%").match("trades")
    assert like_pattern("t_").match("t2")
    assert not like_pattern("t_").match("t")
    # escaped wildcards are literals
    assert like_pattern(r"100\%").match("100%")
    assert not like_pattern(r"100\%").match("100x")
    assert like_pattern(r"a\_b").match("a_b")
    assert not like_pattern(r"a\_b").match("axb")
    # escaped backslash, then a LIVE wildcard
    assert like_pattern(r"a\\%").match("a\\anything")
    assert not like_pattern(r"a\\%").match("ab")
    # trailing lone backslash is a literal; matching stays case-insensitive
    assert like_pattern("t\\").match("t\\")
    assert like_pattern(r"T\_x").match("t_X")


def test_get_tables_like_escapes_end_to_end(client):
    """``_`` matches the one-char table name 't'; ``\\_`` must not."""
    t = _fetch(client, _cmd("CommandGetTables", pb_field(3, b"_")))
    assert "t" in t.column("table_name").to_pylist()
    t = _fetch(client, _cmd("CommandGetTables", pb_field(3, b"\\_")))
    assert t.num_rows == 0
