"""JSON + Avro readers and the get_file_metadata RPC.

Parity: reference register_json/register_avro (client context.rs:358-530)
and SchedulerGrpc.get_file_metadata (grpc.rs:271-325).  The avro codec is
home-grown (utils/avro.py) since no avro library ships in this image — the
round-trip tests double as its correctness suite.
"""
import json

import numpy as np
import pandas as pd
import pytest

from arrow_ballista_tpu.client.context import BallistaContext
from arrow_ballista_tpu.utils.avro import avro_to_arrow, read_avro, write_avro
import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

AVRO_SCHEMA = {
    "type": "record",
    "name": "row",
    "fields": [
        {"name": "k", "type": "long"},
        {"name": "v", "type": "double"},
        {"name": "s", "type": "string"},
        {"name": "maybe", "type": ["null", "long"]},
        {"name": "flag", "type": "boolean"},
    ],
}


def _rows(n=500, seed=4):
    rng = np.random.default_rng(seed)
    return [{
        "k": int(rng.integers(0, 7)),
        "v": float(rng.random()),
        "s": str(rng.choice(["x", "y", "z"])),
        "maybe": None if rng.random() < 0.2 else int(rng.integers(0, 100)),
        "flag": bool(rng.integers(0, 2)),
    } for _ in range(n)]


@pytest.mark.parametrize("codec", ["null", "deflate"])
def test_avro_roundtrip(tmp_path, codec):
    rows = _rows()
    p = tmp_path / "data.avro"
    write_avro(str(p), AVRO_SCHEMA, rows, codec=codec)
    schema, back = read_avro(str(p))
    assert schema["fields"][0]["name"] == "k"
    assert back == rows


def test_avro_to_arrow_types(tmp_path):
    rows = _rows(50)
    p = tmp_path / "data.avro"
    write_avro(str(p), AVRO_SCHEMA, rows)
    t = avro_to_arrow(str(p))
    assert t.num_rows == 50
    assert str(t.schema.field("k").type) == "int64"
    assert str(t.schema.field("v").type) == "double"
    assert t.column("maybe").null_count == sum(1 for r in rows if r["maybe"] is None)


def test_register_avro_sql(tmp_path):
    rows = _rows(2000)
    write_avro(str(tmp_path / "a.avro"), AVRO_SCHEMA, rows, codec="deflate")
    ctx = BallistaContext.local()
    try:
        ctx.register_avro("t", str(tmp_path / "a.avro"))
        got = ctx.sql("SELECT k, COUNT(*) AS c, SUM(v) AS sv FROM t "
                      "GROUP BY k ORDER BY k").to_pandas()
    finally:
        ctx.shutdown()
    df = pd.DataFrame(rows)
    want = df.groupby("k", as_index=False).agg(c=("v", "size"), sv=("v", "sum"))
    assert got["c"].tolist() == want["c"].tolist()
    np.testing.assert_allclose(got["sv"], want["sv"], rtol=1e-9)


def test_register_json_sql(tmp_path):
    rng = np.random.default_rng(9)
    rows = [{"g": int(rng.integers(0, 4)), "x": float(rng.random())}
            for _ in range(1500)]
    p = tmp_path / "data.json"
    p.write_text("\n".join(json.dumps(r) for r in rows))
    ctx = BallistaContext.local()
    try:
        ctx.register_json("j", str(p))
        got = ctx.sql("SELECT g, SUM(x) AS sx FROM j GROUP BY g ORDER BY g").to_pandas()
    finally:
        ctx.shutdown()
    want = pd.DataFrame(rows).groupby("g", as_index=False).agg(sx=("x", "sum"))
    np.testing.assert_allclose(got["sx"], want["sx"], rtol=1e-9)


def test_get_file_metadata_rpc(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    from arrow_ballista_tpu.net import wire
    from arrow_ballista_tpu.scheduler.netservice import SchedulerNetService

    pq.write_table(pa.table({"a": [1, 2], "b": ["x", "y"]}),
                   str(tmp_path / "f.parquet"))
    write_avro(str(tmp_path / "f.avro"), AVRO_SCHEMA, _rows(5))
    sched = SchedulerNetService("127.0.0.1", 0, rest_port=None)
    sched.start()
    try:
        out, _ = wire.call("127.0.0.1", sched.port, "get_file_metadata",
                           {"path": str(tmp_path / "f.parquet")})
        assert out["format"] == "parquet"
        assert [f["name"] for f in out["schema"]] == ["a", "b"]
        out, _ = wire.call("127.0.0.1", sched.port, "get_file_metadata",
                           {"path": str(tmp_path / "f.avro")})
        assert out["format"] == "avro"
        assert [f["name"] for f in out["schema"]][:2] == ["k", "v"]
    finally:
        sched.stop()


def test_avro_through_remote_context(tmp_path):
    from arrow_ballista_tpu.executor.server import ExecutorServer
    from arrow_ballista_tpu.scheduler.netservice import SchedulerNetService

    rows = _rows(800)
    write_avro(str(tmp_path / "t.avro"), AVRO_SCHEMA, rows)
    sched = SchedulerNetService("127.0.0.1", 0, rest_port=None)
    sched.start()
    ex = ExecutorServer("127.0.0.1", sched.port, "127.0.0.1", 0,
                        work_dir=str(tmp_path / "w"))
    ex.start()
    try:
        ctx = BallistaContext.remote("127.0.0.1", sched.port)
        ctx.register_avro("t", str(tmp_path / "t.avro"))
        got = ctx.sql("SELECT COUNT(*) AS c FROM t WHERE flag").to_pandas()
        ctx.shutdown()
        assert got["c"].tolist() == [sum(1 for r in rows if r["flag"])]
    finally:
        ex.stop(notify=False)
        sched.stop()


def test_nyctaxi_benchmark_harness(tmp_path):
    """The nyctaxi harness (reference benchmarks/src/bin/nyctaxi.rs) runs
    end to end: synthesize tripdata, run fare_amt_by_passenger."""
    import json
    import subprocess
    import sys

    env = {**__import__("os").environ, "JAX_PLATFORMS": "cpu"}
    gen = subprocess.run(
        [sys.executable, "-m", "benchmarks.nyctaxi", "generate",
         "--rows", "20000", "--output", str(tmp_path)],
        capture_output=True, text=True, timeout=300, cwd=REPO_ROOT, env=env)
    assert gen.returncode == 0, gen.stderr[-1500:]
    run = subprocess.run(
        [sys.executable, "-m", "benchmarks.nyctaxi", "benchmark",
         "--path", str(tmp_path), "--iterations", "1"],
        capture_output=True, text=True, timeout=600, cwd=REPO_ROOT, env=env)
    assert run.returncode == 0, run.stderr[-1500:]
    out = json.loads(run.stdout.strip().splitlines()[-1])
    assert out["results"]["fare_amt_by_passenger"]["min_ms"] > 0
