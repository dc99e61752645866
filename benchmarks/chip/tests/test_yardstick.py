"""The yardstick's own arithmetic: the trace reduction on a synthetic trace,
the byte counts behind the roofline share, the generator's fixed
cardinalities, and windows that close at pass boundaries."""
import threading
import time

import numpy as np
import pytest

from benchmarks.chip import datagen, trace_reduce, traffic, work

MS = 1e-3
TABLES = ["customer", "lineitem", "orders"]


def _raw(ops, programs, spans):
    return trace_reduce.RawTrace(
        ops={"/device:TPU:0": [(a * MS, b * MS, n) for a, b, n in ops]},
        programs={"/device:TPU:0": [(a * MS, b * MS, n)
                                    for a, b, n in programs]},
        host_spans=[(a * MS, b * MS, n) for a, b, n in spans])


def test_reduce_unions_overlapping_ops_and_attributes_gaps():
    # four task threads feed one chip: their ops overlap, busy counts once.
    # window 0..100 ms; q1 0..40, q6 60..100; idle 30..40 inside q1,
    # 40..60 between queries, 90..100 inside q6
    ops = [(0, 10, "fusion.1"), (5, 20, "fusion.2"), (8, 30, "sort.3"),
           (12, 18, "fusion.4"), (60, 90, "fusion.5")]
    programs = [(0, 30, "jit_fused_agg"), (60, 90, "jit_pack_for_host")]
    spans = [(0, 100, trace_reduce.WINDOW_SPAN), (0, 40, "in q1"),
             (60, 100, "in q6")]
    r = trace_reduce.reduce(_raw(ops, programs, spans))
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.060)          # 0..30 and 60..90
    assert dict(r["device_ops"]) == pytest.approx(
        {"jit_fused_agg": 0.030, "jit_pack_for_host": 0.030})
    assert dict(r["idle_gaps"]) == pytest.approx({
        "in q1 after jit_fused_agg": 0.010,
        "between queries after jit_fused_agg": 0.020,
        "in q6 after jit_pack_for_host": 0.010})
    assert r["query_shares"] == pytest.approx({"q1": 1.0, "q6": 1.0})


def test_reduce_clips_to_the_window_and_counts_cut_queries_by_share():
    ops = [(-10, 10, "fusion.1"), (90, 120, "fusion.2")]
    spans = [(0, 100, trace_reduce.WINDOW_SPAN)]
    # the harness's own records, on a host clock that reads 5.0 s when the
    # window span opens: q1 lies inside, q6 is half outside
    records = [(5.000, 5.050, "q1"), (5.080, 5.120, "q6")]
    r = trace_reduce.reduce(_raw(ops, [], spans), host_lo=5.0,
                            query_records=records)
    assert r["busy_s"] == pytest.approx(0.020)
    assert r["query_shares"] == pytest.approx({"q1": 1.0, "q6": 0.5})


def test_reduce_returns_nothing_without_device_ops_or_window():
    assert trace_reduce.reduce(_raw([], [], [(0, 1, "traced window")])) \
        is None           # a device plane with no operation in it
    assert trace_reduce.reduce(trace_reduce.RawTrace()) is None
    assert trace_reduce.reduce(_raw([(0, 1, "f")], [], [])) is None


def test_extract_reads_a_tpu_shaped_xplane():
    from jax.profiler import ProfileData

    text = """
    planes { id: 1 name: "/device:TPU:0"
      event_metadata { key: 1 value { id: 1 name: "fusion.7" } }
      event_metadata { key: 2 value { id: 2 name: "jit_fused_agg(123456)" } }
      lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
        events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 } }
      lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
        events { metadata_id: 2 offset_ps: 0 duration_ps: 6000000 } }
      lines { id: 3 name: "Steps" timestamp_ns: 1000
        events { metadata_id: 2 offset_ps: 0 duration_ps: 9000000 } } }
    planes { id: 2 name: "/host:CPU"
      event_metadata { key: 1 value { id: 1 name: "traced window" } }
      event_metadata { key: 2 value { id: 2 name: "in q1" } }
      lines { id: 7 name: "main" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
      lines { id: 8 name: "chipbench-stream-0" timestamp_ns: 1000
        events { metadata_id: 2 offset_ps: 0 duration_ps: 8000000 } } }
    """
    blob = ProfileData.text_proto_to_serialized_xspace(text)
    raw = trace_reduce.extract(ProfileData.from_serialized_xspace(blob))
    assert not raw.simulated_device
    assert [n for _, _, n in raw.programs["/device:TPU:0"]] == \
        ["jit_fused_agg"]
    r = trace_reduce.reduce(raw)
    assert r["window_s"] == pytest.approx(10e-6)
    assert r["busy_s"] == pytest.approx(3e-6)       # "Steps" is not an op
    assert r["query_shares"] == {"q1": 1.0}


def test_q1_and_q3_bytes_at_sf1():
    cards = datagen.cardinalities(1.0, TABLES)
    assert cards == {"lineitem": 5999584, "orders": 1500000,
                     "customer": 150000}
    q1 = traffic.load_query("q1")["columns"]
    q3 = traffic.load_query("q3")["columns"]
    assert work.row_bytes("lineitem", q1["lineitem"]) == 44
    assert work.query_bytes(q1, cards) == 5999584 * 44          # 264 MB
    assert work.query_bytes(q3, cards) == \
        5999584 * 28 + 1500000 * 24 + 150000 * 12               # 206 MB


def test_two_seeds_give_equal_row_counts_and_different_values():
    a = datagen.generate_tables(0.01, 1, TABLES)
    b = datagen.generate_tables(0.01, 2 ** 31 + 5, TABLES)
    for t in a:
        assert a[t].num_rows == b[t].num_rows == \
            datagen.cardinalities(0.01, TABLES)[t]
        assert a[t].schema == b[t].schema
    assert not np.array_equal(a["lineitem"]["l_quantity"].to_numpy(),
                              b["lineitem"]["l_quantity"].to_numpy())
    assert a["lineitem"]["l_orderkey"].equals(b["lineitem"]["l_orderkey"])
    for t in TABLES:     # seed -> same data, whatever is made beside it
        assert datagen.generate_tables(0.01, 1, [t])[t].equals(a[t])


def test_a_table_without_a_generator_file_is_refused():
    with pytest.raises(SystemExit, match="tables/part.py"):
        datagen.cardinalities(1.0, ["part"])


def test_a_deployment_without_a_file_is_refused():
    from benchmarks.chip import deploy

    with pytest.raises(SystemExit, match="deployments/mesh4.py"):
        deploy.deploy({"deployment": "mesh4"}, "", [])


def test_data_is_made_anew_in_place_of_what_a_run_left(tmp_path):
    import os

    ddir = str(tmp_path / "cell")
    datagen.write_data(ddir, 0.01, 1, ["customer", "orders"])
    datagen.write_data(ddir, 0.01, 2, ["customer"])
    assert os.listdir(ddir) == ["customer.parquet"]     # nothing is kept


class _SleepyContext:
    """Answers any SQL after a fixed sleep."""

    def __init__(self, seconds):
        self.seconds = seconds

    def sql(self, text):
        return self

    def to_arrow(self):
        time.sleep(self.seconds)
        return "answer"


def test_window_closes_at_the_first_pass_boundary_after_seconds():
    import contextlib

    queries = [{"name": "a", "sql": ""}, {"name": "b", "sql": ""},
               {"name": "c", "sql": ""}]
    gate = threading.Event()
    logs, threads = traffic.run_window(
        [_SleepyContext(0.02), _SleepyContext(0.031)], queries, 0.1,
        lambda name: contextlib.nullcontext(), gate)
    gate.set()
    for t in threads:
        t.join()
    for log in logs.values():
        assert len(log.records) == 3 * log.passes       # whole passes only
        span = log.closed - log.opened
        assert span >= 0.1
        # the pass before the last ended before the window's length
        assert log.records[-4].t1 - log.opened < 0.1
        assert [r.query for r in log.records[:3]] == ["a", "b", "c"]
    e2e = traffic.end_to_end(logs)
    assert e2e["failed"] == 0
    assert e2e["query_s"] == pytest.approx(
        e2e["stream_seconds"] / e2e["completed"])


def test_benchmark_json_names_only_files_that_exist():
    import json
    import os

    from benchmarks.chip import run

    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    here = run.HERE
    for w in bench["workloads"]:
        spec = run.load_cell(w["name"])         # config, mix and queries load
        for q in spec["queries"]:
            assert os.path.exists(os.path.join(here, "oracles",
                                               f"{q['name']}.py"))
            for table, cols in q["columns"].items():
                assert set(cols) <= set(datagen.columns(table))
        assert {m["name"] for m in spec["end_to_end"]} >= {"setup_s",
                                                            "query_s"}
    for m in bench["per_layer"]:
        with open(os.path.join(here, "layer_metrics",
                               f"{m['name']}.json")) as fh:
            reader = json.load(fh)["reader"]
        assert os.path.exists(os.path.join(here, "readers", f"{reader}.py"))
    for c in bench["configs"]:
        with open(os.path.join(run.ROOT, c["file"])) as fh:
            conf = json.load(fh)
        assert conf["source"] == c["source"] and conf["name"] == c["name"]
