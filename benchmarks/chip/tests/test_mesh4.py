"""The cell ``sf10_mesh4_scanagg`` rehearsed whole on the CPU (harness,
files, comparison, control; data that small has one scan partition, so the
mesh itself is covered by ``tests/test_mesh4.py``), the deployment's own
check driven with a job that ran a mesh operator and one that did not, and
the two new readers on made-up evidence."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks.chip import control, run
from benchmarks.chip.deployments import mesh4
from benchmarks.chip.readers import mesh_agg_roofline, mesh_spans

CELL = "sf10_mesh4_scanagg"
ROOT = run.ROOT


def test_the_cell_rehearses_correct_and_says_what_the_check_found():
    """In a process of its own: the cell asks for four devices, which the
    CPU has only where XLA is told so before jax starts."""
    captured = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELL,
         "--seed", str(2 ** 31 + 2801), "--seconds", "0.5", "--trace", "1",
         "--allow-cpu", "--scale", "0.02"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert captured.returncode == 0, captured.stderr[-3000:]
    out = json.loads(captured.stdout.strip().splitlines()[-1])
    assert out["correct"] and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"       # never read as a chip
    # no mesh operator at this size: the check says so and does not fail
    found = [line for line in captured.stderr.splitlines()
             if line.startswith("[mesh4] ")]
    assert len(found) == 1 and " 0 of them ran a mesh operator" in found[0]
    # the two span and trace readers find nothing and report nothing
    assert "mesh_program_s_per_query" not in out["metrics"]
    assert "mesh_agg_hbm_roofline" not in out["metrics"]
    assert out["metrics"]["mesh_reshard_mbytes_per_query"]["value"] == 0


@pytest.mark.parametrize("seed", [31, 2 ** 31 + 33])
def test_control_in_float32_is_not_correct(seed):
    v = control.control_verdict(CELL, seed, scale=0.02)
    assert not v["correct"]
    assert v["numbers"]["answers_wrong"]["value"] >= 1


def _stats(**operators):
    return {"stages": [{"operators": operators}]}


MESH_JOB = _stats(**{"0.0.0:MeshAggregateExec": {"mesh_devices": 4.0,
                                                 "output_rows": 4.0},
                     "0:SortExec": {"output_rows": 4.0}})
FILE_JOB = _stats(**{"0:HashAggregateExec": {"output_rows": 4.0}})


def test_the_check_tells_a_mesh_job_from_one_that_left_the_mesh():
    assert mesh4.mesh_devices_of(MESH_JOB) == 4
    assert mesh4.mesh_devices_of(FILE_JOB) == 0
    assert mesh4.mesh_devices_of(None) == 0
    ok, found = mesh4.check_mesh(2, [MESH_JOB, FILE_JOB, MESH_JOB, FILE_JOB],
                                 devices=4, programs=2)
    assert ok, found
    # a grouped statement whose job left the mesh
    ok, found = mesh4.check_mesh(2, [MESH_JOB, FILE_JOB, FILE_JOB, FILE_JOB],
                                 devices=4, programs=2)
    assert not ok and "1 of them ran a mesh operator over 4" in found
    # an operator that says mesh and a program that dispatched none
    ok, found = mesh4.check_mesh(2, [MESH_JOB, FILE_JOB, MESH_JOB, FILE_JOB],
                                 devices=4, programs=1)
    assert not ok and "1 mesh programs dispatched" in found
    # a mesh of two devices is not the configuration's
    two = _stats(**{"0:MeshAggregateExec": {"mesh_devices": 2.0}})
    assert not mesh4.check_mesh(1, [two], devices=4, programs=1)[0]
    # nothing grouped served is no pass either
    assert not mesh4.check_mesh(0, [FILE_JOB], devices=4, programs=0)[0]


class _Scheduler:
    class metrics:
        @staticmethod
        def record_submitted(*a):
            pass


def _deployment(monkeypatch, platform: str, stats):
    """A mesh4 deployment that served one grouped statement in one job with
    ``stats``, on a device of ``platform``, built without a context; the
    program counts one mesh program where the job ran a mesh operator."""
    import jax
    from arrow_ballista_tpu.obs import device

    class Stub(mesh4.Deployment):
        def build(self):
            self.scheduler = _Scheduler()
            self.ctx = type("Ctx", (), {"shutdown": lambda s: closed.append(1),
                                        "sql": lambda s, text: text})()

        def job_stats(self, job_id):
            return stats

    closed = []
    dev = type("Dev", (), {"platform": platform})()
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    d = Stub({"settings": {}, "task_slots": 4, "executors": 1,
              "mesh": {"axis": "part", "devices": 4}}, "", [])
    assert d.session().sql("select a from t group by a") is not None
    d.session().sql("select sum(a) from t")
    assert d.grouped_statements == 1
    d.submitted.append({"job_id": "j1"})
    if mesh4.mesh_devices_of(stats):
        device.record_mesh_program(0)
    return d, closed


def test_close_fails_the_run_on_a_tpu_and_only_reports_on_a_cpu(
        monkeypatch, capsys):
    d, closed = _deployment(monkeypatch, "tpu", FILE_JOB)
    with pytest.raises(SystemExit, match="did not run on the mesh"):
        d.close()
    assert closed == [1]            # the context was shut down all the same
    d, closed = _deployment(monkeypatch, "cpu", FILE_JOB)
    d.close()
    assert "0 of them ran a mesh operator" in capsys.readouterr().err
    d, closed = _deployment(monkeypatch, "tpu", MESH_JOB)
    d.close()
    assert closed == [1]


def test_a_program_without_the_counter_is_refused_before_it_is_built(
        monkeypatch):
    """PR 28's parent: no ``mesh_programs`` in ``obs.device.STATS``, and a
    kernel whose q1 sums are wrong on the chip on some seeds."""
    from arrow_ballista_tpu.obs import device

    built = []

    class Stub(mesh4.Deployment):
        def build(self):
            built.append(1)

    older = {k: v for k, v in device.STATS.snapshot().items()
             if not k.startswith("mesh_")}
    monkeypatch.setattr(device.STATS, "snapshot", lambda: dict(older))
    with pytest.raises(SystemExit, match="keeps no mesh_programs counter"):
        Stub({"settings": {}, "task_slots": 4, "executors": 1,
              "mesh": {"axis": "part", "devices": 4}}, "", [])
    assert not built


def _trace(**over):
    base = {"simulated_device": False, "query_shares": {"q1": 4.0, "q6": 4.0},
            "devices": 4, "busy_s": 1.0, "window_s": 2.0,
            "device_ops": [["jit_mesh_agg_dense__k2", 2.56],
                           ["jit_batch_concat", 0.1]]}
    return {**base, **over}


def test_mesh_agg_roofline_counts_bytes_once_and_seconds_on_every_device():
    evidence = {"trace": _trace(), "peaks": {"hbm_bytes_per_s": 819e9},
                "queries": {"q1": {"columns": {"lineitem": [
                    "l_quantity", "l_extendedprice", "l_discount", "l_tax",
                    "l_returnflag", "l_linestatus", "l_shipdate"]}}},
                "cardinalities": {"lineitem": 60_004_710}}
    args = {"query": "q1", "program": "jit_mesh_agg_dense"}
    value = mesh_agg_roofline.read(evidence, **args)
    # 44 B a row, four q1 in the window, over one chip's rate, against the
    # program's 2.56 device-seconds summed over the four planes
    assert value == pytest.approx(
        100 * 4 * 60_004_710 * 44 / 819e9 / 2.56)
    assert 0 < value < 100
    for nothing in ({"trace": None}, {"peaks": None},
                    {"trace": _trace(simulated_device=True)},
                    {"trace": _trace(device_ops=[["jit_batch_concat", 1.0]])},
                    {"trace": _trace(query_shares={"q6": 4.0})}):
        assert mesh_agg_roofline.read({**evidence, **nothing}, **args) is None


def test_mesh_spans_sums_the_spans_under_the_windows_tasks():
    from arrow_ballista_tpu.obs.tracing import RING, ROOT, span

    RING.clear()
    made = {}
    for job in ("in-window", "warm-up"):
        with span(f"task {job}/1/0", "executor", ROOT, job_id=job):
            with span("MeshAggregateExec", "operator"):
                with span("mesh_reshard", "device", bytes=8, devices=4,
                          rows=2) as reshard:
                    pass
                with span("mesh_program", "device", program="p",
                          collective="dense_reduce") as program:
                    pass
        made[job] = (reshard, program)
    reshard, program = made["in-window"]
    evidence = {"jobs": [{"job_id": "in-window"}],
                "window": {"completed": 2}}
    t = mesh_spans.seconds(evidence)
    assert t["tasks"] == 1 and t["mesh_program_n"] == 1
    assert t["mesh_program"] == program.end_ns - program.start_ns
    assert t["mesh_reshard"] == reshard.end_ns - reshard.start_ns
    assert mesh_spans.read(dict(evidence), span="mesh_program") == \
        t["mesh_program"] / 2 / 1e9
    # a window whose tasks ran no mesh operator reports nothing, never 0
    with span("task file/1/0", "executor", ROOT, job_id="file"):
        pass
    assert mesh_spans.read({"jobs": [{"job_id": "file"}],
                            "window": {"completed": 1}},
                           span="mesh_program") is None
    RING.clear()
