"""Deployment entry points as REAL processes: scheduler_daemon +
executor_daemon subprocesses, remote client over the wire, SIGTERM drain.

This is the path docker-compose/helm run (reference scheduler_process.rs /
executor_process.rs); everything else in the suite exercises the same
machinery in-process."""
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pyarrow as pa
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn(mod, *args, log_dir=None, env_extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    # daemon output goes to a FILE, never a PIPE: an undrained pipe fills
    # at ~64KB and blocks the daemon mid-log (observed: the scheduler froze
    # and stopped accepting connections); proc._log_path is read back for
    # failure messages
    import tempfile

    log = tempfile.NamedTemporaryFile(
        mode="w", dir=log_dir, prefix=f"{mod.rsplit('.', 1)[-1]}-",
        suffix=".log", delete=False)
    proc = subprocess.Popen(
        [sys.executable, "-m", mod, *args], cwd=REPO, env=env,
        stdout=log, stderr=subprocess.STDOUT, text=True)
    proc._log_path = log.name
    return proc


def _log_tail(proc, n=2000):
    try:
        with open(proc._log_path) as f:
            return f.read()[-n:]
    except OSError:
        return "<no log>"


def _wait_ping(port, deadline_s=60):
    from arrow_ballista_tpu.net import wire

    deadline = time.monotonic() + deadline_s
    while True:
        try:
            wire.call("127.0.0.1", port, "ping", timeout=2.0)
            return
        except Exception:  # noqa: BLE001
            if time.monotonic() > deadline:
                raise
            time.sleep(0.3)


def test_daemons_end_to_end(tmp_path):
    port = _free_port()
    rest = _free_port()
    sched = _spawn("arrow_ballista_tpu.scheduler_daemon",
                   "--bind-host", "127.0.0.1", "--bind-port", str(port),
                   "--rest-port", str(rest),
                   "--state-dir", str(tmp_path / "state"),
                   log_dir=str(tmp_path))
    ex = None
    try:
        _wait_ping(port)
        ex = _spawn("arrow_ballista_tpu.executor_daemon",
                    "--scheduler-port", str(port),
                    "--work-dir", str(tmp_path / "work"),
                    log_dir=str(tmp_path))

        from arrow_ballista_tpu.client.context import BallistaContext
        from arrow_ballista_tpu.utils.config import BallistaConfig

        ctx = BallistaContext.remote("127.0.0.1", port, BallistaConfig(
            {"ballista.shuffle.partitions": "2",
             "ballista.job.timeout.seconds": "120"}))
        rng = np.random.default_rng(1)
        ctx.register_table("t", pa.table({
            "g": pa.array(rng.integers(0, 5, 5000).astype(np.int64)),
            "v": pa.array(rng.integers(0, 100, 5000).astype(np.int64))}))
        # executor registration is async — retry until slots exist
        deadline = time.monotonic() + 60
        while True:
            try:
                out = ctx.sql("select g, sum(v) s, count(*) n from t "
                              "group by g order by g").to_pandas()
                break
            except Exception:  # noqa: BLE001
                if time.monotonic() > deadline:
                    raise
                time.sleep(1)
        assert len(out) == 5 and out.n.sum() == 5000

        # web ui + api live on the daemon's rest port
        import json
        import urllib.request

        jobs = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{rest}/api/jobs", timeout=10))
        assert any(j["state"] == "successful" for j in jobs)

        ctx.shutdown()
    finally:
        for proc, name in ((ex, "executor"), (sched, "scheduler")):
            if proc is None:
                continue
            proc.send_signal(signal.SIGTERM)
            try:
                # generous: the suite shares one CPU core and a graceful
                # drain competes with every other test's work
                rc = proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                proc.kill()
                raise AssertionError(
                    f"{name} did not exit on SIGTERM\n{_log_tail(proc)}")
            assert rc == 0, f"{name} exited rc={rc}\n{_log_tail(proc)}"


def test_multihost_hybrid_exchange_real_processes(tmp_path):
    """VERDICT item: the hybrid exchange (mesh WITHIN a host, file shuffle
    ACROSS hosts) in REAL processes — 2 executor daemons, each a virtual
    4-device 'host', results bit-identical to the plain file path."""
    port = _free_port()
    sched = _spawn("arrow_ballista_tpu.scheduler_daemon",
                   "--bind-host", "127.0.0.1", "--bind-port", str(port),
                   "--rest-port", "-1",
                   "--state-dir", str(tmp_path / "state"),
                   log_dir=str(tmp_path))
    exes = []
    try:
        _wait_ping(port)
        for i in range(2):
            exes.append(_spawn(
                "arrow_ballista_tpu.executor_daemon",
                "--scheduler-port", str(port),
                "--work-dir", str(tmp_path / f"work{i}"),
                "--concurrent-tasks", "2", log_dir=str(tmp_path),
                env_extra={
                    "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}))

        from arrow_ballista_tpu.client.context import BallistaContext
        from arrow_ballista_tpu.utils.config import BallistaConfig

        rng = np.random.default_rng(5)
        n = 20_000
        tbl = pa.table({
            "g": pa.array(rng.integers(0, 50, n).astype(np.int64)),
            "k": pa.array(rng.integers(0, 200, n).astype(np.int64)),
            "v": pa.array(rng.integers(0, 1000, n).astype(np.int64))})
        dim = pa.table({
            "k": pa.array(np.arange(200, dtype=np.int64)),
            "w": pa.array(rng.integers(0, 9, 200).astype(np.int64))})

        def run(settings):
            ctx = BallistaContext.remote("127.0.0.1", port, BallistaConfig({
                "ballista.shuffle.partitions": "4",
                "ballista.job.timeout.seconds": "180", **settings}))
            ctx.register_table("t", tbl)
            ctx.register_table("d", dim)
            deadline = time.monotonic() + 90
            while True:  # executors register async
                try:
                    agg = ctx.sql("select g, sum(v) s, count(*) c from t "
                                  "group by g order by g").to_pandas()
                    break
                except Exception:  # noqa: BLE001
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(1)
            join = ctx.sql(
                "select d.w as w, sum(t.v) s from t join d on t.k = d.k "
                "group by d.w order by w").to_pandas()
            ctx.shutdown()
            return agg, join

        plain_agg, plain_join = run({})
        hyb_agg, hyb_join = run({"ballista.shuffle.mesh": "true",
                                 "ballista.shuffle.mesh.hybrid": "true"})
        assert plain_agg.equals(hyb_agg)
        assert plain_join.equals(hyb_join)
    finally:
        for proc in exes + [sched]:
            proc.send_signal(signal.SIGTERM)
        for proc in exes + [sched]:
            try:
                proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                proc.kill()
