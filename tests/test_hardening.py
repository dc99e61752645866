"""Round-2 hardening: reliable status reporting, atomic lock takeover,
heartbeat auto re-registration, 64-bit data frames, data-plane auth.

Reference parity: executor_server.rs status batching/retry, grpc.rs:174-241
heartbeat re-register, cluster/storage lock semantics, flight_service.rs
bearer-token auth.
"""
import json
import os
import socket
import threading
import time

import pytest

from arrow_ballista_tpu.net import wire
from arrow_ballista_tpu.scheduler.persistence import FileJobStateBackend
from arrow_ballista_tpu.scheduler.types import (
    ExecutorHeartbeat,
    ExecutorMetadata,
    TaskId,
    TaskStatus,
)


# --------------------------------------------------------------------------
# wire framing
# --------------------------------------------------------------------------


def test_wire_header_is_64bit():
    # a 6 GiB binary length must survive header round-trip (u32 truncated it)
    big = 6 << 30
    hdr = wire._HDR.pack(10, big)
    jlen, blen = wire._HDR.unpack(hdr)
    assert jlen == 10 and blen == big
    assert wire.MAX_BIN > (4 << 30)


def test_wire_roundtrip_with_binary():
    a, b = socket.socketpair()
    try:
        payload = os.urandom(1 << 16)
        wire.send_frame(a, {"method": "x"}, payload)
        obj, binary = wire.recv_frame(b)
        assert obj == {"method": "x"} and binary == payload
    finally:
        a.close()
        b.close()


# --------------------------------------------------------------------------
# push-mode status reporting survives scheduler outages
# --------------------------------------------------------------------------


class _FlakyScheduler:
    def __init__(self, fail_times: int):
        self.fail_times = fail_times
        self.got = []
        self.lock = threading.Lock()

    def update_task_status(self, executor_id, statuses):
        with self.lock:
            if self.fail_times > 0:
                self.fail_times -= 1
                raise ConnectionError("scheduler briefly unreachable")
            self.got.extend(statuses)

    def heartbeat(self, *a, **k):
        pass

    def register_executor(self, *a, **k):
        pass

    def executor_stopped(self, *a, **k):
        pass


def test_push_status_retries_until_delivered(tmp_path):
    from arrow_ballista_tpu.executor.server import ExecutorServer

    srv = ExecutorServer("127.0.0.1", 1, port=0, work_dir=str(tmp_path),
                         policy="push")
    flaky = _FlakyScheduler(fail_times=2)
    srv.scheduler = flaky
    srv.start(register=False)
    try:
        st = TaskStatus(TaskId("jobz", 1, 0), srv.metadata.executor_id, "success")
        srv._report_status(st)
        deadline = time.time() + 15
        while not flaky.got and time.time() < deadline:
            time.sleep(0.05)
        assert flaky.got and flaky.got[0].task.job_id == "jobz"
        assert flaky.fail_times == 0  # the transient failures actually happened
    finally:
        srv.stop(notify=False)


# --------------------------------------------------------------------------
# stale-lock takeover is atomic
# --------------------------------------------------------------------------


def test_stale_lock_single_winner(tmp_path):
    backend = FileJobStateBackend(str(tmp_path))
    lock = os.path.join(str(tmp_path), "jobr.lock")
    with open(lock, "w") as f:
        json.dump({"owner": "dead-scheduler", "ts": time.time() - 3600}, f)

    results = {}
    barrier = threading.Barrier(8)

    def contend(i):
        barrier.wait()
        results[i] = backend.try_acquire_job("jobr", f"sched-{i}",
                                             stale_after_s=60.0)

    threads = [threading.Thread(target=contend, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sum(1 for ok in results.values() if ok) == 1
    # the winner's lock is in place and fresh
    with open(lock) as f:
        holder = json.load(f)
    winner = [i for i, ok in results.items() if ok][0]
    assert holder["owner"] == f"sched-{winner}"


def test_fresh_lock_not_stolen(tmp_path):
    backend = FileJobStateBackend(str(tmp_path))
    assert backend.try_acquire_job("jobf", "sched-a")
    assert not backend.try_acquire_job("jobf", "sched-b")
    assert backend.try_acquire_job("jobf", "sched-a")  # reentrant for owner


# --------------------------------------------------------------------------
# heartbeat auto re-registration
# --------------------------------------------------------------------------


def test_heartbeat_reregisters_unknown_executor():
    from arrow_ballista_tpu.scheduler.scheduler import SchedulerServer, TaskLauncher

    class NullLauncher(TaskLauncher):
        def launch_tasks(self, executor_id, tasks):
            pass

        def cancel_tasks(self, executor_id, job_id):
            pass

        def stop(self):
            pass

    server = SchedulerServer(NullLauncher())
    server.init(start_reaper=False)
    try:
        meta = ExecutorMetadata("exec-zombie", host="h1", port=7000, task_slots=2)
        # no registration — straight to heartbeat, as after a scheduler restart
        server.heartbeat(ExecutorHeartbeat("exec-zombie", metadata=meta))
        got = server.cluster.get_executor("exec-zombie")
        assert got is not None and got.host == "h1" and got.task_slots == 2
        # terminating executors are not reaped while still heartbeating
        server.heartbeat(ExecutorHeartbeat("exec-zombie", status="terminating",
                                           metadata=meta))
        assert "exec-zombie" not in server.cluster.expired_executors(60.0)
        assert "exec-zombie" not in server.cluster.alive_executors(60.0)
    finally:
        server.shutdown()


# --------------------------------------------------------------------------
# data-plane auth token
# --------------------------------------------------------------------------


def test_data_plane_token(tmp_path, monkeypatch):
    monkeypatch.setenv("BALLISTA_DATA_PLANE_TOKEN", "sekrit")
    import numpy as np

    from arrow_ballista_tpu.executor.server import ExecutorServer
    from arrow_ballista_tpu.models.batch import ColumnBatch
    from arrow_ballista_tpu.models.ipc import write_ipc_file
    from arrow_ballista_tpu.models.schema import Field, INT64, Schema
    from arrow_ballista_tpu.utils.errors import ExecutionError

    srv = ExecutorServer("127.0.0.1", 1, port=0, work_dir=str(tmp_path),
                         policy="push")
    try:
        p = tmp_path / "jobt" / "f.arrow"
        p.parent.mkdir(parents=True)
        schema = Schema([Field("v", INT64)])
        write_ipc_file(ColumnBatch.from_numpy(
            schema, {"v": np.arange(4, dtype=np.int64)}), str(p))
        frames = []

        def send(obj, chunk):
            frames.append((obj, chunk))

        with pytest.raises(ExecutionError):
            srv._fetch_partition_stream({"path": str(p)}, b"", send)
        with pytest.raises(ExecutionError):
            srv._fetch_partition_stream(
                {"path": str(p), "token": "wrong"}, b"", send)
        assert frames == []
        srv._fetch_partition_stream(
            {"path": str(p), "token": "sekrit"}, b"", send)
        assert frames[0][0]["payload"]["rows"] == 4 and frames[0][1]
        assert frames[-1][0]["payload"]["eos"]
    finally:
        srv.stop(notify=False)


# --------------------------------------------------------------------------
# a launch payload is the grouped shape or an error: never guessed at
# --------------------------------------------------------------------------


def test_launch_without_stages_is_an_error_and_starts_no_task(tmp_path):
    from arrow_ballista_tpu.executor.server import ExecutorServer
    from arrow_ballista_tpu.net import wire

    srv = ExecutorServer("127.0.0.1", 1, port=0, work_dir=str(tmp_path))
    srv.rpc.start()
    try:
        flat = {"tasks": [{"task": {}, "plan": {}, "internal_id": 1,
                           "scalars": {}}]}
        with pytest.raises(wire.RemoteError, match="no 'stages'"):
            wire.call("127.0.0.1", srv.rpc.port, "launch_multi_task", flat)
        assert srv.executor.active_tasks() == 0
        payload, _ = wire.call("127.0.0.1", srv.rpc.port,
                               "launch_multi_task", {"stages": []})
        assert payload == {"accepted": 0}
    finally:
        srv.stop(notify=False)


# --------------------------------------------------------------------------
# bounded-concurrency remote shuffle fetch
# --------------------------------------------------------------------------


def test_concurrent_remote_fetch(tmp_path):
    """Many remote locations fetch in parallel (reference: <=50 concurrent
    Flight fetches, shuffle_reader.rs:123) and results stay correct."""
    import numpy as np
    import pyarrow as pa

    from arrow_ballista_tpu.models.batch import ColumnBatch
    from arrow_ballista_tpu.models.ipc import write_ipc_file
    from arrow_ballista_tpu.models.schema import Field, INT64, Schema
    from arrow_ballista_tpu.net.dataplane import stream_partition
    from arrow_ballista_tpu.net.rpc import RpcServer
    from arrow_ballista_tpu.ops.physical import TaskContext
    from arrow_ballista_tpu.ops.shuffle import PartitionLocation, ShuffleReaderExec

    schema = Schema([Field("v", INT64)])
    n_locs = 12
    paths = []
    for i in range(n_locs):
        b = ColumnBatch.from_numpy(schema, {"v": np.full(4, i, dtype=np.int64)})
        p = str(tmp_path / f"data-{i}.arrow")
        write_ipc_file(b, p)
        paths.append(p)

    inflight = {"now": 0, "max": 0}
    lock = threading.Lock()

    def fetch(payload, _bin, send):
        with lock:
            inflight["now"] += 1
            inflight["max"] = max(inflight["max"], inflight["now"])
        time.sleep(0.05)  # hold the slot so overlap is observable
        try:
            stream_partition(payload["path"], payload, send)
        finally:
            with lock:
                inflight["now"] -= 1

    server = RpcServer("127.0.0.1", 0)
    server.register_stream("fetch_partition_stream", fetch)
    server.start()
    try:
        locs = [PartitionLocation("exec-remote", i, 0, paths[i], num_rows=4,
                                  host="127.0.0.1", port=server.port)
                for i in range(n_locs)]
        reader = ShuffleReaderExec(1, schema, 1, {0: locs})
        ctx = TaskContext(executor_id="exec-local")
        batches = reader.execute(0, ctx)
        vals = sorted(int(x) for b in batches
                      for x in np.asarray(b.columns["v"])[np.asarray(b.mask)])
        assert vals == sorted(int(v) for i in range(n_locs) for v in [i] * 4)
        assert inflight["max"] > 1  # fetches actually overlapped
    finally:
        server.stop()
