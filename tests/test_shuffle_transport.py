"""Shuffle transport tests: zero-copy co-located mmap reads, the chunked
streaming wire protocol (per-chunk CRC, resume-from-chunk, compression
negotiation) through a bare RPC server and through a real executor's
handler, the retry-policy split between corrupt payloads (immediate
re-fetch) and dead peers (backoff), and the one entry point every
consumer reaches the network through.

Everything asserts BIT-IDENTITY against a direct local read of the same
partition file: a transport is only correct if no path can change a
single value.
"""
import os
import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from arrow_ballista_tpu.models.ipc import (crc32_file, read_ipc_files,
                                           write_ipc_rows)
from arrow_ballista_tpu.models.schema import DataType, Field, Schema
from arrow_ballista_tpu.net import dataplane as dp
from arrow_ballista_tpu.net.rpc import RpcServer
from arrow_ballista_tpu.ops.physical import TaskContext
from arrow_ballista_tpu.ops.shuffle import PartitionLocation, ShuffleReaderExec
from arrow_ballista_tpu.utils.config import BallistaConfig
from arrow_ballista_tpu.utils.errors import FetchFailedError, IntegrityError

SCHEMA = Schema([
    Field("s", DataType("string")),     # dictionary-encoded on the wire
    Field("small", DataType("int64")),  # int32-narrowable values
    Field("big", DataType("int64")),    # exceeds int32 -> stays int64
    Field("d", DataType("decimal", 2)),  # scaled-int64 physical
    Field("f", DataType("float64")),
])

N_ROWS = 50_000
N_KEYS = 40


def _write_partition(path: str, n: int = N_ROWS, seed: int = 7):
    rng = np.random.default_rng(seed)
    data = {
        "s": rng.integers(0, N_KEYS, n).astype(np.int32),
        "small": rng.integers(-10_000, 10_000, n),
        "big": rng.integers(1, 9) * (1 << 40) + rng.integers(0, 1000, n),
        "d": rng.integers(-500_000, 500_000, n),
        "f": rng.standard_normal(n),
    }
    dicts = {"s": np.asarray([f"key-{i:05d}" for i in range(N_KEYS)],
                             dtype=object)}
    rows, nbytes = write_ipc_rows(SCHEMA, data, dicts, path)
    assert rows == n
    return nbytes, crc32_file(path)


def _table_of(batches):
    """Logical pyarrow table of a batch list — the bit-identity currency."""
    return pa.concat_tables([b.to_arrow() for b in batches])


@pytest.fixture()
def partition(tmp_path):
    path = str(tmp_path / "data-0.arrow")
    nbytes, crc = _write_partition(path)
    return path, nbytes, crc


@pytest.fixture()
def stream_server(tmp_path):
    """Bare RPC server speaking the fetch protocol over ``tmp_path``."""
    srv = RpcServer("127.0.0.1", 0)
    srv.register_stream(
        "fetch_partition_stream",
        lambda p, b, send: dp.stream_partition(p["path"], p, send))
    srv.start()
    yield srv
    srv.stop()


# short deadlines and backoff, so a retried fetch costs milliseconds
FAST = {"ballista.rpc.connect.timeout.seconds": "2.0",
        "ballista.rpc.read.timeout.seconds": "20.0",
        "ballista.rpc.retry.base.seconds": "0.01",
        "ballista.rpc.retry.cap.seconds": "0.02",
        "ballista.batch.size": "8192"}


def _fetch(port, path, crc=-1, *, chunk_rows=None, compression=None):
    """One fetch through the entry point every consumer calls."""
    conf = dict(FAST)
    if chunk_rows is not None:
        conf["ballista.shuffle.wire.chunk_rows"] = str(chunk_rows)
    if compression is not None:
        conf["ballista.shuffle.wire.compression"] = compression
    loc = PartitionLocation("producer-exec", 0, 0, path, host="127.0.0.1",
                            port=port, checksum=crc)
    return dp.fetch_partition(loc, SCHEMA, BallistaConfig(conf))


# --------------------------------------------------------------------------
# wire-format matrix: chunking x compression all decode to the exact same
# logical table as a direct local read
# --------------------------------------------------------------------------

@pytest.mark.parametrize("codec", ["lz4", "zstd", "none"])
@pytest.mark.parametrize("chunk_rows", [1 << 16, 7_000])
def test_stream_matrix_bit_identical(partition, stream_server, codec,
                                     chunk_rows):
    path, nbytes, crc = partition
    baseline = _table_of(read_ipc_files([path], SCHEMA, capacity=8192))
    batches, stats = _fetch(stream_server.port, path, crc,
                            chunk_rows=chunk_rows, compression=codec)
    assert _table_of(batches).equals(baseline)
    assert stats["chunks"] == -(-N_ROWS // chunk_rows)
    assert stats["raw_bytes"] == nbytes
    if codec in ("lz4", "zstd") and pa.Codec.is_available(codec):
        assert stats["codec"] == codec
        assert stats["wire_bytes"] < nbytes, \
            "compression must shrink this synthetic (compressible) data"
    else:
        assert stats["codec"] == "none"


def test_unknown_codec_degrades_to_uncompressed(partition, stream_server):
    path, nbytes, crc = partition
    baseline = _table_of(read_ipc_files([path], SCHEMA, capacity=8192))
    batches, stats = _fetch(stream_server.port, path, crc,
                            compression="brotli-9000")
    assert stats["codec"] == "none"
    assert _table_of(batches).equals(baseline)


# --------------------------------------------------------------------------
# the same protocol through a real executor's handler: what it serves is the
# file, and what it refuses (a path outside its work dir, a file that is not
# there, a caller without the token) it refuses by name
# --------------------------------------------------------------------------

@pytest.fixture()
def executor(tmp_path, monkeypatch):
    """A real ExecutorServer listening on its one port (no scheduler: only
    its RPC listener is started), with ``tmp_path`` as its work dir."""
    from arrow_ballista_tpu.executor.server import ExecutorServer

    monkeypatch.setenv("BALLISTA_DATA_PLANE_TOKEN", "sekrit")
    srv = ExecutorServer("127.0.0.1", 1, port=0, work_dir=str(tmp_path))
    srv.rpc.start()
    assert srv.metadata.port == srv.rpc.port
    yield srv
    srv.stop(notify=False)


def test_executor_serves_the_file_bit_identical(partition, executor):
    path, nbytes, crc = partition
    baseline = _table_of(read_ipc_files([path], SCHEMA, capacity=8192))
    batches, stats = _fetch(executor.rpc.port, path, crc, chunk_rows=7_000)
    assert _table_of(batches).equals(baseline)
    assert stats["raw_bytes"] == nbytes and stats["chunks"] == 8


@pytest.mark.parametrize("case", ["outside_work_dir", "missing_file"])
def test_executor_refuses_by_name(partition, executor, tmp_path_factory,
                                  case):
    from arrow_ballista_tpu.net.wire import RemoteError

    if case == "outside_work_dir":
        path = str(tmp_path_factory.mktemp("elsewhere") / "data-0.arrow")
        _write_partition(path, n=100)
        want = "escapes the work dir"
    else:
        path = os.path.join(os.path.dirname(partition[0]), "data-9.arrow")
        want = "no such shuffle file"
    with pytest.raises(RemoteError, match=want) as err:
        _fetch(executor.rpc.port, path)
    assert path in str(err.value)


def test_executor_wants_the_token(partition, executor, monkeypatch):
    from arrow_ballista_tpu.net.wire import RemoteError

    path, _, crc = partition
    for token in ("wrong", ""):
        monkeypatch.setenv("BALLISTA_DATA_PLANE_TOKEN", token)
        with pytest.raises(RemoteError, match="auth failed"):
            _fetch(executor.rpc.port, path, crc)
    monkeypatch.setenv("BALLISTA_DATA_PLANE_TOKEN", "sekrit")
    batches, _ = _fetch(executor.rpc.port, path, crc)
    assert sum(b.num_rows for b in batches) == N_ROWS


def test_executor_eight_concurrent_fetches_bit_identical(partition,
                                                         executor):
    from concurrent.futures import ThreadPoolExecutor

    path, _, crc = partition
    baseline = _table_of(read_ipc_files([path], SCHEMA, capacity=8192))
    with ThreadPoolExecutor(8) as pool:
        got = list(pool.map(
            lambda _: _fetch(executor.rpc.port, path, crc, chunk_rows=7_000),
            range(8)))
    assert len(got) == 8
    for batches, stats in got:
        assert _table_of(batches).equals(baseline)
        assert stats["resumed_chunks"] == 0


def test_stats_remote_bytes_grow_by_the_wire_bytes(partition, executor):
    path, _, crc = partition
    before = dp.STATS.snapshot()
    _, stats = _fetch(executor.rpc.port, path, crc)
    after = dp.STATS.snapshot()
    assert stats["wire_bytes"] > 0
    assert after["bytes_fetched"]["remote"] - \
        before["bytes_fetched"]["remote"] == stats["wire_bytes"]
    assert after["fetches"]["remote"] - before["fetches"]["remote"] == 1
    assert after["wire_bytes"] - before["wire_bytes"] == stats["wire_bytes"]


# --------------------------------------------------------------------------
# resume-from-chunk + retry classification
# --------------------------------------------------------------------------

def test_corrupt_chunk_resumes_without_refetching_verified_chunks(
        partition, stream_server):
    from arrow_ballista_tpu import faults

    path, _, crc = partition
    baseline = _table_of(read_ipc_files([path], SCHEMA, capacity=8192))
    plan = faults.FaultPlan.from_obj({"rules": [{
        "site": "shuffle.fetch.recv", "action": "corrupt", "times": 1,
        "match": {"chunk": 3}}]})
    with faults.use_plan(plan):
        batches, stats = _fetch(stream_server.port, path, crc,
                                chunk_rows=7_000)
    assert plan.schedule() == (("shuffle.fetch.recv", 0, 1, "corrupt"),)
    assert _table_of(batches).equals(baseline)
    # the retry started at the corrupted chunk, keeping chunks 0-2
    assert stats["resumed_chunks"] == 3


def test_dropped_chunk_resumes(partition, stream_server):
    from arrow_ballista_tpu import faults

    path, _, crc = partition
    baseline = _table_of(read_ipc_files([path], SCHEMA, capacity=8192))
    plan = faults.FaultPlan.from_obj({"rules": [{
        "site": "shuffle.fetch.recv", "action": "drop", "times": 1,
        "match": {"chunk": 2}}]})
    with faults.use_plan(plan):
        batches, stats = _fetch(stream_server.port, path, crc,
                                chunk_rows=7_000)
    assert _table_of(batches).equals(baseline)
    assert stats["resumed_chunks"] == 2


def test_integrity_retries_immediately_connection_backs_off(
        partition, stream_server, monkeypatch):
    """Regression for the retry-loop split: an IntegrityError (corrupt
    payload) must re-fetch with NO backoff sleep — the peer is reachable
    and fresh bytes may be clean — while connection failures keep the
    jittered backoff."""
    from arrow_ballista_tpu import faults

    path, _, crc = partition
    sleeps = []
    monkeypatch.setattr(dp.time, "sleep", lambda s: sleeps.append(s))

    # corrupt twice: two in-loop retries, no sleeps
    plan = faults.FaultPlan.from_obj({"rules": [{
        "site": "shuffle.fetch.recv", "action": "corrupt", "times": 2}]})
    with faults.use_plan(plan):
        _fetch(stream_server.port, path, crc)
    assert len(plan.events) == 2
    assert sleeps == [], "corrupt payloads must re-fetch without backoff"

    # drop twice: two connection failures, two backoff sleeps
    plan = faults.FaultPlan.from_obj({"rules": [{
        "site": "shuffle.fetch.recv", "action": "drop", "times": 2}]})
    with faults.use_plan(plan):
        _fetch(stream_server.port, path, crc)
    assert len(sleeps) == 2, "connection failures must keep the backoff"
    assert all(s > 0 for s in sleeps)


def test_on_disk_corruption_fails_fast_without_refetch(tmp_path,
                                                       stream_server):
    """A server-side checksum mismatch means the PRODUCER's file is bad:
    re-fetching cannot heal it, so the client must escalate after ONE
    attempt (lineage recovery re-runs the producer)."""
    path = str(tmp_path / "data-0.arrow")
    _, crc = _write_partition(path, n=5_000)
    with open(path, "r+b") as f:  # flip one byte on disk
        f.seek(100)
        b = f.read(1)
        f.seek(100)
        f.write(bytes([b[0] ^ 0xFF]))
    calls = []
    orig = dp.stream_partition
    stream_server.register_stream(
        "fetch_partition_stream",
        lambda p, b, send: (calls.append(1), orig(p["path"], p, send)))
    with pytest.raises(IntegrityError, match="corrupt"):
        _fetch(stream_server.port, path, crc)
    assert len(calls) == 1, "disk corruption must not be re-fetched"


# --------------------------------------------------------------------------
# co-located mmap local path
# --------------------------------------------------------------------------

def _reader_for(path, crc, nbytes, *, host="node-a", port=1,
                conf=None, exec_host="node-a"):
    reader = ShuffleReaderExec(stage_id=1, schema=SCHEMA, partition_count=1,
                               locations={0: [PartitionLocation(
                                   "producer-exec", 0, 0, path,
                                   num_rows=N_ROWS, num_bytes=nbytes,
                                   host=host, port=port, checksum=crc,
                                   format="arrow_file")]})
    ctx = TaskContext(config=BallistaConfig(conf or {}),
                      executor_id="consumer-exec", executor_host=exec_host)
    return reader, ctx


def test_host_match_mmap_bit_identical(partition):
    path, nbytes, crc = partition
    baseline = _table_of(read_ipc_files([path], SCHEMA, capacity=8192))
    before = dp.STATS.snapshot()
    reader, ctx = _reader_for(path, crc, nbytes)
    got = _table_of(reader._execute(0, ctx))
    assert got.equals(baseline)
    after = dp.STATS.snapshot()
    assert after["bytes_fetched"]["local_mmap"] - \
        before["bytes_fetched"]["local_mmap"] == nbytes
    assert reader.metrics().to_dict().get("bytes_local_mmap") == nbytes
    # no remote fetch happened (port=1 would have failed to connect)
    assert "remote_fetches" not in reader.metrics().to_dict()


def test_host_match_mmap_equals_wire_path(partition, stream_server):
    """The mmap read and the streamed+compressed wire read of the same file
    must be indistinguishable downstream."""
    path, nbytes, crc = partition
    reader, ctx = _reader_for(path, crc, nbytes)
    via_mmap = _table_of(reader._execute(0, ctx))
    via_wire, _ = _fetch(stream_server.port, path, crc, chunk_rows=7_000,
                         compression="zstd")
    assert via_mmap.equals(_table_of(via_wire))


def test_host_mismatch_goes_remote(partition, stream_server):
    path, nbytes, crc = partition
    baseline = _table_of(read_ipc_files([path], SCHEMA, capacity=8192))
    reader, ctx = _reader_for(path, crc, nbytes, host="127.0.0.1",
                              port=stream_server.port,
                              exec_host="node-a")
    got = _table_of(reader._execute(0, ctx))
    assert got.equals(baseline)
    assert reader.metrics().to_dict().get("remote_fetches") == 1
    assert reader.metrics().to_dict().get("fetch_chunks", 0) >= 1


def test_host_match_disabled_goes_remote(partition, stream_server):
    path, nbytes, crc = partition
    reader, ctx = _reader_for(
        path, crc, nbytes, host="127.0.0.1", exec_host="127.0.0.1",
        port=stream_server.port,
        conf={"ballista.shuffle.local.host_match": "false"})
    reader._execute(0, ctx)
    assert reader.metrics().to_dict().get("remote_fetches") == 1


def test_stale_local_file_falls_back_to_remote(partition, stream_server,
                                               tmp_path):
    """Same host + same path but the local bytes don't match the producer's
    record (size or CRC): the reader must silently take the remote fetch,
    whose own verification runs against the authoritative copy."""
    path, nbytes, crc = partition
    baseline = _table_of(read_ipc_files([path], SCHEMA, capacity=8192))
    # wrong checksum recorded -> local CRC verify rejects the mmap
    reader, ctx = _reader_for(path, crc ^ 0x1, nbytes, host="127.0.0.1",
                              exec_host="127.0.0.1",
                              port=stream_server.port)
    with pytest.raises(FetchFailedError):
        # remote verify also fails (the recorded CRC is simply wrong):
        # corruption is never silently accepted on ANY path
        reader._execute(0, ctx)
    # wrong size recorded -> local rejects, remote (no integrity check on a
    # -1 checksum) serves the real file
    reader, ctx = _reader_for(path, -1, nbytes + 1, host="127.0.0.1",
                              exec_host="127.0.0.1",
                              port=stream_server.port)
    got = _table_of(reader._execute(0, ctx))
    assert got.equals(baseline)
    assert reader.metrics().to_dict().get("remote_fetches") == 1


def test_identity_local_still_wins_over_host_match(partition):
    """Producer == consumer executor keeps the original identity fast path
    (plain read, no per-location verification)."""
    path, nbytes, crc = partition
    reader = ShuffleReaderExec(stage_id=1, schema=SCHEMA, partition_count=1,
                               locations={0: [PartitionLocation(
                                   "exec-a", 0, 0, path, num_rows=N_ROWS,
                                   num_bytes=nbytes, host="node-a", port=9,
                                   checksum=crc)]})
    ctx = TaskContext(config=BallistaConfig(), executor_id="exec-a",
                      executor_host="node-a")
    assert sum(b.num_rows for b in reader._execute(0, ctx)) == N_ROWS
    assert "bytes_local_mmap" not in reader.metrics().to_dict()


# --------------------------------------------------------------------------
# shared fetch pool + concurrency cap
# --------------------------------------------------------------------------

def test_fetch_pool_is_process_shared():
    a = ShuffleReaderExec._fetch_pool()
    b = ShuffleReaderExec._fetch_pool()
    assert a is b


def test_max_concurrent_fetches_config_bounds_fetches(tmp_path,
                                                      stream_server):
    paths = []
    for i in range(6):
        p = str(tmp_path / f"data-{i}.arrow")
        nbytes, crc = _write_partition(p, n=2_000, seed=i)
        paths.append((p, nbytes, crc))
    locs = [PartitionLocation("producer-exec", i, 0, p, num_rows=2_000,
                              num_bytes=nb, host="127.0.0.1",
                              port=stream_server.port, checksum=c)
            for i, (p, nb, c) in enumerate(paths)]
    reader = ShuffleReaderExec(stage_id=1, schema=SCHEMA, partition_count=1,
                               locations={0: locs})
    ctx = TaskContext(
        config=BallistaConfig(
            {"ballista.shuffle.max_concurrent_fetches": "2"}),
        executor_id="consumer-exec", executor_host="node-a")

    active, peak = [0], [0]
    lock = threading.Lock()
    orig = ShuffleReaderExec._fetch_remote

    def spy(self, loc, c):
        with lock:
            active[0] += 1
            peak[0] = max(peak[0], active[0])
        try:
            time.sleep(0.02)  # widen the overlap window
            return orig(self, loc, c)
        finally:
            with lock:
                active[0] -= 1

    ShuffleReaderExec._fetch_remote = spy
    try:
        batches = reader._execute(0, ctx)
    finally:
        ShuffleReaderExec._fetch_remote = orig
    assert sum(b.num_rows for b in batches) == 6 * 2_000
    assert peak[0] <= 2, f"semaphore must cap in-flight fetches, saw {peak}"


# --------------------------------------------------------------------------
# serde: PartitionLocation round trip
# --------------------------------------------------------------------------

def test_location_serde_round_trip_and_tolerance():
    from arrow_ballista_tpu import serde

    loc = PartitionLocation("e1", 2, 3, "/w/j/1/2/data-3.arrow",
                            num_rows=10, num_bytes=999, host="node-a",
                            port=50051, checksum=123, format="arrow_file")
    obj = serde.location_to_obj(loc)
    assert obj["port"] == 50051 and obj["format"] == "arrow_file"
    assert serde.location_from_obj(obj) == loc
    # a key this build does not know (a job persisted by another build of
    # the scheduler) is dropped, not fatal
    obj["hypothetical_v9_field"] = {"x": 1}
    assert serde.location_from_obj(obj) == loc


# --------------------------------------------------------------------------
# end-to-end: a real two-executor cluster on one host serves every
# cross-executor shuffle read through the zero-copy mmap path, visibly in
# the path-labelled metrics, with results identical to host-match off
# --------------------------------------------------------------------------

SQL = "select g, sum(v) as s, count(*) as n from t group by g order by g"


def _cluster(tmp_path, conf):
    from arrow_ballista_tpu.executor.server import ExecutorServer
    from arrow_ballista_tpu.scheduler.netservice import SchedulerNetService

    sched = SchedulerNetService("127.0.0.1", 0, config=BallistaConfig(conf))
    sched.start()
    executors = []
    for i in range(2):
        work = tmp_path / f"exec{i}"
        work.mkdir(parents=True)
        ex = ExecutorServer("127.0.0.1", sched.port, "127.0.0.1", 0,
                            work_dir=str(work), concurrent_tasks=2,
                            executor_id=f"transport-exec-{i}",
                            config=BallistaConfig(conf))
        ex.start()
        executors.append(ex)
    return sched, executors


def _run_cluster_query(tmp_path, conf):
    from arrow_ballista_tpu.client.context import BallistaContext

    sched, executors = _cluster(tmp_path, conf)
    try:
        c = BallistaContext.remote(
            "127.0.0.1", sched.port,
            BallistaConfig({"ballista.shuffle.partitions": "4"}))
        rng = np.random.default_rng(41)
        c.register_table("t", pa.table({
            "g": pa.array(rng.integers(0, 2_000, 30_000).astype(np.int64)),
            "v": pa.array(rng.integers(0, 100, 30_000).astype(np.int64)),
        }))
        df = c.sql(SQL).to_pandas()
        metrics_text = executors[0].executor.metrics.gather()
        c.shutdown()
        return df, metrics_text
    finally:
        for ex in executors:
            ex.stop(notify=False)
        sched.stop()


def test_cluster_host_match_uses_mmap_path_and_matches_remote(tmp_path):
    import pandas as pd

    base = {"ballista.shuffle.partitions": "4"}
    before = dp.STATS.snapshot()
    on_df, metrics_text = _run_cluster_query(tmp_path / "on", dict(base))
    mid = dp.STATS.snapshot()
    assert mid["bytes_fetched"]["local_mmap"] > \
        before["bytes_fetched"]["local_mmap"], \
        "co-located cross-executor reads must take the mmap path"
    # result collection by the CLIENT (not an executor) still crosses the
    # data plane; shuffle reads between the co-located executors must not
    on_remote = mid["fetches"]["remote"] - before["fetches"]["remote"]
    # the path label is visible on the executor scrape surface
    assert 'shuffle_bytes_fetched_total{path="local_mmap"}' in metrics_text
    assert "shuffle_wire_compression_ratio" in metrics_text

    off_df, _ = _run_cluster_query(
        tmp_path / "off",
        dict(base, **{"ballista.shuffle.local.host_match": "false"}))
    after = dp.STATS.snapshot()
    off_remote = after["fetches"]["remote"] - mid["fetches"]["remote"]
    assert off_remote > on_remote, \
        "host-match off must push cross-executor shuffle reads onto the " \
        f"wire (on={on_remote}, off={off_remote})"
    assert after["chunks"] > mid["chunks"], "wire reads must stream chunks"
    pd.testing.assert_frame_equal(on_df.reset_index(drop=True),
                                  off_df.reset_index(drop=True),
                                  check_dtype=False)


# --------------------------------------------------------------------------
# one entry point: the shuffle reader, the remote client's result collection
# and the Flight SQL result path all reach the network through
# net.dataplane.fetch_partition
# --------------------------------------------------------------------------

@pytest.fixture()
def flight_cluster(tmp_path):
    """Scheduler with its Flight SQL door + one executor, table ``t``."""
    from arrow_ballista_tpu.catalog import MemoryTable
    from arrow_ballista_tpu.executor.server import ExecutorServer
    from arrow_ballista_tpu.scheduler.netservice import SchedulerNetService

    conf = {"ballista.shuffle.partitions": "2"}
    sched = SchedulerNetService("127.0.0.1", 0, config=BallistaConfig(conf),
                                flight_port=0)
    sched.start()
    ex = ExecutorServer("127.0.0.1", sched.port, "127.0.0.1", 0,
                        work_dir=str(tmp_path / "exec"), concurrent_tasks=2,
                        executor_id="one-port-exec",
                        config=BallistaConfig(conf))
    ex.start()
    rng = np.random.default_rng(41)
    sched.catalog.register(MemoryTable("t", pa.table({
        "g": pa.array(rng.integers(0, 50, 5_000).astype(np.int64)),
        "v": pa.array(rng.integers(0, 100, 5_000).astype(np.int64))})))
    yield sched
    ex.stop(notify=False)
    sched.stop()


class _NoSharedDisk:
    """``os`` as a scheduler sees it whose disk holds none of the
    executors' files: every other name is the real module's."""

    class path:
        exists = staticmethod(lambda p: False)

    def __getattr__(self, name):
        return getattr(os, name)


def _flight_sql(sched, sql):
    import pyarrow.flight as fl

    client = fl.connect(f"grpc://127.0.0.1:{sched.flight.port}")
    try:
        info = client.get_flight_info(
            fl.FlightDescriptor.for_command(sql.encode()))
        return client.do_get(info.endpoints[0].ticket).read_all()
    finally:
        client.close()


def test_flight_sql_fetches_a_result_that_is_not_on_its_disk(
        flight_cluster, monkeypatch):
    from arrow_ballista_tpu.scheduler import flight_service

    local = _flight_sql(flight_cluster, SQL)
    before = dp.STATS.snapshot()
    monkeypatch.setattr(flight_service, "os", _NoSharedDisk())
    remote = _flight_sql(flight_cluster, SQL)
    after = dp.STATS.snapshot()
    assert after["fetches"]["remote"] > before["fetches"]["remote"]
    assert after["chunks"] > before["chunks"]
    assert remote.num_rows == 50 and remote.equals(local)


@pytest.mark.parametrize("consumer", ["shuffle_reader", "remote_client",
                                      "flight_sql"])
def test_every_consumer_fetches_through_the_one_entry_point(
        partition, stream_server, flight_cluster, monkeypatch, consumer):
    calls = []
    real = dp.fetch_partition

    def counted(loc, schema, config, fault_ctx=None):
        calls.append((loc.host, loc.port, loc.path))
        return real(loc, schema, config, fault_ctx)

    monkeypatch.setattr(dp, "fetch_partition", counted)
    if consumer == "shuffle_reader":
        path, nbytes, crc = partition
        reader, ctx = _reader_for(path, crc, nbytes, host="127.0.0.1",
                                  port=stream_server.port)
        assert sum(b.num_rows for b in reader._execute(0, ctx)) == N_ROWS
        assert calls == [("127.0.0.1", stream_server.port, path)]
        return
    executor_port = flight_cluster.server.cluster.get_executor(
        "one-port-exec").port
    if consumer == "remote_client":
        from arrow_ballista_tpu.client.context import BallistaContext

        c = BallistaContext.remote("127.0.0.1", flight_cluster.port)
        try:
            assert len(c.sql(SQL).to_pandas()) == 50
        finally:
            c.shutdown()
    else:
        from arrow_ballista_tpu.scheduler import flight_service

        monkeypatch.setattr(flight_service, "os", _NoSharedDisk())
        assert _flight_sql(flight_cluster, SQL).num_rows == 50
    assert calls, f"{consumer} did not reach net.dataplane.fetch_partition"
    assert {(h, p) for h, p, _ in calls} == {("127.0.0.1", executor_port)}
