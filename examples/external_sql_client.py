#!/usr/bin/env python3
"""SQL over the wire WITHOUT the BallistaContext client library.

Demonstrates the scheduler's external SQL surface (the Arrow Flight SQL
role of the reference, ballista/scheduler/src/flight_sql.rs:83-911): any
client that can speak the framing below — open a session, prepare/execute
SQL, poll status, fetch result partitions from the executors — can
run queries.  Only stdlib + pyarrow (for decoding the Arrow IPC result
chunks) are used; nothing from arrow_ballista_tpu.

Usage:
    # start a cluster:
    python -m arrow_ballista_tpu.scheduler_daemon --bind-port 50050 &
    python -m arrow_ballista_tpu.executor_daemon --scheduler-port 50050 &
    # register data + query it:
    python examples/external_sql_client.py localhost 50050 \
        "create external table lineitem stored as parquet location '/data/lineitem.parquet'" \
        "select count(*) from lineitem"

Wire protocol (net/wire.py): frame = u32 json_len | u64 bin_len | json | bin;
request json = {"method": ..., "payload": {...}}; response json =
{"ok": bool, "payload"|"error": ...}.  ``fetch_partition_stream`` answers
one request with many frames: {"chunk", "rows", "crc", "chunks"} + the
chunk's bytes, then {"eos": true, ...}.
"""
import io
import json
import socket
import struct
import sys
import time
import zlib

HDR = struct.Struct("!IQ")


def _send(sock, method, payload):
    body = json.dumps({"method": method, "payload": payload or {}}).encode()
    sock.sendall(HDR.pack(len(body), 0) + body)


def _recv_frame(sock):
    jlen, blen = HDR.unpack(_recv(sock, HDR.size))
    obj = json.loads(_recv(sock, jlen))
    binary = _recv(sock, blen) if blen else b""
    if not obj.get("ok"):
        raise RuntimeError(obj.get("error", "remote error"))
    return obj.get("payload", {}), binary


def call(host, port, method, payload=None, timeout=60.0):
    sock = socket.create_connection((host, port), timeout=timeout)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    try:
        _send(sock, method, payload)
        return _recv_frame(sock)
    finally:
        sock.close()


def fetch_partition(host, port, path, timeout=60.0):
    """One stored partition from the executor that owns it: one request,
    then a stream of frames, each a self-contained Arrow IPC stream with
    its CRC-32, until the frame that says ``eos``."""
    import pyarrow.ipc as ipc

    sock = socket.create_connection((host, port), timeout=timeout)
    try:
        _send(sock, "fetch_partition_stream",
              {"path": path, "compression": "none"})
        tables = []
        while True:
            frame, chunk = _recv_frame(sock)
            if frame.get("eos"):
                return tables
            if zlib.crc32(chunk) != frame["crc"]:
                raise RuntimeError(f"chunk {frame['chunk']} of {path} is corrupt")
            tables.append(ipc.open_stream(io.BytesIO(chunk)).read_all())
    finally:
        sock.close()


def _recv(sock, n):
    chunks, got = [], 0
    while got < n:
        b = sock.recv(min(n - got, 1 << 20))
        if not b:
            raise ConnectionError("peer closed")
        chunks.append(b)
        got += len(b)
    return b"".join(chunks)


def run_sql(host, port, session_id, sql):
    # prepare first: validates the statement and returns the result schema
    prep, _ = call(host, port, "prepare", {"session_id": session_id, "sql": sql})
    print(f"-- prepared {prep['statement_id']} "
          f"({len(prep['schema'])} output columns)")
    payload, _ = call(host, port, "execute_query",
                      {"session_id": session_id,
                       "statement_id": prep["statement_id"]})
    job_id = payload["job_id"]
    while True:
        status, _ = call(host, port, "get_job_status", {"job_id": job_id})
        if status["state"] == "successful":
            break
        if status["state"] in ("failed", "cancelled", "not_found"):
            raise RuntimeError(f"job {job_id}: {status}")
        time.sleep(0.1)

    import pyarrow as pa

    tables = []
    for part in sorted(status["locations"], key=int):
        for loc in status["locations"][part]:
            if not loc["num_rows"]:
                continue
            # fetch the partition from the executor that owns it
            tables.extend(fetch_partition(loc["host"], loc["port"],
                                          loc["path"]))
    if not tables:
        print("(empty result)")
        return
    result = pa.concat_tables(tables, promote_options="permissive")
    print(result.to_pandas().to_string(index=False))


def main():
    if len(sys.argv) < 4:
        raise SystemExit(__doc__)
    host, port = sys.argv[1], int(sys.argv[2])
    session, _ = call(host, port, "create_session", {"settings": {}})
    sid = session["session_id"]
    print(f"-- session {sid}")
    try:
        for sql in sys.argv[3:]:
            if sql.strip().lower().startswith("create external table"):
                # minimal DDL: parse name/format/location
                import re

                m = re.match(
                    r"create external table (\w+) stored as (\w+) location '([^']+)'",
                    sql.strip(), re.IGNORECASE)
                if not m:
                    raise SystemExit(f"cannot parse DDL: {sql}")
                call(host, port, "register_external_table",
                     {"session_id": sid, "name": m.group(1),
                      "format": m.group(2).lower(), "path": m.group(3)})
                print(f"-- registered {m.group(1)}")
            else:
                run_sql(host, port, sid, sql)
    finally:
        call(host, port, "remove_session", {"session_id": sid})


if __name__ == "__main__":
    main()
