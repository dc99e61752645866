"""Standard Arrow Flight data plane on the executor.

Parity: reference executors serve shuffle partitions to peers AND stock
Arrow clients via Flight ``do_get(Ticket{FetchPartition})``
(reference ballista/executor/src/flight_service.rs:82-120, two-slot
streaming channel; handshake issues a bearer token, :136-157).  The
engine's own peers use the chunked stream on the executor's RPC port
(net/dataplane.py) — this door exists so ANY Arrow-speaking client can
fetch a partition with no Ballista code: the shuffle files on disk are
plain Arrow IPC in physical representation (models/ipc.py), streamed
as-is.

Tickets: JSON ``{"path": ..., "token": ...}`` or raw path bytes — the
scheme a stock ``pyarrow.flight`` client can build by hand from the
PartitionLocation the scheduler hands out.  Auth mirrors the RPC data
plane: when BALLISTA_DATA_PLANE_TOKEN is set, tickets must carry it.
"""
from __future__ import annotations

import json
import logging
import os
import threading
from typing import Optional

log = logging.getLogger(__name__)


class ExecutorFlightServer:
    """Flight door over an ExecutorServer's work dir (lazy pyarrow.flight
    import, same pattern as the scheduler's BallistaFlightServer)."""

    def __init__(self, work_dir: str, token: str = "",
                 host: str = "127.0.0.1", port: int = 0):
        import pyarrow.flight as fl

        outer = self
        self.work_dir = work_dir
        self._token = token

        class _Server(fl.FlightServerBase):
            def __init__(self):
                super().__init__(location=f"grpc://{host}:{port}")

            def do_get(self, context, ticket):
                return outer._do_get(bytes(ticket.ticket))

        self._fl = fl
        self._server = _Server()
        self.host = host
        self.port = self._server.port
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self._server.serve,
                                        name=f"exec-flight-{self.port}",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        try:
            self._server.shutdown()
        except Exception:  # noqa: BLE001 — shutdown is best-effort
            log.debug("executor flight shutdown", exc_info=True)
        if self._thread is not None:
            self._thread.join(timeout=5.0)

    # --- serving ---------------------------------------------------------
    def _resolve(self, raw: bytes) -> str:
        token = ""
        try:
            obj = json.loads(raw.decode("utf-8"))
            path = obj["path"]
            token = obj.get("token", "")
        # not an error path: a non-JSON ticket IS the raw shuffle-file path
        # ballista: allow=recovery-path-logging — expected legacy-ticket shape
        except Exception:  # noqa: BLE001 — raw path ticket
            path = raw.decode("utf-8")
        if self._token and token != self._token:
            raise self._fl.FlightUnauthorizedError("data plane auth failed")
        base = os.path.realpath(self.work_dir)
        target = os.path.realpath(path)
        if os.path.commonpath([base, target]) != base:
            raise self._fl.FlightServerError(
                f"path {path!r} escapes the work dir")
        if not os.path.exists(target):
            raise self._fl.FlightServerError(f"no such shuffle file: {path}")
        return target

    def _do_get(self, raw: bytes):
        import pyarrow as pa

        path = self._resolve(raw)
        reader = pa.ipc.open_file(pa.memory_map(path))
        # stream batch-by-batch off the memory map (the reference's
        # two-slot streaming channel shape) — read_all() would hold the
        # whole partition in executor RAM per concurrent fetch
        batches = (reader.get_batch(i)
                   for i in range(reader.num_record_batches))
        return self._fl.GeneratorStream(reader.schema, batches)
