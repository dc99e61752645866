"""The one traffic generator: closed-loop streams that repeat a pass.

A mix is a data file, ``traffic/<mix>.json``: ``streams`` closed-loop
streams, each a session of its own, each repeating ``pass`` (a list of query
names from ``queries/``).  A stream sends its next query as soon as the last
has answered: there is no think time, and ``query_s`` counts none.

The window opens for all streams at once and closes, per stream, at the
first pass boundary at or after ``seconds``: every window holds whole
passes, so its mix never shifts with speed.
"""
from __future__ import annotations

import json
import math
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as fh:
        mix = json.load(fh)
    if int(mix["streams"]) < 1 or not mix["pass"]:
        raise SystemExit(f"traffic mix {name}: needs streams >= 1 and a "
                         "non-empty pass")
    return mix


def load_query(name: str) -> dict:
    base = os.path.join(HERE, "queries", name)
    with open(base + ".sql") as fh:
        sql = fh.read()
    with open(base + ".columns.json") as fh:
        columns = json.load(fh)
    return {"name": name, "sql": sql, "columns": columns}


@dataclass
class QueryRecord:
    stream: int
    query: str
    t0: float
    t1: float
    table: object = None          # the answer (pyarrow Table), None if failed
    error: Optional[str] = None


@dataclass
class StreamLog:
    records: List[QueryRecord] = field(default_factory=list)
    passes: int = 0
    opened: float = 0.0
    closed: float = 0.0


def run_pass(ctx, stream: int, queries: List[dict], log: StreamLog,
             annotate: Callable) -> None:
    """One pass: every query of the mix once, the clock on the client's
    side around ``ctx.sql(text).to_arrow()``."""
    for q in queries:
        t0 = time.perf_counter()
        table, error = None, None
        try:
            with annotate(f"in {q['name']}"):
                table = ctx.sql(q["sql"]).to_arrow()
        except Exception as e:  # noqa: BLE001 — counted as failed, reported
            error = f"{type(e).__name__}: {e}"
        log.records.append(QueryRecord(stream, q["name"], t0,
                                       time.perf_counter(), table, error))
    log.passes += 1


def run_window(sessions: list, queries: List[dict],
               seconds: float, annotate: Callable,
               gate: threading.Event) -> Tuple[Dict[int, StreamLog], list]:
    """Start one thread per session; each waits for ``gate``, then repeats
    the pass until a pass boundary at or after ``seconds``.  Returns
    ``(logs, threads)`` at once (the logs fill while the threads run), for
    the caller to open the gate and join."""
    logs = {i: StreamLog() for i in range(len(sessions))}

    def stream(i: int, ctx) -> None:
        gate.wait()
        log = logs[i]
        log.opened = time.perf_counter()
        while True:
            run_pass(ctx, i, queries, log, annotate)
            log.closed = time.perf_counter()
            if log.closed - log.opened >= seconds:
                return

    threads = [threading.Thread(target=stream, args=(i, ctx),
                                name=f"chipbench-stream-{i}", daemon=True)
               for i, ctx in enumerate(sessions)]
    for t in threads:
        t.start()
    return logs, threads


def nearest_rank(sorted_values: list, q: float):
    """The q-quantile by nearest rank: the smallest value with at least
    ``q`` of the values at or below it."""
    rank = max(1, math.ceil(q * len(sorted_values) - 1e-9))
    return sorted_values[rank - 1]


def end_to_end(logs: Dict[int, StreamLog]) -> dict:
    """``query_s``: the sum over streams of window seconds over all queries
    completed in those windows.  ``query_p90_s``: the 90th percentile
    (nearest rank) of the client-side latency of every completed query."""
    done = [r for log in logs.values() for r in log.records
            if r.error is None]
    attempted = sum(len(log.records) for log in logs.values())
    window = sum(log.closed - log.opened for log in logs.values())
    out = {"attempted": attempted, "failed": attempted - len(done),
           "completed": len(done), "stream_seconds": window}
    if done:
        lat = sorted(r.t1 - r.t0 for r in done)
        out["query_s"] = window / len(done)
        out["query_p90_s"] = nearest_rank(lat, 0.9)
    return out
