"""The deployment a configuration names, built from its file.

A deployment is a file of its own, ``deployments/<kind>.py``, found by the
configuration's ``deployment`` key: a class ``Deployment(Base)`` that builds
the system in ``build``, hands out one context per stream in ``session`` and
stops what it started in ``close``.  A new shape (daemons, a mesh over four
chips) adds a file and edits nothing.

Whatever the kind, ``scheduler`` is the ``SchedulerServer`` whose ``metrics``
and ``jobs`` the readers look at, and ``submitted`` collects one record per
job from ``metrics.record_submitted``, shadowed here from the benchmark's
side (the program's own recorder still runs).
"""
from __future__ import annotations

import importlib
import os
import time
from typing import Dict, List


class Base:
    scheduler = None        # set by build()

    def __init__(self, config: dict, data_dir: str, tables: List[str]):
        from arrow_ballista_tpu.utils.config import BallistaConfig

        self.settings = dict(config["settings"])
        self.conf = lambda: BallistaConfig(dict(self.settings))
        self.slots = int(config["task_slots"])
        self.executors = int(config["executors"])
        self.data_dir = data_dir
        self.tables = sorted(tables)
        self.submitted: List[Dict] = []     # job_id, queued/submitted ms
        self.build()
        self._shadow_record_submitted()

    def build(self) -> None:
        raise NotImplementedError

    def session(self):
        """A context for one stream."""
        raise NotImplementedError

    def close(self) -> None:
        raise NotImplementedError

    def path(self, table: str) -> str:
        return os.path.join(self.data_dir, f"{table}.parquet")

    def _shadow_record_submitted(self) -> None:
        original = self.scheduler.metrics.record_submitted

        def record(job_id, queued_at_ms, submitted_at_ms):
            self.submitted.append({"job_id": job_id,
                                   "queued_at_ms": queued_at_ms,
                                   "submitted_at_ms": submitted_at_ms,
                                   "at": time.time()})
            original(job_id, queued_at_ms, submitted_at_ms)

        self.scheduler.metrics.record_submitted = record

    def job_stats(self, job_id: str):
        graph = self.scheduler.jobs.get_graph(job_id)
        return None if graph is None else graph.stats.snapshot()


def deploy(config: dict, data_dir: str, tables: List[str]) -> Base:
    kind = config["deployment"]
    try:
        mod = importlib.import_module(f"{__package__}.deployments.{kind}")
    except ModuleNotFoundError as e:
        raise SystemExit(f"configuration names deployment {kind!r}: add "
                         f"benchmarks/chip/deployments/{kind}.py ({e})")
    return mod.Deployment(config, data_dir, tables)
