"""The three-tier path: one ``SchedulerNetService``, ``executors``
``ExecutorServer`` registered with it over TCP, tables on the scheduler's
shared catalog, and one ``BallistaContext.remote`` (a server-side session of
its own) per stream.  All in this process, because only the process that
holds the chip can trace it; every RPC, task launch, shuffle fetch and
result fetch still crosses TCP and serde."""
import os
import shutil
import tempfile

from ..deploy import Base


class Deployment(Base):
    def build(self) -> None:
        from arrow_ballista_tpu.catalog import ParquetTable
        from arrow_ballista_tpu.executor.server import ExecutorServer
        from arrow_ballista_tpu.scheduler.netservice import \
            SchedulerNetService

        self._contexts, self._stops = [], []
        self._tmp = tempfile.mkdtemp(prefix="chipbench-cluster-")
        self.svc = SchedulerNetService("127.0.0.1", 0, config=self.conf())
        self.svc.start()
        self._stops.append(self.svc.stop)
        self.scheduler = self.svc.server
        for i in range(self.executors):
            work = os.path.join(self._tmp, f"exec{i}")
            os.makedirs(work)
            ex = ExecutorServer("127.0.0.1", self.svc.port, "127.0.0.1", 0,
                                work_dir=work, concurrent_tasks=self.slots,
                                executor_id=f"chipbench-{i}",
                                config=self.conf())
            ex.start()
            self._stops.insert(0, lambda ex=ex: ex.stop(notify=False))
        for t in self.tables:
            self.svc.catalog.register(ParquetTable(t, self.path(t)))

    def session(self):
        from arrow_ballista_tpu.client.context import BallistaContext

        ctx = BallistaContext.remote("127.0.0.1", self.svc.port, self.conf())
        self._contexts.append(ctx)
        return ctx

    def close(self) -> None:
        for ctx in self._contexts:
            ctx.shutdown()
        for stop in self._stops:        # executors first, then scheduler
            stop()
        shutil.rmtree(self._tmp, ignore_errors=True)
        self._contexts, self._stops = [], []
