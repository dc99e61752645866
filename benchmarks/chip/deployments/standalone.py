"""``BallistaContext.standalone``: scheduler and executor in process, no
network.  Every stream shares the one context (its scheduler serialises
nothing between callers)."""
from ..deploy import Base


class Deployment(Base):
    def build(self) -> None:
        from arrow_ballista_tpu.client.context import BallistaContext

        self.ctx = BallistaContext.standalone(
            self.conf(), concurrent_tasks=self.slots,
            num_executors=self.executors)
        self.scheduler = self.ctx._standalone.scheduler
        for t in self.tables:
            self.ctx.register_parquet(t, self.path(t))

    def session(self):
        return self.ctx

    def close(self) -> None:
        self.ctx.shutdown()
