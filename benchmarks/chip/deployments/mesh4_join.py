"""``mesh4``'s deployment (``BallistaContext.standalone``, the host's chips
as one device mesh) with a check of its own at ``close``, for a cell that
exists for the join's exchange over the mesh: every statement this
deployment served must have run, in its job, a ``MeshJoinExec`` over as
many devices as the configuration states with its partitioned program
(both sides through ``all_to_all``; the operator counts
``partitioned_joins``) and no ``all_gather`` broadcast
(``broadcast_joins``), by the operators' metrics in the job's
``graph.stats``.  ``mesh4``'s own check runs too.  On a TPU a miss ends the
run with no result line; on a CPU rehearsal the planner's gate (8M
estimated rows) keeps the joins off the mesh, so there the check only says
what it found.

A program that keeps no ``mesh_join_retries`` counter in
``obs.device.STATS`` is older than ``partitioned_joins``: there a job
passes where its ``MeshJoinExec`` ran over the configuration's devices and
counted no ``broadcast_joins``, so that such a program, where it can run
the cell, is compared like any other."""
import sys

from . import mesh4

JOIN_OP = ":MeshJoinExec"


class _Session(mesh4._Session):
    """The context, counting every statement it is handed."""

    def sql(self, text: str):
        self._deployment.statements += 1
        return super().sql(text)


def joins_of(stats):
    """``(devices, partitioned, broadcast)`` of a job's ``MeshJoinExec``
    operators: the most devices one counted, and the partitioned and
    broadcast programs they ran to a result."""
    devices = partitioned = broadcast = 0
    for stage in (stats or {}).get("stages", []):
        for key, m in stage.get("operators", {}).items():
            if key.endswith(JOIN_OP):
                devices = max(devices, int(m.get("mesh_devices", 0)))
                partitioned += int(m.get("partitioned_joins", 0))
                broadcast += int(m.get("broadcast_joins", 0))
    return devices, partitioned, broadcast


def counts_partitioned() -> bool:
    """Whether the program counts ``partitioned_joins`` (it came with the
    counter ``mesh_join_retries``)."""
    from arrow_ballista_tpu.obs.device import STATS

    return "mesh_join_retries" in STATS.snapshot()


def check_joins(statements: int, job_stats: list, devices: int,
                counted: bool):
    """``(ok, what was found)``: as many jobs ran ``MeshJoinExec``'s
    partitioned program over ``devices`` devices, and no broadcast, as
    statements were served, at least one.  ``counted``: the program counts
    ``partitioned_joins``; where it does not, a mesh join that did not
    broadcast stands for one."""
    def partitioned(stats) -> bool:
        n_dev, part, bcast = joins_of(stats)
        return n_dev >= devices and not bcast and (part > 0 or not counted)

    on_mesh = sum(1 for s in job_stats if partitioned(s))
    found = (f"{statements} statements, {len(job_stats)} jobs, {on_mesh} of "
             f"them ran MeshJoinExec's partitioned program over {devices} "
             f"devices" + ("" if counted else " (by mesh_devices and no "
                                              "broadcast_joins)"))
    return statements > 0 and on_mesh == statements, found


class Deployment(mesh4.Deployment):
    def __init__(self, config: dict, data_dir: str, tables):
        self.statements = 0
        self.counted = counts_partitioned()
        super().__init__(config, data_dir, tables)

    def session(self):
        return _Session(self)

    def close(self) -> None:
        import jax

        try:
            ok, found = check_joins(
                self.statements,
                [self.job_stats(j["job_id"]) for j in self.submitted],
                self.mesh_devices, self.counted)
            print(f"[mesh4_join] {found}", file=sys.stderr, flush=True)
        finally:
            super().close()
        if not ok and jax.devices()[0].platform == "tpu":
            raise SystemExit(f"the join cell's joins did not run on the "
                             f"mesh's partitioned program: {found}")
