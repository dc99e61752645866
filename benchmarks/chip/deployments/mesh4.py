"""``BallistaContext.standalone`` on one host whose chips the executor holds
as one device mesh (the configuration's ``mesh``: axis and device count;
``ballista.shuffle.mesh=true`` in its settings).  The standalone shape, and
a check of its own at ``close``: a cell named for the mesh that measured the
file path would be worse than no cell, so every grouped statement this
deployment served (``GROUP BY`` in its text: q1 here) must have run, in its
job, an operator that counted ``mesh_devices`` over as many devices as the
configuration states (the operators' metrics in the job's ``graph.stats``),
and the program must have counted a mesh program dispatched for each
(``mesh_programs`` in ``obs.device.STATS``).  On a TPU a miss ends the run
with no result line; on a CPU rehearsal the planner's gate (8M estimated
rows) cannot be reached, so there the check only says what it found.

A program that keeps no ``mesh_programs`` counter is refused at once, before
anything is built, on any device.  That is the program as it stood before
the cell came (PR 28's parent).  It runs q1 on the mesh, but it cannot run
this configuration to its guarantee: on the v5e its ``grouped_sums_i64``
recombines the exact limb sums wrongly in some fusions, so every q1 of some
seeds has one sum wrong (PERF.md section 6, PR 28, which repaired the kernel
and brought the counter in one change).  A wrong answer has no ``query_s``
to compare, so such a program fails here cleanly instead."""
import re
import sys

from .standalone import Deployment as Standalone

GROUPED = re.compile(r"\bgroup\s+by\b", re.IGNORECASE)


class _Session:
    """The context, counting the grouped statements it is handed."""

    def __init__(self, deployment):
        self._deployment = deployment

    def sql(self, text: str):
        if GROUPED.search(text):
            self._deployment.grouped_statements += 1
        return self._deployment.ctx.sql(text)

    def __getattr__(self, name):
        return getattr(self._deployment.ctx, name)


def mesh_devices_of(stats) -> int:
    """The most devices a mesh operator of the job counted in one stage
    (``mesh_devices`` adds the mesh's size at every execution), 0 where
    none ran or the job left no statistics."""
    return max((int(m.get("mesh_devices", 0))
                for stage in (stats or {}).get("stages", [])
                for m in stage.get("operators", {}).values()), default=0)


def mesh_programs_counted():
    """The program's own count of the mesh programs it has dispatched in
    this process, None where it keeps none."""
    from arrow_ballista_tpu.obs.device import STATS

    return STATS.snapshot().get("mesh_programs")


def check_mesh(grouped_statements: int, job_stats: list, devices: int,
               programs: int):
    """``(ok, what was found)``: as many jobs ran a mesh operator over
    ``devices`` devices as grouped statements were served, at least one,
    and ``programs``, the mesh programs the program counted meanwhile,
    are no fewer."""
    on_mesh = sum(1 for s in job_stats if mesh_devices_of(s) >= devices)
    found = (f"{grouped_statements} grouped statements, {len(job_stats)} "
             f"jobs, {on_mesh} of them ran a mesh operator over "
             f"{devices} devices, {programs} mesh programs dispatched")
    return (grouped_statements > 0 and on_mesh == grouped_statements
            and programs >= grouped_statements), found


class Deployment(Standalone):
    def __init__(self, config: dict, data_dir: str, tables):
        if "devices" not in config.get("mesh", {}):
            raise SystemExit("benchmarks/chip/deployments/mesh4.py needs the "
                             "configuration to state its mesh: "
                             '"mesh": {"axis": ..., "devices": n}')
        self.mesh_devices = int(config["mesh"]["devices"])
        self.grouped_statements = 0
        self.programs_before = mesh_programs_counted()
        if self.programs_before is None:
            raise SystemExit(
                "benchmarks/chip/deployments/mesh4.py: this program keeps no "
                "mesh_programs counter in obs.device.STATS, so it is older "
                "than the repair of kernels.grouped_sums_i64 that came with "
                "it (PR 28) and gives wrong q1 sums on a v5e on some seeds: "
                "it cannot run this configuration to its guarantee")
        super().__init__(config, data_dir, tables)

    def session(self):
        return _Session(self)

    def close(self) -> None:
        import jax

        try:
            ok, found = check_mesh(
                self.grouped_statements,
                [self.job_stats(j["job_id"]) for j in self.submitted],
                self.mesh_devices,
                int(mesh_programs_counted() - self.programs_before))
        finally:
            super().close()
        print(f"[mesh4] {found}", file=sys.stderr, flush=True)
        if not ok and jax.devices()[0].platform == "tpu":
            raise SystemExit(f"the mesh cell did not run on the mesh: {found}")
