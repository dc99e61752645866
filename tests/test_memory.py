"""Memory-pressure robustness plane tests (arrow_ballista_tpu/memory/).

Covers the contract from the memory subsystem:

- governor reserve/grant/release accounting over the host/device pools,
  budget 0 = unlimited, ``try_reserve`` denial -> spill path (or re-raise
  with spill disabled), ``force_reserve`` over-budget grants counted;
- the ``executor.memory.reserve`` failpoint denies/delays grants so chaos
  plans can force the spill path on an unconstrained executor;
- spill runs: Arrow IPC write/read round trip, CRC verification turning
  silent disk corruption into a retryable :class:`IntegrityError`;
- concurrent reservations never oversubscribe a budgeted pool and never
  leak (final reserved == 0);
- spilled grouped aggregation and hash joins are BIT-IDENTICAL to their
  in-memory execution (the tentpole claim), via a tiny host budget that
  denies every materialization;
- executor pressure degrades scheduler offers and feeds admission
  shedding (retriable, never a quarantine strike).
"""
import threading

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from arrow_ballista_tpu import Field, INT64, Schema, faults
from arrow_ballista_tpu.memory import MemoryGovernor, Reservation, STATS
from arrow_ballista_tpu.memory.spill import Spiller
from arrow_ballista_tpu.utils.config import (
    MEM_HOST_BUDGET,
    MEM_SPILL_ENABLED,
    BallistaConfig,
)
from arrow_ballista_tpu.utils.errors import IntegrityError, MemoryExhausted


@pytest.fixture(autouse=True)
def _fresh_stats():
    """Process-global memory STATS must not leak between tests (or into
    the rest of the suite)."""
    STATS.reset()
    faults.clear()
    yield
    STATS.reset()
    faults.clear()


# --------------------------------------------------------------------------
# governor accounting units
# --------------------------------------------------------------------------

def test_unlimited_budget_always_grants_and_accounts():
    gov = MemoryGovernor()  # budget 0 = unlimited
    assert gov.available("host") is None
    r = gov.reserve(1 << 30, site="unit")
    assert gov.reserved("host") == 1 << 30
    assert STATS.snapshot()["reserved_bytes.host"] == 1 << 30
    assert gov.pressure() == 0.0, "unbudgeted pools exert no pressure"
    r.release()
    assert gov.reserved("host") == 0
    r.release()  # idempotent
    assert gov.reserved("host") == 0
    assert STATS.snapshot()["reserved_bytes.host"] == 0


def test_budgeted_reserve_denial_and_pressure():
    gov = MemoryGovernor(host_budget=1000)
    a = gov.reserve(600, site="op-a")
    assert gov.available("host") == 400
    assert gov.pressure() == pytest.approx(0.6)
    with pytest.raises(MemoryExhausted):
        gov.reserve(500, site="op-b")
    assert gov.reserved("host") == 600, "denied reservation must not leak"
    b = gov.reserve(400, site="op-b")
    assert gov.pressure() == pytest.approx(1.0)
    a.release()
    b.release()
    assert gov.pressure() == 0.0


def test_try_reserve_denial_is_the_spill_signal():
    gov = MemoryGovernor(host_budget=100)
    assert isinstance(gov.try_reserve(100), Reservation)
    denied = gov.try_reserve(1)
    assert denied is None, "None tells the operator to take its spill path"
    assert STATS.snapshot()["reserve_denied_total"] == 1


def test_try_reserve_reraises_with_spill_disabled():
    gov = MemoryGovernor(host_budget=100, spill_enabled=False)
    gov.reserve(100)
    with pytest.raises(MemoryExhausted) as exc:
        gov.try_reserve(50, site="agg-state")
    assert exc.value.retryable, \
        "a denial that cannot degrade to spill must stay retryable"
    assert STATS.snapshot()["reserve_denied_total"] == 1


def test_force_reserve_overshoots_and_counts():
    gov = MemoryGovernor(host_budget=100)
    r = gov.force_reserve(250, site="left-outer-build")
    assert gov.reserved("host") == 250
    assert gov.pressure() == pytest.approx(2.5), \
        "the overshoot must be visible in the pressure signal"
    assert STATS.snapshot()["over_budget_grants_total"] == 1
    r.release()
    # within budget: no over-budget count
    gov.force_reserve(10).release()
    assert STATS.snapshot()["over_budget_grants_total"] == 1


def test_reservation_context_manager_unwinds():
    gov = MemoryGovernor(host_budget=100)
    with pytest.raises(RuntimeError):
        with gov.reserve(80):
            assert gov.reserved("host") == 80
            raise RuntimeError("operator blew up")
    assert gov.reserved("host") == 0


def test_from_config_budgets_and_spill_knob():
    gov = MemoryGovernor.from_config(BallistaConfig({
        MEM_HOST_BUDGET: "4096", MEM_SPILL_ENABLED: "false"}))
    assert gov.budget("host") == 4096
    assert gov.budget("device") == 0
    assert gov.spill_enabled is False
    auto = MemoryGovernor.from_config(BallistaConfig({MEM_HOST_BUDGET: "auto"}))
    assert auto.budget("host") > (1 << 30), "'auto' resolves a real budget"


# --------------------------------------------------------------------------
# executor.memory.reserve failpoint
# --------------------------------------------------------------------------

def test_reserve_failpoint_denies_an_unlimited_pool():
    """Chaos plans force the spill path without configuring any budget:
    error=resource at the failpoint IS a governor denial."""
    gov = MemoryGovernor()  # unlimited
    plan = faults.FaultPlan.from_obj({"seed": 5, "rules": [{
        "site": "executor.memory.reserve", "action": "raise",
        "error": "resource", "times": 1}]})
    with faults.use_plan(plan):
        assert gov.try_reserve(1024, site="agg-state") is None
        assert gov.try_reserve(1024, site="agg-state") is not None
    assert plan.schedule() == (("executor.memory.reserve", 0, 1, "raise"),)
    assert STATS.snapshot()["reserve_denied_total"] == 1
    assert gov.reserved("host") == 1024, \
        "the denied attempt must not have reserved anything"


def test_reserve_failpoint_match_filters_on_op():
    plan = faults.FaultPlan.from_obj({"seed": 5, "rules": [{
        "site": "executor.memory.reserve", "action": "raise",
        "error": "resource", "times": -1, "match": {"op": "join-build"}}]})
    gov = MemoryGovernor()
    with faults.use_plan(plan):
        assert gov.try_reserve(10, site="agg-state") is not None
        assert gov.try_reserve(10, site="join-build") is None


# --------------------------------------------------------------------------
# concurrent reservations: no oversubscription, no leaks
# --------------------------------------------------------------------------

def test_concurrent_reservations_race():
    budget = 10_000
    gov = MemoryGovernor(host_budget=budget)
    errors = []
    granted = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        for _ in range(200):
            n = int(rng.integers(1, 4000))
            r = gov.try_reserve(n, site=f"w{seed}")
            if r is None:
                continue
            held = gov.reserved("host")
            if held > budget:
                errors.append(f"oversubscribed: {held} > {budget}")
            granted.append(n)
            r.release()

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:3]
    assert granted, "some reservations must have been granted"
    assert gov.reserved("host") == 0, "every grant must release"
    assert STATS.snapshot()["reserved_bytes.host"] == 0


# --------------------------------------------------------------------------
# spill runs: IPC round trip + CRC integrity
# --------------------------------------------------------------------------

def _spill_schema():
    return Schema([Field("g", INT64), Field("v", INT64)])


def test_spiller_round_trip(tmp_path):
    sp = Spiller(str(tmp_path), job_id="j1", tag="agg")
    schema = _spill_schema()
    sp.write_run(schema, {"g": np.array([1, 2], dtype=np.int64),
                          "v": np.array([10, 20], dtype=np.int64)}, {})
    sp.write_run(schema, {"g": np.array([3], dtype=np.int64),
                          "v": np.array([30], dtype=np.int64)}, {})
    batches = sp.read(schema)
    got = pd.concat([b.to_pandas() for b in batches], ignore_index=True)
    pd.testing.assert_frame_equal(
        got, pd.DataFrame({"g": [1, 2, 3], "v": [10, 20, 30]}),
        check_dtype=False)
    snap = STATS.snapshot()
    assert snap["spill_runs_total"] == 2
    assert snap["spill_bytes_total"] > 0
    sp.cleanup()
    assert sp.runs == []


def test_spill_corruption_detected_on_read(tmp_path):
    sp = Spiller(str(tmp_path), job_id="j1", tag="agg")
    schema = _spill_schema()
    run = sp.write_run(schema, {"g": np.arange(100, dtype=np.int64),
                                "v": np.arange(100, dtype=np.int64)}, {})
    with open(run.path, "r+b") as fh:  # silent bit rot after the CRC
        fh.seek(32)
        fh.write(b"\xff")
    with pytest.raises(IntegrityError) as exc:
        sp.read(schema)
    assert exc.value.retryable, \
        "spill corruption is lineage-recoverable, so it must be retryable"


def test_spill_write_failpoint_corrupts_after_crc(tmp_path):
    plan = faults.FaultPlan.from_obj({"seed": 3, "rules": [{
        "site": "executor.spill.write", "action": "corrupt", "times": 1}]})
    sp = Spiller(str(tmp_path), job_id="j1", tag="agg")
    schema = _spill_schema()
    with faults.use_plan(plan):
        sp.write_run(schema, {"g": np.arange(50, dtype=np.int64),
                              "v": np.arange(50, dtype=np.int64)}, {})
    assert plan.schedule() == (("executor.spill.write", 0, 1, "corrupt"),)
    with pytest.raises(IntegrityError):
        sp.read(schema)


# --------------------------------------------------------------------------
# spilled execution is bit-identical to in-memory (the tentpole claim)
# --------------------------------------------------------------------------

QUERIES = (
    # grouped aggregation: sum/count/min/max state spills per input batch
    "select g, sum(v) as s, count(*) as n, min(v) as lo, max(v) as hi "
    "from t group by g order by g",
    # hash join: the build side spills as hash-range partitions
    "select t.g, sum(t.v + d.w) as s from t join d on t.g = d.g "
    "group by t.g order by t.g",
    # semi/anti shapes ride the probe-mask merge path
    "select count(*) as n from t where g in (select g from d where w > 50)",
    "select count(*) as n from t where g not in (select g from d)",
)


def _memory_ctx(budget=None):
    from arrow_ballista_tpu.client.context import BallistaContext

    conf = {"ballista.shuffle.partitions": "4"}
    if budget is not None:
        conf[MEM_HOST_BUDGET] = str(budget)
    c = BallistaContext.local(BallistaConfig(conf))
    rng = np.random.default_rng(23)
    c.register_table("t", pa.table({
        "g": pa.array(rng.integers(0, 40, 6000).astype(np.int64)),
        "v": pa.array(rng.integers(0, 1000, 6000).astype(np.int64)),
    }))
    c.register_table("d", pa.table({
        "g": pa.array(np.arange(0, 25, dtype=np.int64)),
        "w": pa.array(rng.integers(0, 100, 25).astype(np.int64)),
    }))
    return c


def test_forced_spill_results_bit_identical():
    base_ctx = _memory_ctx()
    base = [base_ctx.sql(q).to_pandas() for q in QUERIES]
    assert STATS.snapshot().get("spill_runs_total", 0) == 0, \
        "the unlimited baseline must not spill"

    STATS.reset()
    tiny_ctx = _memory_ctx(budget=2048)  # denies every materialization
    got = [tiny_ctx.sql(q).to_pandas() for q in QUERIES]
    snap = STATS.snapshot()
    assert snap["reserve_denied_total"] > 0
    assert snap["spill_runs_total"] > 0, "the tiny budget must force spill"
    assert snap["reserved_bytes.host"] == 0, "no reservation leaks"
    for q, b, g in zip(QUERIES, base, got):
        pd.testing.assert_frame_equal(b.reset_index(drop=True),
                                      g.reset_index(drop=True))


def test_spill_disabled_denial_raises_retryable():
    from arrow_ballista_tpu.client.context import BallistaContext

    c = BallistaContext.local(BallistaConfig({
        "ballista.shuffle.partitions": "2",
        MEM_HOST_BUDGET: "1024", MEM_SPILL_ENABLED: "false"}))
    rng = np.random.default_rng(7)
    c.register_table("t", pa.table({
        "g": pa.array(rng.integers(0, 10, 4000).astype(np.int64)),
        "v": pa.array(rng.integers(0, 100, 4000).astype(np.int64)),
    }))
    with pytest.raises(MemoryExhausted):
        c.sql("select g, sum(v) as s from t group by g order by g").to_pandas()


# --------------------------------------------------------------------------
# pressure-aware offers + admission shed
# --------------------------------------------------------------------------

def test_offers_prefer_low_pressure_executors():
    from arrow_ballista_tpu.scheduler.cluster import ClusterState
    from arrow_ballista_tpu.scheduler.types import (
        ExecutorHeartbeat,
        ExecutorMetadata,
    )

    cs = ClusterState()
    for eid, pressure in (("hot", 0.95), ("calm", 0.1)):
        cs.register_executor(ExecutorMetadata(eid, task_slots=4))
        cs.save_heartbeat(ExecutorHeartbeat(eid, memory_pressure=pressure))
    got = cs.reserve_slots(2)
    assert got and all(r.executor_id == "calm" for r in got), \
        f"offers must land on the low-pressure executor first: {got}"
    assert cs.min_alive_pressure() == pytest.approx(0.1)
    cs.save_heartbeat(ExecutorHeartbeat("calm", memory_pressure=0.97))
    assert cs.min_alive_pressure() == pytest.approx(0.95), \
        "the fleet floor rises only when EVERY executor is saturated"


def test_admission_memory_shed_retriable():
    from arrow_ballista_tpu.admission import AdmissionController

    pressure = [0.99]
    failures = []
    admitted = []

    def make(threshold=0.95):
        return AdmissionController(
            admit_cb=lambda job_id, plan_fn: admitted.append(job_id),
            fail_cb=lambda job_id, msg: failures.append((job_id, msg)),
            pending_tasks_fn=lambda: 0,
            total_slots_fn=lambda: 8,
            memory_pressure_fn=lambda: pressure[0],
            memory_shed_threshold=threshold)

    ctl = make()
    ctl.submit("j-shed", lambda: None)
    assert not admitted
    assert failures and failures[0][0] == "j-shed"
    assert "memory saturated" in failures[0][1]
    assert "retry after" in failures[0][1]
    assert ctl.snapshot()["memory_shed_total"] == 1
    # pressure drops below the threshold: jobs admit normally again
    pressure[0] = 0.2
    make().submit("j-ok", lambda: None)
    assert admitted == ["j-ok"]
    # threshold <= 0 disables the feed entirely
    pressure[0] = 1.0
    make(threshold=0.0).submit("j-off", lambda: None)
    assert admitted == ["j-ok", "j-off"]


# --------------------------------------------------------------------------
# bugfix regression: governor denial never takes a quarantine strike
# --------------------------------------------------------------------------

def test_resource_exhausted_takes_no_quarantine_strike():
    """Two RESOURCE_EXHAUSTED failures back to back would quarantine the
    executor if they counted as strikes (threshold default 3, but any
    strike is wrong: the executor protected itself from OOM).  They must
    neither strike NOR clear an existing IO_ERROR streak."""
    from arrow_ballista_tpu.scheduler.types import (
        FailedReason,
        IO_ERROR,
        RESOURCE_EXHAUSTED,
        TaskId,
        TaskStatus,
    )
    from tests.test_scheduler import scheduler_test

    server, _launcher = scheduler_test(n_executors=1)
    try:
        def failed(kind, attempt):
            return TaskStatus(
                TaskId("job-m", 1, 0, task_attempt=attempt), "exec-0",
                "failed", failure=FailedReason(kind, "m"))

        for attempt in range(5):
            server._record_quarantine_signals(
                "exec-0", [failed(RESOURCE_EXHAUSTED, attempt)])
        assert server.quarantine.count() == 0, \
            "memory back-pressure must never quarantine an executor"
        # and it must not RESET a real failure streak either: two genuine
        # IO errors with a shed in between still quarantine at threshold 2
        server.quarantine.threshold = 2
        server._record_quarantine_signals("exec-0", [failed(IO_ERROR, 10)])
        server._record_quarantine_signals(
            "exec-0", [failed(RESOURCE_EXHAUSTED, 11)])
        server._record_quarantine_signals("exec-0", [failed(IO_ERROR, 12)])
        assert server.quarantine.count() == 1, \
            "a shed between two IO strikes must not have reset the streak"
    finally:
        server.shutdown()


def test_resource_exhausted_classification():
    """RESOURCE_EXHAUSTED is retryable (the scheduler re-runs the task,
    ideally elsewhere) AND bounds retries (count_to_failures, so a
    saturated cluster cannot loop a task forever) — while staying exempt
    from quarantine strikes (previous test)."""
    from arrow_ballista_tpu.scheduler.types import (
        FailedReason,
        RESOURCE_EXHAUSTED,
    )

    reason = FailedReason(RESOURCE_EXHAUSTED, "governor denied")
    assert reason.retryable
    assert reason.count_to_failures
    assert MemoryExhausted("host", 10, 0, "agg").retryable
    assert IntegrityError("executor.spill.read", "crc", path="x").retryable
