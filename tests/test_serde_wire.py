"""Exhaustive wire-type serde round-trips.

Reflects over ``serde.WIRE_TYPES`` so the NEXT control-plane dataclass that
gets registered is automatically exercised — and an unregistered one fails
the companion lint (serde-completeness) plus the sample-coverage assertion
here.  The universal property is canonical round-trip stability,
``to(from(to(x))) == to(x)``, which holds even for types whose fields
(plan objects, span objects) lack structural ``__eq__``; every encoding
must also survive ``json.dumps`` (the wire framing is JSON).
"""
import json

import numpy as np
import pyarrow as pa
import pytest

from arrow_ballista_tpu import serde
from arrow_ballista_tpu.models import expr as E
from arrow_ballista_tpu.models.schema import INT64, Field, Schema
from arrow_ballista_tpu.obs.journal import JournalEvent
from arrow_ballista_tpu.obs.tracing import Span
from arrow_ballista_tpu.ops.physical import MemoryScanExec, Partitioning
from arrow_ballista_tpu.ops.shuffle import (
    PartitionLocation,
    ShuffleWriterExec,
    ShuffleWritePartition,
)
from arrow_ballista_tpu.scheduler.types import (
    EXECUTION_ERROR,
    FETCH_PARTITION_ERROR,
    ExecutorHeartbeat,
    ExecutorMetadata,
    ExecutorReservation,
    FailedReason,
    JobLease,
    JobStatus,
    TaskDescription,
    TaskId,
    TaskStatus,
)

SCHEMA = Schema([Field("k", INT64), Field("v", INT64)])


def _plan():
    table = pa.table({"k": pa.array(np.arange(8, dtype=np.int64)),
                      "v": pa.array(np.arange(8, dtype=np.int64))})
    return ShuffleWriterExec(MemoryScanExec(SCHEMA, table, partitions=2),
                             Partitioning.hash([E.Column("k")], 4),
                             stage_id=3)


LOCATION = PartitionLocation("exec-1", 2, 5, "/tmp/shuffle/data-5.arrow",
                             num_rows=100, num_bytes=4096,
                             host="10.0.0.2", port=50051)

# representative payloads per registered wire type: defaults-only AND
# fully-populated variants, plus the tricky shapes (nested Optional
# metadata, int-keyed location maps, span-bearing statuses)
SAMPLES = {
    TaskId: [
        TaskId("job-1", 2, 7),
        TaskId("job-1", 2, 7, task_attempt=3, stage_attempt=1),
        TaskId("job-1", 2, 7, task_attempt=4, stage_attempt=1,
               speculative=True),
    ],
    TaskDescription: [
        TaskDescription(TaskId("job-1", 3, 0), _plan()),
        TaskDescription(TaskId("job-1", 3, 1), _plan(), task_internal_id=42,
                        scalars={"sq0": 12.5},
                        trace={"trace_id": "t" * 32, "span_id": "s" * 16}),
    ],
    TaskStatus: [
        TaskStatus(TaskId("job-1", 1, 0), "exec-1", "success"),
        TaskStatus(TaskId("job-1", 1, 1), "exec-2", "failed",
                   shuffle_writes=[ShuffleWritePartition(0, "/tmp/d0", 5, 64)],
                   failure=FailedReason(FETCH_PARTITION_ERROR, "gone",
                                        map_stage_id=1, map_partition_id=4,
                                        executor_id="exec-3"),
                   launch_time_ms=1, start_time_ms=2, end_time_ms=3,
                   metrics={"0:ScanExec": {"output_rows": 8}},
                   process_id="pid-1",
                   spans=[Span("task", trace_id="t" * 32, span_id="s" * 16,
                               kind="executor", start_ms=1.0, end_ms=2.0)]),
        TaskStatus(TaskId("job-1", 1, 2), "exec-1", "success",
                   device_stats={"jit_compiles": 4, "jit_retraces": 1,
                                 "jit_compile_time": 0.82,
                                 "h2d_bytes": 17408, "d2h_bytes": 16392,
                                 "device_mem_peak": 262144,
                                 "host_mem_peak": 104857600}),
    ],
    FailedReason: [
        FailedReason(EXECUTION_ERROR, "boom"),
        FailedReason(FETCH_PARTITION_ERROR, "lost", map_stage_id=2,
                     map_partition_id=9, executor_id="exec-9"),
    ],
    ShuffleWritePartition: [
        ShuffleWritePartition(3, "/tmp/shuffle/data-3.arrow", 128, 8192),
        ShuffleWritePartition(4, "/tmp/shuffle/data-4.arrow", 128, 8192,
                              checksum=0xDEADBEEF),
    ],
    PartitionLocation: [
        PartitionLocation("exec-1", 0, 1, "/tmp/p"),
        PartitionLocation("exec-1", 0, 2, "/tmp/p2", checksum=0xCAFEF00D),
        PartitionLocation("exec-1", 1, 3, "/tmp/p3", num_rows=9,
                          num_bytes=512, host="10.0.0.3", port=50051,
                          checksum=0x1234, format="arrow_file"),
        LOCATION,
    ],
    ExecutorMetadata: [
        ExecutorMetadata("exec-1"),
        ExecutorMetadata("exec-2", host="10.0.0.9", port=7000,
                         task_slots=8),
    ],
    ExecutorHeartbeat: [
        ExecutorHeartbeat("exec-1", timestamp=123.5),
        ExecutorHeartbeat("exec-2", timestamp=124.0, status="terminating",
                          metadata=ExecutorMetadata("exec-2", port=7000)),
        ExecutorHeartbeat("exec-3", timestamp=125.0, memory_pressure=0.7),
    ],
    ExecutorReservation: [
        ExecutorReservation("exec-1"),
        ExecutorReservation("exec-2", job_id="job-9"),
    ],
    JobStatus: [
        JobStatus("job-1", "running"),
        JobStatus("job-2", "failed", error="shed", retriable=True),
        JobStatus("job-3", "successful",
                  locations={0: [LOCATION], 3: [LOCATION, LOCATION]}),
    ],
    JobLease: [
        JobLease("job-1"),
        JobLease("job-2", owner="scheduler-a1b2", epoch=7, ts=1700000000.25,
                 endpoint="10.0.0.7:50050"),
    ],
    JournalEvent: [
        JournalEvent(seq=1, ts_ms=1700000000123, kind="job.submitted"),
        JournalEvent(seq=9, ts_ms=1700000000456, kind="task.finish",
                     actor="scheduler-a1b2", job_id="job-1", epoch=3,
                     parent=4, attrs={"stage_id": 2, "partition": 0,
                                      "attempt": 1, "state": "success",
                                      "executor_id": "exec-1"}),
    ],
}


def test_every_wire_type_has_samples():
    missing = [t.__name__ for t in serde.WIRE_TYPES if t not in SAMPLES]
    assert not missing, (
        f"wire types without representative payloads: {missing} — add "
        f"SAMPLES entries so new registrations are actually exercised")
    stale = [t.__name__ for t in SAMPLES if t not in serde.WIRE_TYPES]
    assert not stale, f"SAMPLES covers unregistered types: {stale}"


@pytest.mark.parametrize("wire_type", sorted(serde.WIRE_TYPES,
                                             key=lambda t: t.__name__),
                         ids=lambda t: t.__name__)
def test_round_trip_stability_and_json_safety(wire_type):
    to_obj, from_obj = serde.WIRE_TYPES[wire_type]
    for sample in SAMPLES.get(wire_type, []):
        encoded = to_obj(sample)
        # the wire framing is JSON: every encoding must survive it verbatim
        rehydrated = json.loads(json.dumps(encoded))
        decoded = from_obj(rehydrated)
        assert isinstance(decoded, wire_type)
        assert to_obj(decoded) == encoded, (
            f"{wire_type.__name__} round-trip is not stable")


def test_decoded_fields_match_for_value_types():
    """Types whose fields are all plain values must decode EQUAL, not just
    stably — catches a to/from pair that consistently drops a field."""
    for wire_type in (TaskId, FailedReason, ShuffleWritePartition,
                      PartitionLocation, ExecutorMetadata,
                      ExecutorReservation, JobLease):
        to_obj, from_obj = serde.WIRE_TYPES[wire_type]
        for sample in SAMPLES[wire_type]:
            assert from_obj(json.loads(json.dumps(to_obj(sample)))) == sample


def test_job_status_locations_rekeyed_to_int():
    to_obj, from_obj = serde.WIRE_TYPES[JobStatus]
    decoded = from_obj(json.loads(json.dumps(to_obj(SAMPLES[JobStatus][2]))))
    assert set(decoded.locations) == {0, 3}
    assert all(isinstance(k, int) for k in decoded.locations)
    assert decoded.locations[3][1] == LOCATION


def test_heartbeat_nested_metadata_round_trips():
    to_obj, from_obj = serde.WIRE_TYPES[ExecutorHeartbeat]
    hb = SAMPLES[ExecutorHeartbeat][1]
    decoded = from_obj(json.loads(json.dumps(to_obj(hb))))
    assert decoded.metadata == hb.metadata
    assert from_obj(to_obj(SAMPLES[ExecutorHeartbeat][0])).metadata is None


def test_heartbeat_memory_pressure_omitted_when_zero():
    """Pressure 0.0 (the unbudgeted default) must stay off the wire so
    idle fleets and old-wire peers pay nothing; a nonzero value round
    trips exactly."""
    to_obj, from_obj = serde.WIRE_TYPES[ExecutorHeartbeat]
    calm = to_obj(SAMPLES[ExecutorHeartbeat][0])
    assert "memory_pressure" not in calm
    assert from_obj(calm).memory_pressure == 0.0
    hot = to_obj(SAMPLES[ExecutorHeartbeat][2])
    assert hot["memory_pressure"] == pytest.approx(0.7)
    assert from_obj(json.loads(json.dumps(hot))).memory_pressure == \
        pytest.approx(0.7)


def test_scalarref_carries_dtype_for_planless_substitution():
    """A deserialized scalar ref has no plan (only the id crosses the
    wire) — the result dtype must ride along so remote executors can
    re-scale decimal scaled-int values without dereferencing the plan."""
    from arrow_ballista_tpu.models.schema import DataType
    from arrow_ballista_tpu.ops.operators import _substitute_scalars

    dec = Schema([Field("s", DataType("decimal", 2))])

    class _Plan:  # serialization only reads plan.schema
        schema = dec

    plan = E.ScalarSubquery(_Plan())
    object.__setattr__(plan, "scalar_id", "sq7")

    obj = json.loads(json.dumps(serde.expr_to_obj(plan)))
    assert obj["dt"] == {"kind": "decimal", "scale": 2}

    decoded = serde.expr_from_obj(obj)
    assert decoded.plan is None
    assert decoded.scalar_dtype == DataType("decimal", 2)
    # re-serialization of a deserialized ref keeps the dtype (executors
    # re-serde plans on some paths)
    assert serde.expr_to_obj(decoded)["dt"] == obj["dt"]

    # value arrives as a raw scaled int; substitution must rescale it
    # using the attached dtype, not the (absent) plan schema
    lit = _substitute_scalars(decoded, {"sq7": 12345})
    assert isinstance(lit, E.Lit)
    assert lit.value == 123.45


def test_device_stats_key_absent_when_empty():
    """Observatory-off statuses must be byte-identical to the pre-device
    wire format: the device_stats key only appears when non-empty."""
    bare = TaskStatus(TaskId("job-1", 4, 0), "exec-1", "success")
    obj = serde.status_to_obj(bare)
    assert "device_stats" not in obj
    assert serde.status_from_obj(obj).device_stats == {}
    carrying = TaskStatus(TaskId("job-1", 4, 1), "exec-1", "success",
                          device_stats={"h2d_bytes": 1024})
    assert serde.status_to_obj(carrying)["device_stats"] == \
        {"h2d_bytes": 1024}


def test_journal_key_absent_when_empty():
    """Flight-recorder-off statuses and checkpoints must be byte-identical
    to the pre-journal wire format: the journal key only appears when
    events actually ride along (same contract as device_stats)."""
    bare = TaskStatus(TaskId("job-1", 4, 0), "exec-1", "success")
    obj = serde.status_to_obj(bare)
    assert "journal" not in obj
    assert serde.status_from_obj(obj).journal == []
    events = [{"seq": 3, "ts_ms": 1700000000789, "kind": "task.run",
               "actor": "exec-1", "job_id": "job-1",
               "attrs": {"stage_id": 4, "partition": 0}}]
    carrying = TaskStatus(TaskId("job-1", 4, 1), "exec-1", "success",
                          journal=[dict(e) for e in events])
    wired = json.loads(json.dumps(serde.status_to_obj(carrying)))
    assert serde.status_from_obj(wired).journal == events
