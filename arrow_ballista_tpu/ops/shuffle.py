"""Shuffle operators: the exchange layer between stages.

Parity with the reference's three Ballista-specific operators
(reference ballista/core/src/execution_plans/):

- ``ShuffleWriterExec`` (shuffle_writer.rs:65-424): stage root; executes its
  child for one input partition, hash-partitions rows, writes one Arrow IPC
  file per output partition under
  ``<work_dir>/<job>/<stage>/<input_partition>/data-<output_partition>.arrow``,
  returns metadata (partition, path, rows, bytes).
- ``ShuffleReaderExec`` (shuffle_reader.rs:60-411): stage leaf; reads the
  shuffle files for its output partition (local fast path; remote fetch via
  the executor data-plane client when locations are on other hosts).
- ``UnresolvedShuffleExec`` (unresolved_shuffle.rs:34-106): placeholder leaf
  for a not-yet-computed producer stage; refuses to execute.

TPU-first difference: partition ids are computed on device in the stage's
fused program (hash64 % P), rows are compacted on device, and only live rows
cross to the host for IPC write.  On-pod, `parallel/ici_shuffle.py` replaces
the file hop with an all_to_all over the ICI mesh.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..models import expr as E
from ..models.batch import ColumnBatch, concat_batches
from ..models.batch import round_capacity as _round_capacity
from ..models.ipc import crc32_file, read_ipc_files, write_ipc_file, write_ipc_rows
from ..models.schema import Schema
from ..obs.device import observed_jit
from ..utils.errors import FetchFailedError, InternalError
from .expressions import ExprCompiler
from . import kernels as K
from .physical import (ExecutionPlan, Partitioning, TaskContext,
                       exprs_sig, schema_sig, shared_program)


@dataclasses.dataclass
class ShuffleWritePartition:
    """Metadata row describing one written shuffle partition (parity:
    reference proto ShuffleWritePartition, ballista.proto:222-232)."""

    output_partition: int
    path: str
    num_rows: int
    num_bytes: int
    # CRC-32 of the file bytes, verified by remote fetchers before
    # deserialization; -1 = not recorded (pre-upgrade checkpoints)
    checksum: int = -1


@dataclasses.dataclass
class PartitionLocation:
    """Where a map output lives (reference ballista.proto:211-221).
    ``host``/``port`` address the owning executor's one RPC port, which
    serves the chunked fetch (the reference embeds ExecutorMetadata the
    same way)."""

    executor_id: str
    map_partition: int
    output_partition: int
    path: str
    num_rows: int = 0
    num_bytes: int = 0
    host: str = ""
    port: int = 0
    checksum: int = -1  # producer-recorded CRC-32; -1 = unknown, skip verify
    # on-disk representation; "" = unknown (treated as arrow_file).
    # Lets a consumer reject a same-host mmap of a format it can't read
    # if the disk layout ever changes.
    format: str = ""


class ShuffleWriterExec(ExecutionPlan):
    """``partitioning=None`` marks a **final** stage (reference
    shuffle_writer.rs with ``shuffle_output_partitioning: None``): the input
    partition's rows are written verbatim to one file, and the metadata's
    output_partition is the input partition index — the client fetches these
    as the query result."""

    def __init__(self, input: ExecutionPlan, partitioning: Optional[Partitioning],
                 stage_id: int = 0):
        self.input = input
        self.partitioning = partitioning
        self.stage_id = stage_id
        self._schema = input.schema
        self._compiled = None

    def children(self):
        return [self.input]

    def output_partition_count(self):
        # input partition count == number of map tasks
        return self.input.output_partition_count()

    def output_partitioning(self):
        return self.partitioning or Partitioning.unknown(self.output_partition_count())

    def execute_write(self, partition: int, ctx: TaskContext) -> List[ShuffleWritePartition]:
        with ctx.op_span(self):
            return self._execute_write(partition, ctx)

    def _execute_write(self, partition: int, ctx: TaskContext) -> List[ShuffleWritePartition]:
        """Run the child for ``partition`` and write shuffle files."""
        ctx.check_cancelled()
        batches = self.input.execute(partition, ctx)
        ctx.check_cancelled()
        big = concat_batches(self.input.schema, batches).shrink()
        base = os.path.join(ctx.work_dir, ctx.job_id, str(self.stage_id), str(partition))

        if self.partitioning is None:
            # final stage: pass-through; output partition == input partition
            path = os.path.join(base, "data-0.arrow")
            with self.metrics().timer("write_time"):
                rows, nbytes = write_ipc_file(big, path)
            self.metrics().add("input_rows", big.num_rows)
            self.metrics().add("output_rows", rows)
            return [ShuffleWritePartition(partition, path, rows, nbytes,
                                          checksum=crc32_file(path))]

        num_out = self.partitioning.count
        if self.partitioning.kind == "hash" and num_out > 1:
            # Device computes only the per-row bucket id (elementwise hash —
            # compiles in seconds); then ONE device->host transfer per
            # column and a host-side stable grouping sort hand the writer
            # contiguous per-partition slices that wrap zero-copy into
            # arrow arrays.  The reference streams batches through
            # BatchPartitioner+IPCWriter incrementally
            # (shuffle_writer.rs:214-252); the earlier rendition here
            # materialized num_out full-capacity host copies instead, which
            # made write_time dominate q1 wall-clock.  Grouping stays OFF
            # the device on purpose: data-dependent sorts are the one XLA
            # program measured to compile pathologically on TPU
            # (kernels.py grouped_aggregate notes).
            with self.xla_lock():
                if self._compiled is None:
                    def build():
                        comp = ExprCompiler(self.input.schema, "device")
                        keys_c = [comp.compile_key(e)
                                  for e in self.partitioning.exprs]

                        def bucket_fn(cols, mask, aux):
                            keys = [c.fn(cols, aux) for c in keys_c]
                            return K.bucket_of(keys, num_out)

                        return comp, observed_jit("shuffle.bucket",
                                                  bucket_fn)

                    self._compiled = shared_program(
                        ("bucket", num_out, schema_sig(self.input.schema),
                         exprs_sig(self.partitioning.exprs)), build)
            comp, bfn = self._compiled
            with self.metrics().timer("repart_time"):
                aux = comp.aux_arrays(big.dicts)
                # ONE packed device->host transfer for columns + bucket ids
                # + live-row count (compacted on device): a per-array fetch
                # pays a fixed transfer latency each, and padded-capacity
                # arrays multiply the bytes
                host_cols, n = big.packed_numpy(
                    hint=getattr(self, "_pack_hint", None),
                    extra32={"__bucket__": bfn(big.columns, big.mask, aux)})
                self._pack_hint = _round_capacity(n)
                buckets = host_cols.pop("__bucket__")
                order = np.argsort(buckets, kind="stable")
                counts = np.bincount(buckets, minlength=num_out)[:num_out]
                host_cols = {k: v[order] for k, v in host_cols.items()}
            offsets = np.concatenate([[0], np.cumsum(counts)])
            out: List[ShuffleWritePartition] = []
            with self.metrics().timer("write_time"):
                for q in range(num_out):
                    lo, hi = int(offsets[q]), int(offsets[q + 1])
                    data = {k: v[lo:hi] for k, v in host_cols.items()}
                    path = os.path.join(base, f"data-{q}.arrow")
                    rows, nbytes = write_ipc_rows(big.schema, data, big.dicts, path)
                    out.append(ShuffleWritePartition(q, path, rows, nbytes,
                                                     checksum=crc32_file(path)))
            self.metrics().add("input_rows", n)
            self.metrics().add("output_rows", sum(p.num_rows for p in out))
            return out

        out = []
        with self.metrics().timer("write_time"):
            for q in range(num_out):
                part_mask = big.mask if q == 0 else jnp.zeros_like(big.mask)
                pb = ColumnBatch(big.schema, big.columns, part_mask, big.dicts)
                path = os.path.join(base, f"data-{q}.arrow")
                rows, nbytes = write_ipc_file(pb, path)
                out.append(ShuffleWritePartition(q, path, rows, nbytes,
                                                 checksum=crc32_file(path)))
        self.metrics().add("input_rows", big.num_rows)
        self.metrics().add(
            "output_rows", sum(p.num_rows for p in out)
        )
        return out

    def execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        # when executed as a plain operator (local mode), write then return
        # nothing useful; the graph machinery calls execute_write directly
        self.execute_write(partition, ctx)
        return []

    def _label(self):
        part = ("final" if self.partitioning is None
                else f"{self.partitioning.kind}[{self.partitioning.count}]")
        return f"ShuffleWriterExec: stage={self.stage_id} {part}"


class ShuffleReaderExec(ExecutionPlan):
    """Reads one reduce partition's inputs from all map tasks.

    ``locations[q]`` is the list of PartitionLocation for output partition q,
    installed by the scheduler when the producer stage completes (parity:
    reference shuffle_reader.rs:60-66 partition: Vec<Vec<PartitionLocation>>).
    """

    def __init__(self, stage_id: int, schema: Schema, partition_count: int,
                 locations: Optional[Dict[int, List[PartitionLocation]]] = None):
        self.stage_id = stage_id
        self._schema = schema
        self.partition_count = partition_count
        self.locations = locations or {}

    def output_partition_count(self):
        return self.partition_count

    def execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        with ctx.op_span(self):
            return self._execute(partition, ctx)

    def _execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        locs = self.locations.get(partition)
        if locs is None:
            locs = ctx.shuffle_locations.get((self.stage_id, partition))
        if locs is None:
            raise InternalError(
                f"no shuffle locations for stage {self.stage_id} partition {partition}"
            )
        from ..utils.config import SHUFFLE_LOCAL_HOST_MATCH

        host_match = bool(ctx.config.get(SHUFFLE_LOCAL_HOST_MATCH)) \
            and bool(ctx.executor_host)
        paths = []
        colocated: List[PartitionLocation] = []
        remote: List[PartitionLocation] = []
        for loc in locs:
            if loc.num_rows == 0:
                continue  # skip empty map outputs
            # local fast path (shuffle_reader.rs:316) gated on executor
            # IDENTITY, not file existence: a same-named path on a different
            # machine may be a stale leftover.  port==0 means the deployment
            # has no data plane (in-proc / shared fs), where the path is
            # authoritative.
            if loc.executor_id == ctx.executor_id or loc.port == 0:
                if not os.path.exists(loc.path):
                    raise FetchFailedError(
                        loc.executor_id, self.stage_id, loc.map_partition,
                        f"shuffle file missing: {loc.path}")
                paths.append(loc.path)
            elif (host_match and loc.host == ctx.executor_host
                  and loc.format in ("", "arrow_file")
                  and os.path.exists(loc.path)):
                # co-located producer on the SAME advertised host: its file
                # is reachable through the filesystem, so mmap it instead of
                # round-tripping the bytes through the data plane.  The host
                # stamp comes from cluster metadata (not path guessing) and
                # the size/CRC check below rejects a stale same-named file;
                # any doubt falls back to the remote fetch.
                colocated.append(loc)
            else:
                remote.append(loc)
        with self.metrics().timer("fetch_time"):
            batches = read_ipc_files(paths, self._schema, capacity=ctx.config.batch_size)
            for loc in colocated:
                got = self._read_colocated(loc, ctx)
                if got is None:
                    remote.append(loc)  # verification failed -> fetch instead
                else:
                    batches.extend(got)
            batches.extend(self._fetch_remote_all(remote, ctx))
        self.metrics().add("output_rows", sum(b.num_rows for b in batches))
        return batches

    # back-compat alias: the reference semaphore size (shuffle_reader.rs:123),
    # now the default of config key ballista.shuffle.max_concurrent_fetches
    MAX_CONCURRENT_FETCHES = 50

    def _read_colocated(self, loc: PartitionLocation,
                        ctx: TaskContext) -> Optional[List[ColumnBatch]]:
        """Zero-copy read of a co-located producer's shuffle file via mmap,
        with lazy integrity verification: size checked against the producer's
        recorded num_bytes, then (under shuffle integrity) CRC-32 computed
        over the mapped buffer — the kernel faults pages in as the checksum
        walks them, so cold files stream once and page-cache-hot files verify
        without any copy.  Returns None when anything disagrees (stale file,
        checksum mismatch, mmap failure): the caller silently falls back to
        the remote fetch, which has its own verification + lineage escalation.
        """
        import zlib

        import pyarrow as pa
        import pyarrow.ipc as ipc

        from ..models.ipc import physical_table_to_batches
        from ..net.dataplane import STATS
        from ..utils.config import SHUFFLE_INTEGRITY

        try:
            st = os.stat(loc.path)
            if loc.num_bytes > 0 and st.st_size != loc.num_bytes:
                return None  # stale or partially-written same-named file
            path_label = "local_mmap"
            try:
                source = pa.memory_map(loc.path, "r")
            except OSError:
                # filesystem refuses mmap (some network mounts): plain read
                source = pa.OSFile(loc.path, "rb")
                path_label = "local_copy"
            with source:
                if ctx.config.get(SHUFFLE_INTEGRITY) and loc.checksum >= 0:
                    buf = source.read_buffer()  # zero-copy view of the map
                    if zlib.crc32(memoryview(buf)) != loc.checksum:
                        return None
                    source.seek(0)
                table = ipc.open_file(source).read_all()
            batches = physical_table_to_batches(table, self._schema,
                                                capacity=ctx.config.batch_size)
        except Exception:  # noqa: BLE001 — any local doubt -> remote fetch
            return None
        STATS.record(path_label, st.st_size)
        self.metrics().add(f"bytes_{path_label}", st.st_size)
        return batches

    # process-shared fetch pool: one bounded pool for ALL concurrent reduce
    # tasks, not one ThreadPoolExecutor per task invocation — with 8 reduce
    # tasks each fanning out to 48 map outputs the old scheme spun up (and
    # tore down) ~400 threads per wave.  The semaphore (sized per-call from
    # ballista.shuffle.max_concurrent_fetches) bounds in-flight fetches; the
    # pool itself is a reusable hard cap.
    _FETCH_POOL = None
    _FETCH_POOL_LOCK = __import__("threading").Lock()
    _FETCH_POOL_WORKERS = 64

    @classmethod
    def _fetch_pool(cls):
        if cls._FETCH_POOL is None:
            from concurrent.futures import ThreadPoolExecutor

            with cls._FETCH_POOL_LOCK:
                if cls._FETCH_POOL is None:
                    cls._FETCH_POOL = ThreadPoolExecutor(
                        max_workers=cls._FETCH_POOL_WORKERS,
                        thread_name_prefix="shuffle-fetch")
        return cls._FETCH_POOL

    def _fetch_remote_all(self, remote: List[PartitionLocation],
                          ctx: TaskContext) -> List[ColumnBatch]:
        """Bounded-concurrency remote fetch (reference send_fetch_partitions:
        <=50 concurrent Flight fetches, locations shuffled so simultaneous
        readers don't all hammer the same executor, shuffle_reader.rs:123,
        267-318)."""
        if not remote:
            return []
        if len(remote) == 1:
            return self._fetch_remote(remote[0], ctx)
        import random
        import threading

        from ..utils.config import SHUFFLE_MAX_CONCURRENT_FETCHES

        limit = max(1, int(ctx.config.get(SHUFFLE_MAX_CONCURRENT_FETCHES)))
        gate = threading.Semaphore(min(limit, len(remote)))
        order = list(remote)
        random.shuffle(order)

        def fetch(loc: PartitionLocation) -> List[ColumnBatch]:
            with gate:
                return self._fetch_remote(loc, ctx)

        out: List[ColumnBatch] = []
        for got in self._fetch_pool().map(fetch, order):
            out.extend(got)
        return out

    def _fetch_remote(self, loc: PartitionLocation, ctx: TaskContext) -> List[ColumnBatch]:
        from ..net.dataplane import fetch_partition

        try:
            batches, stats = fetch_partition(
                loc, self._schema, ctx.config,
                fault_ctx={"stage_id": self.stage_id,
                           "map_partition": loc.map_partition,
                           "executor_id": loc.executor_id})
        except Exception as err:  # noqa: BLE001 — retries exhausted
            raise FetchFailedError(loc.executor_id, self.stage_id, loc.map_partition,
                                   f"remote fetch failed: {err}") from err
        self.metrics().add("remote_fetches", 1)
        self.metrics().add("fetch_chunks", stats["chunks"])
        self.metrics().add("wire_bytes", stats["wire_bytes"])
        self.metrics().add("raw_bytes", stats["raw_bytes"])
        return batches

    def _label(self):
        return f"ShuffleReaderExec: stage={self.stage_id} partitions={self.partition_count}"


class UnresolvedShuffleExec(ExecutionPlan):
    def __init__(self, stage_id: int, schema: Schema, output_partition_count: int):
        self.stage_id = stage_id
        self._schema = schema
        self._count = output_partition_count

    def output_partition_count(self):
        return self._count

    def execute(self, partition: int, ctx: TaskContext):
        raise InternalError(
            f"UnresolvedShuffleExec(stage={self.stage_id}) cannot execute; "
            "the scheduler must resolve it to a ShuffleReaderExec first"
        )

    def _label(self):
        return f"UnresolvedShuffleExec: stage={self.stage_id}"


class RepartitionExec(ExecutionPlan):
    """Logical exchange marker.  In distributed plans the DistributedPlanner
    replaces it with a ShuffleWriter/Reader stage pair (the reference's
    planner does exactly this for RepartitionExec(Hash),
    reference ballista/scheduler/src/planner.rs:133-152).

    It is also directly executable for in-process local mode: the child runs
    once (all partitions, cached), rows are hash-split in memory.
    """

    def __init__(self, input: ExecutionPlan, partitioning: Partitioning):
        self.input = input
        self.partitioning = partitioning
        self._schema = input.schema
        self._cache: Optional[List[List[ColumnBatch]]] = None
        self._compiled = None

    def children(self):
        return [self.input]

    def output_partition_count(self):
        return self.partitioning.count

    def output_partitioning(self):
        return self.partitioning

    def _materialize(self, ctx: TaskContext):
        num_out = self.partitioning.count
        parts: List[List[ColumnBatch]] = [[] for _ in range(num_out)]
        if self.partitioning.kind == "hash" and num_out > 1:
            comp = ExprCompiler(self.input.schema, "device")
            keys_c = [comp.compile_key(e) for e in self.partitioning.exprs]

            def bucket_fn(cols, mask, aux):
                keys = [c.fn(cols, aux) for c in keys_c]
                b = K.bucket_of(keys, num_out)
                return [mask & (b == q) for q in range(num_out)]

            bfn = observed_jit("repartition.bucket", bucket_fn)
            for p in range(self.input.output_partition_count()):
                for b in self.input.execute(p, ctx):
                    aux = comp.aux_arrays(b.dicts)
                    masks = bfn(b.columns, b.mask, aux)
                    for q in range(num_out):
                        parts[q].append(ColumnBatch(b.schema, b.columns, masks[q], b.dicts))
        else:
            for p in range(self.input.output_partition_count()):
                parts[0].extend(self.input.execute(p, ctx))
        self._cache = parts

    def execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        with ctx.op_span(self):
            return self._execute(partition, ctx)

    def _execute(self, partition: int, ctx: TaskContext) -> List[ColumnBatch]:
        if self._cache is None:
            self._materialize(ctx)
        return self._cache[partition]

    def _label(self):
        return f"RepartitionExec: {self.partitioning.kind}[{self.partitioning.count}]"
