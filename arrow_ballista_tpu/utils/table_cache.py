"""Device-resident scan cache: HBM is the buffer pool.

The reference leans on ParquetExec + the OS page cache to make repeated
scans cheap (reference ballista/core/src/utils.rs object-store registry +
DataFusion ParquetExec; the README's benchmark methodology assumes warm
file caches).  On a TPU the analogous resource is **HBM**: the expensive
step is not the disk read but the conversion and the host->device
transfer, so the TPU-native buffer pool keeps the *converted device batches* resident
across queries.

Granularity: one entry per (scan partition, projection, capacity) — the
exact list of ColumnBatches a ``ScanExec.execute`` call produces BEFORE
filter masks are applied (filters only derive new masks on top, so cached
batches are shared safely).  Keys embed file mtime+size, so a rewritten
file can never serve stale rows; stale entries age out by LRU.

Budget: bytes of device buffers (columns + mask), LRU-evicted.  Host-side
string dictionaries ride along uncounted (they are small next to the
column data and live in host RAM).
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import List, Optional, Tuple

from ..obs.tracing import TracedLock

DEFAULT_BUDGET = 6 << 30  # fits SF10 lineitem device form in 16 GB HBM
# CPU backends: "device" arrays ARE host RAM, and every CPU-only daemon
# process would pin its own duplicate copy — keep the pool small there
DEFAULT_BUDGET_CPU = 1 << 30


def _batch_bytes(b) -> int:
    n = int(b.mask.nbytes)
    for v in b.columns.values():
        n += int(v.nbytes)
    return n


class DeviceTableCache:
    def __init__(self, budget_bytes: int = DEFAULT_BUDGET):
        self._lock = TracedLock("scan_cache")
        self._entries: "OrderedDict[Tuple, Tuple[list, int]]" = OrderedDict()
        self._bytes = 0
        self._budget = budget_bytes
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def set_budget(self, budget_bytes: int) -> None:
        with self._lock:
            if budget_bytes == self._budget:
                return
            self._budget = budget_bytes
            self._evict_locked()

    def get(self, key: Tuple) -> Optional[List]:
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return list(entry[0])

    def put(self, key: Tuple, batches: List) -> None:
        size = sum(_batch_bytes(b) for b in batches)
        with self._lock:
            if size > self._budget:
                return  # larger than the whole pool: never cache
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
            self._entries[key] = (list(batches), size)
            self._bytes += size
            self._evict_locked()

    def _evict_locked(self) -> None:
        while self._bytes > self._budget and self._entries:
            _, (_, size) = self._entries.popitem(last=False)
            self._bytes -= size
            self.evictions += 1

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0

    def stats(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "bytes": self._bytes,
                "budget": self._budget,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
            }


# process-wide singleton: same-process executors (standalone mode, daemon
# task slots) share one HBM pool the way they share the one device
CACHE = DeviceTableCache()


def resolve_budget(value) -> int:
    """Config value -> bytes.  '0'/0 -> disabled.  'auto' is keyed on the
    backend platform like ``resolve_task_budget`` (utils/config.py):
    accelerators get the HBM-sized default, CPU backends the small one."""
    if isinstance(value, str):
        if value.strip().lower() == "auto":
            from ..models.batch import _platform_remote

            return DEFAULT_BUDGET if _platform_remote() else DEFAULT_BUDGET_CPU
        value = int(value)
    return int(value)
