"""Per-executor memory governor: reserve -> grant -> release accounting.

The data plane's two unbounded-state consumers — hash-join build sides
and grouped-aggregation state — ask the governor for a reservation
*before* materializing.  A grant means "proceed in memory"; a denial
means "degrade to spill" (memory/spill.py), never "crash the executor".
Two pools:

- ``host``   — RSS budget (``ballista.memory.host.budget.bytes``).
  Pure reservation accounting: the governor is the only admission gate,
  so reserved bytes are the authoritative model of operator-held state.
- ``device`` — HBM budget (``ballista.memory.device.budget.bytes``),
  fed by the PR-12 watermark sampler: availability subtracts the *live*
  device-buffer bytes the observatory measures, so reservations compose
  with allocations the governor never saw (compiled program temps,
  cached build sides).

The reserve path is a failpoint (``executor.memory.reserve``): chaos
runs deny or delay grants here to force the spill path and prove it
bit-identical.  A denial raises :class:`~..utils.errors.MemoryExhausted`
— retryable back-pressure by classification, and explicitly exempted from
quarantine strikes (scheduler/scheduler.py): an executor protecting
itself must not be blamed into quarantine for it.

Process-global :data:`STATS` mirrors the data-plane/device observatories
(models/ipc.py STATS, obs/device.py STATS): executor metrics gather the
``memory_reserved_bytes`` gauge and ``memory_spill_bytes_total`` counter
from here.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional

from .. import faults
from ..obs.tracing import TracedLock
from ..utils.config import (
    MEM_DEVICE_BUDGET,
    MEM_HOST_BUDGET,
    MEM_SPILL_ENABLED,
    resolve_pool_budget,
)
from ..utils.errors import MemoryExhausted

#: reservation pools; ``host`` covers operator state materialized via
#: host-visible buffers, ``device`` covers HBM-resident state.
POOLS = ("host", "device")


class _MemoryStats:
    """Process-global memory-plane totals (one per executor process;
    standalone in-proc executors share it, same as the data-plane
    STATS)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._c: Dict[str, float] = {}
        self._reserved: Dict[str, int] = {p: 0 for p in POOLS}

    def add(self, key: str, v: float = 1) -> None:
        with self._lock:
            self._c[key] = self._c.get(key, 0) + v

    def reserve_delta(self, pool: str, delta: int) -> None:
        with self._lock:
            self._reserved[pool] = self._reserved.get(pool, 0) + delta

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            out = dict(self._c)
            for p, v in self._reserved.items():
                out[f"reserved_bytes.{p}"] = v
            return out

    def reset(self) -> None:
        with self._lock:
            self._c.clear()
            self._reserved = {p: 0 for p in POOLS}


STATS = _MemoryStats()


def _device_live_bytes() -> int:
    """Live HBM bytes per the PR-12 watermark sampler (0 when the
    observatory is off — the device pool then degrades to pure
    reservation accounting, same model as the host pool)."""
    try:
        from ..obs import device as device_obs

        sample = device_obs.sample_watermarks()
        if sample is not None:
            return int(sample[0])
    except Exception:
        pass
    return 0


class Reservation:
    """A granted byte reservation; context-managed or released
    explicitly.  ``release()`` is idempotent (operators release eagerly
    on the happy path and rely on ``with`` for unwind)."""

    __slots__ = ("pool", "nbytes", "_gov")

    def __init__(self, gov: "MemoryGovernor", pool: str, nbytes: int):
        self._gov = gov
        self.pool = pool
        self.nbytes = int(nbytes)

    def release(self) -> None:
        gov, self._gov = self._gov, None
        if gov is not None:
            gov._release(self.pool, self.nbytes)

    def __enter__(self) -> "Reservation":
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def __repr__(self):
        state = "released" if self._gov is None else "held"
        return f"Reservation({self.pool}, {self.nbytes} bytes, {state})"


class MemoryGovernor:
    """Reserve/grant/release accounting over the host and device pools.

    Budget 0 means *unlimited* (the default): every reservation is
    granted and only the accounting runs — so the pressure signal and
    metrics work even on unconstrained executors.  Thread-safe: task
    pool threads reserve concurrently.
    """

    def __init__(self, host_budget: int = 0, device_budget: int = 0,
                 spill_enabled: bool = True):
        self._lock = TracedLock("memory_governor")
        self._budget = {"host": int(host_budget),
                        "device": int(device_budget)}
        self._reserved = {p: 0 for p in POOLS}
        self.spill_enabled = bool(spill_enabled)

    @staticmethod
    def from_config(cfg) -> "MemoryGovernor":
        return MemoryGovernor(
            host_budget=resolve_pool_budget(cfg, MEM_HOST_BUDGET),
            device_budget=resolve_pool_budget(cfg, MEM_DEVICE_BUDGET),
            spill_enabled=cfg.get(MEM_SPILL_ENABLED))

    # --- introspection --------------------------------------------------
    def budget(self, pool: str = "host") -> int:
        return self._budget[pool]

    def reserved(self, pool: str = "host") -> int:
        with self._lock:
            return self._reserved[pool]

    def available(self, pool: str = "host") -> Optional[int]:
        """Grantable bytes, or None when the pool is unlimited."""
        budget = self._budget[pool]
        if budget <= 0:
            return None
        extern = _device_live_bytes() if pool == "device" else 0
        with self._lock:
            return budget - self._reserved[pool] - extern

    def pressure(self) -> float:
        """Fraction of the most-loaded budgeted pool in use (0.0 when
        every pool is unlimited).  Rides executor heartbeats into the
        scheduler's offer ordering and admission shed decisions."""
        worst = 0.0
        for pool, budget in self._budget.items():
            if budget <= 0:
                continue
            extern = _device_live_bytes() if pool == "device" else 0
            with self._lock:
                used = self._reserved[pool] + extern
            worst = max(worst, used / budget)
        return worst

    # --- reserve / release ----------------------------------------------
    def reserve(self, nbytes: int, pool: str = "host", *,
                site: str = "") -> Reservation:
        """Grant ``nbytes`` from ``pool`` or raise
        :class:`MemoryExhausted`.  The failpoint fires first so chaos
        plans can deny (``error=resource``) or delay any grant."""
        nbytes = int(nbytes)
        faults.inject("executor.memory.reserve", pool=pool, nbytes=nbytes,
                      op=site)
        budget = self._budget[pool]
        extern = _device_live_bytes() if pool == "device" else 0
        with self._lock:
            if budget > 0:
                avail = budget - self._reserved[pool] - extern
                if nbytes > avail:
                    raise MemoryExhausted(pool, nbytes, max(0, avail), site)
            self._reserved[pool] += nbytes
        STATS.reserve_delta(pool, nbytes)
        return Reservation(self, pool, nbytes)

    def try_reserve(self, nbytes: int, pool: str = "host", *,
                    site: str = "") -> Optional[Reservation]:
        """Grant-or-None: the operator protocol.  None tells the caller
        to take its spill path (or, with spill disabled, to re-raise the
        denial so the scheduler retries the task elsewhere)."""
        try:
            return self.reserve(nbytes, pool, site=site)
        except MemoryExhausted:
            STATS.add("reserve_denied_total")
            if not self.spill_enabled:
                raise
            return None

    def force_reserve(self, nbytes: int, pool: str = "host", *,
                      site: str = "") -> Reservation:
        """Over-budget grant for operators with a hard single-pass
        requirement (left/full outer joins must see the whole build
        side).  Never denies; the overshoot is visible in the pressure
        signal and the ``over_budget_grants_total`` counter so the
        doctor can point at the query shape."""
        nbytes = int(nbytes)
        avail = self.available(pool)
        if avail is not None and nbytes > avail:
            STATS.add("over_budget_grants_total")
        with self._lock:
            self._reserved[pool] += nbytes
        STATS.reserve_delta(pool, nbytes)
        return Reservation(self, pool, nbytes)

    def _release(self, pool: str, nbytes: int) -> None:
        with self._lock:
            self._reserved[pool] -= nbytes
        STATS.reserve_delta(pool, -nbytes)
