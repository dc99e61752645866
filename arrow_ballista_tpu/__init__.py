"""arrow_ballista_tpu: a TPU-native distributed SQL query engine.

Ground-up rebuild of the capabilities of arrow-ballista (distributed SQL on
Arrow/DataFusion, reference at /root/reference) re-designed for TPU:

- columnar data lives as fixed-capacity JAX device arrays (HBM-resident),
- physical operators are XLA/Pallas programs with static shapes,
- shuffles run over the ICI mesh via all_to_all when co-located, with an
  Arrow-IPC file/stream fallback across hosts,
- the control plane (scheduler, execution graph, fault tolerance) keeps the
  reference's architecture: stage DAGs split at exchange boundaries, event-
  driven scheduling, shuffle-lineage retry.
"""
from __future__ import annotations

import os as _os
import sys as _sys

# pyarrow's bundled mimalloc pool was observed corrupting memory when it
# shares a process with XLA's runtime (scheduler daemon SIGSEGV inside
# ipc write_table, ~60% of runs; 10/10 clean with the system allocator).
# Force the system pool before pyarrow first allocates.
_os.environ.setdefault("ARROW_DEFAULT_MEMORY_POOL", "system")
if "pyarrow" in _sys.modules:  # imported before us: switch the pool live
    try:
        _sys.modules["pyarrow"].set_memory_pool(
            _sys.modules["pyarrow"].system_memory_pool())
    except Exception:  # noqa: BLE001 — allocator choice is a mitigation
        pass

# A process pinned to the CPU platform loads its cached host programs with a
# ~3KB benign feature-mismatch ERROR pair each on XLA's C++ stderr channel
# (the prefer-no-scatter/gather tuning pseudo-features never appear in the
# host probe) — enough to fill a pipe nobody drains and wedge a daemon.
# Engine errors surface as Python exceptions, so that channel is silenced
# there unless the user overrides.  On an accelerator it stays open: it is
# where the compiler and the runtime say why they refused something.
if _os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
    _os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import jax as _jax

# int64 is load-bearing: decimals are fixed-point int64 (exact money math on
# TPU, which has no native f64).  Without x64, JAX silently truncates to int32.
_jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: every ctx.sql() builds fresh operator
# instances, so in-memory jit caches never hit across queries — but the HLO
# is identical, and device sort / cumsum programs take tens of seconds each
# to compile for the TPU.  The disk cache turns repeat compiles into loads,
# across queries AND processes.
#
# The caller places the cache: JAX reads JAX_COMPILATION_CACHE_DIR itself,
# and where it is set no directory is set here.  Otherwise one fixed
# directory in the checkout serves every process and platform — the path is
# part of the cache key, so a directory that moves never hits.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _cache = _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".xla_cache")
    _os.makedirs(_cache, exist_ok=True)
    _jax.config.update("jax_compilation_cache_dir", _cache)
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
_jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

__version__ = "0.1.0"

from .models.schema import (  # noqa: E402,F401
    BOOL,
    DATE32,
    FLOAT32,
    FLOAT64,
    INT32,
    INT64,
    STRING,
    DataType,
    Field,
    Schema,
    decimal,
)
from .models.batch import ColumnBatch, concat_batches  # noqa: E402,F401
from .utils.config import BallistaConfig  # noqa: E402,F401


def __getattr__(name):
    # Lazy: avoid importing the whole engine for schema-only users.
    if name == "BallistaContext":
        try:
            from .client.context import BallistaContext
        except ModuleNotFoundError as e:
            raise AttributeError(f"BallistaContext unavailable: {e}") from e
        return BallistaContext
    raise AttributeError(name)
