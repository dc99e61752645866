"""Device-level execution observatory: the accounting layer UNDER the
operator metrics.

The operator observability stack (obs/stats.py, obs/tracing.py) stops at
the operator boundary — rows, bytes, wall-time.  This module observes the
JAX layer underneath, the part that actually decides single-query speed
on an accelerator:

- **JIT compiles / retraces / cache hits** per (operator signature,
  shape key).  ``observed_jit`` wraps ``jax.jit``, names the program
  after its signature (the name XLA, the profiler and the compile cache
  see) and mirrors XLA's own trace-cache discipline: arrays key by
  (shape, dtype), static args by
  value, traced Python scalars by type only.  First key seen through a
  wrapper is a *compile*, every later new key is a *retrace*, a repeat
  key is a *cache hit*.  Compile wall-time is the dispatch time of the
  first call at a new key (trace + lowering + backend compile happen
  synchronously inside it).
- **Host<->device transfer bytes** through the engine's two sanctioned
  materialization sites (``ColumnBatch.from_numpy`` / ``packed_numpy``,
  models/batch.py) — the same boundary the hot-path-purity lint models —
  and, as intervals, every place a task thread waits for the device:
  ``device_wait`` / ``h2d`` scopes that are spans of obs/tracing.py and
  feed the byte counters from the same two readings of the clock.
- **Memory watermarks**: live device-buffer bytes (``jax.live_arrays``)
  and host RSS peak, sampled at task and operator boundaries.

The static mirror of this runtime view is
``analysis/jit_discipline.py``: it models every ``observed_jit`` site
ahead of time (trace-key stability, donation safety, host/device
boundary) and reports findings under the same operator signatures these
counters use, so a predicted retrace storm and a measured one carry the
same name.

Attribution is scope-based and thread-local: ``TaskContext.op_span``
enters an *op scope* (the operator's MetricsSet), the executor's
``run_task`` enters a *task scope* (a per-task accumulator that becomes
``TaskStatus.device_stats``), and a process-global ``STATS`` feeds the
executor's ``/metrics`` exposition.  Device events recorded while a
scope is open land in all three; the MetricsSet keys reuse the existing
``_time``/``_bytes`` suffix conventions so they fold into stage
summaries, EXPLAIN ANALYZE and profiles with no extra plumbing.

Everything is behind ``ballista.observability.device.enabled``; when off
every entry point is one predicate check and the scopes are a shared
null context manager.
"""
from __future__ import annotations

import contextlib
import functools
import inspect
import re
import threading
import types
from typing import Dict, Iterable, Optional, Tuple

from . import tracing

# process-wide switches; flipped from config by Executor.__init__ and the
# local-engine entry points (module default matches the config default)
_enabled = True
_watermarks = True


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def set_watermarks(on: bool) -> None:
    global _watermarks
    _watermarks = bool(on)


# --------------------------------------------------------------------------
# process-global counters (executor /metrics)
# --------------------------------------------------------------------------

_COUNTER_KEYS = (
    "jit_compiles", "jit_retraces", "jit_cache_hits", "jit_compile_time",
    "h2d_bytes", "d2h_bytes", "h2d_transfers", "d2h_transfers",
    "h2d_time", "d2h_time",
    "program_cache_hits", "program_cache_misses",
    "mesh_reshard_bytes", "mesh_programs", "mesh_collective_bytes",
    "mesh_unshard_bytes", "mesh_exchange_retries",
)
_PEAK_KEYS = ("device_live_peak_bytes", "host_rss_peak_bytes")


class _ProcessStats:
    """Monotone process totals + watermark maxima (one per executor
    process; standalone in-proc executors share it, same as the
    data-plane STATS)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._c: Dict[str, float] = {k: 0 for k in _COUNTER_KEYS}
        self._p: Dict[str, int] = {k: 0 for k in _PEAK_KEYS}

    def add(self, key: str, v: float) -> None:
        with self._lock:
            self._c[key] = self._c.get(key, 0) + v

    def peak(self, key: str, v: int) -> None:
        with self._lock:
            if v > self._p.get(key, 0):
                self._p[key] = v

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            out = dict(self._c)
            out.update(self._p)
            return out

    def reset(self) -> None:  # test hook
        with self._lock:
            self._c = {k: 0 for k in _COUNTER_KEYS}
            self._p = {k: 0 for k in _PEAK_KEYS}


STATS = _ProcessStats()

# --------------------------------------------------------------------------
# scope stacks (thread-local: a task runs on one pool thread; work an
# operator farms to helper threads is attributed to process totals only)
# --------------------------------------------------------------------------

_tls = threading.local()

_NULL = contextlib.nullcontext()


def _op_stack(create: bool = False):
    s = getattr(_tls, "ops", None)
    if s is None and create:
        s = _tls.ops = []
    return s


def _task_stack(create: bool = False):
    s = getattr(_tls, "tasks", None)
    if s is None and create:
        s = _tls.tasks = []
    return s


def _record(key: str, v: float) -> None:
    """Fold one device event into every open scope + the process totals."""
    STATS.add(key, v)
    ops = _op_stack()
    if ops:
        ops[-1].add(key, v)
    tasks = _task_stack()
    if tasks:
        tasks[-1].add(key, v)


class _OpScope:
    """Binds an operator's MetricsSet as the attribution target for
    device events recorded inside its execute span."""

    __slots__ = ("_ms",)

    def __init__(self, op):
        self._ms = op.metrics()

    def __enter__(self):
        _op_stack(create=True).append(self._ms)
        return self

    def __exit__(self, *exc):
        stack = _op_stack()
        if stack:
            stack.pop()
        sample_watermarks()
        return False


def op_scope(op):
    """Device-attribution scope for one operator execute call (entered by
    ``TaskContext.op_span`` regardless of tracing; a shared null context
    when the observatory is off)."""
    if not _enabled:
        return _NULL
    return _OpScope(op)


class TaskAccumulator:
    """Per-task device-event fold; ``snapshot()`` becomes
    ``TaskStatus.device_stats`` (only when non-empty, so disabled mode
    adds no serde keys)."""

    __slots__ = ("_lock", "values")

    def __init__(self):
        self._lock = threading.Lock()
        self.values: Dict[str, float] = {}

    def add(self, key: str, v: float) -> None:
        with self._lock:
            self.values[key] = self.values.get(key, 0) + v

    def peak(self, key: str, v: int) -> None:
        with self._lock:
            if v > self.values.get(key, 0):
                self.values[key] = v

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            out = {}
            for k, v in sorted(self.values.items()):
                out[k] = round(v, 6) if isinstance(v, float) else v
            return out


class _TaskScope:
    __slots__ = ("acc",)

    def __init__(self):
        self.acc = TaskAccumulator()

    def __enter__(self):
        _task_stack(create=True).append(self.acc)
        sample_watermarks()
        return self.acc

    def __exit__(self, *exc):
        sample_watermarks()
        stack = _task_stack()
        if stack:
            stack.pop()
        return False


class _NullTaskScope:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_TASK = _NullTaskScope()


def task_scope():
    """Device-accounting scope for one executor task; yields the
    accumulator (or None when the observatory is off)."""
    if not _enabled:
        return _NULL_TASK
    return _TaskScope()


# --------------------------------------------------------------------------
# event recorders
# --------------------------------------------------------------------------

def record_transfer(direction: str, nbytes: int, seconds: float = 0.0) -> None:
    """Account one host<->device materialization.  ``direction`` is
    ``"h2d"`` (device_put dispatch) or ``"d2h"`` (device_get / np.asarray
    materialization).  ``seconds`` is the dispatch wall-time — for d2h
    (synchronous) that is the full transfer; for h2d it is enqueue cost."""
    if not _enabled:
        return
    _record(f"{direction}_bytes", int(nbytes))
    _record(f"{direction}_transfers", 1)
    if seconds:
        _record(f"{direction}_time", seconds)


class _Boundary:
    """A task thread at the host/device boundary, as an interval: a
    ``device_wait`` span (the thread blocks until the device has produced
    what it fetches; ``site`` ``d2h`` for a bulk fetch, ``scalar`` for a
    flag or a count, ``ready`` for arrays that stay on the device) or an
    ``h2d`` span (the enqueue cost of ``device_put``).  Bulk transfers
    feed ``record_transfer`` from the span's own two readings of the
    clock; set ``nbytes`` inside the block where the size is only known
    afterwards.  Scalar waits carry a handful of bytes, ``ready`` waits
    none, and both stay out of the byte counters."""
    __slots__ = ("name", "site", "nbytes", "_scope", "_span", "_t0")

    def __init__(self, name: str, site: str, nbytes: int = 0):
        self.name, self.site, self.nbytes = name, site, nbytes

    def __enter__(self):
        self._scope = tracing.span(self.name, "device", site=self.site)
        self._span = sp = self._scope.__enter__()
        self._t0 = sp.start_ns or tracing.now_ns()
        return self

    def __exit__(self, et, ev, tb):
        sp = self._span
        if self.nbytes:
            sp.set(bytes=int(self.nbytes))
        self._scope.__exit__(et, ev, tb)
        if self.site in ("d2h", "h2d") and et is None:
            record_transfer(self.site, self.nbytes,
                            ((sp.end_ns or tracing.now_ns()) - self._t0)
                            / 1e9)
        return False


def device_wait(site: str, nbytes: int = 0) -> _Boundary:
    return _Boundary("device_wait", site, nbytes)


def h2d(nbytes: int) -> _Boundary:
    return _Boundary("h2d", "h2d", nbytes)


def record_mesh_reshard(nbytes: int) -> None:
    """Bytes of one batch placed row-sharded over the device mesh
    (ops/mesh_exec.py ``_shard_rows``): device to devices, no host
    crossing, so not ``h2d_bytes``."""
    if _enabled:
        _record("mesh_reshard_bytes", int(nbytes))


def record_mesh_program(collective_bytes: int) -> None:
    """One dispatched mesh program and the bytes it hands to its
    collective, summed over the devices (parallel/distributed.py
    ``MeshProgram.collective_bytes``)."""
    if _enabled:
        _record("mesh_programs", 1)
        _record("mesh_collective_bytes", int(collective_bytes))


def record_mesh_unshard(nbytes: int) -> None:
    """Bytes of a mesh program's outputs brought onto one device
    (ops/mesh_exec.py ``_unshard``): devices to device, no host crossing,
    so not ``d2h_bytes``."""
    if _enabled:
        _record("mesh_unshard_bytes", int(nbytes))


def record_mesh_exchange_retry() -> None:
    """One exchange program run again at the send-bucket capacity its
    first run found it needed (ops/mesh_exec.py ``MeshAggregateExec``)."""
    if _enabled:
        _record("mesh_exchange_retries", 1)


def record_program_cache(hit: bool) -> None:
    """Hit/miss accounting for the process-wide shared_program cache
    (ops/physical.py)."""
    if not _enabled:
        return
    _record("program_cache_hits" if hit else "program_cache_misses", 1)


def sample_watermarks() -> Optional[Tuple[int, int]]:
    """Sample device live-buffer bytes + host RSS peak and fold the maxima
    into the open task scope and the process stats.  Returns the sample
    (device_bytes, host_rss_bytes) or None when off."""
    if not (_enabled and _watermarks):
        return None
    dev = 0
    try:
        import jax

        for a in jax.live_arrays():
            dev += int(getattr(a, "nbytes", 0) or 0)
    except Exception:  # noqa: BLE001 — watermarks are best-effort
        dev = 0
    rss = 0
    try:
        import resource

        # ru_maxrss is KB on Linux (bytes on macOS; close enough for a
        # watermark — the exposition documents the Linux unit)
        rss = int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024
    except Exception:  # noqa: BLE001
        rss = 0
    STATS.peak("device_live_peak_bytes", dev)
    STATS.peak("host_rss_peak_bytes", rss)
    tasks = _task_stack()
    if tasks:
        tasks[-1].peak("device_mem_peak", dev)
        tasks[-1].peak("host_mem_peak", rss)
        tasks[-1].add("watermark_samples", 1)
    return dev, rss


class ProfilerSession:
    """A ``jax.profiler`` session held open around an executor's task
    execution (``executor_daemon --profile-dir``): the device's operations
    and, in ``/host:CPU``, every span of obs/tracing.py on the thread that
    did the work.  ``write`` stops the session, which writes
    ``<dir>/plugins/profile/<time>/*.xplane.pb``, and opens the next one
    unless told not to.  The profiler is process-wide: one session at a
    time, whoever starts it."""

    def __init__(self, directory: str):
        self.directory = directory
        self._lock = threading.Lock()
        self._open = False

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # the program's own spans suffice
        opts.host_tracer_level = 1
        with self._lock:
            if not self._open:
                jax.profiler.start_trace(self.directory,
                                         profiler_options=opts)
                self._open = True

    def write(self, reopen: bool = True) -> None:
        import jax

        with self._lock:
            if self._open:
                jax.profiler.stop_trace()
                self._open = False
        if reopen:
            self.start()


# --------------------------------------------------------------------------
# observed_jit: the compile/retrace observatory
# --------------------------------------------------------------------------

def _shape_key(x):
    """XLA trace-cache key of one traced argument: arrays -> (shape,
    dtype), containers recurse, plain Python scalars -> type only (jax
    weak-types them, so a changed value alone does not retrace)."""
    shape = getattr(x, "shape", None)
    if shape is not None:
        return ("a", tuple(shape), str(getattr(x, "dtype", "")))
    if isinstance(x, (list, tuple)):
        return ("c", tuple(_shape_key(v) for v in x))
    if isinstance(x, dict):
        return ("d", tuple((k, _shape_key(x[k])) for k in sorted(x)))
    return ("t", type(x).__name__)


def _static_key(x):
    try:
        hash(x)
        return ("s", x)
    except TypeError:
        return ("s", repr(x))


def program_name(sig: str, variant: str = "") -> str:
    """The name a program carries into XLA (module ``jit_<name>``), the
    profiler's trace and the persistent compilation cache's key: the
    operator signature, plus the build site's ``variant`` where one
    signature covers several programs (``agg_grouped__partial_k2``).  A
    pure function of its arguments: the same in every process and run, or
    every run would compile cold."""
    name = re.sub(r"[^0-9A-Za-z_]+", "_", sig.replace(":", "__")).strip("_")
    if variant:
        name += "__" + re.sub(r"[^0-9A-Za-z_]+", "_", variant).strip("_")
    return name


def _renamed(fn, name: str):
    """``fn`` under another ``__name__``, its signature untouched
    (``static_argnames`` resolve through it)."""
    if isinstance(fn, types.FunctionType):
        out = types.FunctionType(fn.__code__, fn.__globals__, name,
                                 fn.__defaults__, fn.__closure__)
        out.__kwdefaults__ = fn.__kwdefaults__
        out.__dict__.update(fn.__dict__)
        out.__doc__, out.__module__ = fn.__doc__, fn.__module__
    else:  # a partial, a bound method, a callable object
        @functools.wraps(fn)
        def out(*args, **kwargs):
            return fn(*args, **kwargs)
    out.__name__ = out.__qualname__ = name
    return out


class ObservedJit:
    """A ``jax.jit`` wrapper that names the program after what it computes
    (``program_name(sig, variant)``) and mirrors the trace cache's keying
    to count compiles (first key), retraces (later new keys) and cache
    hits (repeat keys), attributing each — plus compile wall-time, as a
    ``compile <name>`` span — to the enclosing operator/task scope.

    The wrapper travels with the closure through ``shared_program``, so
    its key set is shared exactly as far as the underlying executable
    cache is: a query re-run that reuses the shared closure reports 0 new
    compiles, while a fresh jit wrapper (new plan signature) re-traces in
    both worlds.  The key-set membership test is GIL-atomic, not locked —
    two racing first calls can both count a compile, which matches what
    XLA does on a trace race anyway."""

    __slots__ = ("sig", "name", "_fn", "_jfn", "_static_idx",
                 "_static_names", "_seen", "__wrapped__")

    def __init__(self, sig: str, fn, static_argnums: Iterable[int] = (),
                 static_argnames: Iterable[str] = (),
                 donate_argnums: Iterable[int] = (), variant: str = ""):
        import jax

        self.sig = sig
        self.name = program_name(sig, variant)
        self._fn = fn
        self.__wrapped__ = fn
        kw = {}
        if static_argnums:
            kw["static_argnums"] = tuple(static_argnums)
        if static_argnames:
            kw["static_argnames"] = tuple(static_argnames)
        if donate_argnums:
            # buffer donation (fused whole-stage programs): the caller
            # promises the donated inputs are dead after the call; XLA may
            # alias them into the outputs, eliding the copy
            kw["donate_argnums"] = tuple(donate_argnums)
        self._jfn = jax.jit(_renamed(fn, self.name), **kw)
        idx = set(static_argnums or ())
        names = set(static_argnames or ())
        # resolve static names to positions for positional call sites
        # (jax does the same through the signature)
        if names:
            try:
                params = list(inspect.signature(fn).parameters)
                for n in names:
                    if n in params:
                        idx.add(params.index(n))
            except (TypeError, ValueError):
                pass
        self._static_idx = idx
        self._static_names = names
        self._seen = set()

    def key_of(self, args, kwargs) -> tuple:
        key = []
        for i, a in enumerate(args):
            key.append(_static_key(a) if i in self._static_idx
                       else _shape_key(a))
        for k in sorted(kwargs):
            key.append((k, _static_key(kwargs[k]) if k in self._static_names
                        else _shape_key(kwargs[k])))
        return tuple(key)

    def jaxpr(self, *args):
        """The program's jaxpr at ``args`` (arrays or ``ShapeDtypeStruct``s,
        statics by position): traces the wrapped function, compiles and
        counts nothing.  For tests of what a program is made of."""
        import jax

        return jax.make_jaxpr(
            self._fn, static_argnums=tuple(sorted(self._static_idx)))(*args)

    def __call__(self, *args, **kwargs):
        if not _enabled:
            return self._jfn(*args, **kwargs)
        key = self.key_of(args, kwargs)
        if key in self._seen:
            _record("jit_cache_hits", 1)
            return self._jfn(*args, **kwargs)
        first = not self._seen
        with tracing.span(f"compile {self.name}", "device", sig=self.sig,
                          retrace=not first) as sp:
            t0 = sp.start_ns or tracing.now_ns()
            out = self._jfn(*args, **kwargs)
        self._seen.add(key)
        _record("jit_compiles" if first else "jit_retraces", 1)
        _record("jit_compile_time",
                ((sp.end_ns or tracing.now_ns()) - t0) / 1e9)
        return out


def observed_jit(sig: str, fn=None, *, static_argnums: Iterable[int] = (),
                 static_argnames: Iterable[str] = (),
                 donate_argnums: Iterable[int] = (), variant: str = ""):
    """Drop-in for ``jax.jit(fn, ...)`` with compile/retrace accounting
    under operator signature ``sig``, the program named
    ``program_name(sig, variant)``.  Usable inline
    (``observed_jit("filter", fn)``) or as a decorator
    (``@observed_jit("kernels.pack_for_host", static_argnames=(...))``)."""
    if fn is None:
        return lambda f: ObservedJit(sig, f, static_argnums, static_argnames,
                                     donate_argnums, variant)
    return ObservedJit(sig, fn, static_argnums, static_argnames,
                       donate_argnums, variant)
