"""Span layer: one way to open a span, one in-memory store, pluggable export.

A Span is a named interval in a trace, stamped in integer nanoseconds of
the realtime clock (``time.time_ns``) — the clock ``jax.profiler`` stamps
host events with, so a span lies where it belongs on a device profile
(``profile_start_time`` of the trace's ``Task Environment`` plane plus an
event's ``start_ns`` is the same clock).  ``start_ms``/``end_ms`` are
derived, for serde, REST and the Chrome export.

``span(name, kind, parent, **attrs)`` is the single entry point.  Used as a
context manager it also enters a ``jax.profiler.TraceAnnotation`` of the
same name, so with a profiler session open the span is in ``/host:CPU`` on
the thread that did the work; with none open that costs one predicate.
Intervals that begin and end on different threads (the scheduler's job,
phase and stage spans) use ``span(...).begin()`` and ``Span.end()``: same
clock, same store, no annotation.  The tree:

    client.sql, client.collect -> submit / wait / fetch / decode   (client)
      job -> admission / planning / execution -> stage <id>        (scheduler)
        task -> operator -> device_wait / h2d / compile / lock_wait

A finished span enters, once, the process-wide ``RING`` (bounded,
drop-oldest, counted).  Task trees also ride ``TaskStatus.spans`` back to
the scheduler.  Collectors are the export seam: noop (default), ``memory``
(the ring itself) and an OTLP/HTTP-JSON-shaped exporter (stdlib urllib
only; the payload matches the opentelemetry-proto JSON mapping closely
enough for a generic OTLP gateway, and a custom ``sink`` can divert it).
"""
import collections
import contextlib
import random
import threading
import time
import urllib.request
import uuid
from typing import Callable, Dict, List, Optional

now_ns = time.time_ns


def now_ms() -> float:
    return time.time_ns() / 1e6


def new_trace_id() -> str:
    return uuid.uuid4().hex  # 32 hex chars, W3C-sized


def new_span_id() -> str:
    return "%016x" % random.getrandbits(64)


class Span:
    __slots__ = ("name", "trace_id", "span_id", "parent_id", "kind",
                 "start_ns", "end_ns", "status", "attrs", "_ringed")

    def __init__(self, name: str, trace_id: str = "",
                 span_id: Optional[str] = None, parent_id: str = "",
                 kind: str = "internal", start_ms: Optional[float] = None,
                 end_ms: float = 0.0, status: str = "ok",
                 attrs: Optional[Dict] = None,
                 start_ns: Optional[int] = None, end_ns: int = 0):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id or new_span_id()
        self.parent_id = parent_id
        # client | scheduler | executor (a task) | operator | device | lock
        # | internal
        self.kind = kind
        if start_ns is None:
            start_ns = now_ns() if start_ms is None else int(start_ms * 1e6)
        self.start_ns = start_ns
        self.end_ns = end_ns or int(end_ms * 1e6)
        self.status = status
        self.attrs = {} if attrs is None else attrs
        self._ringed = False

    @property
    def start_ms(self) -> float:
        return self.start_ns / 1e6

    @property
    def end_ms(self) -> float:
        return self.end_ns / 1e6

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def end(self, status: Optional[str] = None,
            at_ns: Optional[int] = None) -> "Span":
        """Close the span (first call wins) and enter it in ``RING``."""
        if not self.end_ns:
            self.end_ns = at_ns or now_ns()
        if status is not None:
            self.status = status
        if not self._ringed:
            self._ringed = True
            RING.add(self)
        return self

    @property
    def duration_ms(self) -> float:
        return max(((self.end_ns or now_ns()) - self.start_ns) / 1e6, 0.0)

    def context(self) -> Dict[str, str]:
        """Propagation context for children of this span."""
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, kind={self.kind!r}, "
                f"{self.duration_ms:.3f} ms, {self.attrs})")


class _NullSpan:
    """What a disabled ``span()`` yields: takes attributes, keeps none."""
    __slots__ = ()
    start_ns = end_ns = 0

    def set(self, **attrs) -> None:
        pass

    def context(self) -> Dict[str, str]:
        return {}

    def __bool__(self) -> bool:
        return False


class _NullScope:
    __slots__ = ()

    def __enter__(self):
        return NULL_SPAN

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()
_NULL_SCOPE = _NullScope()
ROOT = object()     # ``parent=ROOT``: the span starts a trace of its own

_tls = threading.local()
_annotation = None  # jax.profiler.TraceAnnotation, imported at first use
_ID_KEYS = ("job_id", "stage_id", "partition")
_LANE_KEYS = ("actor", "lane")


class _Scope:
    """An open span on this thread's stack, inside its TraceAnnotation."""
    __slots__ = ("span", "ids", "_ann")

    def __init__(self, sp: Span, ids: Dict):
        self.span, self.ids = sp, ids

    def begin(self) -> Span:
        """Cross-thread form: the caller ends it with ``Span.end()``."""
        return self.span

    def __enter__(self) -> Span:
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation as _annotation
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self)
        self._ann = ann = _annotation(self.span.name, **self.ids)
        self.span.start_ns = now_ns()
        ann.__enter__()
        return self.span

    def __exit__(self, et, ev, tb):
        self._ann.__exit__(et, ev, tb)
        sp = self.span
        sp.end("error" if et is not None and sp.status == "ok" else None)
        _tls.stack.pop()
        sink = getattr(_tls, "sink", None)
        if sink is not None:
            sink.append(sp)
        return False


def span(name: str, kind: str = "internal", parent=None, **attrs):
    """Open a span.  ``parent``: ``None`` = the span open on this thread
    (and the shared null context where there is none: tracing is off, or
    the caller runs outside any traced task); ``ROOT`` = start a trace; a
    ``Span`` or a propagation context ``{"trace_id", "span_id"}``."""
    if parent is None:
        stack = getattr(_tls, "stack", None)
        if not stack:
            return _NULL_SCOPE
        up = stack[-1]
        for k in _LANE_KEYS:  # the Chrome export's lanes
            if k in up.span.attrs:
                attrs.setdefault(k, up.span.attrs[k])
        return _Scope(Span(name, up.span.trace_id,
                           parent_id=up.span.span_id, kind=kind,
                           attrs=attrs), up.ids)
    if parent is ROOT:
        trace_id, parent_id = new_trace_id(), ""
    elif isinstance(parent, Span):
        trace_id, parent_id = parent.trace_id, parent.span_id
    else:
        trace_id = parent.get("trace_id") or new_trace_id()
        parent_id = parent.get("span_id", "")
    ids = {"trace_id": trace_id}
    for k in _ID_KEYS:
        if k in attrs:
            ids[k] = attrs[k]
    return _Scope(Span(name, trace_id, parent_id=parent_id, kind=kind,
                       attrs=attrs), ids)


def current_context() -> Dict[str, str]:
    """Propagation context of the span open on this thread, or ``{}``."""
    stack = getattr(_tls, "stack", None)
    return stack[-1].span.context() if stack else {}


class TracedLock:
    """A ``threading.Lock`` whose contended acquires are ``lock_wait``
    spans: an acquire that succeeds at once costs one extra call."""
    __slots__ = ("_lock", "name")

    def __init__(self, name: str):
        self._lock = threading.Lock()
        self.name = name

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        if self._lock.acquire(False):
            return True
        if not blocking:
            return False
        with span("lock_wait", "lock", lock=self.name):
            return self._lock.acquire(True, timeout)

    def release(self) -> None:
        self._lock.release()

    def locked(self) -> bool:
        return self._lock.locked()

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self._lock.release()


_SPAN_FIELDS = ("name", "trace_id", "span_id", "parent_id", "kind",
                "start_ms", "end_ms", "status", "start_ns", "end_ns")


def span_to_obj(s: Span) -> Dict:
    o = {k: getattr(s, k) for k in _SPAN_FIELDS}
    o["attrs"] = dict(s.attrs)
    return o


def span_from_obj(o: Dict) -> Span:
    return Span(attrs=dict(o.get("attrs", {})),
                **{k: o[k] for k in _SPAN_FIELDS if k in o})


class SpanCollector:
    """Export seam for finished span batches."""

    def export(self, spans: List[Span]) -> None:
        raise NotImplementedError

    def snapshot(self, trace_id: Optional[str] = None) -> List[Span]:
        return []


class NoopSpanCollector(SpanCollector):
    def export(self, spans: List[Span]) -> None:
        pass


# Twice the largest benchmark cell's run, rounded up to a power of two:
# ``sf1_cluster_streams`` leaves at most 11 558 spans (148 queries of 74,
# warm-up included, the largest of eight runs; ``sf10_scanagg`` 5 616,
# ``sf1_join`` 1 576: chip runs, PR 27, whose one-row global aggregate
# doubled the scan cells' queries a window).  The rule asks for the next
# doubling past 16 384 spans a run, about 210 queries; the ring drops, and
# the benchmark's ``[span_tree]`` line says so, only past 32 768.
RING_CAPACITY = 32768


class SpanRing(SpanCollector):
    """The process's one in-memory store of finished spans: bounded,
    oldest dropped first, ``dropped`` counts what went."""

    def __init__(self, capacity: int = RING_CAPACITY):
        self.capacity = max(int(capacity), 1)
        self.dropped = 0
        self._spans = collections.deque()
        self._lock = threading.Lock()

    def add(self, s: Span) -> None:
        with self._lock:
            if len(self._spans) >= self.capacity:
                self._spans.popleft()
                self.dropped += 1
            self._spans.append(s)

    def export(self, spans: List[Span]) -> None:
        """Collector seam: spans this process closed are in already; what
        another process shipped (task trees on a scheduler) enters here."""
        for s in spans:
            if not s._ringed:
                s._ringed = True
                self.add(s)

    def snapshot(self, trace_id: Optional[str] = None) -> List[Span]:
        with self._lock:
            return [s for s in self._spans
                    if trace_id is None or s.trace_id == trace_id]

    def clear(self) -> None:  # test hook
        with self._lock:
            self._spans.clear()
            self.dropped = 0


RING = SpanRing()


def otlp_payload(spans: List[Span], service_name: str) -> Dict:
    """OTLP/HTTP JSON-shaped resourceSpans payload (nanosecond epochs)."""
    def attrs(d):
        out = []
        for k, v in d.items():
            if isinstance(v, bool):
                val = {"boolValue": v}
            elif isinstance(v, int):
                val = {"intValue": str(v)}
            elif isinstance(v, float):
                val = {"doubleValue": v}
            else:
                val = {"stringValue": str(v)}
            out.append({"key": str(k), "value": val})
        return out

    return {"resourceSpans": [{
        "resource": {"attributes": attrs({"service.name": service_name})},
        "scopeSpans": [{
            "scope": {"name": "arrow_ballista_tpu.obs"},
            "spans": [{
                "traceId": s.trace_id,
                "spanId": s.span_id,
                "parentSpanId": s.parent_id,
                "name": s.name,
                "kind": 1,
                "startTimeUnixNano": str(s.start_ns),
                "endTimeUnixNano": str(s.end_ns or now_ns()),
                "status": {"code": 2 if s.status not in ("ok", "success")
                           else 1},
                "attributes": attrs(s.attrs),
            } for s in spans],
        }],
    }]}


class OtlpSpanCollector(SpanCollector):
    """Best-effort OTLP-shaped export hook.

    Builds the JSON payload and hands it to `sink` (default: POST to
    `endpoint` with a short timeout).  Failures are swallowed — tracing
    must never take a query down.
    """

    def __init__(self, endpoint: str = "",
                 service_name: str = "arrow-ballista-tpu",
                 sink: Optional[Callable[[Dict], None]] = None):
        self.endpoint = endpoint
        self.service_name = service_name
        self.sink = sink

    def export(self, spans: List[Span]) -> None:
        if not spans:
            return
        payload = otlp_payload(spans, self.service_name)
        try:
            if self.sink is not None:
                self.sink(payload)
            elif self.endpoint:
                import json as _json
                req = urllib.request.Request(
                    self.endpoint, data=_json.dumps(payload).encode(),
                    headers={"Content-Type": "application/json"})
                urllib.request.urlopen(req, timeout=2).close()
        except Exception:
            pass


def make_collector(kind: str, endpoint: str = "") -> SpanCollector:
    kind = (kind or "noop").strip().lower()
    if kind == "memory":
        return RING
    if kind == "otlp":
        return OtlpSpanCollector(endpoint)
    return NoopSpanCollector()


class TaskSpanRecorder:
    """One task's span tree, built on the task's executing thread.

    The task span opens here and closes in ``finish``; every span that
    closes on this thread in between (operators, and the ``device_wait``,
    ``h2d``, ``compile`` and ``lock_wait`` spans below them) is kept for
    ``TaskStatus.spans``.  Operator MetricsSets are cumulative per plan
    instance and shared by same-stage tasks; ``op_span`` snapshots
    ``to_dict()`` around each execute call and attaches the *delta* as span
    attributes, which is this task's contribution (up to interleaving with
    concurrent tasks of the same stage on this executor).
    """

    def __init__(self, trace_id: Optional[str] = None, parent_id: str = "",
                 name: str = "task", kind: str = "executor",
                 attrs: Optional[Dict] = None):
        self._scope = span(name, kind, {"trace_id": trace_id,
                                        "span_id": parent_id or ""},
                           **(attrs or {}))
        self._done: List[Span] = []
        self._outer_sink = getattr(_tls, "sink", None)
        self.root = self._scope.__enter__()
        _tls.sink = self._done

    def annotate(self, **attrs) -> None:
        self.root.attrs.update(attrs)

    @contextlib.contextmanager
    def op_span(self, op, **attrs):
        name = op if isinstance(op, str) else type(op).__name__
        before: Dict[str, float] = {}
        ms = getattr(op, "metrics", None)
        if callable(ms):
            try:
                before = ms().to_dict()
            except Exception:
                ms = None
        with span(name, "operator", **attrs) as sp:
            try:
                yield sp
            finally:
                if callable(ms):
                    try:
                        for k, v in ms().to_dict().items():
                            delta = v - before.get(k, 0.0)
                            if delta:
                                sp.attrs[k] = round(float(delta), 6)
                    except Exception:
                        pass

    def finish(self, status: str = "ok") -> List[Span]:
        _tls.sink = self._outer_sink
        self.root.status = status
        self._scope.__exit__(None, None, None)
        return [self.root] + list(self._done)
