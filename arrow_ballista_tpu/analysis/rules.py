"""Built-in lint rules: the conventions this codebase actually relies on.

Each rule documents the invariant it guards and where breaking it was (or
would be) observed.  Add a rule by subclassing ``framework.Rule`` and
decorating with ``@register``; see docs/developer-guide/static-analysis.md.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .framework import (
    Project,
    Rule,
    SourceFile,
    Violation,
    dotted_name,
    import_aliases,
    is_self_attr,
    register,
)

PKG = "arrow_ballista_tpu"


# --------------------------------------------------------------------------
# hot-path-purity
# --------------------------------------------------------------------------

@register
class HotPathPurityRule(Rule):
    """No host materialization primitives in operator hot-path modules.

    ``np.asarray``/``jax.device_get``/``jax.device_put``/
    ``.block_until_ready()``/``.tolist()`` inside ops/kernels.py,
    ops/operators.py, ops/expressions.py each force a device<->host
    sync (a fixed latency per transfer on accelerator
    backends) and silently turn a fused device pipeline into a host
    round-trip.  ``jax.device_put`` is additionally banned because direct
    uploads bypass the transfer accounting in models/batch.py (the device
    observatory would under-report h2d bytes).  Deliberate host-mode paths
    (host UDF projection, the single packed scalar fetch) carry
    ``# ballista: allow=hot-path-purity`` with a justification.
    """

    name = "hot-path-purity"
    description = ("no np.asarray / jax.device_get / jax.device_put / "
                   ".block_until_ready() / .tolist() in operator hot-path "
                   "modules")

    FILES = (f"{PKG}/ops/kernels.py", f"{PKG}/ops/operators.py",
             f"{PKG}/ops/expressions.py", f"{PKG}/compile/fused.py",
             f"{PKG}/compile/chains.py", f"{PKG}/compile/fuse.py")
    BANNED_MODULE_CALLS = {("numpy", "asarray"), ("jax", "device_get"),
                           ("jax", "device_put")}
    BANNED_METHODS = {"block_until_ready", "tolist"}

    def check(self, project: Project) -> Iterable[Violation]:
        for relpath in self.FILES:
            sf = project.file(relpath)
            if sf is None or sf.tree is None:
                continue
            aliases = import_aliases(sf.tree)
            for node in ast.walk(sf.tree):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                if not isinstance(f, ast.Attribute):
                    continue
                if isinstance(f.value, ast.Name):
                    mod = aliases.get(f.value.id, f.value.id)
                    if (mod, f.attr) in self.BANNED_MODULE_CALLS:
                        yield Violation(
                            self.name, sf.path, node.lineno,
                            f"{f.value.id}.{f.attr}() forces a device->host "
                            f"materialization in a hot-path module")
                        continue
                if f.attr in self.BANNED_METHODS:
                    yield Violation(
                        self.name, sf.path, node.lineno,
                        f".{f.attr}() forces a device->host sync in a "
                        f"hot-path module")


# --------------------------------------------------------------------------
# span-coverage
# --------------------------------------------------------------------------

@register
class SpanCoverageRule(Rule):
    """Every physical-operator ``execute``/``execute_write`` override must
    run under ``ctx.op_span(self)`` so per-operator profiling (PR 2) covers
    the whole plan — one unwrapped operator leaves a hole in every profile
    and breaks the >=95%-coverage tracing test.

    Compliant shapes: a ``with ctx.op_span(self):`` anywhere in the body,
    a body that only raises (abstract / refuses-to-run operators), or a
    delegation to a sibling ``self.execute*`` method that spans.

    PR 6 extension: any OTHER operator-signature method in ops/ that
    emits operator stats (``self.metrics()...`` or ``deferred_rows``)
    is held to the same standard — metrics recorded outside a span are
    invisible to the profile's operator attribution and silently skew
    EXPLAIN ANALYZE.  Private helpers reached from a (checked) spanning
    entry point are exempt: being called as ``self.<name>`` elsewhere in
    the module (this covers overrides dispatched from a base class's
    spanning execute) means the span is already open on the stack.
    """

    name = "span-coverage"
    description = "operator execute() overrides wrapped via ctx.op_span"

    DIR = (f"{PKG}/ops/", f"{PKG}/compile/")
    METHODS = ("execute", "execute_write")
    # record_transfer feeds the device observatory's per-operator transfer
    # accounting; calling it outside ctx.op_span(self) silently drops the
    # bytes from the enclosing operator's stage summary.
    STATS_FNS = ("deferred_rows", "record_transfer")

    def check(self, project: Project) -> Iterable[Violation]:
        for sf in project.source_files():
            if not sf.path.startswith(self.DIR) or sf.tree is None:
                continue
            for cls in sf.tree.body:
                if not isinstance(cls, ast.ClassDef):
                    continue
                for fn in cls.body:
                    if (not isinstance(fn, ast.FunctionDef)
                            or not self._is_operator_sig(fn)):
                        continue
                    if fn.name in self.METHODS:
                        if not self._compliant(fn):
                            yield Violation(
                                self.name, sf.path, fn.lineno,
                                f"{cls.name}.{fn.name} is not wrapped in "
                                f"ctx.op_span(self) (and neither raises nor "
                                f"delegates to a spanning execute method)")
                    elif (self._emits_stats(fn)
                            and fn.name not in self._called_internally(
                                sf.tree, excluding=fn)
                            and not self._compliant(fn)):
                        yield Violation(
                            self.name, sf.path, fn.lineno,
                            f"{cls.name}.{fn.name} emits operator metrics "
                            f"but runs outside ctx.op_span(self) and is "
                            f"never reached from a spanning entry point")

    @staticmethod
    def _is_operator_sig(fn: ast.FunctionDef) -> bool:
        args = [a.arg for a in fn.args.args]
        return len(args) >= 3 and args[0] == "self" and "ctx" in args

    def _emits_stats(self, fn: ast.FunctionDef) -> bool:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            d = dotted_name(node.func)
            if d == "self.metrics" or d in self.STATS_FNS:
                return True
        return False

    @staticmethod
    def _called_internally(tree: ast.Module,
                           excluding: ast.FunctionDef) -> Set[str]:
        """Method names invoked as ``self.<name>(...)`` anywhere in the
        module outside the method itself (recursion doesn't self-exempt;
        module scope so a base class dispatching to an override counts)."""
        skip = set(map(id, ast.walk(excluding)))
        called: Set[str] = set()
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call) and id(node) not in skip
                    and isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "self"):
                called.add(node.func.attr)
        return called

    def _compliant(self, fn: ast.FunctionDef) -> bool:
        body = [s for s in fn.body
                if not (isinstance(s, ast.Expr)
                        and isinstance(s.value, ast.Constant)
                        and isinstance(s.value.value, str))]  # skip docstring
        if body and all(isinstance(s, ast.Raise) for s in body):
            return True
        for node in ast.walk(fn):
            if isinstance(node, ast.With):
                for item in node.items:
                    c = item.context_expr
                    if (isinstance(c, ast.Call)
                            and dotted_name(c.func) == "ctx.op_span"):
                        return True
            if isinstance(node, ast.Call):
                d = dotted_name(node.func)
                if d is not None and d.startswith("self.execute"):
                    return True
        return False


# --------------------------------------------------------------------------
# serde-completeness
# --------------------------------------------------------------------------

def _dataclass_names(tree: ast.Module) -> List[Tuple[str, int]]:
    out = []
    for node in tree.body:
        if not isinstance(node, ast.ClassDef):
            continue
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            d = dotted_name(target)
            if d in ("dataclass", "dataclasses.dataclass"):
                out.append((node.name, node.lineno))
                break
    return out


@register
class SerdeCompletenessRule(Rule):
    """Every wire dataclass must be registered (with a to/from pair) in
    ``serde.WIRE_TYPES``.  The control plane serializes exactly these
    shapes over the JSON framing; an unregistered dataclass means some
    call site is hand-rolling ``vars()`` without a deserializer contract,
    and the next added field silently drops on the wire.
    """

    name = "serde-completeness"
    description = "wire dataclasses registered for round-trip in serde.py"

    WIRE_FILES = (f"{PKG}/scheduler/types.py", f"{PKG}/net/wire.py")
    SERDE_FILE = f"{PKG}/serde.py"

    def check(self, project: Project) -> Iterable[Violation]:
        serde = project.file(self.SERDE_FILE)
        registered: Set[str] = set()
        registry_found = False
        if serde is not None and serde.tree is not None:
            for node in serde.tree.body:
                if (isinstance(node, ast.Assign)
                        and any(isinstance(t, ast.Name) and t.id == "WIRE_TYPES"
                                for t in node.targets)
                        and isinstance(node.value, ast.Dict)):
                    registry_found = True
                    for k in node.value.keys:
                        if isinstance(k, ast.Name):
                            registered.add(k.id)
        if not registry_found:
            yield Violation(self.name, self.SERDE_FILE, 0,
                            "no WIRE_TYPES registry found (expected a "
                            "module-level dict literal keyed by wire "
                            "dataclass)")
            return
        for relpath in self.WIRE_FILES:
            sf = project.file(relpath)
            if sf is None or sf.tree is None:
                continue
            for name, line in _dataclass_names(sf.tree):
                if name not in registered:
                    yield Violation(
                        self.name, sf.path, line,
                        f"wire dataclass {name} is not registered in "
                        f"serde.WIRE_TYPES (add a to_obj/from_obj pair)")


# --------------------------------------------------------------------------
# config-registry
# --------------------------------------------------------------------------

@register
class ConfigRegistryRule(Rule):
    """Every ``ballista.*`` config key must be registered in the
    ``utils/config.py`` entry registry, carry a non-empty doc string, be
    rendered into docs/user-guide/configs.md, and every string-literal
    ``.get("ballista.*")``/``.set(...)`` call site must name a registered
    key.  An unregistered key raises at runtime only when that code path
    runs; this catches it at lint time.
    """

    name = "config-registry"
    description = "ballista.* keys registered, documented, and rendered"

    CONFIG_FILE = f"{PKG}/utils/config.py"
    DOC_FILE = "docs/user-guide/configs.md"

    def check(self, project: Project) -> Iterable[Violation]:
        sf = project.file(self.CONFIG_FILE)
        if sf is None or sf.tree is None:
            return
        consts: Dict[str, Tuple[str, int]] = {}  # NAME -> (key, line)
        for node in sf.tree.body:
            if (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Constant)
                    and isinstance(node.value.value, str)
                    and node.value.value.startswith("ballista.")):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        consts[t.id] = (node.value.value, node.lineno)
        entries: Dict[str, int] = {}  # key -> line of its ConfigEntry(...)
        undocumented: List[Tuple[str, int]] = []
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "ConfigEntry" and node.args):
                continue
            arg0 = node.args[0]
            if isinstance(arg0, ast.Constant) and isinstance(arg0.value, str):
                key = arg0.value
            elif isinstance(arg0, ast.Name) and arg0.id in consts:
                key = consts[arg0.id][0]
            else:
                continue
            entries[key] = node.lineno
            doc = None
            if len(node.args) >= 4:
                doc = node.args[3]
            for kw in node.keywords:
                if kw.arg == "doc":
                    doc = kw.value
            if (doc is None or (isinstance(doc, ast.Constant)
                                and not str(doc.value).strip())):
                undocumented.append((key, node.lineno))

        for name, (key, line) in sorted(consts.items()):
            if key not in entries:
                yield Violation(self.name, sf.path, line,
                                f"config constant {name} = {key!r} has no "
                                f"ConfigEntry registration")
        for key, line in undocumented:
            yield Violation(self.name, sf.path, line,
                            f"config key {key!r} has an empty doc string")
        doc_text = project.read_text(self.DOC_FILE)
        if doc_text is None:
            yield Violation(self.name, self.DOC_FILE, 0,
                            "docs/user-guide/configs.md is missing (run "
                            "python docs/gen_configs.py)")
        else:
            for key in sorted(entries):
                if f"`{key}`" not in doc_text:
                    yield Violation(
                        self.name, self.DOC_FILE, 0,
                        f"registered key {key!r} is absent from "
                        f"{self.DOC_FILE} (run python docs/gen_configs.py)")
        # literal call sites anywhere in the package
        for src in project.source_files():
            if src.tree is None:
                continue
            for node in ast.walk(src.tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in ("get", "set") and node.args):
                    continue
                arg0 = node.args[0]
                if (isinstance(arg0, ast.Constant)
                        and isinstance(arg0.value, str)
                        and arg0.value.startswith("ballista.")
                        and arg0.value not in entries):
                    yield Violation(
                        self.name, src.path, node.lineno,
                        f".{node.func.attr}({arg0.value!r}) names an "
                        f"unregistered config key")


# --------------------------------------------------------------------------
# lock-discipline
# --------------------------------------------------------------------------

@register
class LockDisciplineRule(Rule):
    """Mutations of known shared scheduler state containers must happen
    inside ``with self._lock``/``self._cond`` (or in a ``*_locked`` helper,
    the repo convention for 'caller holds the lock').  These containers are
    hit concurrently by the event loop, the launch pool, the reaper, and
    RPC threads; one unlocked mutation is a rare-flake generator.
    """

    name = "lock-discipline"
    description = "shared scheduler state mutated only under self._lock"

    # (file, class) -> guarded attribute names
    GUARDED: Dict[Tuple[str, str], Set[str]] = {
        (f"{PKG}/scheduler/cluster.py", "ClusterState"):
            {"_executors", "_heartbeats", "_available", "_rr_cursor"},
        (f"{PKG}/scheduler/cluster.py", "JobState"):
            {"_status", "_graphs", "_done"},
        (f"{PKG}/scheduler/session.py", "SessionManager"):
            {"_sessions"},
        (f"{PKG}/scheduler/scheduler.py", "SchedulerServer"):
            {"_cleanup_timers", "_status_inbox"},
    }
    LOCK_ATTRS = {"_lock", "_cond", "_cleanup_lock", "_status_lock"}
    MUTATORS = {"append", "pop", "clear", "update", "setdefault", "add",
                "remove", "extend", "popitem", "insert", "discard"}

    def check(self, project: Project) -> Iterable[Violation]:
        by_file: Dict[str, List[Tuple[str, Set[str]]]] = {}
        for (path, cls), attrs in self.GUARDED.items():
            by_file.setdefault(path, []).append((cls, attrs))
        for path, classes in sorted(by_file.items()):
            sf = project.file(path)
            if sf is None or sf.tree is None:
                continue
            for node in sf.tree.body:
                if not isinstance(node, ast.ClassDef):
                    continue
                for cls_name, attrs in classes:
                    if node.name != cls_name:
                        continue
                    yield from self._check_class(sf, node, attrs)

    def _check_class(self, sf: SourceFile, cls: ast.ClassDef,
                     attrs: Set[str]) -> Iterable[Violation]:
        for fn in cls.body:
            if not isinstance(fn, ast.FunctionDef):
                continue
            if fn.name == "__init__" or fn.name.endswith("_locked"):
                continue
            yield from self._walk(sf, cls.name, fn.name, fn.body, attrs,
                                  locked=False)

    def _walk(self, sf: SourceFile, cls: str, fn: str, body, attrs: Set[str],
              locked: bool) -> Iterable[Violation]:
        for stmt in body:
            if isinstance(stmt, ast.With):
                inside = locked or any(
                    is_self_attr(item.context_expr, self.LOCK_ATTRS)
                    for item in stmt.items)
                yield from self._walk(sf, cls, fn, stmt.body, attrs, inside)
                continue
            if not locked:
                attr = self._mutated_attr(stmt, attrs)
                if attr is not None:
                    yield Violation(
                        self.name, sf.path, stmt.lineno,
                        f"{cls}.{fn} mutates shared attr self.{attr} "
                        f"outside 'with self._lock'")
            # nested bodies (if/for/try/...)
            for field in ("body", "orelse", "finalbody"):
                sub = getattr(stmt, field, None)
                if isinstance(sub, list):
                    yield from self._walk(sf, cls, fn, sub, attrs, locked)
            for handler in getattr(stmt, "handlers", []) or []:
                yield from self._walk(sf, cls, fn, handler.body, attrs, locked)
            # inner defs inherit nothing: a nested closure may run later on
            # another thread, so treat its body as unlocked
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._walk(sf, cls, fn, stmt.body, attrs, False)

    def _mutated_attr(self, stmt: ast.stmt, attrs: Set[str]) -> Optional[str]:
        targets: List[ast.AST] = []
        if isinstance(stmt, ast.Assign):
            targets = list(stmt.targets)
        elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
            targets = [stmt.target]
        elif isinstance(stmt, ast.Delete):
            targets = list(stmt.targets)
        for t in targets:
            if isinstance(t, ast.Subscript):
                t = t.value
            if is_self_attr(t, attrs):
                return t.attr
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Call):
            f = stmt.value.func
            if (isinstance(f, ast.Attribute) and f.attr in self.MUTATORS
                    and is_self_attr(f.value, attrs)):
                return f.value.attr
        return None


# --------------------------------------------------------------------------
# no-blocking-in-event-loop
# --------------------------------------------------------------------------

@register
class NoBlockingInEventLoopRule(Rule):
    """No ``time.sleep`` or socket calls on the scheduler event loop.

    Every state transition funnels through the single-consumer loop
    (scheduler/event_loop.py); one blocking call there stalls all
    scheduling — exactly the slow-event class the loop's own watchdog
    warns about, but caught statically.  Checked in event_loop.py itself
    and in SchedulerServer's ``_on_*``/``_offer``/``_absorb*`` handlers.
    """

    name = "no-blocking-in-event-loop"
    description = "no time.sleep / socket calls in event-loop handlers"

    LOOP_FILE = f"{PKG}/scheduler/event_loop.py"
    SCHED_FILE = f"{PKG}/scheduler/scheduler.py"
    HANDLER_RE = re.compile(r"^(_on_|_offer$|_absorb)")

    def check(self, project: Project) -> Iterable[Violation]:
        sf = project.file(self.LOOP_FILE)
        if sf is not None and sf.tree is not None:
            yield from self._scan(sf, sf.tree)
        sf = project.file(self.SCHED_FILE)
        if sf is not None and sf.tree is not None:
            for cls in sf.tree.body:
                if not (isinstance(cls, ast.ClassDef)
                        and cls.name == "SchedulerServer"):
                    continue
                for fn in cls.body:
                    if (isinstance(fn, ast.FunctionDef)
                            and self.HANDLER_RE.match(fn.name)):
                        yield from self._scan(sf, fn)

    def _scan(self, sf: SourceFile, node: ast.AST) -> Iterable[Violation]:
        aliases = import_aliases(sf.tree)
        for n in ast.walk(node):
            if not isinstance(n, ast.Call):
                continue
            d = dotted_name(n.func)
            if d is None:
                continue
            root = d.split(".")[0]
            resolved = aliases.get(root, root)
            full = d.replace(root, resolved, 1)
            if full == "time.sleep" or full.startswith("socket."):
                yield Violation(
                    self.name, sf.path, n.lineno,
                    f"{d}() blocks the scheduler event loop")


# --------------------------------------------------------------------------
# metrics-docs (folded in from tools/check_metrics_docs.py)
# --------------------------------------------------------------------------

@register
class MetricsDocsRule(Rule):
    """Every prometheus metric family the collectors emit must be
    documented in docs/user-guide/metrics.md.  Runtime-reflective (it
    instantiates the collectors and renders their exposition), so it only
    runs against the importable package — fixture projects select it
    explicitly when they want it.
    """

    name = "metrics-docs"
    description = "emitted prometheus metric families documented"

    DOC_FILE = "docs/user-guide/metrics.md"

    def emitted_metric_names(self) -> List[str]:
        from ..executor.metrics import ExecutorMetrics
        from ..scheduler.metrics import InMemoryMetricsCollector

        text = InMemoryMetricsCollector().gather() + ExecutorMetrics().gather()
        return sorted(set(re.findall(r"^# TYPE (\S+) \S+$", text, re.M)))

    def check(self, project: Project) -> Iterable[Violation]:
        doc = project.read_text(self.DOC_FILE)
        if doc is None:
            yield Violation(self.name, self.DOC_FILE, 0,
                            "docs/user-guide/metrics.md is missing")
            return
        for name in self.emitted_metric_names():
            if name not in doc:
                yield Violation(
                    self.name, self.DOC_FILE, 0,
                    f"metric family {name!r} is emitted by a collector but "
                    f"absent from {self.DOC_FILE}")


# --------------------------------------------------------------------------
# recovery-path-logging
# --------------------------------------------------------------------------

@register
class RecoveryPathLoggingRule(Rule):
    """Broad exception handlers on recovery paths must log or re-raise.

    The executor/scheduler retry loops lean on ``except Exception`` to
    survive transient failures — correct, but a silent ``pass`` there
    turns a dying scheduler into an executor that spins forever with no
    trace (the failure mode the PR-4 chaos suite reproduces).  Any bare /
    ``Exception`` / ``BaseException`` handler under ``executor/`` or
    ``scheduler/`` must contain a ``raise`` or a logging call; deliberate
    silences carry ``# ballista: allow=recovery-path-logging`` with a
    justification (e.g. best-effort cleanup where the peer is already
    gone and the outcome is reported elsewhere).
    """

    name = "recovery-path-logging"
    description = ("broad except handlers in executor/ and scheduler/ "
                   "log or re-raise")

    DIRS = (f"{PKG}/executor/", f"{PKG}/scheduler/")
    BROAD = {"Exception", "BaseException"}
    LOG_METHODS = {"debug", "info", "warning", "warn", "error", "exception",
                   "critical", "log"}

    def check(self, project: Project) -> Iterable[Violation]:
        for sf in project.source_files():
            if sf.tree is None or not sf.path.startswith(self.DIRS):
                continue
            for node in ast.walk(sf.tree):
                if not isinstance(node, ast.ExceptHandler):
                    continue
                if not self._is_broad(node.type):
                    continue
                if not self._handles(node):
                    yield Violation(
                        self.name, sf.path, node.lineno,
                        "broad except swallows the error silently — log it, "
                        "re-raise, or justify with "
                        "'# ballista: allow=recovery-path-logging'")

    def _is_broad(self, t: Optional[ast.expr]) -> bool:
        if t is None:  # bare except:
            return True
        if isinstance(t, ast.Tuple):
            return any(self._is_broad(e) for e in t.elts)
        return dotted_name(t) in self.BROAD

    def _handles(self, handler: ast.ExceptHandler) -> bool:
        for n in ast.walk(handler):
            if isinstance(n, ast.Raise):
                return True
            if isinstance(n, ast.Call):
                d = dotted_name(n.func)
                if (d is not None and d.split(".")[-1] in self.LOG_METHODS
                        and "log" in d.lower()):
                    return True
        return False
