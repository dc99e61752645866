"""JIT-discipline analyzer: static verification of every device kernel.

The device observatory (obs/device.py) made retraces, transfers, and
donation *observable at runtime*; this module proves jit discipline
*before merge*.  It builds a per-call-site **JitSiteModel** for every
``observed_jit`` construction under ``ops/``, ``compile/``, ``models/``,
and ``obs/device.py`` — the signature string the runtime observatory
reports under, the traced callable (lambda, named def, or decorated
function), resolved ``static_argnums``/``static_argnames`` positions,
``donate_argnums``, and every reachable call site (linked
interprocedurally through the repo's binding idioms: direct names,
``self._attr`` assignment, tuple returns from ``shared_program``
builders matched to same-shape unpacks in the same class, decorators,
and cross-module from-imports of module-level wrappers).

Four rules consume the model:

``trace-key-stability``
    Batch-varying VALUES (reads of ``.columns``/``.mask``/``.dicts``/
    ``.num_rows``, or results of other jit calls) flowing into a static
    argument position mint a new trace key per distinct value — a
    retrace storm the observatory would count as ``jit_retraces`` under
    the same signature this rule reports.  Values are considered clean
    again after passing a *sanitizer* (``round_capacity``,
    ``dense_domain``, ``.bit_length()``-based pow2 bucketing, or a
    ``.capacity`` read — capacities are pow2-padded by construction).
    Also flags wrappers constructed inside loops (each construction
    starts an empty trace cache) and traced bodies that close over a
    batch-varying local (the value is baked into the trace).

``donation-safety``
    For donated arguments, XLA deletes the input buffer: any later read
    of the same attribute, any escape of the base object, or any method
    call on it (which may read buffers internally) is a
    *use-after-donation* violation.  A call inside a loop counts reads
    anywhere in that loop unless the base is the loop's own target
    (rebound each iteration).  Conversely, an undonated argument that
    shares a donated argument's base and is provably dead after every
    call — or whose base is freshly produced by another jit call in the
    same function and dead after — is reported as a
    *provably-safe-but-undonated* advisory.

``host-device-boundary``
    Inside traced bodies: host ``numpy`` calls, ``.tolist()``/
    ``.item()``, ``float()``/``int()``/``bool()`` concretization, and
    float64 promotion are host round-trips or weak-type hazards that
    the shape-keyed trace cache cannot see.  Outside traced bodies:
    ``jax.device_get``/``jax.device_put`` in a function that never
    opens ``device_wait``/``h2d`` (or calls ``record_transfer``) is an
    unaccounted transfer — the observatory's byte counters and the
    task's span tree silently lie about it.

``fusion-verdict-consistency``
    ``compile/fuse.py``'s ``DEFAULT_OPERATORS`` allowlist, the
    ``_op_verdict`` per-node doubts, ``compile/fused.py``'s kernel
    builders, and ``compile/chains.py``'s static reason tables must
    agree with the operator classes that actually exist: every
    allowlisted name is a real operator with a builder branch and a
    verdict branch, verdicts consult ``host_mode`` when the operator
    has one, and chain tables name no phantom classes.

A fifth, repo-wide rule:

``deprecated-jax-api``
    ``jax.shard_map`` does not exist in jax 0.4.x — every call raises
    ``AttributeError`` at dispatch time (the 47 standing tier-1
    failures).  Flags the stale convention with the remediation:
    ``jax.experimental.shard_map.shard_map(f, mesh=..., in_specs=...,
    out_specs=...)`` or pjit-with-shardings (ROADMAP #1).

Suppressions use the standard grammar
(``# ballista: allow=<rule> — justification``); findings on deliberate
trade-offs (the above-ceiling exact-size join compile, batched scalar
syncs) are suppressed at the tainting assignment, not the call, so the
justification sits next to the branch that makes the trade.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .framework import (
    Project,
    Rule,
    SourceFile,
    Violation,
    dotted_name,
    import_aliases,
    register,
)

# Model scope: every observed_jit construction in the execution engine.
_SCAN_DIRS = ("ops", "compile", "models")
_SCAN_FILES = ("obs/device.py",)

_WRAPPER = "observed_jit"

#: ColumnBatch attributes whose VALUES vary per batch — the taint seeds.
_VALUE_ATTRS = frozenset({"columns", "mask", "dicts",
                          "num_rows", "_num_rows"})

#: Attribute reads yielding shape-class metadata: ``capacity`` is
#: pow2-padded by ``round_capacity`` at construction, shapes key the
#: trace anyway.  Reading one of these is NOT a per-batch value.
_SANITIZED_ATTRS = frozenset({"capacity", "shape", "ndim", "size"})

#: Calls whose result is shape-class-stable even over tainted inputs:
#: pow2 bucketing and dict-domain bounds take a bounded set of values.
_SANITIZERS = frozenset({"round_capacity", "dense_domain", "bit_length"})

#: Host-only ColumnBatch attributes: reading one after donation is safe
#: (no device buffer involved).
_HOST_ATTRS = frozenset({"schema", "dicts", "capacity", "num_rows",
                         "_num_rows", "names", "fields", "dtype"})


# --------------------------------------------------------------------------
# model data structures
# --------------------------------------------------------------------------

@dataclasses.dataclass
class CallSite:
    """One resolved invocation of a jit wrapper."""

    path: str
    node: ast.Call
    func: Optional[ast.AST]  # enclosing FunctionDef (None = module level)


@dataclasses.dataclass
class JitSite:
    """One ``observed_jit(...)`` construction plus everything the rules
    need to reason about it."""

    path: str
    line: int
    sig: str                       # runtime signature ("<dynamic>" if not
                                   # a string literal)
    ctor: ast.Call
    scope_key: str                 # enclosing class name or "<module>"
    enclosing_fn: Optional[ast.AST]
    fn_node: Optional[ast.AST]     # traced Lambda/FunctionDef, if resolved
    fn_params: Optional[List[str]]
    has_varargs: bool = False
    static_argnums: Tuple[int, ...] = ()
    static_argnames: Tuple[str, ...] = ()
    donate_argnums: Tuple[int, ...] = ()
    calls: List[CallSite] = dataclasses.field(default_factory=list)

    def static_positions(self) -> Set[int]:
        pos = set(self.static_argnums)
        if self.fn_params:
            for name in self.static_argnames:
                if name in self.fn_params:
                    pos.add(self.fn_params.index(name))
        return pos


class _ModuleModel:
    """Per-file AST indexes shared by the rules."""

    def __init__(self, sf: SourceFile):
        self.sf = sf
        self.path = sf.path
        self.tree = sf.tree
        self.parents: Dict[int, ast.AST] = {}
        self.aliases = import_aliases(self.tree) if self.tree else {}
        if self.tree is not None:
            for parent in ast.walk(self.tree):
                for child in ast.iter_child_nodes(parent):
                    self.parents[id(child)] = parent

    def parent_chain(self, node: ast.AST) -> Iterable[ast.AST]:
        cur = self.parents.get(id(node))
        while cur is not None:
            yield cur
            cur = self.parents.get(id(cur))

    def enclosing(self, node: ast.AST, kinds) -> Optional[ast.AST]:
        for anc in self.parent_chain(node):
            if isinstance(anc, kinds):
                return anc
        return None

    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        return self.enclosing(
            node, (ast.FunctionDef, ast.AsyncFunctionDef))

    def enclosing_class_name(self, node: ast.AST) -> str:
        cls = self.enclosing(node, ast.ClassDef)
        return cls.name if cls is not None else "<module>"


# --------------------------------------------------------------------------
# scope-local statement walking (never descends into nested defs)
# --------------------------------------------------------------------------

_SCOPE_KINDS = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda,
                ast.ClassDef)


def _scope_nodes(root: ast.AST) -> List[ast.AST]:
    """All descendants of *root* in root's own scope — nested function /
    class bodies are opaque (they are their own scopes)."""
    out: List[ast.AST] = []

    def rec(node: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            out.append(child)
            if not isinstance(child, _SCOPE_KINDS):
                rec(child)

    rec(root)
    return out


def _target_names(target: ast.AST) -> List[str]:
    if isinstance(target, ast.Name):
        return [target.id]
    if isinstance(target, ast.Starred):
        return _target_names(target.value)
    if isinstance(target, (ast.Tuple, ast.List)):
        out: List[str] = []
        for elt in target.elts:
            out.extend(_target_names(elt))
        return out
    return []


def _literal_int_tuple(node: ast.AST) -> Tuple[int, ...]:
    try:
        val = ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return ()
    if isinstance(val, int):
        return (val,)
    if isinstance(val, (tuple, list)):
        return tuple(v for v in val if isinstance(v, int))
    return ()


def _literal_str_tuple(node: ast.AST) -> Tuple[str, ...]:
    try:
        val = ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return ()
    if isinstance(val, str):
        return (val,)
    if isinstance(val, (tuple, list)):
        return tuple(v for v in val if isinstance(v, str))
    return ()


# --------------------------------------------------------------------------
# taint analysis: which expressions carry per-batch VALUES
# --------------------------------------------------------------------------

TaintSources = Set[Tuple[int, str]]


def _expr_taint(node: Optional[ast.AST],
                env: Dict[str, TaintSources]) -> TaintSources:
    """Source set (line, why) if *node* carries a batch-varying value;
    empty set = shape-class-stable."""
    if node is None:
        return set()
    if isinstance(node, ast.Name):
        # Store/Del contexts (comprehension targets, assignment targets)
        # BIND the name — they do not read the enclosing scope's value.
        if not isinstance(node.ctx, ast.Load):
            return set()
        return env.get(node.id, set())
    if isinstance(node, ast.Attribute):
        if node.attr in _VALUE_ATTRS:
            return {(node.lineno,
                     f"reads batch content '.{node.attr}'")}
        if node.attr in _SANITIZED_ATTRS:
            return set()
        return _expr_taint(node.value, env)
    if isinstance(node, ast.Call):
        fname = None
        if isinstance(node.func, ast.Name):
            fname = node.func.id
        elif isinstance(node.func, ast.Attribute):
            fname = node.func.attr
        if fname in _SANITIZERS:
            return set()
        out: TaintSources = set()
        for arg in node.args:
            out |= _expr_taint(arg, env)
        for kw in node.keywords:
            out |= _expr_taint(kw.value, env)
        if isinstance(node.func, ast.Attribute):
            out |= _expr_taint(node.func.value, env)
        return out
    if isinstance(node, (ast.GeneratorExp, ast.ListComp, ast.SetComp,
                         ast.DictComp)):
        # comprehension targets shadow enclosing names: evaluate the
        # element in an env where each target carries its iterable's
        # taint, not the (unrelated) function-local binding.
        inner = dict(env)
        out: TaintSources = set()
        for gen in node.generators:
            iter_taint = _expr_taint(gen.iter, inner)
            out |= iter_taint
            for name in _target_names(gen.target):
                inner[name] = set(iter_taint)
            for cond in gen.ifs:
                out |= _expr_taint(cond, inner)
        if isinstance(node, ast.DictComp):
            out |= _expr_taint(node.key, inner)
            out |= _expr_taint(node.value, inner)
        else:
            out |= _expr_taint(node.elt, inner)
        return out
    if isinstance(node, (ast.Constant, ast.Lambda, ast.JoinedStr)):
        return set()
    out = set()
    for child in ast.iter_child_nodes(node):
        out |= _expr_taint(child, env)
    return out


_MUTATORS = frozenset({"append", "add", "extend", "update", "insert"})


def _function_taint_env(fn: ast.AST) -> Dict[str, TaintSources]:
    """Flow-insensitive name -> taint-source map for one function scope.

    Sources collapse to the tainting ASSIGNMENT line, so a suppression
    sits next to the branch that introduces the hazard, not the call."""
    nodes = _scope_nodes(fn)
    env: Dict[str, TaintSources] = {}

    def mark(name: str, line: int, why: str) -> bool:
        prev = env.setdefault(name, set())
        entry = (line, why)
        if entry in prev:
            return False
        prev.add(entry)
        return True

    for _ in range(4):
        changed = False
        for node in nodes:
            if isinstance(node, ast.Assign):
                taint = _expr_taint(node.value, env)
                if taint:
                    for target in node.targets:
                        for name in _target_names(target):
                            changed |= mark(
                                name, node.lineno,
                                "assigned from a batch-varying "
                                "expression")
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                if _expr_taint(node.value, env):
                    for name in _target_names(node.target):
                        changed |= mark(
                            name, node.lineno,
                            "assigned from a batch-varying expression")
            elif isinstance(node, ast.AugAssign):
                if _expr_taint(node.value, env):
                    for name in _target_names(node.target):
                        changed |= mark(
                            name, node.lineno,
                            "accumulates a batch-varying expression")
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                if _expr_taint(node.iter, env):
                    for name in _target_names(node.target):
                        changed |= mark(
                            name, node.lineno,
                            "iterates a batch-varying sequence")
            elif isinstance(node, ast.Expr) and isinstance(node.value,
                                                           ast.Call):
                call = node.value
                if (isinstance(call.func, ast.Attribute)
                        and call.func.attr in _MUTATORS
                        and isinstance(call.func.value, ast.Name)):
                    taint: TaintSources = set()
                    for arg in call.args:
                        taint |= _expr_taint(arg, env)
                    if taint:
                        changed |= mark(
                            call.func.value.id, call.lineno,
                            "mutated with a batch-varying element")
        if not changed:
            break
    return env


def _free_loads(fn: ast.AST) -> Dict[str, int]:
    """Names loaded in *fn* (including nested scopes) but never bound
    there: closure captures.  Maps name -> first-use line."""
    bound: Set[str] = set()
    args = fn.args
    for a in (args.posonlyargs + args.args + args.kwonlyargs):
        bound.add(a.arg)
    if args.vararg:
        bound.add(args.vararg.arg)
    if args.kwarg:
        bound.add(args.kwarg.arg)
    loads: Dict[str, int] = {}
    body = fn.body if isinstance(fn.body, list) else [fn.body]
    for stmt in body:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Name):
                if isinstance(node.ctx, (ast.Store, ast.Del)):
                    bound.add(node.id)
                elif node.id not in loads:
                    loads[node.id] = node.lineno
            elif isinstance(node, ast.arg):
                bound.add(node.arg)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                bound.add(node.name)
    return {n: ln for n, ln in loads.items() if n not in bound}


# --------------------------------------------------------------------------
# JitSiteModel construction
# --------------------------------------------------------------------------

class JitSiteModel:
    """All jit sites in scope, with call sites resolved."""

    def __init__(self) -> None:
        self.sites: List[JitSite] = []
        self.modules: Dict[str, _ModuleModel] = {}
        # wrapper alias names per (path, scope_key); used by the
        # donation freshness proof to recognize "result of a jit call".
        self.alias_names: Dict[Tuple[str, str], Set[str]] = {}
        self._env_cache: Dict[int, Dict[str, TaintSources]] = {}

    def taint_env(self, fn: Optional[ast.AST]) -> Dict[str, TaintSources]:
        if fn is None:
            return {}
        key = id(fn)
        if key not in self._env_cache:
            self._env_cache[key] = _function_taint_env(fn)
        return self._env_cache[key]

    def wrapper_names_in(self, path: str, scope_key: str) -> Set[str]:
        return (self.alias_names.get((path, scope_key), set())
                | self.alias_names.get((path, "<module>"), set()))


def _scan_files(project: Project) -> List[SourceFile]:
    out: List[SourceFile] = []
    pkg = project.package
    for sf in project.source_files():
        rel = sf.path
        if not rel.startswith(pkg + "/"):
            continue
        sub = rel[len(pkg) + 1:]
        if sub in _SCAN_FILES or any(
                sub.startswith(d + "/") for d in _SCAN_DIRS):
            out.append(sf)
    return out


def _is_wrapper_ctor(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and ((isinstance(node.func, ast.Name)
                  and node.func.id == _WRAPPER)
                 or (isinstance(node.func, ast.Attribute)
                     and node.func.attr == _WRAPPER)))


def _resolve_starred_dict(call: ast.Call, fn: Optional[ast.AST],
                          key: str) -> Optional[ast.AST]:
    """Resolve ``f(**kw)`` keyword *key* through ``kw[key] = <literal>``
    subscript assignments in the enclosing function."""
    names = {kw.value.id for kw in call.keywords
             if kw.arg is None and isinstance(kw.value, ast.Name)}
    if not names or fn is None:
        return None
    for node in _scope_nodes(fn):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Subscript)):
            sub = node.targets[0]
            if (isinstance(sub.value, ast.Name) and sub.value.id in names
                    and isinstance(sub.slice, ast.Constant)
                    and sub.slice.value == key):
                return node.value
    return None


def _lookup_def(name: str, mod: _ModuleModel,
                around: ast.AST) -> Optional[ast.AST]:
    """Find ``def name`` in the enclosing function chain or at module
    level."""
    scopes: List[ast.AST] = []
    fn = mod.enclosing_function(around)
    while fn is not None:
        scopes.append(fn)
        fn = mod.enclosing_function(fn)
    scopes.append(mod.tree)
    for scope in scopes:
        for stmt in _scope_nodes(scope):
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and stmt.name == name:
                return stmt
    return None


def _extract_site(ctor: ast.Call, mod: _ModuleModel,
                  decorated: Optional[ast.AST]) -> JitSite:
    sig = "<dynamic>"
    if ctor.args and isinstance(ctor.args[0], ast.Constant) \
            and isinstance(ctor.args[0].value, str):
        sig = ctor.args[0].value
    fn_node: Optional[ast.AST] = decorated
    if fn_node is None and len(ctor.args) >= 2:
        cand = ctor.args[1]
        if isinstance(cand, ast.Lambda):
            fn_node = cand
        elif isinstance(cand, ast.Name):
            fn_node = _lookup_def(cand.id, mod, ctor)
    fn_params: Optional[List[str]] = None
    has_varargs = False
    if fn_node is not None:
        args = fn_node.args
        fn_params = [a.arg for a in (args.posonlyargs + args.args)]
        has_varargs = args.vararg is not None

    statics: Tuple[int, ...] = ()
    static_names: Tuple[str, ...] = ()
    donated: Tuple[int, ...] = ()
    enclosing = mod.enclosing_function(ctor)
    for kw in ctor.keywords:
        if kw.arg == "static_argnums":
            statics = _literal_int_tuple(kw.value)
        elif kw.arg == "static_argnames":
            static_names = _literal_str_tuple(kw.value)
        elif kw.arg == "donate_argnums":
            donated = _literal_int_tuple(kw.value)
    if not donated:
        resolved = _resolve_starred_dict(ctor, enclosing, "donate_argnums")
        if resolved is not None:
            donated = _literal_int_tuple(resolved)

    return JitSite(
        path=mod.path, line=ctor.lineno, sig=sig, ctor=ctor,
        scope_key=mod.enclosing_class_name(ctor),
        enclosing_fn=enclosing, fn_node=fn_node, fn_params=fn_params,
        has_varargs=has_varargs, static_argnums=statics,
        static_argnames=static_names, donate_argnums=donated)


def build_model(project: Project) -> JitSiteModel:
    cached = getattr(project, "_jit_discipline_model", None)
    if cached is not None:
        return cached
    model = JitSiteModel()
    # name aliases: (path, scope_key, name) -> [sites]
    name_aliases: Dict[Tuple[str, str, str], List[JitSite]] = {}
    attr_aliases: Dict[Tuple[str, str, str], List[JitSite]] = {}
    # tuple shapes: (path, scope_key, arity) -> [(index, site)]
    shapes: Dict[Tuple[str, str, int], List[Tuple[int, JitSite]]] = {}
    # module-level wrapper names visible cross-file
    exports: Dict[str, JitSite] = {}

    def add_alias(table, key, site):
        table.setdefault(key, []).append(site)
        model.alias_names.setdefault((key[0], key[1]), set()).add(key[2])

    for sf in _scan_files(project):
        if sf.tree is None:
            continue
        mod = _ModuleModel(sf)
        model.modules[sf.path] = mod
        decorated_ctors: Dict[int, ast.AST] = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if _is_wrapper_ctor(dec):
                        decorated_ctors[id(dec)] = node
        for node in ast.walk(mod.tree):
            if not _is_wrapper_ctor(node):
                continue
            decorated = decorated_ctors.get(id(node))
            site = _extract_site(node, mod, decorated)
            model.sites.append(site)
            scope = site.scope_key
            if decorated is not None:
                add_alias(name_aliases, (sf.path, scope, decorated.name),
                          site)
                if mod.enclosing_function(decorated) is None \
                        and scope == "<module>":
                    exports[decorated.name] = site
                continue
            parent = mod.parents.get(id(node))
            if isinstance(parent, ast.Assign) and len(parent.targets) == 1:
                target = parent.targets[0]
                if isinstance(target, ast.Name):
                    add_alias(name_aliases, (sf.path, scope, target.id),
                              site)
                    if mod.enclosing_function(parent) is None \
                            and scope == "<module>":
                        exports[target.id] = site
                elif isinstance(target, ast.Attribute) \
                        and isinstance(target.value, ast.Name) \
                        and target.value.id == "self":
                    add_alias(attr_aliases, (sf.path, scope, target.attr),
                              site)
            elif isinstance(parent, (ast.Tuple, ast.List)):
                grand = mod.parents.get(id(parent))
                index = next(i for i, e in enumerate(parent.elts)
                             if e is node)
                arity = len(parent.elts)
                if isinstance(grand, (ast.Return, ast.Assign)):
                    shapes.setdefault((sf.path, scope, arity), []) \
                        .append((index, site))
                if isinstance(grand, ast.Assign) \
                        and len(grand.targets) == 1 \
                        and isinstance(grand.targets[0], ast.Attribute) \
                        and isinstance(grand.targets[0].value, ast.Name) \
                        and grand.targets[0].value.id == "self":
                    # self._x = (..., wrapper, ...): unpacks of self._x
                    # match through the same shape table.
                    pass

    # second pass: match same-shape tuple unpacks to register aliases
    for sf_path, mod in model.modules.items():
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], (ast.Tuple, ast.List))):
                continue
            elts = node.targets[0].elts
            if any(isinstance(e, ast.Starred) for e in elts):
                continue
            scope = mod.enclosing_class_name(node)
            for index, site in shapes.get((sf_path, scope, len(elts)), ()):
                target = elts[index]
                if isinstance(target, ast.Name) and target.id != "_":
                    add_alias(name_aliases, (sf_path, scope, target.id),
                              site)
                elif isinstance(target, ast.Attribute) \
                        and isinstance(target.value, ast.Name) \
                        and target.value.id == "self":
                    add_alias(attr_aliases, (sf_path, scope, target.attr),
                              site)

    # third pass: resolve call sites against the alias tables
    def plausible(site: JitSite, call: ast.Call) -> bool:
        if site.fn_params is None or site.has_varargs:
            return True
        return len(call.args) + len(call.keywords) <= len(site.fn_params)

    for sf_path, mod in model.modules.items():
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call) or _is_wrapper_ctor(node):
                continue
            scope = mod.enclosing_class_name(node)
            targets: List[JitSite] = []
            if isinstance(node.func, ast.Name):
                name = node.func.id
                targets += name_aliases.get((sf_path, scope, name), [])
                if scope != "<module>":
                    targets += name_aliases.get(
                        (sf_path, "<module>", name), [])
                if not targets and name in exports \
                        and name in mod.aliases:
                    targets.append(exports[name])
            elif isinstance(node.func, ast.Attribute) \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id == "self":
                targets += attr_aliases.get(
                    (sf_path, scope, node.func.attr), [])
            fn = mod.enclosing_function(node)
            for site in targets:
                if plausible(site, node):
                    site.calls.append(CallSite(sf_path, node, fn))

    project._jit_discipline_model = model  # type: ignore[attr-defined]
    return model


# --------------------------------------------------------------------------
# shared read-after analysis (donation)
# --------------------------------------------------------------------------

def _pos_after(node: ast.AST, call: ast.Call) -> bool:
    end_line = getattr(call, "end_lineno", call.lineno)
    end_col = getattr(call, "end_col_offset", 0)
    return (node.lineno, node.col_offset) > (end_line, end_col)


def _reads_after(mod: _ModuleModel, fn: Optional[ast.AST], call: ast.Call,
                 base: str, attr: Optional[str]) -> List[Tuple[int, str]]:
    """Reads of *base* (restricted to *attr* when given) that can observe
    state after *call* ran: later in source, or anywhere inside a shared
    loop that does not rebind *base* per iteration."""
    if fn is None:
        return []
    in_call = {id(n) for n in ast.walk(call)}
    shared_loops = []
    for anc in mod.parent_chain(call):
        if anc is fn:
            break
        if isinstance(anc, ast.While):
            shared_loops.append(anc)
        elif isinstance(anc, (ast.For, ast.AsyncFor)):
            if base not in _target_names(anc.target):
                shared_loops.append(anc)
    loop_members = set()
    for loop in shared_loops:
        loop_members |= {id(n) for n in ast.walk(loop)}

    out: List[Tuple[int, str]] = []
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Name) and node.id == base
                and isinstance(node.ctx, ast.Load)):
            continue
        if id(node) in in_call:
            continue
        if not (_pos_after(node, call) or id(node) in loop_members):
            continue
        parent = mod.parents.get(id(node))
        if isinstance(parent, ast.Attribute) and parent.value is node:
            a = parent.attr
            grand = mod.parents.get(id(parent))
            is_method = isinstance(grand, ast.Call) and grand.func is parent
            if attr is not None:
                if a == attr:
                    out.append((node.lineno, f"re-reads '.{a}'"))
                elif a in _HOST_ATTRS or a in _VALUE_ATTRS \
                        or a in _SANITIZED_ATTRS:
                    continue  # a different, undonated buffer / host data
                elif is_method:
                    out.append((node.lineno,
                                f"calls '.{a}()' which may read the "
                                f"donated buffer"))
                else:
                    out.append((node.lineno, f"reads '.{a}'"))
            else:
                if a in _HOST_ATTRS:
                    continue
                out.append((node.lineno, f"reads '.{a}'"))
        else:
            out.append((node.lineno, "the object escapes"))
    return out


def _arg_base(expr: ast.AST) -> Optional[Tuple[str, Optional[str]]]:
    """(base_name, attr) for ``b.columns`` / plain ``b`` arguments."""
    if isinstance(expr, ast.Attribute) and isinstance(expr.value, ast.Name):
        return expr.value.id, expr.attr
    if isinstance(expr, ast.Name):
        return expr.id, None
    return None


# --------------------------------------------------------------------------
# rule 1: trace-key-stability
# --------------------------------------------------------------------------

@register
class TraceKeyStabilityRule(Rule):
    name = "trace-key-stability"
    description = ("batch-varying values must not reach static argument "
                   "positions, be baked into traced closures, or rebuild "
                   "wrappers per loop iteration — each mints a new trace "
                   "(seen as jit_retraces under the same signature in "
                   "the device observatory)")

    def check(self, project: Project) -> Iterable[Violation]:
        model = build_model(project)
        seen: Set[Tuple[str, int, str]] = set()

        def emit(path: str, line: int, msg: str):
            key = (path, line, msg)
            if key not in seen:
                seen.add(key)
                yield Violation(self.name, path, line, msg)

        for site in model.sites:
            mod = model.modules[site.path]
            # (a) construction inside a loop: empty trace cache per pass
            for anc in mod.parent_chain(site.ctor):
                if site.enclosing_fn is not None and anc is site.enclosing_fn:
                    break
                if isinstance(anc, (ast.For, ast.AsyncFor, ast.While)):
                    yield from emit(
                        site.path, site.line,
                        f"jit site '{site.sig}' is constructed inside a "
                        f"loop — every construction starts an empty "
                        f"trace cache, so each iteration recompiles")
                    break
            # (b) batch-varying closure captures baked into the trace
            if site.fn_node is not None and site.enclosing_fn is not None:
                env = model.taint_env(site.enclosing_fn)
                for name in sorted(_free_loads(site.fn_node)):
                    for src_line, why in sorted(env.get(name, ())):
                        yield from emit(
                            site.path, site.line,
                            f"traced body of '{site.sig}' closes over "
                            f"'{name}' ({why} at line {src_line}) — the "
                            f"value is baked into the trace and every "
                            f"new value retraces")
            # (c) batch-varying values flowing into static positions
            static_pos = site.static_positions()
            static_kw = set(site.static_argnames)
            if not static_pos and not static_kw:
                continue
            for cs in site.calls:
                env = model.taint_env(cs.func)
                exprs: List[Tuple[str, ast.AST]] = []
                for p in sorted(static_pos):
                    if p < len(cs.node.args):
                        exprs.append((f"position {p}", cs.node.args[p]))
                for kw in cs.node.keywords:
                    if kw.arg in static_kw:
                        exprs.append((f"'{kw.arg}'", kw.value))
                for desc, expr in exprs:
                    for src_line, why in sorted(_expr_taint(expr, env)):
                        yield from emit(
                            cs.path, src_line,
                            f"static argument {desc} of jit site "
                            f"'{site.sig}' (called at line "
                            f"{cs.node.lineno}) takes a batch-varying "
                            f"value ({why}) — every distinct value "
                            f"mints a new trace; sanitize through "
                            f"round_capacity/pow2 bucketing or demote "
                            f"from the static set")


# --------------------------------------------------------------------------
# rule 2: donation-safety
# --------------------------------------------------------------------------

@register
class DonationSafetyRule(Rule):
    name = "donation-safety"
    description = ("donated buffers are deleted by XLA: flags reads after "
                   "the donating call (use-after-donation) and advises on "
                   "arguments provably dead after every call "
                   "(provably-safe-but-undonated)")

    def check(self, project: Project) -> Iterable[Violation]:
        model = build_model(project)
        for site in model.sites:
            mod = model.modules[site.path]
            static_pos = site.static_positions()
            if site.donate_argnums:
                yield from self._check_donated(site, mod)
                yield from self._advise_shared_base(site, mod, static_pos)
            else:
                yield from self._advise_fresh(site, mod, model, static_pos)

    def _check_donated(self, site: JitSite,
                       mod: _ModuleModel) -> Iterable[Violation]:
        for cs in site.calls:
            for p in site.donate_argnums:
                if p >= len(cs.node.args):
                    continue
                based = _arg_base(cs.node.args[p])
                if based is None:
                    continue
                base, attr = based
                for line, why in _reads_after(mod, cs.func, cs.node,
                                              base, attr):
                    arg = base if attr is None else f"{base}.{attr}"
                    yield Violation(
                        self.name, cs.path, line,
                        f"use-after-donation: argument {p} ('{arg}') of "
                        f"jit site '{site.sig}' is donated at line "
                        f"{cs.node.lineno}, but this {why} — the buffer "
                        f"is deleted by XLA after the call")

    def _advise_shared_base(self, site: JitSite, mod: _ModuleModel,
                            static_pos: Set[int]) -> Iterable[Violation]:
        """Undonated args sharing a donated arg's base and dead after
        every call can ride the same freshness proof."""
        if site.fn_params is None or not site.calls:
            return
        arity = len(site.fn_params)
        for p in range(arity):
            if p in site.donate_argnums or p in static_pos:
                continue
            proof = []
            for cs in site.calls:
                if p >= len(cs.node.args):
                    proof = None
                    break
                based = _arg_base(cs.node.args[p])
                if based is None or based[1] is None:
                    proof = None
                    break
                base, attr = based
                donated_bases = {
                    _arg_base(cs.node.args[d])[0]
                    for d in site.donate_argnums
                    if d < len(cs.node.args)
                    and _arg_base(cs.node.args[d]) is not None}
                if base not in donated_bases:
                    proof = None
                    break
                if _reads_after(mod, cs.func, cs.node, base, attr):
                    proof = None
                    break
                proof.append(f"'{base}.{attr}'")
            if proof is None:
                continue
            yield Violation(
                self.name, site.path, site.line,
                f"provably-safe-but-undonated: argument {p} "
                f"({', '.join(sorted(set(proof)))}) of jit site "
                f"'{site.sig}' shares the donated arguments' provenance "
                f"and is dead after every call site — extend "
                f"donate_argnums to include {p}")

    def _advise_fresh(self, site: JitSite, mod: _ModuleModel,
                      model: JitSiteModel,
                      static_pos: Set[int]) -> Iterable[Violation]:
        """Undonated sites whose inputs are freshly produced by another
        jit call in the same function and dead after every call."""
        if site.fn_params is None or not site.calls:
            return
        arity = len(site.fn_params)
        for p in range(arity):
            if p in static_pos:
                continue
            ok = bool(site.calls)
            names = set()
            for cs in site.calls:
                if cs.func is None or p >= len(cs.node.args):
                    ok = False
                    break
                based = _arg_base(cs.node.args[p])
                if based is None:
                    ok = False
                    break
                base, attr = based
                if not self._always_fresh(base, cs, mod, model):
                    ok = False
                    break
                if _reads_after(mod, cs.func, cs.node, base, attr):
                    ok = False
                    break
                names.add(base if attr is None else f"{base}.{attr}")
            if ok:
                yield Violation(
                    self.name, site.path, site.line,
                    f"provably-safe-but-undonated: argument {p} "
                    f"({', '.join(sorted(names))}) of jit site "
                    f"'{site.sig}' is freshly produced by another jit "
                    f"call and dead after every call site — donate it "
                    f"(donate_argnums=({p},)) to let XLA reuse the "
                    f"buffer")

    @staticmethod
    def _always_fresh(base: str, cs: CallSite, mod: _ModuleModel,
                      model: JitSiteModel) -> bool:
        """True when *base* is bound ONLY from jit-wrapper call results
        in the call's enclosing function (a fresh device buffer this
        function owns)."""
        wrappers = model.wrapper_names_in(
            cs.path, mod.enclosing_class_name(cs.node))
        found = False
        for node in _scope_nodes(cs.func):
            if isinstance(node, ast.Assign):
                bound = []
                for t in node.targets:
                    bound.extend(_target_names(t))
                if base not in bound:
                    continue
                value = node.value
                is_wrapper_call = (
                    isinstance(value, ast.Call)
                    and ((isinstance(value.func, ast.Name)
                          and value.func.id in wrappers)
                         or (isinstance(value.func, ast.Attribute)
                             and value.func.attr in wrappers)))
                if not is_wrapper_call:
                    return False
                found = True
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                if base in _target_names(node.target):
                    return False
            elif isinstance(node, ast.AugAssign):
                if base in _target_names(node.target):
                    return False
        return found


# --------------------------------------------------------------------------
# rule 3: host-device-boundary
# --------------------------------------------------------------------------

# obs/device.py: the boundary scopes (a span plus the byte counters, from
# the same two readings of the clock) and the bare counter call
_TRANSFER_ACCOUNTING = ("record_transfer", "device_wait", "h2d")


@register
class HostDeviceBoundaryRule(Rule):
    name = "host-device-boundary"
    description = ("traced bodies must stay on-device (no host numpy, "
                   ".tolist/.item, float()/int()/bool() concretization, "
                   "or float64 promotion); device_get/device_put outside "
                   "the accounted materialization sites must run inside "
                   "device_wait/h2d (or call record_transfer)")

    def check(self, project: Project) -> Iterable[Violation]:
        model = build_model(project)
        seen_bodies: Set[int] = set()
        for site in model.sites:
            if site.fn_node is None or id(site.fn_node) in seen_bodies:
                continue
            seen_bodies.add(id(site.fn_node))
            mod = model.modules[site.path]
            yield from self._check_body(site, mod)
        for path, mod in model.modules.items():
            yield from self._check_transfers(mod)

    def _check_body(self, site: JitSite,
                    mod: _ModuleModel) -> Iterable[Violation]:
        numpy_names = {local for local, target in mod.aliases.items()
                       if target == "numpy"}
        body = site.fn_node.body
        stmts = body if isinstance(body, list) else [body]
        for stmt in stmts:
            for node in ast.walk(stmt):
                if isinstance(node, ast.Call):
                    func = node.func
                    if isinstance(func, ast.Attribute):
                        root = func.value
                        while isinstance(root, ast.Attribute):
                            root = root.value
                        if isinstance(root, ast.Name) \
                                and root.id in numpy_names:
                            yield Violation(
                                self.name, site.path, node.lineno,
                                f"host numpy call "
                                f"'{dotted_name(func)}' inside the "
                                f"traced body of '{site.sig}' — "
                                f"materializes on host under jit")
                        if func.attr in ("tolist", "item"):
                            yield Violation(
                                self.name, site.path, node.lineno,
                                f"'.{func.attr}()' inside the traced "
                                f"body of '{site.sig}' forces a "
                                f"device->host sync per trace")
                        if func.attr == "astype" and node.args \
                                and isinstance(node.args[0], ast.Name) \
                                and node.args[0].id == "float":
                            yield Violation(
                                self.name, site.path, node.lineno,
                                f"astype(float) inside the traced body "
                                f"of '{site.sig}' promotes to float64 "
                                f"(weak-typed python float)")
                    elif isinstance(func, ast.Name) \
                            and func.id in ("float", "int", "bool"):
                        yield Violation(
                            self.name, site.path, node.lineno,
                            f"'{func.id}()' inside the traced body of "
                            f"'{site.sig}' concretizes a tracer — "
                            f"aborts tracing or forces a host sync")
                elif isinstance(node, ast.Attribute) \
                        and node.attr == "float64":
                    yield Violation(
                        self.name, site.path, node.lineno,
                        f"float64 inside the traced body of "
                        f"'{site.sig}' — x64 promotion doubles "
                        f"transfer bytes and splits the trace-key "
                        f"space")

    def _check_transfers(self, mod: _ModuleModel) -> Iterable[Violation]:
        jax_names = {local for local, target in mod.aliases.items()
                     if target == "jax"}
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if mod.enclosing_function(node) is not None:
                continue  # nested defs are covered by their outer walk
            transfers: List[Tuple[int, str]] = []
            accounted = False
            for sub in ast.walk(node):
                if not isinstance(sub, ast.Call):
                    continue
                dn = dotted_name(sub.func) or ""
                leaf = dn.rsplit(".", 1)[-1]
                if leaf in _TRANSFER_ACCOUNTING:
                    accounted = True
                elif leaf in ("device_get", "device_put") and (
                        "." not in dn or dn.split(".", 1)[0] in jax_names):
                    transfers.append((sub.lineno, leaf))
            if transfers and not accounted:
                for line, leaf in transfers:
                    yield Violation(
                        self.name, mod.path, line,
                        f"'{leaf}' in '{node.name}' outside device_wait/"
                        f"h2d and without record_transfer — the transfer is "
                        f"invisible to the device observatory's byte "
                        f"accounting (models/batch.py shows the "
                        f"sanctioned pattern)")


# --------------------------------------------------------------------------
# rule 4: fusion-verdict-consistency
# --------------------------------------------------------------------------

@register
class FusionVerdictConsistencyRule(Rule):
    name = "fusion-verdict-consistency"
    description = ("compile/fuse.py's operator allowlist, _op_verdict "
                   "branches, fused.py kernel builders, and chains.py "
                   "reason tables must agree with the operator classes "
                   "that exist (and consult host_mode where the class "
                   "has one)")

    def check(self, project: Project) -> Iterable[Violation]:
        pkg = project.package
        fuse = project.file(f"{pkg}/compile/fuse.py")
        if fuse is None or fuse.tree is None:
            return
        fused = project.file(f"{pkg}/compile/fused.py")
        chains = project.file(f"{pkg}/compile/chains.py")
        classes = self._class_index(project)
        model = build_model(project)

        allow, allow_line = self._allowlist(fuse)
        verdicts = self._verdict_branches(fuse)
        builder_names = self._referenced_names(fused)

        impure: Dict[str, List[Violation]] = {}
        body_rule = HostDeviceBoundaryRule()
        for site in model.sites:
            if site.scope_key == "<module>" or site.fn_node is None:
                continue
            mod = model.modules[site.path]
            hits = list(body_rule._check_body(site, mod))
            if hits:
                impure.setdefault(site.scope_key, []).extend(hits)

        for name in sorted(allow):
            if name not in classes:
                yield Violation(
                    self.name, fuse.path, allow_line,
                    f"allowlisted operator '{name}' is not a class "
                    f"under ops/ or compile/ — stale allowlist entry")
                continue
            if name not in builder_names:
                yield Violation(
                    self.name, fuse.path, allow_line,
                    f"allowlisted operator '{name}' has no kernel "
                    f"builder in compile/fused.py — fusion would fail "
                    f"at stage resolution")
            if name not in verdicts:
                yield Violation(
                    self.name, fuse.path, allow_line,
                    f"allowlisted operator '{name}' has no per-node "
                    f"branch in _op_verdict — nodes fuse without a "
                    f"doubt check")
            elif classes[name][1] and not verdicts[name]:
                yield Violation(
                    self.name, fuse.path, allow_line,
                    f"'{name}' has a host_mode escape hatch but its "
                    f"_op_verdict branch never consults it — host-mode "
                    f"nodes would fuse onto the device path")
            for v in impure.get(name, ()):
                yield Violation(
                    self.name, v.path, v.line,
                    f"allowlisted operator '{name}' builds an impure "
                    f"device closure: {v.message}")

        if chains is not None and chains.tree is not None:
            for table in ("UNFUSABLE", "STATIC_REASONS"):
                for name, line in self._table_names(chains, table):
                    if name not in classes:
                        yield Violation(
                            self.name, chains.path, line,
                            f"{table} names '{name}', which is not a "
                            f"class under ops/ or compile/ — stale "
                            f"chain-table entry")

    @staticmethod
    def _class_index(project: Project) -> Dict[str, Tuple[str, bool]]:
        """class name -> (path, has host_mode) over ops/ + compile/."""
        out: Dict[str, Tuple[str, bool]] = {}
        pkg = project.package
        for sf in project.source_files():
            sub = sf.path[len(pkg) + 1:] if sf.path.startswith(pkg + "/") \
                else sf.path
            if not (sub.startswith("ops/") or sub.startswith("compile/")):
                continue
            if sf.tree is None:
                continue
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.ClassDef):
                    has_hm = any(
                        isinstance(n, (ast.Attribute, ast.arg, ast.Name))
                        and (getattr(n, "attr", None) == "host_mode"
                             or getattr(n, "arg", None) == "host_mode"
                             or getattr(n, "id", None) == "host_mode")
                        for n in ast.walk(node))
                    out[node.name] = (sf.path, has_hm)
        return out

    @staticmethod
    def _allowlist(fuse: SourceFile) -> Tuple[Set[str], int]:
        for node in ast.walk(fuse.tree):
            if isinstance(node, ast.Assign) \
                    and any(isinstance(t, ast.Name)
                            and t.id == "DEFAULT_OPERATORS"
                            for t in node.targets):
                try:
                    names = ast.literal_eval(
                        node.value.args[0]
                        if isinstance(node.value, ast.Call)
                        and node.value.args else node.value)
                except (ValueError, SyntaxError, AttributeError):
                    return set(), node.lineno
                return {n for n in names if isinstance(n, str)}, \
                    node.lineno
        return set(), 0

    @staticmethod
    def _verdict_branches(fuse: SourceFile) -> Dict[str, bool]:
        """class name -> its _op_verdict branch mentions host_mode."""
        out: Dict[str, bool] = {}
        for node in ast.walk(fuse.tree):
            if isinstance(node, ast.FunctionDef) \
                    and node.name == "_op_verdict":
                for sub in ast.walk(node):
                    if not isinstance(sub, ast.If):
                        continue
                    names = []
                    for c in ast.walk(sub.test):
                        if isinstance(c, ast.Call) \
                                and isinstance(c.func, ast.Name) \
                                and c.func.id == "isinstance" \
                                and len(c.args) == 2:
                            cls = c.args[1]
                            if isinstance(cls, ast.Name):
                                names.append(cls.id)
                            elif isinstance(cls, ast.Tuple):
                                names += [e.id for e in cls.elts
                                          if isinstance(e, ast.Name)]
                    if not names:
                        continue
                    branch_hm = any(
                        getattr(n, "attr", None) == "host_mode"
                        for b in sub.body for n in ast.walk(b)) or any(
                        getattr(n, "attr", None) == "host_mode"
                        for n in ast.walk(sub.test))
                    for n in names:
                        out[n] = out.get(n, False) or branch_hm
        return out

    @staticmethod
    def _referenced_names(fused: Optional[SourceFile]) -> Set[str]:
        if fused is None or fused.tree is None:
            return set()
        return {n.id for n in ast.walk(fused.tree)
                if isinstance(n, ast.Name)}

    @staticmethod
    def _table_names(chains: SourceFile,
                     table: str) -> List[Tuple[str, int]]:
        for node in ast.walk(chains.tree):
            if isinstance(node, ast.Assign) \
                    and any(isinstance(t, ast.Name) and t.id == table
                            for t in node.targets):
                try:
                    val = ast.literal_eval(node.value)
                except (ValueError, SyntaxError):
                    return []
                names = list(val.keys()) if isinstance(val, dict) \
                    else list(val)
                return [(n, node.lineno) for n in names
                        if isinstance(n, str)]
        return []


# --------------------------------------------------------------------------
# rule 5: deprecated-jax-api
# --------------------------------------------------------------------------

@register
class DeprecatedJaxApiRule(Rule):
    name = "deprecated-jax-api"
    description = ("jax.shard_map does not exist in jax 0.4.x — flags "
                   "the stale calling convention with its remediation "
                   "(the root cause of the standing multi-device test "
                   "failures)")

    _REMEDIATION = (
        "'jax.shard_map' is not an attribute in jax 0.4.x — this raises "
        "AttributeError at dispatch time (the 47 standing tier-1 "
        "failures in tests/test_parallel.py and test_udf.py).  Port to "
        "jax.experimental.shard_map.shard_map(f, mesh=..., in_specs=..., "
        "out_specs=...) — same kwargs, verified against the pinned jax — "
        "or pjit with shardings (ROADMAP #1)")

    def check(self, project: Project) -> Iterable[Violation]:
        for sf in project.source_files():
            if sf.tree is None:
                continue
            jax_names = {local for local, target
                         in import_aliases(sf.tree).items()
                         if target == "jax"}
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.Attribute) \
                        and node.attr == "shard_map" \
                        and isinstance(node.value, ast.Name) \
                        and node.value.id in jax_names:
                    yield Violation(self.name, sf.path, node.lineno,
                                    self._REMEDIATION)
                elif isinstance(node, ast.ImportFrom) \
                        and node.module == "jax" \
                        and any(a.name == "shard_map"
                                for a in node.names):
                    yield Violation(self.name, sf.path, node.lineno,
                                    self._REMEDIATION)
