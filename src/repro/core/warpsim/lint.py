"""warpsim-lint: the stack's conventions as enforced static analysis.

The reproduction's correctness story — bit-identical records across five
engines, three backends, and a federated mesh — rests on invariants that
earlier PRs established by convention and (in PR 4's case) re-learned
the hard way. This module turns each of them into a stdlib-``ast`` check
that runs over the tree and fails CI on violations, so the conventions
ratchet instead of eroding:

``jax-containment``
    ``import jax`` (any spelling) and use of an unbound ``jax`` name in
    ``repro/core/`` modules outside the allowlist (``compat.py``,
    ``_pallas.py``). New jax surface is reviewed in one place only if
    the compat module is the one choke point it flows through.
``typed-http-boundary``
    ``urllib.request.urlopen`` outside the two blessed transport
    wrappers (``work_queue._http_json``, ``benchmarks/service_smoke``),
    and any ``except urllib.error.*`` handler that does not raise a
    ``faults.ServiceError`` subtype on every path. PR 7's contract: raw
    urllib exceptions never escape a typed boundary.
``lock-discipline``
    Module-level mutable containers in warpsim modules must carry a
    ``# guarded-by: <lock>`` annotation (``# guarded-by: frozen`` for
    populate-once constants), and every mutation site must sit inside
    ``with <lock>:``. The static twin of PR 4's concurrency bugfix
    sweep.
``determinism``
    ``time.time`` / ``datetime.now`` / global-RNG ``random.*`` /
    unseeded RNG constructors / iteration over ``set`` literals inside
    the cache-key/expansion/timing modules. Bit-identity of cached
    records depends on these modules being pure functions of their
    inputs. Scope is the ``DETERMINISM_MODULES`` list below; ``obs.py``
    is deliberately outside it (see the note on the list).
``fault-registry``
    Every literal ``fault_point("...")`` must match a pattern in
    ``faults.KNOWN_POINTS`` — the chaos harness's grammar cannot drift
    from the points the daemons actually consult.
``env-registry``
    Every ``WARPSIM_*`` environment read goes through the
    ``repro.core.warpsim.envcfg`` accessors (name + default + doc in one
    registry); raw ``os.environ`` reads inside warpsim modules are
    flagged regardless of name.

Findings print as ``file:line rule-id message``; the CLI exits 1 when
any survive::

    python -m repro.core.warpsim.lint [--json] [paths ...]

A finding is suppressed by a trailing comment on its line::

    data = urllib.request.urlopen(url)  # warpsim-lint: disable=typed-http-boundary

For a *simple* statement that spans multiple lines (a wrapped call,
a parenthesized assignment), the comment may sit on any line of the
statement — findings anchor on the first line, but the natural home
for a trailing comment is often the closing one, and both work.
Compound statements (``def``/``if``/``with``/...) get no such
spreading: a comment inside a body never silences the header.
Each suppression silences exactly the named rule(s) on exactly that
statement; an unknown rule id in a suppression is itself a finding
(``bad-suppression``). Suppressions are for deliberate exceptions (tests
speaking raw HTTP at a daemon to assert protocol behavior) — document
the why next to them.
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import fnmatch
import io
import json
import os
import re
import sys
import tokenize
from typing import Dict, Iterable, Iterator, List, Optional, Set, Tuple

from repro.core.warpsim import faults as _faults
from repro.core.warpsim.faults import KNOWN_POINTS

#: rule-id -> one-line description (the ``--list-rules`` output and the
#: vocabulary `# warpsim-lint: disable=` suppressions are checked against).
RULES: Dict[str, str] = {  # guarded-by: frozen
    "jax-containment":
        "jax is imported directly outside compat.py/_pallas.py",
    "typed-http-boundary":
        "raw urlopen outside the blessed transports, or an urllib.error "
        "handler that can exit without raising a faults.ServiceError",
    "lock-discipline":
        "module-level mutable container without a '# guarded-by:' "
        "annotation, or mutated outside its lock",
    "determinism":
        "wall-clock / global-RNG / set-literal iteration inside a "
        "cache-key, expansion, or timing module",
    "fault-registry":
        "fault_point(...) literal not registered in faults.KNOWN_POINTS",
    "env-registry":
        "WARPSIM_* environment read bypassing envcfg accessors",
    "bad-suppression":
        "warpsim-lint suppression naming an unknown rule id",
    "parse-error":
        "file could not be parsed",
}

#: Basenames allowed to touch jax inside repro/core/ (the compat choke
#: point itself, and the device engine built on top of it).
JAX_ALLOWLIST = ("compat.py", "_pallas.py")

#: The two blessed transport wrappers — the only call sites where
#: ``urllib.request.urlopen`` is legal (path suffixes, "/"-normalized).
HTTP_TRANSPORTS = (
    "repro/core/warpsim/work_queue.py",   # _http_json: the typed transport
    "benchmarks/service_smoke.py",        # _get: the daemon boot prober
)

#: Warpsim modules whose outputs feed cache keys / cached records.
#: Anything nondeterministic here silently poisons bit-identity.
#:
#: ``obs.py`` is *deliberately absent*: observability is the one module
#: whose whole job is reading a clock, and it is allowed
#: ``time.monotonic`` because (a) the clock is injectable
#: (``Observability(clock=...)`` / ``MetricsRegistry(clock=...)``) so
#: tests pin it, and (b) nothing obs measures — span durations, stage
#: histograms — ever feeds a cache key or a cached record; it only
#: annotates them. The determinism modules themselves stay clock-free
#: by calling ``obs.stage(...)`` / ``obs.span(...)``: the context
#: manager is imported *into* e.g. ``sweep.py``, but the clock reads
#: resolve inside ``obs.py``, outside this scope. Timing a stage from
#: a determinism module directly (``time.monotonic()`` in ``sweep.py``)
#: is still a finding — route it through obs.
DETERMINISM_MODULES = frozenset({
    "config.py", "trace.py", "divergence.py", "coalesce.py", "sweep.py",
    "timing.py", "machines.py", "_native.py", "_pallas.py",
})

#: Exception names accepted as "typed" raises at an urllib boundary:
#: exactly the faults.ServiceError family, derived from the module so
#: the set cannot drift from faults.py. Other exceptions that merely
#: live in faults (e.g. FaultError) do NOT satisfy the boundary rule.
SERVICE_ERROR_NAMES = frozenset(
    name for name, obj in vars(_faults).items()
    if isinstance(obj, type) and issubclass(obj, _faults.ServiceError))

#: Container methods that mutate in place (dict/list/set/OrderedDict/deque).
MUTATOR_METHODS = frozenset({
    "add", "append", "appendleft", "clear", "discard", "extend", "insert",
    "move_to_end", "pop", "popitem", "popleft", "remove", "setdefault",
    "update",
})

#: Constructors whose result is a module-level mutable container.
CONTAINER_CONSTRUCTORS = frozenset({
    "dict", "list", "set",
    "collections.OrderedDict", "collections.defaultdict",
    "collections.deque", "collections.Counter",
})

#: Wall-clock calls (canonical dotted names) banned in determinism modules.
CLOCK_CALLS = frozenset({
    "time.time", "time.time_ns", "time.monotonic", "time.monotonic_ns",
    "time.perf_counter", "time.perf_counter_ns",
    "datetime.datetime.now", "datetime.datetime.utcnow",
    "datetime.datetime.today", "datetime.date.today",
})

#: RNG constructors that are fine *seeded* but nondeterministic bare.
SEEDED_RNG_CONSTRUCTORS = frozenset({
    "random.Random", "numpy.random.default_rng", "numpy.random.RandomState",
    "numpy.random.Generator", "numpy.random.seed",
})

_SUPPRESS_RE = re.compile(r"warpsim-lint:\s*disable=([A-Za-z0-9_,\-]+)")
_GUARDED_RE = re.compile(r"guarded-by:\s*([A-Za-z_][\w.]*)")


@dataclasses.dataclass(frozen=True, order=True)
class Finding:
    """One violation: where, which rule, and what to do about it."""

    path: str
    line: int
    rule: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line} {self.rule} {self.message}"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# Per-file context: imports, comments, suppressions
# ---------------------------------------------------------------------------


def _norm(path: str) -> str:
    return path.replace(os.sep, "/")


def _in_warpsim(path: str) -> bool:
    return "repro/core/warpsim/" in _norm(path)


def _in_core(path: str) -> bool:
    return "repro/core/" in _norm(path)


class _FileContext:
    """Everything the rules need about one source file.

    ``imports`` maps local names to canonical dotted module paths
    (``np`` -> ``numpy``, ``urlopen`` -> ``urllib.request.urlopen``), so
    rules match *what* is called, not how the import spelled it.
    ``comments`` maps line numbers to comment text (via ``tokenize``, so
    string literals that merely look like comments are never matched).
    """

    def __init__(self, path: str, source: str, tree: ast.Module):
        self.path = path
        self.norm = _norm(path)
        self.base = os.path.basename(path)
        self.source = source
        self.tree = tree
        self.imports: Dict[str, str] = {}
        self.bound_names: Set[str] = set()
        self.env_constants: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.asname:
                        self.imports[alias.asname] = alias.name
                    else:
                        root = alias.name.split(".")[0]
                        self.imports[root] = root
            elif isinstance(node, ast.ImportFrom):
                if node.module and node.level == 0:
                    for alias in node.names:
                        name = alias.asname or alias.name
                        self.imports[name] = f"{node.module}.{alias.name}"
            elif isinstance(node, ast.Name) and isinstance(
                    node.ctx, (ast.Store, ast.Del)):
                self.bound_names.add(node.id)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                self.bound_names.add(node.name)
            elif isinstance(node, ast.arg):
                self.bound_names.add(node.arg)
        # Module-level `NAME = "WARPSIM_..."` constants: reading the env
        # through one of these is still a WARPSIM_* read.
        for stmt in tree.body:
            if (isinstance(stmt, ast.Assign)
                    and isinstance(stmt.value, ast.Constant)
                    and isinstance(stmt.value.value, str)
                    and stmt.value.value.startswith("WARPSIM_")):
                for target in stmt.targets:
                    if isinstance(target, ast.Name):
                        self.env_constants.add(target.id)
        self.comments: Dict[int, str] = {}
        try:
            for tok in tokenize.generate_tokens(io.StringIO(source).readline):
                if tok.type == tokenize.COMMENT:
                    self.comments[tok.start[0]] = tok.string
        except tokenize.TokenError:
            pass

    # ------------------------------------------------------------ resolve

    def resolve(self, node: ast.AST) -> Optional[str]:
        """Canonical dotted name of an expression, or None.

        ``np.random.default_rng`` resolves to
        ``numpy.random.default_rng`` under ``import numpy as np``; names
        with no import binding resolve to None (locals are not modules).
        """
        parts: List[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        root = self.imports.get(node.id)
        if root is None:
            return None
        parts.append(root)
        return ".".join(reversed(parts))

    def suppressions(self) -> Tuple[Dict[int, Set[str]], List[Finding]]:
        """line -> suppressed rule ids, plus bad-suppression findings."""
        table: Dict[int, Set[str]] = {}
        bad: List[Finding] = []
        for line, comment in self.comments.items():
            m = _SUPPRESS_RE.search(comment)
            if not m:
                continue
            for rule in m.group(1).split(","):
                rule = rule.strip()
                if not rule:
                    continue
                if rule not in RULES:
                    bad.append(Finding(
                        self.path, line, "bad-suppression",
                        f"unknown rule id {rule!r} in suppression "
                        f"(known: {', '.join(sorted(RULES))})"))
                    continue
                table.setdefault(line, set()).add(rule)
        return table, bad

    def guarded_by(self, line: int) -> Optional[str]:
        """The ``# guarded-by:`` annotation on `line` (or the line above)."""
        for candidate in (line, line - 1):
            comment = self.comments.get(candidate)
            if comment:
                m = _GUARDED_RE.search(comment)
                if m:
                    return m.group(1)
        return None


def _walk_with_ancestors(tree: ast.AST) -> Iterator[Tuple[ast.AST,
                                                          List[ast.AST]]]:
    """Yield every node with the chain of its ancestors (outermost first)."""
    stack: List[ast.AST] = []

    def visit(node: ast.AST) -> Iterator[Tuple[ast.AST, List[ast.AST]]]:
        yield node, list(stack)
        stack.append(node)
        for child in ast.iter_child_nodes(node):
            yield from visit(child)
        stack.pop()

    yield from visit(tree)


# ---------------------------------------------------------------------------
# Rule: jax-containment
# ---------------------------------------------------------------------------


def _check_jax(ctx: _FileContext) -> Iterator[Finding]:
    if not _in_core(ctx.path) or ctx.base in JAX_ALLOWLIST:
        return
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "jax" or alias.name.startswith("jax."):
                    yield Finding(
                        ctx.path, node.lineno, "jax-containment",
                        f"direct 'import {alias.name}': bind jax through "
                        f"repro.compat (e.g. compat.jax_modules()) so "
                        f"new jax surface keeps one choke point")
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if node.level == 0 and (mod == "jax"
                                    or mod.startswith("jax.")):
                yield Finding(
                    ctx.path, node.lineno, "jax-containment",
                    f"direct 'from {mod} import ...': route jax surface "
                    f"through repro.compat")
        elif (isinstance(node, ast.Name) and node.id == "jax"
                and isinstance(node.ctx, ast.Load)
                and "jax" not in ctx.bound_names):
            # `jax` used without any binding in this file: an injected /
            # star-imported module dodging the import rule.
            yield Finding(
                ctx.path, node.lineno, "jax-containment",
                "use of unbound name 'jax': bind it via repro.compat")


# ---------------------------------------------------------------------------
# Rule: typed-http-boundary
# ---------------------------------------------------------------------------


def _is_service_raise(stmt: ast.Raise, ctx: _FileContext) -> bool:
    exc = stmt.exc
    if exc is None:
        return False                 # bare re-raise: the raw error escapes
    if isinstance(exc, ast.Call):
        exc = exc.func
    canonical = ctx.resolve(exc)
    if canonical:
        # Only the ServiceError family counts — `faults.FaultError` and
        # other faults-module exceptions are not typed boundary raises.
        return canonical.rsplit(".", 1)[-1] in SERVICE_ERROR_NAMES
    # Locally-defined name (e.g. a subclass in the same file).
    if isinstance(exc, ast.Name):
        return exc.id in SERVICE_ERROR_NAMES
    if isinstance(exc, ast.Attribute):
        return exc.attr in SERVICE_ERROR_NAMES
    return False


def _always_raises_service(stmts: List[ast.stmt], ctx: _FileContext) -> bool:
    """Conservatively: does every path through `stmts` raise Service*?

    Statements are scanned in order; the first definitely-raising
    construct decides. ``if``/``else`` counts only when both arms raise;
    ``with`` recurses into its body; anything else falls through, and a
    body that can run off the end (or ``return``) fails the check.
    """
    for stmt in stmts:
        if isinstance(stmt, ast.Raise):
            return _is_service_raise(stmt, ctx)
        if isinstance(stmt, ast.Return):
            return False
        if isinstance(stmt, ast.If) and stmt.orelse:
            if (_always_raises_service(stmt.body, ctx)
                    and _always_raises_service(stmt.orelse, ctx)):
                return True
        if isinstance(stmt, ast.With) and stmt is stmts[-1]:
            return _always_raises_service(stmt.body, ctx)
    return False


def _check_http(ctx: _FileContext) -> Iterator[Finding]:
    blessed = any(ctx.norm.endswith(suffix) for suffix in HTTP_TRANSPORTS)
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) and not blessed:
            if ctx.resolve(node.func) == "urllib.request.urlopen":
                yield Finding(
                    ctx.path, node.lineno, "typed-http-boundary",
                    "raw urllib.request.urlopen: use the typed transport "
                    "(work_queue._http_json / a SweepClient) so failures "
                    "surface as faults.ServiceError")
        elif isinstance(node, ast.ExceptHandler) and node.type is not None:
            types = (node.type.elts if isinstance(node.type, ast.Tuple)
                     else [node.type])
            caught = [ctx.resolve(t) or "" for t in types]
            if not any(c.startswith("urllib.error") for c in caught):
                continue
            if not _always_raises_service(node.body, ctx):
                yield Finding(
                    ctx.path, node.lineno, "typed-http-boundary",
                    "except urllib.error.* handler has a path that does "
                    "not raise a faults.ServiceError subtype — raw "
                    "urllib failures must not escape typed boundaries")


# ---------------------------------------------------------------------------
# Rule: lock-discipline
# ---------------------------------------------------------------------------


def _container_value(ctx: _FileContext, value: ast.AST) -> bool:
    if isinstance(value, (ast.Dict, ast.List, ast.Set, ast.DictComp,
                          ast.ListComp, ast.SetComp)):
        return True
    if isinstance(value, ast.Call):
        canonical = ctx.resolve(value.func)
        if canonical in CONTAINER_CONSTRUCTORS:
            return True
        if (isinstance(value.func, ast.Name)
                and value.func.id in ("dict", "list", "set")):
            return True
    return False


def _is_mutation(node: ast.AST, name: str) -> bool:
    if isinstance(node, ast.Call):
        func = node.func
        return (isinstance(func, ast.Attribute)
                and func.attr in MUTATOR_METHODS
                and isinstance(func.value, ast.Name)
                and func.value.id == name)
    if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target])
        for target in targets:
            if (isinstance(target, ast.Subscript)
                    and isinstance(target.value, ast.Name)
                    and target.value.id == name):
                return True
    if isinstance(node, ast.Delete):
        for target in node.targets:
            if (isinstance(target, ast.Subscript)
                    and isinstance(target.value, ast.Name)
                    and target.value.id == name):
                return True
    return False


def _holds_lock(ancestors: List[ast.AST], lock: str) -> bool:
    for node in ancestors:
        if isinstance(node, ast.With):
            for item in node.items:
                try:
                    if ast.unparse(item.context_expr).strip() == lock:
                        return True
                except Exception:       # pragma: no cover - unparse quirk
                    continue
    return False


def _check_locks(ctx: _FileContext) -> Iterator[Finding]:
    if not _in_warpsim(ctx.path):
        return
    guarded: Dict[str, str] = {}
    for stmt in ctx.tree.body:
        if isinstance(stmt, ast.Assign):
            targets, value = stmt.targets, stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets, value = [stmt.target], stmt.value
        else:
            continue
        if not _container_value(ctx, value):
            continue
        for target in targets:
            if not isinstance(target, ast.Name):
                continue
            if target.id.startswith("__") and target.id.endswith("__"):
                continue    # __all__ and friends: interpreter conventions
            lock = ctx.guarded_by(stmt.lineno)
            if lock is None:
                yield Finding(
                    ctx.path, stmt.lineno, "lock-discipline",
                    f"module-level mutable container {target.id!r} needs "
                    f"a '# guarded-by: <lock>' annotation ('frozen' for "
                    f"populate-once constants)")
            else:
                guarded[target.id] = lock
    if not guarded:
        return
    for node, ancestors in _walk_with_ancestors(ctx.tree):
        for name, lock in guarded.items():
            if not _is_mutation(node, name):
                continue
            line = getattr(node, "lineno", 1)
            if lock == "frozen":
                yield Finding(
                    ctx.path, line, "lock-discipline",
                    f"{name!r} is annotated frozen but mutated here — "
                    f"register a real lock or stop mutating it")
            elif not _holds_lock(ancestors, lock):
                yield Finding(
                    ctx.path, line, "lock-discipline",
                    f"mutation of {name!r} outside 'with {lock}:' — "
                    f"unguarded interleavings corrupt shared state "
                    f"(PR 4's bug class)")


# ---------------------------------------------------------------------------
# Rule: determinism
# ---------------------------------------------------------------------------


def _check_determinism(ctx: _FileContext) -> Iterator[Finding]:
    if not _in_warpsim(ctx.path) or ctx.base not in DETERMINISM_MODULES:
        return
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            canonical = ctx.resolve(node.func) or ""
            if canonical in CLOCK_CALLS:
                yield Finding(
                    ctx.path, node.lineno, "determinism",
                    f"{canonical}() in a bit-identity module: cached "
                    f"records must be pure functions of their inputs")
            elif canonical in SEEDED_RNG_CONSTRUCTORS:
                if not node.args:
                    yield Finding(
                        ctx.path, node.lineno, "determinism",
                        f"unseeded {canonical}(): pass an explicit seed")
            elif (canonical.startswith("random.")
                    or canonical.startswith("numpy.random.")):
                yield Finding(
                    ctx.path, node.lineno, "determinism",
                    f"{canonical}() uses the global RNG: thread a seeded "
                    f"generator instead")
        iters: List[ast.AST] = []
        if isinstance(node, ast.For):
            iters.append(node.iter)
        elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                               ast.GeneratorExp)):
            iters.extend(gen.iter for gen in node.generators)
        for it in iters:
            if isinstance(it, (ast.Set, ast.SetComp)) or (
                    isinstance(it, ast.Call)
                    and isinstance(it.func, ast.Name)
                    and it.func.id in ("set", "frozenset")):
                yield Finding(
                    ctx.path, it.lineno, "determinism",
                    "iteration over a set: order depends on hash "
                    "randomization — sort it or use a tuple/dict")


# ---------------------------------------------------------------------------
# Rule: fault-registry
# ---------------------------------------------------------------------------


def _check_fault_registry(ctx: _FileContext) -> Iterator[Finding]:
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        name = (func.id if isinstance(func, ast.Name)
                else func.attr if isinstance(func, ast.Attribute) else None)
        if name != "fault_point" or not node.args:
            continue
        arg = node.args[0]
        if not (isinstance(arg, ast.Constant) and isinstance(arg.value, str)):
            continue                    # dynamic point: validated at runtime
        point = arg.value
        if not any(point == pat or fnmatch.fnmatchcase(point, pat)
                   for pat in KNOWN_POINTS):
            yield Finding(
                ctx.path, node.lineno, "fault-registry",
                f"fault point {point!r} is not registered in "
                f"faults.KNOWN_POINTS — chaos plans would never match it")


# ---------------------------------------------------------------------------
# Rule: env-registry
# ---------------------------------------------------------------------------


def _env_read_key(ctx: _FileContext, node: ast.AST) -> Optional[ast.AST]:
    """The key expression of an environment *read*, or None."""
    if isinstance(node, ast.Call):
        canonical = ctx.resolve(node.func)
        if canonical == "os.getenv" and node.args:
            return node.args[0]
        func = node.func
        if (isinstance(func, ast.Attribute) and func.attr == "get"
                and ctx.resolve(func.value) == "os.environ" and node.args):
            return node.args[0]
    if (isinstance(node, ast.Subscript)
            and isinstance(node.ctx, ast.Load)
            and ctx.resolve(node.value) == "os.environ"):
        return node.slice
    return None


def _check_env(ctx: _FileContext) -> Iterator[Finding]:
    if ctx.base == "envcfg.py" and _in_warpsim(ctx.path):
        return
    for node in ast.walk(ctx.tree):
        key = _env_read_key(ctx, node)
        if key is None:
            continue
        named: Optional[str] = None
        if isinstance(key, ast.Constant) and isinstance(key.value, str):
            if key.value.startswith("WARPSIM_"):
                named = key.value
        elif isinstance(key, ast.Name) and key.id in ctx.env_constants:
            named = key.id
        if named is not None:
            yield Finding(
                ctx.path, node.lineno, "env-registry",
                f"raw environment read of {named}: go through "
                f"repro.core.warpsim.envcfg (registered name + default "
                f"+ doc)")
        elif _in_warpsim(ctx.path):
            # Inside warpsim even dynamic keys must route through envcfg
            # — that is what keeps the registry exhaustive.
            yield Finding(
                ctx.path, node.lineno, "env-registry",
                "environment read in a warpsim module bypasses envcfg: "
                "use envcfg.get()/enabled()/get_int()")


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

_CHECKS = (
    _check_jax, _check_http, _check_locks, _check_determinism,
    _check_fault_registry, _check_env,
)


def _spread_suppressions(tree: ast.Module,
                         suppressed: Dict[int, Set[str]]) -> None:
    """Spread suppressions across multi-line *simple* statements.

    Findings anchor on a construct's first line, but a trailing
    ``# warpsim-lint: disable=`` comment naturally lands on whatever
    line the statement ends on. A simple (non-compound) statement is
    one construct, so a suppression on any of its lines applies to all
    of them. Compound statements (anything with a ``body``) are
    excluded: a comment inside a function must not silence a finding
    anchored on the enclosing header.
    """
    if not suppressed:
        return
    for node in ast.walk(tree):
        if not isinstance(node, ast.stmt) or hasattr(node, "body"):
            continue
        end = getattr(node, "end_lineno", None) or node.lineno
        if end == node.lineno:
            continue
        span = range(node.lineno, end + 1)
        rules: Set[str] = set()
        for line in span:
            rules |= suppressed.get(line, set())
        if rules:
            for line in span:
                suppressed.setdefault(line, set()).update(rules)


def lint_source(source: str, path: str) -> List[Finding]:
    """All findings for one file's source, suppressions applied.

    `path` scopes the rules (warpsim-only rules key off it), so fixture
    tests can lint a snippet *as if* it lived anywhere in the tree.
    """
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 1, "parse-error", e.msg or "")]
    ctx = _FileContext(path, source, tree)
    suppressed, findings = ctx.suppressions()
    _spread_suppressions(tree, suppressed)
    for check in _CHECKS:
        findings.extend(check(ctx))
    return sorted(
        f for f in findings
        if not (f.rule in suppressed.get(f.line, ()) and
                f.rule != "bad-suppression"))


def lint_file(path: str) -> List[Finding]:
    with open(path, encoding="utf-8") as fh:
        return lint_source(fh.read(), path)


def iter_python_files(paths: Iterable[str]) -> Iterator[str]:
    """Every .py file under `paths` (files taken as-is), sorted, no dupes."""
    seen: Set[str] = set()
    for path in paths:
        if os.path.isfile(path):
            if path not in seen:
                seen.add(path)
                yield path
            continue
        for root, dirs, files in os.walk(path):
            dirs[:] = sorted(d for d in dirs if not d.startswith("."))
            for name in sorted(files):
                if name.endswith(".py"):
                    full = os.path.join(root, name)
                    if full not in seen:
                        seen.add(full)
                        yield full


def lint_paths(paths: Iterable[str]) -> List[Finding]:
    findings: List[Finding] = []
    for path in iter_python_files(paths):
        findings.extend(lint_file(path))
    return sorted(findings)


DEFAULT_PATHS = ("src", "tests", "benchmarks")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.core.warpsim.lint",
        description="AST-based invariant checker for the warpsim stack")
    ap.add_argument("paths", nargs="*", default=None,
                    help=f"files/directories to lint (default: "
                         f"{' '.join(DEFAULT_PATHS)})")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit findings as a JSON array")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule table and exit")
    args = ap.parse_args(argv)
    if args.list_rules:
        for rule in sorted(RULES):
            print(f"{rule:22s} {RULES[rule]}")
        return 0
    paths = args.paths or [p for p in DEFAULT_PATHS if os.path.exists(p)]
    if not paths:
        ap.error("no paths given and none of the defaults exist")
    findings = lint_paths(paths)
    if args.as_json:
        print(json.dumps([f.to_dict() for f in findings], indent=2))
    else:
        for finding in findings:
            print(finding.render())
        if findings:
            print(f"warpsim-lint: {len(findings)} finding(s)",
                  file=sys.stderr)
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
