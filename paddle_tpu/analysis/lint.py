"""Tier-2 static analysis: AST linter for TPU anti-patterns.

Where ``program_audit`` inspects one traced program, this pass sweeps
the whole ``paddle_tpu/`` source tree for the patterns that *produce*
bad programs or wedge the serving hot path:

  TPL001  host concretization inside jit-traced code — ``float()`` /
          ``int()`` / ``bool()`` / ``np.asarray()`` / ``.item()`` /
          ``.numpy()`` / ``.tolist()`` on traced values forces a device
          sync (or a ConcretizationTypeError) per call.
  TPL002  Python-side RNG or wall-clock under jit — ``random.*``,
          ``np.random.*``, ``time.time()`` are evaluated ONCE at trace
          time and baked in as constants: every subsequent call replays
          the first call's "random" draw.
  TPL003  ``list.pop(0)`` — O(n) per call; in a scheduler or history
          loop this is quadratic.  ``collections.deque.popleft()``.
  TPL004  lock discipline — engine state shared with the scheduler
          thread mutated outside ``with self._cond`` (configured per
          class; helpers named ``*_locked`` assert they are called
          under the lock and are exempt, as is ``__init__`` which runs
          before the thread starts).
  TPL005  per-step host sync inside a training loop — ``float()`` /
          ``.item()`` / ``np.asarray()`` on step results executed
          unconditionally in a loop over a loader/batch source (or in
          a function such a loop body calls, one level deep)
          serializes every step on a device round-trip.  Reads gated
          behind an ``if`` (log/epoch boundaries) are the sanctioned
          pattern and exempt.
  TPL006  eager collective wrapper inside a compiled/scanned region —
          the ``distributed/collective.py`` APIs (``dist.all_reduce``
          and friends) dispatch their own shard_map program per call
          and sync host-side state (groups, monitor counters); traced
          under ``jit``/``to_static`` or inside a ``lax.scan`` body
          they either fail to trace or smuggle a host round-trip into
          the compiled program.  Compiled regions must use the traced
          psum-family primitives (``jax.lax.psum`` / ``all_gather`` /
          ... under ``shard_map``) — which are exempt.

Scope detection is LEXICAL and per-file: a function counts as jitted
when it is decorated with ``jax.jit``/``functools.partial(jax.jit,
...)``/``to_static``, or when the same file passes its name to a
``*.jit(...)`` call (the ``prog = jax.jit(fn, donate_argnums=...)``
idiom).  Cross-file tracing is the jaxpr auditor's job; anything this
cheap pass gets wrong is ratcheted through the checked-in baseline
file with a one-line justification, never silently.

This module is deliberately stdlib-only (``ast``/``json``) so the CI
gate (tools/tpu_lint.py) can load it standalone without importing jax
— the tier-1 lane budget is < 10 s.
"""
from __future__ import annotations

import ast
import dataclasses
import json
import os
from collections import Counter, deque
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = [
    "LintFinding", "RULES", "lint_source", "lint_file", "lint_paths",
    "load_baseline", "save_baseline", "diff_against_baseline",
    "unjustified_entries", "PLACEHOLDER_JUSTIFICATION", "publish",
]

SEVERITY_ERROR = "error"
SEVERITY_WARNING = "warning"

#: rule_id -> (severity, summary, fix hint)
RULES: Dict[str, Tuple[str, str, str]] = {
    "TPL001": (SEVERITY_ERROR,
               "host concretization inside jit-traced code",
               "keep the value on device (jnp) or hoist the read out of "
               "the compiled region"),
    "TPL002": (SEVERITY_ERROR,
               "Python RNG / wall-clock under jit is baked in at trace "
               "time",
               "thread a jax PRNG key through the program; time on the "
               "host around the call"),
    "TPL003": (SEVERITY_ERROR,
               "list.pop(0) is O(n) per call",
               "use collections.deque and popleft()"),
    "TPL004": (SEVERITY_ERROR,
               "engine state mutated outside the scheduler lock",
               "mutate under `with self._cond:` or move the mutation "
               "into a *_locked helper only called under the lock"),
    "TPL005": (SEVERITY_ERROR,
               "per-step host sync inside a training loop",
               "keep step results device-resident (async dispatch) and "
               "force them only at log/epoch boundaries — gate the read "
               "behind a boundary condition"),
    "TPL006": (SEVERITY_ERROR,
               "eager collective wrapper inside a compiled/scanned "
               "region",
               "use the traced primitive (jax.lax.psum / all_gather / "
               "psum_scatter under shard_map) inside compiled code, or "
               "hoist the eager collective out of the jit/scan region"),
}

_CONCRETIZE_BUILTINS = {"float", "int", "bool"}
_CONCRETIZE_METHODS = {"item", "numpy", "tolist"}
_CONCRETIZE_CALLS = {"np.asarray", "np.array", "numpy.asarray",
                     "numpy.array"}
_RNG_PREFIXES = ("random.", "np.random.", "numpy.random.")
_TIME_CALLS = {"time.time", "time.perf_counter", "time.monotonic"}
_MUTATOR_METHODS = {"append", "appendleft", "extend", "extendleft",
                    "pop", "popleft", "remove", "clear", "insert", "add",
                    "discard", "update", "setdefault"}

#: the eager collective API surface (distributed/collective.py): each
#: wrapper dispatches its own shard_map program and touches host-side
#: group/monitor state per call — never traceable (TPL006)
_EAGER_COLLECTIVES = {
    "all_reduce", "all_gather", "all_gather_object", "reduce_scatter",
    "broadcast", "reduce", "scatter", "all_to_all", "alltoall",
    "send", "recv", "isend", "irecv", "barrier",
}
#: dotted-call bases that unambiguously name the eager API (a bare
#: `reduce(...)` only counts when the file imports it from the
#: distributed package — see _eager_collective_imports)
_EAGER_COLLECTIVE_BASES = ("dist", "collective", "distributed")

#: lock-discipline configuration: class name -> (lock attr, guarded attrs).
#: Today this covers the continuous-batching engine (ISSUE 3); add
#: entries as new scheduler-shaped classes land.
LOCK_CLASSES: Dict[str, Tuple[str, frozenset]] = {
    "ContinuousBatchingEngine": ("_cond", frozenset({
        "_active", "_reserved_pages", "_reserved_draft_pages",
        "_next_seq", "_stop", "_draining", "steps",
        # heterogeneous-workload scheduler state (ISSUE 7): the
        # admission queues (WorkloadScheduler has no lock of its own —
        # every mutation must happen under the engine's _cond) and the
        # mid-prefill lists the drain/reap/preemption paths walk
        # (these replaced the pre-PR-7 _queue/_admitting attributes)
        "_sched", "_prefilling", "_preempted",
        # crash consistency (ISSUE 8): the snapshot() quiesce barrier —
        # the loop thread and snapshotting threads hand off through
        # these under _cond
        "_stepping", "_snap_waiters",
        # overload protection (ISSUE 19): the brownout ladder rung —
        # written by _set_brownout_locked on the scheduler thread,
        # read by submit()'s shed decision and retry_after_hint under
        # _cond
        "_brownout"})),
}


@dataclasses.dataclass(frozen=True)
class LintFinding:
    rule_id: str
    severity: str
    path: str
    line: int
    scope: str
    code: str
    message: str
    hint: str

    def key(self) -> Tuple[str, str, str, str]:
        """Baseline identity: line-number-insensitive so pure code
        motion never churns the baseline file."""
        return (self.rule_id, self.path, self.scope, self.code)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        return (f"{self.rule_id} {self.severity} {self.path}:{self.line} "
                f"[{self.scope}] {self.message} — {self.code}")


def _dotted(node) -> str:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else node.attr
    return ""


def _is_jit_name(dotted: str) -> bool:
    return dotted in {"jit", "pjit"} or dotted.endswith(".jit") \
        or dotted.endswith(".pjit")


def _decorator_marks_jit(dec) -> bool:
    """True when any node inside the decorator expression names jit or
    to_static (covers ``@jax.jit``, ``@functools.partial(jax.jit, ...)``,
    ``@to_static`` / ``@paddle.jit.to_static``)."""
    for node in ast.walk(dec):
        if isinstance(node, (ast.Name, ast.Attribute)):
            d = _dotted(node)
            if _is_jit_name(d) or d == "to_static" \
                    or d.endswith(".to_static"):
                return True
    return False


def _jitted_local_names(tree) -> Set[str]:
    """Function names the file passes to a ``*.jit(...)`` call — the
    ``prog = jax.jit(fn, donate_argnums=...)`` idiom."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _is_jit_name(_dotted(node.func)):
            if node.args and isinstance(node.args[0], ast.Name):
                names.add(node.args[0].id)
    return names


_LAX_LOOPS = ("scan", "while_loop", "fori_loop")


def _lax_loop_imports(tree) -> Dict[str, str]:
    """alias -> canonical lax-loop name for ``from jax.lax import
    scan``-style bindings — the only case a BARE loop call counts
    (mirrors _eager_collective_imports: a local ``table.scan`` or a
    user-defined ``scan`` helper must not mark its callback as traced
    code)."""
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.ImportFrom):
            continue
        module = node.module or ""
        if module != "jax.lax" and not module.endswith(".lax"):
            continue
        for alias in node.names:
            if alias.name in _LAX_LOOPS:
                out[alias.asname or alias.name] = alias.name
    return out


def _scanned_local_names(tree) -> Set[str]:
    """Function names the file passes as a ``jax.lax`` loop body — the
    ``lax.scan(body, ...)`` / ``lax.while_loop(cond, body, ...)`` /
    ``lax.fori_loop(lo, hi, body, ...)`` idiom.  Their bodies trace
    exactly like jitted code (TPL006)."""
    lax_imports = _lax_loop_imports(tree)
    names: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        canonical = lax_imports.get(dotted)
        if canonical is None:
            canonical = next(
                (nm for nm in _LAX_LOOPS
                 if dotted == f"lax.{nm}"
                 or dotted.endswith(f".lax.{nm}")), None)
        if canonical == "scan":
            args = node.args[:1]
        elif canonical == "while_loop":
            args = node.args[:2]
        elif canonical == "fori_loop":
            args = node.args[2:3]
        else:
            continue
        for a in args:
            if isinstance(a, ast.Name):
                names.add(a.id)
    return names


def _eager_collective_imports(tree) -> Set[str]:
    """Bare names this file imports FROM the distributed package that
    shadow an eager collective (``from paddle_tpu.distributed import
    all_reduce``) — the only case a bare call counts for TPL006."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.ImportFrom):
            continue
        module = node.module or ""
        if "distributed" not in module and \
                not module.endswith("collective"):
            continue
        for alias in node.names:
            if alias.name in _EAGER_COLLECTIVES:
                names.add(alias.asname or alias.name)
    return names


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, source_lines: Sequence[str],
                 jitted_names: Set[str],
                 scanned_names: Set[str] = frozenset(),
                 collective_imports: Set[str] = frozenset()):
        self.path = path
        self.lines = source_lines
        self.jitted_names = jitted_names
        self.scanned_names = scanned_names
        self.collective_imports = collective_imports
        self.findings: List[LintFinding] = []
        self.scope: List[str] = []
        self.jit_depth = 0
        self.scan_depth = 0
        self.class_stack: List[str] = []
        self.lock_depth = 0

    # ---------------------------------------------------------- plumbing
    def _code(self, node) -> str:
        try:
            return self.lines[node.lineno - 1].strip()
        except Exception:
            return ""

    def _emit(self, rule_id: str, node, detail: str = "") -> None:
        severity, summary, hint = RULES[rule_id]
        msg = f"{summary}: {detail}" if detail else summary
        self.findings.append(LintFinding(
            rule_id=rule_id, severity=severity, path=self.path,
            line=getattr(node, "lineno", 0),
            scope=".".join(self.scope) or "<module>",
            code=self._code(node), message=msg, hint=hint))

    # ------------------------------------------------------------ scopes
    def visit_ClassDef(self, node):
        self.class_stack.append(node.name)
        self.scope.append(node.name)
        self.generic_visit(node)
        self.scope.pop()
        self.class_stack.pop()

    def _visit_function(self, node):
        jitted = (any(_decorator_marks_jit(d) for d in node.decorator_list)
                  or node.name in self.jitted_names)
        scanned = node.name in self.scanned_names
        self.scope.append(node.name)
        self.jit_depth += 1 if jitted else 0
        self.scan_depth += 1 if scanned else 0
        saved_lock = self.lock_depth
        self.lock_depth = 0           # lock scopes never span functions
        self.generic_visit(node)
        self.lock_depth = saved_lock
        self.scan_depth -= 1 if scanned else 0
        self.jit_depth -= 1 if jitted else 0
        self.scope.pop()

    visit_FunctionDef = _visit_function
    visit_AsyncFunctionDef = _visit_function

    # -------------------------------------------------------------- lock
    def _lock_config(self):
        for cls in reversed(self.class_stack):
            cfg = LOCK_CLASSES.get(cls)
            if cfg is not None:
                return cfg
        return None

    def _in_exempt_method(self) -> bool:
        fn = self.scope[-1] if self.scope else ""
        return fn == "__init__" or fn.endswith("_locked")

    def visit_With(self, node):
        cfg = self._lock_config()
        holds = False
        if cfg is not None:
            lock_attr = cfg[0]
            for item in node.items:
                ctx = item.context_expr
                if isinstance(ctx, ast.Attribute) \
                        and isinstance(ctx.value, ast.Name) \
                        and ctx.value.id == "self" \
                        and ctx.attr == lock_attr:
                    holds = True
        self.lock_depth += 1 if holds else 0
        self.generic_visit(node)
        self.lock_depth -= 1 if holds else 0

    def _check_state_mutation(self, target_attr, node):
        cfg = self._lock_config()
        if cfg is None or self.lock_depth > 0 or self._in_exempt_method():
            return
        _, guarded = cfg
        if isinstance(target_attr, ast.Attribute) \
                and isinstance(target_attr.value, ast.Name) \
                and target_attr.value.id == "self" \
                and target_attr.attr in guarded:
            self._emit("TPL004", node, f"self.{target_attr.attr}")

    def visit_Assign(self, node):
        for tgt in node.targets:
            for el in (tgt.elts if isinstance(tgt, ast.Tuple) else [tgt]):
                self._check_state_mutation(el, node)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        self._check_state_mutation(node.target, node)
        self.generic_visit(node)

    # ------------------------------------------------------------- calls
    def visit_Call(self, node):
        func = node.func
        dotted = _dotted(func)

        # TPL003: anywhere, any receiver
        if isinstance(func, ast.Attribute) and func.attr == "pop" \
                and len(node.args) == 1 \
                and isinstance(node.args[0], ast.Constant) \
                and node.args[0].value == 0:
            self._emit("TPL003", node, _dotted(func.value) or "<expr>")

        # TPL004: mutating method calls on guarded engine state
        if isinstance(func, ast.Attribute) \
                and func.attr in _MUTATOR_METHODS:
            self._check_state_mutation(func.value, node)

        if self.jit_depth > 0:
            self._check_jit_scope_call(node, func, dotted)
        if self.jit_depth > 0 or self.scan_depth > 0:
            self._check_eager_collective(node, func, dotted)
        self.generic_visit(node)

    def _check_eager_collective(self, node, func, dotted):
        """TPL006: an eager distributed/collective.py wrapper in traced
        code.  jax.lax primitives (the sanctioned in-program form) are
        exempt; bare names only count when the file imported them from
        the distributed package."""
        if dotted.startswith("jax.") or ".lax." in dotted \
                or dotted.startswith("lax."):
            return
        if isinstance(func, ast.Attribute):
            if func.attr not in _EAGER_COLLECTIVES:
                return
            base = _dotted(func.value)
            base_tail = base.rsplit(".", 1)[-1]
            if base_tail not in _EAGER_COLLECTIVE_BASES:
                return
            self._emit("TPL006", node, f"{dotted}()")
        elif isinstance(func, ast.Name) \
                and func.id in self.collective_imports:
            self._emit("TPL006", node, f"{func.id}()")

    def _check_jit_scope_call(self, node, func, dotted):
        # TPL001: builtins that force concretization (constant / len()
        # arguments are static python values, not traced)
        if isinstance(func, ast.Name) \
                and func.id in _CONCRETIZE_BUILTINS and node.args:
            arg = node.args[0]
            static = isinstance(arg, ast.Constant) or (
                isinstance(arg, ast.Call)
                and isinstance(arg.func, ast.Name)
                and arg.func.id == "len")
            if not static:
                self._emit("TPL001", node, f"{func.id}()")
        elif isinstance(func, ast.Attribute) \
                and func.attr in _CONCRETIZE_METHODS and not node.args:
            self._emit("TPL001", node, f".{func.attr}()")
        elif dotted in _CONCRETIZE_CALLS:
            self._emit("TPL001", node, f"{dotted}()")
        # TPL002: host RNG / clock under trace
        elif dotted.startswith(_RNG_PREFIXES) or dotted in _TIME_CALLS:
            self._emit("TPL002", node, f"{dotted}()")


# -------------------------------------------- TPL005: training-loop sync
#: substrings a ``for`` loop's iterable source must mention to count as
#: a training loop (``for step, batch in enumerate(loader)`` and its
#: sampler/dataset variants)
_LOOP_SOURCES = ("loader", "batch", "dataset", "train_data", "eval_data")
_SYNC_BUILTINS = {"float"}
_SYNC_METHODS = {"item", "numpy", "tolist"}


def _scope_walk(node, scope, on_loop):
    """Recursive walk tracking the qualified scope; calls ``on_loop``
    for every For/While statement with its enclosing scope."""
    for child in ast.iter_child_nodes(node):
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
            _scope_walk(child, scope + [child.name], on_loop)
        else:
            if isinstance(child, (ast.For, ast.While)):
                on_loop(child, scope)
            _scope_walk(child, scope, on_loop)


def _function_index(tree):
    """bare name -> [(qualname, FunctionDef)] for the one-level
    loop-callee expansion."""
    by_bare: Dict[str, List] = {}

    def visit(node, scope):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = scope + [child.name]
                by_bare.setdefault(child.name, []).append(
                    (".".join(q), child))
                visit(child, q)
            elif isinstance(child, ast.ClassDef):
                visit(child, scope + [child.name])
            else:
                visit(child, scope)
    visit(tree, [])
    return by_bare


def _unconditional_syncs(body_nodes):
    """(sync_calls, all_calls) executed on EVERY pass through
    ``body_nodes``: the scan stops at ``If`` statements (boundary-gated
    reads — the sanctioned log/epoch pattern) and at nested function
    definitions (their call time is unknown)."""
    syncs: List[Tuple[ast.Call, str]] = []
    calls: List[ast.Call] = []

    def scan(node):
        if isinstance(node, ast.If):
            # the TEST runs on every iteration (`if float(loss) > t:`
            # is a per-step sync); only the gated body/orelse is the
            # sanctioned boundary-read pattern
            scan(node.test)
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return
        if isinstance(node, ast.Call):
            func = node.func
            dotted = _dotted(func)
            if isinstance(func, ast.Name) \
                    and func.id in _SYNC_BUILTINS and node.args:
                arg = node.args[0]
                static = isinstance(arg, ast.Constant) or (
                    isinstance(arg, ast.Call)
                    and isinstance(arg.func, ast.Name)
                    and arg.func.id == "len")
                if not static:
                    syncs.append((node, f"{func.id}()"))
            elif isinstance(func, ast.Attribute) \
                    and func.attr in _SYNC_METHODS and not node.args:
                syncs.append((node, f".{func.attr}()"))
            elif dotted in _CONCRETIZE_CALLS:
                syncs.append((node, f"{dotted}()"))
            calls.append(node)
        for child in ast.iter_child_nodes(node):
            scan(child)

    for n in body_nodes:
        scan(n)
    return syncs, calls


def _lint_training_loops(tree, path: str,
                         lines: Sequence[str]) -> List[LintFinding]:
    """TPL005: host-sync idioms executed once per training-loop step —
    lexically in the loop body, or in a locally-defined function the
    body calls (``self.train_batch(x, y)`` one level deep)."""
    findings: List[LintFinding] = []
    by_bare = _function_index(tree)
    visited = set()

    def emit(node, scope, detail, loop_line):
        severity, summary, hint = RULES["TPL005"]
        try:
            code = lines[node.lineno - 1].strip()
        except Exception:
            code = ""
        findings.append(LintFinding(
            rule_id="TPL005", severity=severity, path=path,
            line=getattr(node, "lineno", 0), scope=scope, code=code,
            message=f"{summary}: {detail} (loop at line {loop_line})",
            hint=hint))

    def callee_defs(call):
        func = call.func
        if isinstance(func, ast.Name):
            name = func.id
        elif isinstance(func, ast.Attribute) \
                and isinstance(func.value, ast.Name) \
                and func.value.id == "self":
            name = func.attr
        else:
            return []
        return by_bare.get(name, [])

    def _loop_source_names(loop):
        """Dotted names that tie the loop to a data source: the For's
        iterable expression, or — for the ``while True: batch =
        next(loader_it)`` form — the arguments of ``next()`` calls in
        a While's body."""
        if isinstance(loop, ast.For):
            exprs = [loop.iter]
        else:
            exprs = [a for n in ast.walk(loop)
                     if isinstance(n, ast.Call)
                     and isinstance(n.func, ast.Name)
                     and n.func.id == "next"
                     for a in n.args]
        return [_dotted(n).lower() for e in exprs for n in ast.walk(e)
                if isinstance(n, (ast.Name, ast.Attribute))]

    def on_loop(loop, scope):
        names = _loop_source_names(loop)
        if not any(src in d for d in names for src in _LOOP_SOURCES):
            return
        body = list(loop.body) + list(loop.orelse)
        syncs, calls = _unconditional_syncs(body)
        for node, detail in syncs:
            emit(node, ".".join(scope) or "<module>", detail, loop.lineno)
        for call in calls:
            for qual, fn_node in callee_defs(call):
                if id(fn_node) in visited:
                    continue
                visited.add(id(fn_node))
                inner_syncs, _ = _unconditional_syncs(fn_node.body)
                for node, detail in inner_syncs:
                    emit(node, qual, detail, loop.lineno)

    _scope_walk(tree, [], on_loop)
    return findings


# ------------------------------------------------------------ tree sweep
def lint_source(source: str, path: str = "<string>") -> List[LintFinding]:
    tree = ast.parse(source)
    linter = _Linter(path, source.splitlines(), _jitted_local_names(tree),
                     _scanned_local_names(tree),
                     _eager_collective_imports(tree))
    linter.visit(tree)
    linter.findings.extend(
        _lint_training_loops(tree, path, source.splitlines()))
    return linter.findings


def lint_file(file_path: str, rel_path: Optional[str] = None
              ) -> List[LintFinding]:
    with open(file_path, encoding="utf-8") as f:
        source = f.read()
    return lint_source(source, rel_path or file_path)


def lint_paths(root: str, rel_to: Optional[str] = None
               ) -> List[LintFinding]:
    """Lint every ``*.py`` under ``root``; paths in findings are
    relative to ``rel_to`` (default: ``root``'s parent) so the baseline
    file is location-independent."""
    rel_to = rel_to or os.path.dirname(os.path.abspath(root))
    findings: List[LintFinding] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            full = os.path.join(dirpath, fname)
            rel = os.path.relpath(full, rel_to).replace(os.sep, "/")
            findings.extend(lint_file(full, rel))
    findings.sort(key=lambda f: (f.path, f.line, f.rule_id))
    return findings


# -------------------------------------------------------------- baseline
def load_baseline(path: str) -> List[dict]:
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    return list(doc.get("findings", []))


PLACEHOLDER_JUSTIFICATION = "TODO: justify or fix"


def save_baseline(path: str, findings: Sequence[LintFinding]) -> None:
    """Rewrite the ratchet from the current findings.  Justifications
    already filled in for surviving entries are PRESERVED (matched by
    the same line-insensitive key the gate uses); only genuinely new
    entries get the placeholder."""
    prior: Dict[Tuple[str, str, str, str], deque] = {}
    for e in load_baseline(path):
        j = e.get("justification", "")
        if j and j != PLACEHOLDER_JUSTIFICATION:
            prior.setdefault(_baseline_key(e), deque()).append(j)
    doc = {
        "comment": "tpu_lint ratchet: every entry is an ACCEPTED finding "
                   "with a one-line justification; new findings fail CI. "
                   "Amend with tools/tpu_lint.py --update-baseline, then "
                   "fill in each justification (the gate rejects the "
                   "TODO placeholder).",
        "findings": [
            {"rule_id": f.rule_id, "path": f.path, "scope": f.scope,
             "code": f.code,
             "justification": (prior[f.key()].popleft()
                               if prior.get(f.key())
                               else PLACEHOLDER_JUSTIFICATION)}
            for f in findings],
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2, sort_keys=False)
        f.write("\n")


def _baseline_key(entry: dict) -> Tuple[str, str, str, str]:
    return (entry.get("rule_id", ""), entry.get("path", ""),
            entry.get("scope", ""), entry.get("code", ""))


def diff_against_baseline(findings: Sequence[LintFinding],
                          baseline: Sequence[dict]
                          ) -> Tuple[List[LintFinding], List[dict]]:
    """(new_findings, stale_baseline_entries).  Keys are line-number
    insensitive; duplicates are matched as a multiset so adding a second
    instance of a baselined pattern still counts as new."""
    allowance = Counter(_baseline_key(e) for e in baseline)
    new: List[LintFinding] = []
    for f in findings:
        k = f.key()
        if allowance.get(k, 0) > 0:
            allowance[k] -= 1
        else:
            new.append(f)
    stale_keys = {k for k, n in allowance.items() if n > 0}
    stale, seen = [], Counter()
    for e in baseline:
        k = _baseline_key(e)
        if k in stale_keys and seen[k] < allowance[k]:
            seen[k] += 1
            stale.append(e)
    return new, stale


def unjustified_entries(baseline: Sequence[dict]) -> List[dict]:
    """Baseline entries whose justification is missing or still the
    placeholder — the gate rejects these so grandfathering stays
    explicit, never silent."""
    return [e for e in baseline
            if not e.get("justification")
            or e["justification"] == PLACEHOLDER_JUSTIFICATION]


def publish(findings: Sequence[LintFinding]) -> bool:
    """Export finding counts through ``paddle_tpu.monitor`` (no-op when
    the module is loaded standalone, outside the package)."""
    try:
        from ..monitor import counter
    except Exception:
        return False
    c = counter("lint_findings_total",
                "tpu_lint findings observed this process",
                ("rule_id", "severity"))
    for f in findings:
        c.inc(rule_id=f.rule_id, severity=f.severity)
    return True
