"""skelly-lint engine: module parsing, jit-reachability, pragmas, rule driver.

Pure-stdlib AST analysis (no jax import — the linter must run before any
backend exists, e.g. as the first CI gate). The engine is repo-aware in two
ways the generic linters we could not `pip install` are not:

* **import-alias tables** per module, so rules match `jax.numpy` through any
  local alias (`jnp`, `_jnp`, ...) instead of a hardcoded spelling;
* a **jit-reachability call graph**: functions are seeds when decorated with
  (or wrapped by) `jax.jit`, and reachability propagates through calls the
  AST can resolve — bare names (from-imports / same-module defs), module
  aliases (`fc.update_cache`), and `self.` methods. Trace-hygiene findings
  fire only inside reachable functions, so host-side code (trajectory
  writers, the adaptive run loop, Ewald planning) is not flooded with
  false positives for its legitimate `float()` / `np.*` use.

Suppressions are pragmas with a mandatory reason, parsed from real comment
tokens only (pragma examples inside strings/docstrings are inert)::

    x = jnp.zeros(n)  # skelly-lint: ignore[dtype-discipline] -- reason here

A per-line pragma on a comment-only line applies to the next line. The
function-scoped variant ``ignore-function`` sits on (or immediately above) a
``def`` line and suppresses the named rules in that whole function — for
host-precompute helpers whose np-on-static-int work is deliberately frozen
into the trace. Pragmas that suppress nothing are themselves findings
(`lint-pragma`), so every pragma in the tree stays load-bearing: deleting
any one of them re-exposes its finding and the lint gate fails.
"""

from __future__ import annotations

import ast
import io
import os
import re
import tokenize
from dataclasses import dataclass, field

#: directories (relative to the package root) whose code is "hot path" —
#: inside the per-step jit programs or the multi-chip evaluators. Blanket
#: host-sync checks (block_until_ready / device_get) apply to every function
#: here, reachable or not.
HOT_PATH_DIRS = ("ops", "solver", "fibers", "bodies", "periphery", "parallel",
                 "system")

#: declared mixed-precision seams: files whose whole point is explicit
#: hi/lo dtype surgery (double-float kernels). dtype-discipline's
#: hardcoded-dtype check does not apply there.
DTYPE_SEAM_FILES = ("ops/df_kernels.py", "ops/pallas_df.py",
                    "ops/block_df.py")

PRAGMA_RE = re.compile(
    r"#\s*skelly-lint:\s*(ignore|ignore-function)\[([^\]]*)\]"
    r"\s*(?:—|–|--|-)?\s*(.*)")


@dataclass(frozen=True)
class Finding:
    path: str          # path as given on the command line (relative ok)
    line: int
    col: int
    rule: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule}: {self.message}"


@dataclass
class Pragma:
    line: int          # line the pragma comment sits on
    target_line: int   # line (or `def` line for function scope) it covers
    rules: tuple       # rule ids it names
    reason: str
    #: "line" or "function" (`ignore-function` covers the def's whole body)
    scope: str = "line"
    used: bool = False


@dataclass
class FunctionInfo:
    qualname: str      # "fn" or "Class.method"; nested defs fold into parents
    node: ast.AST      # FunctionDef / AsyncFunctionDef
    cls: str | None    # enclosing class name, None at module level
    is_seed: bool = False


@dataclass
class ModuleInfo:
    path: str                      # as passed on the CLI
    relpath: str                   # package-relative, posix ("ops/kernels.py")
    tree: ast.Module = None
    lines: list = field(default_factory=list)
    pragmas: list = field(default_factory=list)       # [Pragma]
    functions: dict = field(default_factory=dict)     # qualname -> FunctionInfo
    #: local alias -> dotted module ("jnp" -> "jax.numpy", "fc" -> "...container")
    import_aliases: dict = field(default_factory=dict)
    #: local name -> (module, attr) for `from m import a [as b]`
    from_imports: dict = field(default_factory=dict)
    syntax_error: str | None = None

    def in_hot_path(self) -> bool:
        top = self.relpath.split("/", 1)[0]
        return top in HOT_PATH_DIRS

    @property
    def np_aliases(self) -> frozenset:
        """Local names bound to numpy (computed once; rules hit this for
        every visited Call node)."""
        if "_np_aliases" not in self.__dict__:
            self.__dict__["_np_aliases"] = frozenset(
                a for a, m in self.import_aliases.items() if m == "numpy")
        return self.__dict__["_np_aliases"]

    @property
    def jnp_aliases(self) -> frozenset:
        if "_jnp_aliases" not in self.__dict__:
            self.__dict__["_jnp_aliases"] = frozenset(
                a for a, m in self.import_aliases.items()
                if m == "jax.numpy")
        return self.__dict__["_jnp_aliases"]


def _parse_pragmas(src: str):
    """Pragmas from COMMENT tokens only — the rendered syntax inside
    docstrings (docs, error messages, this file) must stay inert."""
    pragmas = []
    try:
        tokens = tokenize.generate_tokens(io.StringIO(src).readline)
        comments = [(t.start[0], t.start[1], t.string)
                    for t in tokens if t.type == tokenize.COMMENT]
    except (tokenize.TokenError, IndentationError):
        return pragmas
    lines = src.splitlines()
    for lineno, col, text in comments:
        m = PRAGMA_RE.match(text)
        if m is None:
            continue
        kind = m.group(1)
        rules = tuple(r.strip() for r in m.group(2).split(",") if r.strip())
        reason = m.group(3).strip()
        own_line = col == 0 or lines[lineno - 1][:col].strip() == ""
        pragmas.append(Pragma(
            line=lineno, target_line=lineno + 1 if own_line else lineno,
            rules=rules, reason=reason,
            scope="function" if kind == "ignore-function" else "line"))
    return pragmas


def _module_relpath(path: str) -> str:
    """Path relative to the skellysim_tpu package root, posix separators.
    Files outside the package keep their basename-led path (rules that scope
    by package dir simply will not match them)."""
    norm = path.replace(os.sep, "/")
    marker = "skellysim_tpu/"
    idx = norm.rfind(marker)
    if idx >= 0:
        return norm[idx + len(marker):]
    return norm.lstrip("./")


def parse_module(path: str) -> ModuleInfo:
    mod = ModuleInfo(path=path, relpath=_module_relpath(path))
    with open(path, "r", encoding="utf-8") as fh:
        src = fh.read()
    mod.lines = src.splitlines()
    try:
        mod.tree = ast.parse(src, filename=path)
    except SyntaxError as e:  # compileall gates this first; report anyway
        mod.syntax_error = f"syntax error: {e.msg} (line {e.lineno})"
        return mod
    mod.pragmas = _parse_pragmas(src)

    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                mod.import_aliases[a.asname or a.name.split(".")[0]] = a.name
        elif isinstance(node, ast.ImportFrom):
            if node.module is None:
                # `from . import X [as Y]` binds a module object
                for a in node.names:
                    mod.import_aliases[a.asname or a.name] = a.name
            else:
                for a in node.names:
                    mod.from_imports[a.asname or a.name] = (node.module,
                                                            a.name)

    def collect(body, cls, prefix):
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{node.name}"
                mod.functions[qual] = FunctionInfo(qualname=qual, node=node,
                                                   cls=cls)
            elif isinstance(node, ast.ClassDef):
                collect(node.body, node.name, f"{node.name}.")

    collect(mod.tree.body, None, "")
    return mod


# --------------------------------------------------------------- call graph

def _is_cached_fn(fi: FunctionInfo) -> bool:
    """True for functions decorated with functools.lru_cache/cache.

    These are sound REACHABILITY BARRIERS: a cached function hashes its
    arguments, and JAX tracers are unhashable — so in any working program a
    cached function (and everything below it) only ever sees static host
    values. Its np-heavy body is the repo's deliberate
    build-constants-at-trace-time pattern (FibMats, Vandermonde caches),
    not a trace-hygiene violation.
    """
    for d in fi.node.decorator_list:
        target = d.func if isinstance(d, ast.Call) else d
        name = (target.attr if isinstance(target, ast.Attribute)
                else target.id if isinstance(target, ast.Name) else None)
        if name in ("lru_cache", "cache"):
            return True
    return False


#: callables that compile their first argument like `jax.jit` does — the
#: repo's own jit twins count as reachability seeds too: skelly-scope's
#: compile observer (`obs.compile_log.observed_jit`, what System/ensemble
#: entry points route through since the telemetry PR) and the test/audit
#: trace counter. Missing one of these would silently drop whole call
#: trees out of the dtype/trace/host-sync gates (caught when the
#: observed_jit migration orphaned two pragmas).
_JIT_WRAPPER_NAMES = ("jit", "observed_jit", "trace_counting_jit")


def _is_jit_expr(node, mod: ModuleInfo) -> bool:
    """True for expressions that (possibly via functools.partial) name
    jax.jit or a repo jit twin: `jax.jit`, `jit` (from-imported),
    `observed_jit`, `trace_counting_jit`, `partial(jax.jit, ...)`."""
    if isinstance(node, ast.Attribute) and node.attr in _JIT_WRAPPER_NAMES:
        return True
    if isinstance(node, ast.Name):
        tgt = mod.from_imports.get(node.id)
        if tgt is not None and tgt[1] in _JIT_WRAPPER_NAMES:
            return True
    if isinstance(node, ast.Call) and node.args:
        fn = node.func
        is_partial = ((isinstance(fn, ast.Name) and fn.id == "partial")
                      or (isinstance(fn, ast.Attribute)
                          and fn.attr == "partial"))
        if is_partial:
            return _is_jit_expr(node.args[0], mod)
    return False


def _resolve_call(node, mod: ModuleInfo, enclosing_cls, modules_by_name):
    """Resolve a Name/Attribute callee to (module, qualname) or None.

    modules_by_name: dotted-module-suffix -> ModuleInfo for package modules.
    """
    if isinstance(node, ast.Name):
        tgt = mod.from_imports.get(node.id)
        if tgt is not None:
            other = modules_by_name.get(tgt[0].rsplit(".", 1)[-1])
            if other is not None and tgt[1] in other.functions:
                return other, tgt[1]
            return None
        if node.id in mod.functions:
            return mod, node.id
        return None
    if isinstance(node, ast.Attribute):
        recv = node.value
        if isinstance(recv, ast.Name):
            if recv.id == "self" and enclosing_cls is not None:
                qual = f"{enclosing_cls}.{node.attr}"
                if qual in mod.functions:
                    return mod, qual
                return None
            modname = None
            dotted = mod.import_aliases.get(recv.id)
            if dotted is not None:
                modname = dotted.rsplit(".", 1)[-1]
            elif recv.id in mod.from_imports:
                # `from ..bodies import bodies as bd` binds a module object
                # through a from-import; the imported NAME is the module
                modname = mod.from_imports[recv.id][1]
            if modname is not None:
                other = modules_by_name.get(modname)
                if other is not None and node.attr in other.functions:
                    return other, node.attr
    return None


class RepoContext:
    """Cross-module state shared by rules: the jit-reachable function set."""

    def __init__(self, modules):
        self.modules = modules
        # last dotted component -> module. Real module files take priority
        # over package __init__ stems (`bodies/bodies.py` over `bodies/`),
        # matching how `from ..bodies import bodies` resolves; remaining
        # collisions keep the first, which only risks missing an edge,
        # never inventing one.
        self.modules_by_name = {}
        inits = []
        for m in modules:
            if m.tree is None:
                continue
            stem = os.path.splitext(os.path.basename(m.relpath))[0]
            if stem == "__init__":
                inits.append(m)
                continue
            self.modules_by_name.setdefault(stem, m)
        for m in inits:
            stem = os.path.basename(os.path.dirname(m.relpath))
            if stem:
                self.modules_by_name.setdefault(stem, m)
        self.reachable = set()      # {(ModuleInfo, qualname)}
        self._build_reachability()

    # -- seeds -------------------------------------------------------------
    def _seed_functions(self):
        seeds = []
        for mod in self.modules:
            if mod.tree is None:
                continue
            for qual, fi in mod.functions.items():
                if any(_is_jit_expr(d, mod) for d in fi.node.decorator_list):
                    fi.is_seed = True
                    seeds.append((mod, qual))
            # jax.jit(fn, ...) wrapping anywhere in the module (e.g.
            # `self._solve_jit = jax.jit(self._solve_impl, ...)`)
            for node in ast.walk(mod.tree):
                if not (isinstance(node, ast.Call)
                        and _is_jit_expr(node.func, mod) and node.args):
                    continue
                cls = self._enclosing_class(mod, node)
                tgt = _resolve_call(node.args[0], mod, cls,
                                    self.modules_by_name)
                if tgt is not None:
                    tgt[0].functions[tgt[1]].is_seed = True
                    seeds.append(tgt)
        return seeds

    def _enclosing_class(self, mod, node):
        """Class whose method subtree contains ``node`` (None otherwise)."""
        for qual, fi in mod.functions.items():
            if fi.cls is None:
                continue
            for sub in ast.walk(fi.node):
                if sub is node:
                    return fi.cls
        return None

    # -- propagation -------------------------------------------------------
    def _build_reachability(self):
        work = list(self._seed_functions())
        seen = {(m.path, q) for m, q in work}
        while work:
            mod, qual = work.pop()
            fi = mod.functions[qual]
            for node in ast.walk(fi.node):
                target = None
                if isinstance(node, ast.Call):
                    target = _resolve_call(node.func, mod, fi.cls,
                                           self.modules_by_name)
                elif isinstance(node, (ast.Name, ast.Attribute)):
                    # bare references too: functions passed higher-order
                    # (matvec=..., jax.vmap(fn)) are traced when called
                    target = _resolve_call(node, mod, fi.cls,
                                           self.modules_by_name)
                if target is not None:
                    key = (target[0].path, target[1])
                    if (key not in seen
                            and not _is_cached_fn(
                                target[0].functions[target[1]])):
                        seen.add(key)
                        work.append(target)
        self.reachable = seen

    def is_reachable(self, mod: ModuleInfo, qualname: str) -> bool:
        return (mod.path, qualname) in self.reachable


def _function_span(mod: ModuleInfo, def_line: int):
    """(first, last) line of the def anchored at ``def_line``, or None.

    A decorated def's ``node.lineno`` is the ``def`` line, below its
    decorators — but a pragma "directly above the def" lands on the first
    decorator line, so any line from the first decorator through the
    ``def`` itself anchors the pragma.
    """
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        first = min([d.lineno for d in node.decorator_list] + [node.lineno])
        if first <= def_line <= node.lineno:
            return node.lineno, node.end_lineno
    return None


# ------------------------------------------------------------------ driver

def iter_py_files(paths):
    out = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if d not in ("__pycache__", ".git"))
                for f in sorted(files):
                    if f.endswith(".py"):
                        out.append(os.path.join(root, f))
        elif p.endswith(".py"):
            out.append(p)
    # de-dup while keeping order
    seen = set()
    uniq = []
    for p in out:
        key = os.path.abspath(p)
        if key not in seen:
            seen.add(key)
            uniq.append(p)
    return uniq


def lint_paths(paths, rules=None):
    """Run the registered rules over every .py under ``paths``.

    Returns a sorted list of unsuppressed `Finding`s (including lint-pragma
    findings for malformed/unknown/unused pragmas).
    """
    from .rules import RULES

    if rules is None:
        active = list(RULES)
    else:
        known = {r.id for r in RULES}
        unknown = sorted(set(rules) - known)
        if unknown:
            # a typo'd filter must not return a vacuous "clean" result —
            # callers gate on the emptiness of the return value
            raise ValueError(
                f"unknown rule id(s): {', '.join(unknown)} "
                f"(known: {', '.join(sorted(known))})")
        active = [r for r in RULES if r.id in set(rules)]
    files = iter_py_files(paths)
    modules = [parse_module(f) for f in files]
    ctx = RepoContext([m for m in modules if m.tree is not None])

    known_ids = {r.id for r in RULES} | {"lint-pragma"}
    findings = []
    for mod in modules:
        if mod.syntax_error is not None:
            findings.append(Finding(mod.path, 1, 0, "lint-pragma",
                                    mod.syntax_error))
            continue
        raw = []
        for rule in active:
            raw.extend(rule.check(mod, ctx))
        # pragma validation
        for pr in mod.pragmas:
            for rid in pr.rules:
                if rid not in known_ids:
                    findings.append(Finding(
                        mod.path, pr.line, 0, "lint-pragma",
                        f"pragma names unknown rule id {rid!r} "
                        f"(known: {', '.join(sorted(known_ids))})"))
            if not pr.rules:
                findings.append(Finding(
                    mod.path, pr.line, 0, "lint-pragma",
                    "pragma names no rule id: use "
                    "`# skelly-lint: ignore[rule-id] — reason`"))
            if not pr.reason:
                findings.append(Finding(
                    mod.path, pr.line, 0, "lint-pragma",
                    "pragma is missing its reason: every suppression must "
                    "say why (`# skelly-lint: ignore[rule-id] — reason`)"))
        # suppression pass
        spans = {}
        for pr in mod.pragmas:
            if pr.scope == "function":
                spans[pr.line] = _function_span(mod, pr.target_line)
                if spans[pr.line] is None:
                    findings.append(Finding(
                        mod.path, pr.line, 0, "lint-pragma",
                        "ignore-function pragma is not attached to a `def` "
                        "line (place it on, or directly above, the def)"))
        for f in raw:
            suppressed = False
            for pr in mod.pragmas:
                if f.rule not in pr.rules:
                    continue
                if pr.scope == "line":
                    hit = f.line == pr.target_line
                else:
                    span = spans.get(pr.line)
                    hit = span is not None and span[0] <= f.line <= span[1]
                if hit:
                    pr.used = True
                    suppressed = True
            if not suppressed:
                findings.append(f)
        # a pragma that suppresses nothing is dead weight — or a typo hiding
        # the finding it meant to suppress. Only counted when its rules all
        # ran this invocation (a filtered run must not flag pragmas for
        # rules it skipped).
        active_ids = {r.id for r in active}
        for pr in mod.pragmas:
            if (not pr.used and pr.rules and pr.reason
                    and set(pr.rules) <= active_ids):
                findings.append(Finding(
                    mod.path, pr.line, 0, "lint-pragma",
                    f"unused suppression for {', '.join(pr.rules)}: the "
                    "pragma matches no finding on its line — remove it"))

    uniq = sorted(set(findings), key=lambda f: (f.path, f.line, f.col, f.rule,
                                                f.message))
    return uniq
