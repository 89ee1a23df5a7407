"""AST-based standing-policy lint (``python -m repro.analysis.lint``).

The policies this gate enforces are the repo's hard-won JAX-compat
rules (see ROADMAP "standing policies") — each became policy after a
real breakage, and each is mechanically checkable from the source
alone:

``L001`` ``jax.shard_map`` / ``check_vma`` must be imported only
through :mod:`repro.parallel.compat`, the one place they are spelled,
so a move of that API between JAX releases is one edit.

``L002`` ``hypothesis`` must be imported only through
``tests/_hypothesis_compat``: the container has no hypothesis wheel,
and the compat module degrades to a deterministic sampler instead of
a collection error.

``L003`` No ``interpret=True`` *literal default* outside the
whitelisted kernel entry points (``src/repro/kernels/``): the kernels
default to interpret mode by design (CPU validation), but anything
above them must thread the flag explicitly, or a TPU run silently
executes the slow interpreter.

``L005`` No bare wall-clock / sleep call inside ``serve/`` or
``runtime/`` modules: serving and runtime loops must take an
injectable ``clock=``/``sleep=`` (references in *parameter defaults*
like ``clock=time.monotonic`` are the sanctioned idiom), or the loop
can never run under the virtual time the chaos suite and the
deterministic benchmarks depend on.  Flags call sites of
``time.monotonic()`` / ``time.sleep()`` / ``time.time()`` /
``time.perf_counter()``; scoped to path fragments ``/serve/`` and
``/runtime/`` only.

``L006`` Observability must stay deterministic and injectable: (a) no
bare wall-clock / sleep call inside ``obs/`` modules — the tracer's
``clock=`` is the *only* time source, so a trace replayed under a
``VirtualClock`` exports bit-identically (parameter defaults like
``clock=time.perf_counter`` remain the sanctioned idiom); (b) no
``set_active(...)`` ambient-tracer mutation outside ``obs/`` —
instrumented code takes ``tracer=`` or scopes the swap with
``with tracer.activate():``, so no module can leave a global tracer
installed behind a test's back.

``L007`` No raw ``interpret=`` / ``use_kernel=`` keyword at a *call
site* outside ``src/repro/kernels/``: the execution backend is a
first-class :class:`~repro.core.exec_target.ExecTarget` — callers pass
``target=`` and let the kernel wrappers own the raw flag.  The
sanctioned adapter :func:`~repro.core.exec_target.from_flags` (the one
place legacy booleans become a target) is exempt by callee name.

``L008`` No ``jax.lax.conv*`` call inside a backward code path
(functions whose names mention ``bwd``/``backward``/``dgrad``/
``wgrad``) unless an enclosing function is ``_lax_fallback``-suffixed:
the backward pass *executes* through the Pallas kernels (lhs-dilated
dgrad, dW-stationary wgrad), and the only sanctioned lax escape is a
loudly-named fallback that records itself via ``record_fallback`` —
a quiet ``lax.conv`` in a gradient path silently un-does the paper
dataflow while every plan still claims it rode the kernel.

``L004`` No obviously 0-d value returned from a ``shard_map`` body:
scalar residuals crossing a differentiated ``shard_map`` break jax
0.4.x's transpose (``_SpecError`` under ``grad``) — bodies must keep
everything >= 1-D (see ``models/embedding.py``).  The check is a
conservative heuristic: it flags ``return``s whose expression (or
tuple element) is a direct ``jnp.sum/mean/max/min/prod`` call without
``keepdims=True``, or a ``float(...)`` — shapes it can prove 0-d.

Exit status 0 when the tree is clean, 1 otherwise — tier-1 runs this
as a test, and ``benchmarks/plan_audit_bench.py`` publishes the error
count as a gated row.
"""

from __future__ import annotations

import ast
import dataclasses
import sys
from pathlib import Path

#: rule id -> one-line meaning (mirrors plan_check.RULES for the README)
LINT_RULES = {
    "L001": "jax shard_map/check_vma imported outside parallel/compat",
    "L002": "hypothesis imported outside tests/_hypothesis_compat",
    "L003": "interpret=True literal default outside src/repro/kernels/",
    "L004": "provably 0-d value returned from a shard_map body",
    "L005": "bare wall-clock/sleep call in serve/runtime (inject clock=)",
    "L006": "bare clock in obs/, or set_active tracer mutation outside obs/",
    "L007": "interpret=/use_kernel= kwarg passed outside src/repro/kernels/",
    "L008": "jax.lax.conv* in a backward path outside *_lax_fallback",
}

#: path fragments (posix) that exempt a file from a rule
_ALLOW = {
    "L001": ("parallel/compat.py",),
    "L002": ("_hypothesis_compat.py",),
    "L003": ("/kernels/", "core/exec_target.py"),
    "L004": (),
    "L005": (),
    "L006": (),
    # exec_target.py *defines* the backend abstraction — its singleton
    # constructors are the one place the raw flags are spelled out
    "L007": ("/kernels/", "core/exec_target.py"),
    "L008": (),
}

#: function-name fragments marking a backward code path (L008 scope)
_BWD_NAME_FRAGMENTS = ("bwd", "backward", "dgrad", "wgrad")

#: path fragments marking the observability package (L006's pivot:
#: clock calls are banned *inside*, set_active calls *outside*)
_OBS_FRAGMENTS = ("/obs/",)

#: path fragments a rule is *scoped to* (empty: applies everywhere)
_ONLY = {
    "L005": ("/serve/", "/runtime/"),
}

_SCALAR_REDUCERS = {"sum", "mean", "max", "min", "prod"}

#: wall-clock call chains L005 rejects outside parameter defaults
_CLOCK_CALLS = {"time.monotonic", "time.sleep", "time.time",
                "time.perf_counter"}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One policy violation: ``file:line rule message``."""

    rule: str
    path: str
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.rule}: {self.message}"


def _allowed(path: str, rule: str) -> bool:
    p = Path(path).as_posix()
    only = _ONLY.get(rule, ())
    if only and not any(frag in p for frag in only):
        return True                      # rule is scoped elsewhere
    return any(frag in p for frag in _ALLOW[rule])


def _attr_chain(node: ast.AST) -> str:
    """Dotted name of an attribute/name chain ('' when not one)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _returns_scalar(expr: ast.AST) -> bool:
    """True when ``expr`` is provably a 0-d array/scalar."""
    if isinstance(expr, ast.Tuple):
        return any(_returns_scalar(e) for e in expr.elts)
    if isinstance(expr, ast.Constant) and isinstance(expr.value,
                                                    (int, float)):
        return True
    if not isinstance(expr, ast.Call):
        return False
    chain = _attr_chain(expr.func)
    if chain == "float":
        return True
    head, _, tail = chain.rpartition(".")
    if head in ("jnp", "np", "jax.numpy", "numpy") \
            and tail in _SCALAR_REDUCERS:
        for kw in expr.keywords:
            if kw.arg == "keepdims" \
                    and isinstance(kw.value, ast.Constant) \
                    and kw.value.value:
                return False
        # a reduction over an explicit axis keeps the other dims
        return not any(kw.arg == "axis" for kw in expr.keywords) \
            and len(expr.args) < 2
    return False


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str):
        self.path = path
        self.findings: list[Finding] = []
        # every def in the module, by name — shard_map bodies are
        # resolved against this (closures included)
        self.defs: dict[str, ast.FunctionDef] = {}
        # enclosing function names, outermost first — L008 resolves a
        # call site against the whole lexical chain (a closure inside
        # _bwd is still a backward path; a closure inside
        # _dgrad_lax_fallback is still sanctioned)
        self.fn_stack: list[str] = []

    def _emit(self, rule: str, line: int, message: str) -> None:
        if not _allowed(self.path, rule):
            self.findings.append(Finding(rule=rule, path=self.path,
                                         line=line, message=message))

    # -- L001 / L002: import provenance -----------------------------------

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            root = alias.name.split(".")[0]
            if root == "hypothesis":
                self._emit("L002", node.lineno,
                           "import hypothesis directly — use "
                           "tests/_hypothesis_compat")
            if alias.name.startswith("jax") \
                    and "shard_map" in alias.name:
                self._emit("L001", node.lineno,
                           f"import {alias.name} — use "
                           "repro.parallel.compat")
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        mod = node.module or ""
        root = mod.split(".")[0]
        if root == "hypothesis":
            self._emit("L002", node.lineno,
                       f"from {mod} import ... — use "
                       "tests/_hypothesis_compat")
        if root == "jax":
            bad = sorted({a.name for a in node.names}
                         & {"shard_map", "check_vma"})
            if "shard_map" in mod:
                bad = sorted({a.name for a in node.names}) or bad
            if bad:
                self._emit("L001", node.lineno,
                           f"from {mod} import {', '.join(bad)} — "
                           "use repro.parallel.compat")
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        chain = _attr_chain(node)
        if chain in ("jax.shard_map", "jax.experimental.shard_map"):
            self._emit("L001", node.lineno,
                       f"{chain} referenced directly — use "
                       "repro.parallel.compat")
        self.generic_visit(node)

    # -- L003: interpret literal defaults ----------------------------------

    def _check_defaults(self, node) -> None:
        a = node.args
        pairs = list(zip(a.args[len(a.args) - len(a.defaults):],
                         a.defaults))
        pairs += [(k, d) for k, d in zip(a.kwonlyargs, a.kw_defaults)
                  if d is not None]
        for arg, default in pairs:
            if arg.arg == "interpret" \
                    and isinstance(default, ast.Constant) \
                    and default.value is True:
                self._emit("L003", node.lineno,
                           f"def {node.name}(... interpret=True ...) — "
                           "interpret defaults live in "
                           "src/repro/kernels/ only")

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self.defs.setdefault(node.name, node)
        self._check_defaults(node)
        self.fn_stack.append(node.name)
        try:
            self.generic_visit(node)
        finally:
            self.fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    # -- L004: scalars out of shard_map bodies ------------------------------

    def _body_returns(self, fn: ast.AST):
        if isinstance(fn, ast.Lambda):
            yield fn.body.lineno, fn.body
            return
        if isinstance(fn, ast.Call):       # partial(body, ...) et al.
            fn = fn.args[0] if fn.args else None
        if isinstance(fn, ast.Name):
            fn = self.defs.get(fn.id)
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Return) and sub.value is not None:
                    yield sub.lineno, sub.value

    def visit_Call(self, node: ast.Call) -> None:
        chain = _attr_chain(node.func)
        in_obs = any(frag in Path(self.path).as_posix()
                     for frag in _OBS_FRAGMENTS)
        if chain in _CLOCK_CALLS:
            self._emit("L005", node.lineno,
                       f"{chain}() called directly — take an "
                       "injectable clock=/sleep= (defaults like "
                       "clock=time.monotonic are fine)")
            if in_obs:
                self._emit("L006", node.lineno,
                           f"{chain}() called inside obs/ — the "
                           "tracer's injectable clock= is the only "
                           "time source (defaults like "
                           "clock=time.perf_counter are fine)")
        if (chain == "set_active" or chain.endswith(".set_active")) \
                and not in_obs:
            self._emit("L006", node.lineno,
                       "set_active() mutates the ambient tracer "
                       "outside obs/ — pass tracer= or scope it "
                       "with `with tracer.activate():`")
        head, _, tail = chain.rpartition(".")
        if tail.startswith("conv") and head.rpartition(".")[2] == "lax" \
                and any(frag in name for name in self.fn_stack
                        for frag in _BWD_NAME_FRAGMENTS) \
                and not any(name.endswith("_lax_fallback")
                            for name in self.fn_stack):
            self._emit("L008", node.lineno,
                       f"{chain}() inside a backward path — gradients "
                       "execute through the Pallas kernels; the only "
                       "lax escape is a *_lax_fallback function that "
                       "records itself via record_fallback")
        if chain.rpartition(".")[2] != "from_flags":
            for kw in node.keywords:
                if kw.arg in ("interpret", "use_kernel"):
                    self._emit("L007", node.lineno,
                               f"{kw.arg}= passed at a call site — "
                               "pass target= (an ExecTarget) instead; "
                               "raw backend kwargs live under "
                               "src/repro/kernels/ only")
        if (chain == "shard_map" or chain.endswith(".shard_map")) \
                and node.args:
            for line, expr in self._body_returns(node.args[0]):
                if _returns_scalar(expr):
                    self._emit("L004", line,
                               "shard_map body returns a provably 0-d "
                               "value — keep residuals >= 1-D "
                               "(reshape to (1,))")
        self.generic_visit(node)


def lint_file(path: str | Path) -> list[Finding]:
    """Lint one source file; syntax errors are findings, not crashes."""
    path = Path(path)
    try:
        tree = ast.parse(path.read_text(), filename=str(path))
    except SyntaxError as e:
        return [Finding(rule="parse", path=str(path),
                        line=e.lineno or 0, message=str(e.msg))]
    linter = _Linter(str(path))
    # two passes so a shard_map call can resolve a body defined later
    for sub in ast.walk(tree):
        if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
            linter.defs.setdefault(sub.name, sub)
    linter.visit(tree)
    return linter.findings


def repo_root() -> Path:
    """`<root>/src/repro/analysis/lint.py` -> `<root>`."""
    return Path(__file__).resolve().parents[3]


def lint_paths(paths) -> list[Finding]:
    """Lint files and/or directory trees (``.py`` files, recursively)."""
    findings: list[Finding] = []
    for p in paths:
        p = Path(p)
        files = sorted(p.rglob("*.py")) if p.is_dir() else [p]
        for f in files:
            findings.extend(lint_file(f))
    return findings


def lint_repo(root: str | Path | None = None) -> list[Finding]:
    """Lint every tracked source tree of the repo."""
    root = Path(root) if root is not None else repo_root()
    trees = [root / d
             for d in ("src", "models", "tests", "benchmarks",
                       "examples")]
    return lint_paths([t for t in trees if t.is_dir()])


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    findings = lint_paths(argv) if argv else lint_repo()
    for f in findings:
        print(f)
    n = len(findings)
    print(f"lint: {n} error(s)" if n else "lint: clean")
    return 1 if n else 0


if __name__ == "__main__":
    sys.exit(main())
