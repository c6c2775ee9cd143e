"""Repo-specific lint: AST rules for the round path's contracts.

Pure-stdlib (``ast``) so it runs anywhere the code does::

    PYTHONPATH=src python -m repro.analysis.lint src/

Rules (each finding names the rule):

``host-sync``
    No ``jax.device_get`` / ``.item()`` / ``np.asarray`` /
    ``float()``-of-a-dispatch in the round-path modules
    (``fl/engine.py``, ``core/round.py``, ``core/cache_store.py``)
    outside the explicit allowlist of documented sync seams
    (ledger resolve, run-end readbacks, the host reference loop, the
    host store's own gather/apply).  Everything else must stay async.

``mutable-global``
    No new module-global mutable singletons — the deprecated
    ``cache_store.STATS`` pattern (``NAME = SomeClass()`` at module
    level).  Per-engine state belongs on the engine; registries built
    by ``@register_*`` decorators are dict literals and unaffected.

``registry``
    Every ``@register_policy`` / ``@register_dynamics`` /
    ``@register_agg_rule`` / ``@register_metric`` /
    ``@register_adversary`` target is registered under a string
    literal and carries a docstring, and ``FLConfig.__post_init__``
    name-validates each registry axis it configures
    (``available_agg_rules`` / ``available_adversaries`` /
    ``available_dynamics``).

``jit-determinism``
    No wall-clock or host-RNG calls (``time.*``, ``datetime.*``,
    ``random.*``, ``np.random.*``) inside jitted code — they bake a
    trace-time value into the compiled executable.

``deprecated-stats``
    No references to the removed module-global ``cache_store.STATS``.

Extending the allowlist: add the function's qualified name (e.g.
``"FleetEngine._host_rounds"``) to ``HOST_SYNC_ALLOWLIST`` under its
module, with a comment saying why the sync is legitimate.
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple


@dataclasses.dataclass(frozen=True)
class LintFinding:
    path: str
    line: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


# ---------------------------------------------------------------------------
# Rule configuration
# ---------------------------------------------------------------------------

#: modules whose code IS the per-round hot path — host syncs here stall
#: the device pipeline, so every one must be an allowlisted seam
ROUND_PATH_MODULES = (
    "repro/fl/engine.py",
    "repro/core/round.py",
    "repro/core/cache_store.py",
)

#: documented host-sync seams, by qualified name.  A listed name covers
#: everything nested inside it.
HOST_SYNC_ALLOWLIST: Dict[str, Set[str]] = {
    "repro/fl/engine.py": {
        # construction-time placement (before any round runs)
        "make_trainer",
        "FleetEngine.__init__",
        # deferred-ledger resolve: THE documented readback seam — host
        # rows materialize here, traced under a tracer span
        "_RoundLedger.resolve",
        "_RoundLedger.push",
        # run()-scoped seams outside the round loop: final eval /
        # diagnostics / trust readback, policy upload boundary
        "FleetEngine.run",
        "FleetEngine._from_plan",
        "FleetEngine._validate_plan",
        "FleetEngine._book_round",
        "FleetEngine._close_round",
        # the legacy host-RNG reference loop syncs by design
        "FleetEngine._host_rounds",
        # AOT memory profile (tooling, not a round)
        "FleetEngine.server_step_memory",
        # History (de)serialization is host-side by definition
        "History.to_json",
        "History.from_json",
        "_metric_py",
    },
    "repro/core/round.py": {
        # the numpy reference implementation of the jitted cut
        "host_round_cut",
    },
    "repro/core/cache_store.py": {
        # the host store's own plumbing: gather/apply run on host rows,
        # and the stream's pre-issued reads are the documented async
        # fetch path (counted in TransferStats.pre_issued_reads)
        "HostCacheStore",
        "CohortCacheStream",
    },
}

#: sanctioned module-global singletons (immutable/stateless objects)
MUTABLE_GLOBAL_ALLOWLIST: Set[Tuple[str, str]] = {
    # stateless no-op sinks: every method is a constant-return stub
    ("repro/obs/trace.py", "NULL_TRACER"),
    ("repro/obs/trace.py", "_NULL_SPAN"),
}

_REGISTER_DECORATORS = frozenset({
    "register_policy", "register_dynamics", "register_agg_rule",
    "register_metric", "register_adversary",
})

#: registry axes FLConfig configures -> the validator its
#: ``__post_init__`` must call
_POST_INIT_VALIDATORS = (
    "available_agg_rules", "available_adversaries", "available_dynamics",
)

_NONDET_PREFIXES = (
    "time.", "datetime.", "random.", "np.random.", "numpy.random.",
)

_CAMEL_RE = re.compile(r"^_?[A-Z][A-Za-z0-9]*$")


# ---------------------------------------------------------------------------
# AST helpers
# ---------------------------------------------------------------------------

def _dotted(node: ast.AST) -> Optional[str]:
    """``jax.random.fold_in`` -> "jax.random.fold_in"; None if the
    chain bottoms out in something that isn't a plain name."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _covered(qualname: str, allow: Set[str]) -> bool:
    return any(qualname == a or qualname.startswith(a + ".")
               for a in allow)


class _ScopedVisitor(ast.NodeVisitor):
    """Tracks the qualified name of the enclosing def/class."""

    def __init__(self) -> None:
        self._stack: List[str] = []

    @property
    def qualname(self) -> str:
        return ".".join(self._stack) or "<module>"

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef


# ---------------------------------------------------------------------------
# Rule: host-sync
# ---------------------------------------------------------------------------

class _HostSyncVisitor(_ScopedVisitor):
    def __init__(self, path: str, allow: Set[str]) -> None:
        super().__init__()
        self.path = path
        self.allow = allow
        self.findings: List[LintFinding] = []

    def _flag(self, node: ast.AST, what: str) -> None:
        if _covered(self.qualname, self.allow):
            return
        self.findings.append(LintFinding(
            self.path, node.lineno, "host-sync",
            f"{what} in round-path code ({self.qualname}) — a per-round "
            f"host sync; move it behind the round ledger or add the "
            f"function to HOST_SYNC_ALLOWLIST with a justification"))

    def visit_Call(self, node: ast.Call) -> None:
        dotted = _dotted(node.func)
        if dotted == "jax.device_get":
            self._flag(node, "jax.device_get")
        elif dotted is not None and dotted.split(".", 1)[0] in (
                "np", "numpy") and dotted.endswith(".asarray"):
            self._flag(node, f"{dotted}()")
        elif isinstance(node.func, ast.Attribute) \
                and node.func.attr == "item" and not node.args:
            self._flag(node, ".item()")
        elif isinstance(node.func, ast.Name) \
                and node.func.id in ("float", "int") and node.args \
                and isinstance(node.args[0], ast.Call):
            self._flag(node, f"{node.func.id}() over a dispatch result")
        self.generic_visit(node)


# ---------------------------------------------------------------------------
# Rule: mutable-global
# ---------------------------------------------------------------------------

def _check_mutable_globals(path: str, key: str, tree: ast.Module,
                           ) -> List[LintFinding]:
    findings = []
    for node in tree.body:
        targets: List[ast.expr] = []
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        else:
            continue
        if not isinstance(value, ast.Call):
            continue
        callee = _dotted(value.func)
        terminal = callee.rsplit(".", 1)[-1] if callee else ""
        if not _CAMEL_RE.match(terminal):
            continue
        # repo convention: *Config classes are frozen dataclasses —
        # module-level CONFIG = ModelConfig(...) constants are immutable
        if terminal.endswith("Config"):
            continue
        for t in targets:
            if not (isinstance(t, ast.Name) and t.id.isupper()):
                continue
            if (key, t.id) in MUTABLE_GLOBAL_ALLOWLIST:
                continue
            findings.append(LintFinding(
                path, node.lineno, "mutable-global",
                f"module-global singleton {t.id} = {terminal}(...) — "
                f"the deprecated STATS pattern; hold per-engine state "
                f"on the engine (or allowlist a provably stateless "
                f"object in MUTABLE_GLOBAL_ALLOWLIST)"))
    return findings


# ---------------------------------------------------------------------------
# Rule: registry
# ---------------------------------------------------------------------------

def _check_registries(path: str, tree: ast.Module) -> List[LintFinding]:
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
            continue
        for deco in node.decorator_list:
            if not isinstance(deco, ast.Call):
                continue
            name = _dotted(deco.func)
            terminal = name.rsplit(".", 1)[-1] if name else ""
            if terminal not in _REGISTER_DECORATORS:
                continue
            if not (deco.args and isinstance(deco.args[0], ast.Constant)
                    and isinstance(deco.args[0].value, str)):
                findings.append(LintFinding(
                    path, deco.lineno, "registry",
                    f"@{terminal} on {node.name} must register a string "
                    f"literal name (found a computed value) — registry "
                    f"names are config surface and must be greppable"))
            if ast.get_docstring(node) is None:
                findings.append(LintFinding(
                    path, node.lineno, "registry",
                    f"@{terminal} target {node.name} has no docstring — "
                    f"registered names are user-facing config values "
                    f"and must be documented"))
    return findings


def _check_post_init(path: str, tree: ast.Module) -> List[LintFinding]:
    """``FLConfig.__post_init__`` must name-validate each registry axis
    it configures (applies to ``repro/configs/base.py`` only)."""
    post_init = None
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "FLConfig":
            for item in node.body:
                if isinstance(item, ast.FunctionDef) \
                        and item.name == "__post_init__":
                    post_init = item
    if post_init is None:
        return [LintFinding(
            path, 1, "registry",
            "FLConfig has no __post_init__ — registry names "
            "(agg_rule/adversary/dynamics) must fail fast at config "
            "construction")]
    used = {n.id for n in ast.walk(post_init) if isinstance(n, ast.Name)}
    used |= {n.attr for n in ast.walk(post_init)
             if isinstance(n, ast.Attribute)}
    return [
        LintFinding(
            path, post_init.lineno, "registry",
            f"FLConfig.__post_init__ does not validate against "
            f"{validator}() — unknown registry names must be rejected "
            f"at config construction, not deep inside a jitted round")
        for validator in _POST_INIT_VALIDATORS if validator not in used
    ]


# ---------------------------------------------------------------------------
# Rule: jit-determinism
# ---------------------------------------------------------------------------

def _is_jit_decorator(deco: ast.expr) -> bool:
    name = _dotted(deco)
    if name in ("jax.jit", "jit"):
        return True
    if isinstance(deco, ast.Call):
        inner = _dotted(deco.func)
        if inner in ("jax.jit", "jit"):
            return True
        if inner in ("functools.partial", "partial") and deco.args:
            return _dotted(deco.args[0]) in ("jax.jit", "jit")
    return False


def _nondet_calls(root: ast.AST) -> Iterable[ast.Call]:
    for node in ast.walk(root):
        if isinstance(node, ast.Call):
            dotted = _dotted(node.func)
            if dotted and any(dotted.startswith(p)
                              for p in _NONDET_PREFIXES):
                yield node


def _check_jit_determinism(path: str, tree: ast.Module,
                           ) -> List[LintFinding]:
    findings = []

    def flag(call: ast.Call, where: str) -> None:
        findings.append(LintFinding(
            path, call.lineno, "jit-determinism",
            f"{_dotted(call.func)}() inside jitted code ({where}) — "
            f"wall-clock/host-RNG values are baked in at trace time; "
            f"use jax.random with a threaded key, or hoist the value "
            f"to an argument"))

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and any(_is_jit_decorator(d) for d in node.decorator_list):
            for call in _nondet_calls(node):
                flag(call, node.name)
        elif isinstance(node, ast.Call) \
                and _dotted(node.func) in ("jax.jit", "jit"):
            for arg in node.args:
                for call in _nondet_calls(arg):
                    flag(call, f"jax.jit(...) at line {node.lineno}")
    return findings


# ---------------------------------------------------------------------------
# Rule: deprecated-stats
# ---------------------------------------------------------------------------

def _check_deprecated_stats(path: str, tree: ast.Module,
                            ) -> List[LintFinding]:
    findings = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr == "STATS":
            findings.append(LintFinding(
                path, node.lineno, "deprecated-stats",
                "reference to the removed module-global cache_store."
                "STATS — use the per-engine engine.transfer_stats"))
        elif isinstance(node, ast.ImportFrom) \
                and (node.module or "").endswith("cache_store") \
                and any(a.name == "STATS" for a in node.names):
            findings.append(LintFinding(
                path, node.lineno, "deprecated-stats",
                "import of the removed cache_store.STATS — use the "
                "per-engine engine.transfer_stats"))
        elif isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "STATS"
                for t in node.targets):
            findings.append(LintFinding(
                path, node.lineno, "deprecated-stats",
                "module-global STATS assignment — the aggregate "
                "transfer-counter pattern is removed; counters are "
                "per-engine"))
    return findings


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def _module_key(path: str) -> str:
    """Repo-relative module key ("repro/fl/engine.py") for rule scoping."""
    norm = path.replace(os.sep, "/")
    i = norm.rfind("repro/")
    return norm[i:] if i >= 0 else os.path.basename(norm)


def lint_source(src: str, module_key: str, path: str = "<memory>",
                ) -> List[LintFinding]:
    tree = ast.parse(src, filename=path)
    findings: List[LintFinding] = []
    if module_key in ROUND_PATH_MODULES:
        visitor = _HostSyncVisitor(
            path, HOST_SYNC_ALLOWLIST.get(module_key, set()))
        visitor.visit(tree)
        findings += visitor.findings
    findings += _check_mutable_globals(path, module_key, tree)
    findings += _check_registries(path, tree)
    if module_key == "repro/configs/base.py":
        findings += _check_post_init(path, tree)
    findings += _check_jit_determinism(path, tree)
    findings += _check_deprecated_stats(path, tree)
    return sorted(findings, key=lambda f: (f.path, f.line))


def lint_file(path: str) -> List[LintFinding]:
    with open(path, encoding="utf-8") as fh:
        return lint_source(fh.read(), _module_key(path), path)


def iter_python_files(paths: Sequence[str]) -> Iterable[str]:
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if d not in ("__pycache__", ".git"))
                for name in sorted(files):
                    if name.endswith(".py"):
                        yield os.path.join(root, name)
        elif p.endswith(".py"):
            yield p


def lint_paths(paths: Sequence[str]) -> List[LintFinding]:
    findings: List[LintFinding] = []
    for path in iter_python_files(paths):
        findings += lint_file(path)
    return findings


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="Repo-specific AST lint for the round-path "
                    "contracts (stdlib-only).")
    parser.add_argument("paths", nargs="*", default=["src/"],
                        help="files or directories to lint")
    args = parser.parse_args(argv)
    findings = lint_paths(args.paths or ["src/"])
    for f in findings:
        print(f)
    n_files = sum(1 for _ in iter_python_files(args.paths or ["src/"]))
    print(f"linted {n_files} files: {len(findings)} finding(s)")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
