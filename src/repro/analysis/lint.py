"""Repo-specific AST lint: static rejection of bug classes this repo has
already shipped and fixed.

Generic linters (ruff) catch generic mistakes; each rule here encodes a
*specific* incident or contract from this codebase's history:

RPR001 mutable-default
    A dataclass field (or function argument) defaulted to a mutable
    literal or a bare constructor call shares ONE instance across every
    construction.  The PR-3 ``Request.sampling`` bug was exactly this —
    every request silently shared one ``SamplingParams``.  Use
    ``field(default_factory=...)``.
RPR002 bare-assert
    ``assert`` on a runtime path is stripped under ``python -O``: the
    check silently vanishes in optimized deployments.  Raise an explicit
    exception (ValueError/RuntimeError/...) instead.  (Tests are not
    linted — pytest asserts are the idiom there.)
RPR003 serveconfig-unvalidated
    Every ``ServeConfig`` field must be validated in ``__post_init__``.
    Unvalidated knobs fail deep inside the engine (or worse, don't);
    the config layer is where a bad value should die with a clear
    message.  A field counts as validated when ``__post_init__``
    mentions it — as a ``self.<field>`` access or as a string literal
    (the registry-loop idiom ``for knob in ("a", "b"): getattr(...)``).
RPR004 jnp-in-loop
    A ``jnp.*`` call inside a Python-level ``for``/``while`` on the
    host path dispatches one XLA op per iteration — the engine's
    per-token loops must stay in numpy / plain Python, batching device
    work into the jitted step functions.  Scoped to ``core/`` (model
    code legitimately builds layer loops that jit traces once).
RPR005 metrics-unsurfaced
    A numeric ``EngineMetrics`` counter that ``summary()`` never reads
    is write-only telemetry: benchmarks and the regression gate can't
    see it, so regressions in what it counts ship silently.
RPR006 jit-in-hot-path
    ``jax.jit(...)`` constructed anywhere but a setup path (module
    level, ``__init__``, ``build_*`` / ``_build_*``) creates a *fresh* compile cache
    per call — every invocation pays a full XLA compile, the exact
    recompile storm the dispatch sentinel (``analysis/dispatch.py``)
    exists to catch at runtime.  Immediately-invoked ``jax.jit(f)(x)``
    is flagged unconditionally.  Scoped to ``core/``.
RPR007 host-sync-in-loop
    ``.item()`` / ``np.asarray`` / ``jax.device_get`` on device values
    inside a Python-level loop forces one host-device synchronization
    per iteration, serializing the dispatch pipeline the step loop
    relies on.  Hoist the transfer out of the loop and index the result.
    Scoped to ``core/``.
RPR008 pallas-no-contract
    A kernel entry point that launches ``pallas_call`` without any
    explicit argument-contract check (``raise`` on bad shapes/dtypes)
    fails as an opaque Mosaic/XLA error deep in lowering.  Every Pallas
    wrapper must validate its operand shapes/dtypes at entry.  Scoped to
    ``kernels/``.
RPR009 params-unvalidated
    RPR003 generalized to the per-request/-tenant parameter dataclasses:
    every ``SamplingParams`` / ``SLOParams`` / ``TenantTier`` field must
    be mentioned by ``__post_init__`` (same ``self.<field>`` /
    registry-loop string-literal detection).  These objects ride every
    request into the engine's hot paths, where a bad knob surfaces as a
    wrong token or an opaque trace error instead of a config-time
    ValueError.

Run as ``python -m repro.analysis.lint src/ tests/ benchmarks/``
(non-zero exit on findings).  ``--select``/``--ignore`` take
comma-separated codes or names; ``--format github`` emits workflow
annotations for the CI lint job.  A finding is suppressed by a
``# rpr: noqa`` comment on its line (all rules) or
``# rpr: noqa[RPR002,RPR004]`` (those rules only).  Stdlib-only on
purpose: the CI lint job and pre-commit hooks run it without jax/numpy
installed.

Adding a rule: subclass ``Rule``, emit ``Finding``s from ``check``, add
an instance to ``RULES``, and seed ``tests/test_lint.py`` with a fixture
that triggers it (rules must be proven live, not vacuous).
"""
from __future__ import annotations

import argparse
import ast
import re
import sys
from pathlib import Path
from typing import Dict, Iterator, List, NamedTuple, Optional, Sequence, Set


class Finding(NamedTuple):
    path: str
    line: int
    code: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.code} {self.message}"


def _decorator_name(node: ast.expr) -> str:
    """Rightmost dotted name of a decorator, unwrapping calls:
    ``@dataclasses.dataclass(frozen=True)`` -> ``dataclass``."""
    if isinstance(node, ast.Call):
        node = node.func
    while isinstance(node, ast.Attribute):
        node = ast.Name(id=node.attr)
    return node.id if isinstance(node, ast.Name) else ""


def _is_dataclass(node: ast.ClassDef) -> bool:
    return any(_decorator_name(d) == "dataclass" for d in node.decorator_list)


def _call_root(node: ast.expr) -> Optional[str]:
    """Root name of a call target: ``jnp.zeros`` -> ``jnp``."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _callee_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    return f.id if isinstance(f, ast.Name) else ""


class Rule:
    code = ""
    name = ""
    # only lint files whose posix path contains this substring ("" = all)
    scope = ""
    # skip files whose posix path contains this substring ("" = none)
    exclude = ""

    def applies(self, path: str) -> bool:
        p = Path(path).as_posix()
        return self.scope in p and not (self.exclude and self.exclude in p)

    def check(self, tree: ast.Module, path: str) -> Iterator[Finding]:
        raise NotImplementedError


_MUTABLE_LITERALS = (ast.List, ast.Dict, ast.Set,
                     ast.ListComp, ast.DictComp, ast.SetComp)
# calls allowed as defaults: dataclasses.field and immutable constructors
_DEFAULT_CALL_ALLOW = {"field", "frozenset", "tuple", "MappingProxyType"}


class MutableDefault(Rule):
    code = "RPR001"
    name = "mutable-default"

    def _flag(self, node: ast.expr, where: str) -> Iterator[Finding]:
        if isinstance(node, _MUTABLE_LITERALS):
            yield Finding("", node.lineno, self.code,
                          f"mutable literal default on {where}: one instance "
                          "is shared by every call/construction; use "
                          "field(default_factory=...) (or None + init)")
        elif isinstance(node, ast.Call) and \
                _callee_name(node) not in _DEFAULT_CALL_ALLOW:
            yield Finding("", node.lineno, self.code,
                          f"call default on {where} runs ONCE at definition "
                          "time and shares the result (the PR-3 "
                          "Request.sampling bug class); use "
                          "field(default_factory=...)")

    def check(self, tree: ast.Module, path: str) -> Iterator[Finding]:
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                args = node.args
                for default in (*args.defaults, *args.kw_defaults):
                    if default is not None:
                        yield from self._flag(
                            default, f"argument of {node.name}()")
            elif isinstance(node, ast.ClassDef) and _is_dataclass(node):
                for stmt in node.body:
                    value = None
                    if isinstance(stmt, (ast.AnnAssign, ast.Assign)):
                        value = stmt.value
                    if value is not None:
                        yield from self._flag(
                            value, f"dataclass field of {node.name}")


class BareAssert(Rule):
    code = "RPR002"
    name = "bare-assert"
    exclude = "tests/"      # pytest asserts are the idiom there

    def check(self, tree: ast.Module, path: str) -> Iterator[Finding]:
        for node in ast.walk(tree):
            if isinstance(node, ast.Assert):
                yield Finding("", node.lineno, self.code,
                              "bare assert on a runtime path is stripped "
                              "under python -O; raise an explicit exception")


class ServeConfigValidated(Rule):
    code = "RPR003"
    name = "serveconfig-unvalidated"

    def check(self, tree: ast.Module, path: str) -> Iterator[Finding]:
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef) and node.name == "ServeConfig":
                yield from self._check_class(node)

    def _check_class(self, cls: ast.ClassDef) -> Iterator[Finding]:
        fields = {}     # name -> lineno
        post_init = None
        for stmt in cls.body:
            if isinstance(stmt, ast.AnnAssign) and \
                    isinstance(stmt.target, ast.Name):
                ann = ast.unparse(stmt.annotation)
                if not ann.startswith("ClassVar"):
                    fields[stmt.target.id] = stmt.lineno
            elif isinstance(stmt, ast.FunctionDef) and \
                    stmt.name == "__post_init__":
                post_init = stmt
        if not fields:
            return
        mentioned = set()
        if post_init is not None:
            for node in ast.walk(post_init):
                if isinstance(node, ast.Attribute) and \
                        isinstance(node.value, ast.Name) and \
                        node.value.id == "self":
                    mentioned.add(node.attr)
                elif isinstance(node, ast.Constant) and \
                        isinstance(node.value, str):
                    mentioned.add(node.value)
        for name, line in sorted(fields.items(), key=lambda kv: kv[1]):
            if name not in mentioned:
                yield Finding(
                    "", line, self.code,
                    f"{cls.name}.{name} is never validated in "
                    "__post_init__: a bad value should die at construction "
                    "with a clear message, not deep inside the engine")


class ParamsValidated(ServeConfigValidated):
    """RPR009: RPR003's contract generalized to the per-request/-tenant
    parameter dataclasses (``SamplingParams``, ``SLOParams``,
    ``TenantTier``) — a field added to any of them without a validation
    mention in ``__post_init__`` ships an unvalidated knob straight into
    the engine's hot paths."""

    code = "RPR009"
    name = "params-unvalidated"
    classes = ("SamplingParams", "SLOParams", "TenantTier")

    def check(self, tree: ast.Module, path: str) -> Iterator[Finding]:
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef) and node.name in self.classes:
                yield from self._check_class(node)


class JnpInLoop(Rule):
    code = "RPR004"
    name = "jnp-in-loop"
    scope = "repro/core"

    def check(self, tree: ast.Module, path: str) -> Iterator[Finding]:
        findings: List[Finding] = []
        rule = self

        class V(ast.NodeVisitor):
            def __init__(self):
                self.loop_depth = 0

            def _loop(self, node):
                self.loop_depth += 1
                self.generic_visit(node)
                self.loop_depth -= 1

            visit_For = _loop
            visit_While = _loop

            # a nested function def is traced/called elsewhere; don't
            # charge its body to the enclosing loop
            def _func(self, node):
                saved, self.loop_depth = self.loop_depth, 0
                self.generic_visit(node)
                self.loop_depth = saved

            visit_FunctionDef = _func
            visit_AsyncFunctionDef = _func

            def visit_Call(self, node):
                if self.loop_depth and _call_root(node.func) in ("jnp", "jax"):
                    findings.append(Finding(
                        "", node.lineno, rule.code,
                        f"{ast.unparse(node.func)}() inside a Python-level "
                        "loop dispatches one XLA op per iteration on the "
                        "host path; batch it or use numpy"))
                self.generic_visit(node)

        V().visit(tree)
        yield from findings


class MetricsSurfaced(Rule):
    code = "RPR005"
    name = "metrics-unsurfaced"

    def check(self, tree: ast.Module, path: str) -> Iterator[Finding]:
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef) and node.name == "EngineMetrics":
                yield from self._check_class(node)

    def _check_class(self, cls: ast.ClassDef) -> Iterator[Finding]:
        counters = {}   # numeric field name -> lineno
        summary = None
        for stmt in cls.body:
            if isinstance(stmt, ast.AnnAssign) and \
                    isinstance(stmt.target, ast.Name):
                ann = ast.unparse(stmt.annotation)
                if ann in ("int", "float"):
                    counters[stmt.target.id] = stmt.lineno
            elif isinstance(stmt, ast.FunctionDef) and stmt.name == "summary":
                summary = stmt
        read = set()
        if summary is not None:
            for node in ast.walk(summary):
                if isinstance(node, ast.Attribute) and \
                        isinstance(node.value, ast.Name) and \
                        node.value.id == "self":
                    read.add(node.attr)
        for name, line in sorted(counters.items(), key=lambda kv: kv[1]):
            if name not in read:
                yield Finding(
                    "", line, self.code,
                    f"EngineMetrics.{name} is never read in summary(): "
                    "write-only telemetry is invisible to benchmarks and "
                    "the regression gate")


def _is_jit(node: ast.expr) -> bool:
    """``jax.jit`` (dotted, rooted at jax) or a bare ``jit`` name."""
    if isinstance(node, ast.Attribute):
        return node.attr == "jit" and _call_root(node) == "jax"
    return isinstance(node, ast.Name) and node.id == "jit"


_JIT_SETUP_NAMES = ("__init__",)     # plus any function named [_]build*


class JitInHotPath(Rule):
    code = "RPR006"
    name = "jit-in-hot-path"
    scope = "repro/core"

    def check(self, tree: ast.Module, path: str) -> Iterator[Finding]:
        findings: List[Finding] = []
        rule = self

        class V(ast.NodeVisitor):
            def __init__(self):
                self.stack: List[str] = []
                self.skip: Set[int] = set()

            def _func(self, node):
                self.stack.append(node.name)
                self.generic_visit(node)
                self.stack.pop()

            visit_FunctionDef = _func
            visit_AsyncFunctionDef = _func

            def visit_Call(self, node):
                if isinstance(node.func, ast.Call) and _is_jit(node.func.func):
                    findings.append(Finding(
                        "", node.lineno, rule.code,
                        "jax.jit(f)(...) constructs a jitted wrapper and "
                        "invokes it in one expression: the compile cache is "
                        "thrown away per call, so every invocation pays a "
                        "full XLA compile"))
                    self.skip.add(id(node.func))
                if _is_jit(node.func) and id(node) not in self.skip:
                    in_setup = any(
                        name in _JIT_SETUP_NAMES
                        or name.lstrip("_").startswith("build")
                        for name in self.stack)
                    if self.stack and not in_setup:
                        findings.append(Finding(
                            "", node.lineno, rule.code,
                            f"jax.jit constructed inside "
                            f"{self.stack[-1]}(): a fresh compile cache per "
                            "call is a recompile storm; hoist to module "
                            "level, __init__, or a [_]build_* function"))
                self.generic_visit(node)

        V().visit(tree)
        yield from findings


# host-sync callables flagged inside loops: attr calls by name, dotted
# calls by (root, attr)
_SYNC_ATTRS = frozenset({"item"})
_SYNC_CALLS = frozenset({("np", "asarray"), ("np", "array"),
                         ("np", "copy"), ("jax", "device_get"),
                         ("numpy", "asarray"), ("numpy", "array")})


class HostSyncInLoop(Rule):
    code = "RPR007"
    name = "host-sync-in-loop"
    scope = "repro/core"

    def check(self, tree: ast.Module, path: str) -> Iterator[Finding]:
        findings: List[Finding] = []
        rule = self

        class V(ast.NodeVisitor):
            def __init__(self):
                self.loop_depth = 0

            def _loop(self, node):
                self.loop_depth += 1
                self.generic_visit(node)
                self.loop_depth -= 1

            visit_For = _loop
            visit_While = _loop

            def _func(self, node):
                saved, self.loop_depth = self.loop_depth, 0
                self.generic_visit(node)
                self.loop_depth = saved

            visit_FunctionDef = _func
            visit_AsyncFunctionDef = _func

            def visit_Call(self, node):
                if self.loop_depth:
                    f = node.func
                    sync = None
                    if isinstance(f, ast.Attribute):
                        if f.attr in _SYNC_ATTRS and not node.args:
                            sync = f".{f.attr}()"
                        elif (_call_root(f), f.attr) in _SYNC_CALLS:
                            sync = ast.unparse(f) + "()"
                    if sync is not None:
                        findings.append(Finding(
                            "", node.lineno, rule.code,
                            f"{sync} inside a Python-level loop forces one "
                            "host-device sync per iteration, serializing "
                            "the dispatch pipeline; hoist the transfer out "
                            "of the loop and index the host copy"))
                self.generic_visit(node)

        V().visit(tree)
        yield from findings


class PallasContract(Rule):
    code = "RPR008"
    name = "pallas-no-contract"
    scope = "repro/kernels"

    def check(self, tree: ast.Module, path: str) -> Iterator[Finding]:
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            launches = any(
                isinstance(sub, ast.Call) and _callee_name(sub) == "pallas_call"
                for sub in ast.walk(node))
            raises = any(isinstance(sub, ast.Raise) for sub in ast.walk(node))
            if launches and not raises:
                yield Finding(
                    "", node.lineno, self.code,
                    f"{node.name}() launches pallas_call with no explicit "
                    "argument-contract check: a bad shape/dtype dies as an "
                    "opaque Mosaic lowering error; validate operands and "
                    "raise at entry")


RULES: Sequence[Rule] = (MutableDefault(), BareAssert(),
                         ServeConfigValidated(), JnpInLoop(),
                         MetricsSurfaced(), JitInHotPath(),
                         HostSyncInLoop(), PallasContract(),
                         ParamsValidated())


def _iter_files(paths: Sequence[str]) -> Iterator[Path]:
    for p in paths:
        path = Path(p)
        if path.is_dir():
            yield from sorted(path.rglob("*.py"))
        else:
            yield path


# "# rpr: noqa" (all rules) or "# rpr: noqa[RPR002,RPR004]" (those only)
_NOQA_RE = re.compile(r"#\s*rpr:\s*noqa(?:\[([A-Za-z0-9_,\s-]+)\])?")


def _suppressions(source: str) -> Dict[int, Optional[Set[str]]]:
    """line number -> suppressed codes (None = every rule)."""
    sup: Dict[int, Optional[Set[str]]] = {}
    for i, line in enumerate(source.splitlines(), 1):
        m = _NOQA_RE.search(line)
        if m:
            codes = m.group(1)
            sup[i] = (None if codes is None else
                      {c.strip().upper() for c in codes.split(",") if c.strip()})
    return sup


def _suppressed(f: Finding, sup: Dict[int, Optional[Set[str]]]) -> bool:
    codes = sup.get(f.line, ())
    return codes is None or f.code in codes


def lint_paths(paths: Sequence[str],
               select: Optional[Sequence[str]] = None,
               ignore: Optional[Sequence[str]] = None) -> List[Finding]:
    rules = [r for r in RULES if select is None or r.code in select
             or r.name in select]
    if ignore:
        rules = [r for r in rules
                 if r.code not in ignore and r.name not in ignore]
    findings: List[Finding] = []
    for file in _iter_files(paths):
        rel = str(file)
        try:
            source = file.read_text()
            tree = ast.parse(source, filename=rel)
        except SyntaxError as e:
            findings.append(Finding(rel, e.lineno or 0, "RPR000",
                                    f"syntax error: {e.msg}"))
            continue
        sup = _suppressions(source)
        for rule in rules:
            if not rule.applies(rel):
                continue
            findings.extend(f._replace(path=rel)
                            for f in rule.check(tree, rel)
                            if not _suppressed(f, sup))
    findings.sort(key=lambda f: (f.path, f.line, f.code))
    return findings


def _render_github(f: Finding) -> str:
    """GitHub Actions workflow annotation (shows inline on the PR diff)."""
    message = f.message.replace("%", "%25").replace("\n", "%0A")
    return (f"::error file={f.path},line={f.line},"
            f"title={f.code}::{message}")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="repo-specific AST lint (see module docstring for rules)")
    ap.add_argument("paths", nargs="+", help="files or directories to lint")
    ap.add_argument("--select", default=None,
                    help="comma-separated rule codes/names to run "
                         "(default: all)")
    ap.add_argument("--ignore", default=None,
                    help="comma-separated rule codes/names to skip")
    ap.add_argument("--format", default="text", choices=("text", "github"),
                    help="text: path:line: CODE message; github: workflow "
                         "annotations for the CI lint job")
    args = ap.parse_args(argv)
    select = args.select.split(",") if args.select else None
    ignore = args.ignore.split(",") if args.ignore else None
    findings = lint_paths(args.paths, select, ignore)
    for f in findings:
        print(_render_github(f) if args.format == "github" else f.render())
    n_files = sum(1 for _ in _iter_files(args.paths))
    print(f"{len(findings)} finding(s) in {n_files} file(s) "
          f"[{', '.join(r.code for r in RULES)}]")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
