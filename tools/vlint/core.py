"""vlint core: file discovery, suppression handling, check dispatch.

The checks encode invariants no off-the-shelf linter knows about —
JAX purity inside jitted programs, donated-buffer discipline, the
server's threading model, listener config plumbing, and the native
bridge's parity contract with the Python fallback decoder. Each check
is a pure function over parsed sources; nothing here imports jax or
numpy, so the whole tool runs in milliseconds as a tier-1 gate.

Suppression syntax (same line, or alone on the line above):

    # vlint: disable=JX03 reason=warmup must block before serving
    // vlint: disable=NA01 reason=pointer proven non-null by framing

A suppression without a reason does not suppress — it is itself
reported as VL00, so undocumented escapes cannot accumulate.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field


@dataclass(frozen=True)
class Violation:
    path: str          # as given (normalised to posix separators)
    line: int          # 1-based
    rule: str          # "JX01", ...
    message: str

    def __str__(self):
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


@dataclass
class PyModule:
    """One parsed Python source file."""
    path: str
    source: str
    lines: list[str]
    tree: ast.AST


@dataclass
class NativeFile:
    """One C/C++ source file (line-based checks only)."""
    path: str
    source: str
    lines: list[str]


@dataclass
class Project:
    """Everything the cross-file checks need, parsed once."""
    py_modules: list[PyModule] = field(default_factory=list)
    native_files: list[NativeFile] = field(default_factory=list)
    # syntax errors surface as violations instead of crashing the gate
    errors: list[Violation] = field(default_factory=list)


_PY_EXT = (".py",)
_NATIVE_EXT = (".cpp", ".cc", ".cxx", ".h", ".hpp")

_SUPPRESS_RE = re.compile(
    r"(?:#|//)\s*vlint:\s*disable=(?P<rules>[A-Z]{2}\d{2}"
    r"(?:\s*,\s*[A-Z]{2}\d{2})*)(?P<rest>[^\n]*)")
_REASON_RE = re.compile(r"\breason=(?P<reason>\S.*)")


def _norm(path: str) -> str:
    return path.replace(os.sep, "/")


def discover(paths: list[str]) -> list[str]:
    """Expand files/directories into a sorted list of lintable files."""
    out = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if d not in ("__pycache__", "build",
                                              ".git"))
                for f in sorted(files):
                    if f.endswith(_PY_EXT + _NATIVE_EXT):
                        out.append(os.path.join(root, f))
        elif os.path.isfile(p):
            out.append(p)
        else:
            raise FileNotFoundError(p)
    return out


def load_project(files: list[str]) -> Project:
    proj = Project()
    for path in files:
        with open(path, "r", encoding="utf-8", errors="replace") as fh:
            source = fh.read()
        lines = source.splitlines()
        npath = _norm(path)
        if path.endswith(_PY_EXT):
            try:
                tree = ast.parse(source, filename=path)
            except SyntaxError as e:
                proj.errors.append(Violation(
                    npath, e.lineno or 1, "VL01",
                    f"syntax error: {e.msg}"))
                continue
            proj.py_modules.append(PyModule(npath, source, lines, tree))
        else:
            proj.native_files.append(NativeFile(npath, source, lines))
    return proj


# ---------------------------------------------------------------- suppression

def _suppressions(lines: list[str]):
    """Map line number -> (set of suppressed rules) plus VL00 findings
    for suppressions that carry no reason. A suppression comment applies
    to its own line; a line containing ONLY the suppression comment
    applies to the next line as well (for lines with no comment room)."""
    by_line: dict[int, set] = {}
    bad: list[tuple[int, str]] = []
    for i, text in enumerate(lines, start=1):
        m = _SUPPRESS_RE.search(text)
        if not m:
            continue
        rules = {r.strip() for r in m.group("rules").split(",")}
        if not _REASON_RE.search(m.group("rest")):
            bad.append((i, ",".join(sorted(rules))))
            continue
        by_line.setdefault(i, set()).update(rules)
        stripped = text.strip()
        if stripped.startswith(("#", "//")):
            # comment-only suppression: applies to the next code line,
            # skipping the rest of its own comment block (and blanks)
            j = i
            while j < len(lines) and (
                    not lines[j].strip()
                    or lines[j].strip().startswith(("#", "//"))):
                j += 1
            by_line.setdefault(j + 1, set()).update(rules)
    return by_line, bad


def apply_suppressions(path: str, lines: list[str],
                       violations: list[Violation]) -> list[Violation]:
    by_line, bad = _suppressions(lines)
    out = [v for v in violations
           if v.rule not in by_line.get(v.line, ())]
    for lineno, rules in bad:
        out.append(Violation(
            path, lineno, "VL00",
            f"suppression of {rules} has no reason= — every disable "
            "must document why the violation is intentional"))
    return out


# ---------------------------------------------------------------- AST helpers

def dotted(node: ast.AST) -> str | None:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def is_jit_expr(node: ast.AST) -> bool:
    """Does this expression evaluate to jax.jit (possibly via
    functools.partial(jax.jit, ...))?"""
    d = dotted(node)
    if d in ("jax.jit", "jit"):
        return True
    if isinstance(node, ast.Call):
        fd = dotted(node.func)
        if fd in ("functools.partial", "partial") and node.args:
            return is_jit_expr(node.args[0])
    return False


def jit_call_keywords(node: ast.AST) -> list[ast.keyword]:
    """Keywords attached to a jit expression (partial(jax.jit, **kw) or
    the jit call itself)."""
    if isinstance(node, ast.Call):
        kws = list(node.keywords)
        fd = dotted(node.func)
        if fd in ("functools.partial", "partial") and node.args:
            kws += jit_call_keywords(node.args[0])
        return kws
    return []


def literal_ints(node: ast.AST) -> list[int] | None:
    """Evaluate a donate_argnums value: int or tuple/list of ints."""
    try:
        v = ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return None
    if isinstance(v, int):
        return [v]
    if isinstance(v, (tuple, list)) and all(
            isinstance(x, int) for x in v):
        return list(v)
    return None


def literal_strs(node: ast.AST) -> list[str] | None:
    try:
        v = ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return None
    if isinstance(v, str):
        return [v]
    if isinstance(v, (tuple, list)) and all(
            isinstance(x, str) for x in v):
        return list(v)
    return None


def param_names(fn: ast.FunctionDef | ast.AsyncFunctionDef
                | ast.Lambda) -> list[str]:
    a = fn.args
    return ([p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
            + [p.arg for p in a.kwonlyargs])


def int_expr(node: ast.AST) -> int | None:
    """A module-level whole number written as literals joined by `*`,
    `+` or `<<` (16 * 1024 * 1024), or None."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return int(node.value)
    if isinstance(node, ast.BinOp) and isinstance(
            node.op, (ast.Mult, ast.Add, ast.LShift)):
        a, b = int_expr(node.left), int_expr(node.right)
        if a is None or b is None:
            return None
        return (a * b if isinstance(node.op, ast.Mult)
                else a + b if isinstance(node.op, ast.Add) else a << b)
    return None


# ---------------------------------------------------------------- runner

def run_project(proj: Project, config: dict) -> list[Violation]:
    # imported here to avoid a cycle (checks import core helpers)
    from . import native_checks, py_checks

    violations = list(proj.errors)
    ctx = py_checks.build_context(proj, config)
    for mod in proj.py_modules:
        found = py_checks.check_module(mod, ctx, config)
        violations.extend(apply_suppressions(mod.path, mod.lines, found))
    for nf in proj.native_files:
        found = native_checks.check_file(nf, ctx, config)
        violations.extend(apply_suppressions(nf.path, nf.lines, found))
    return sorted(violations, key=lambda v: (v.path, v.line, v.rule))


def run_paths(paths: list[str], config: dict | None = None
              ) -> list[Violation]:
    """Public API: lint files/directories, return sorted violations."""
    from .config import DEFAULT_CONFIG
    cfg = dict(DEFAULT_CONFIG)
    if config:
        cfg.update(config)
    return run_project(load_project(discover(paths)), cfg)
