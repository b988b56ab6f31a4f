"""Line-based C++ passes for native/vtpu_ingest.cpp: NA01 to NA05.

These are deliberately regex-level — the native bridge is one file of
C-with-classes and the two defect classes it has actually shipped
(nullptr .assign(), parity-diverging recursion caps) are recognisable
from surface syntax. A real C++ frontend would be overkill for a
tier-1 gate that must run in milliseconds with no extra deps.
"""

from __future__ import annotations

import ast
import re

from .core import NativeFile, Violation, int_expr

# const uint8_t *k = nullptr, *v = nullptr;   (captures each name)
_NULLPTR_DECL_RE = re.compile(r"\*\s*(\w+)\s*=\s*nullptr\b")
# later rebinding that clears the nullptr taint: k = <something>;
_REBIND_RE = re.compile(r"(?:^|[^\w.>])%s\s*=\s*(?!nullptr)[^=]")
# .assign(reinterpret_cast<const char*>(k), kn)  /  ->assign(...)
_ASSIGN_RE = re.compile(
    r"(?:\.|->)assign\(\s*reinterpret_cast<[^>]*>\(\s*(\w+)\s*\)")
# a guard that proves the pointer was examined: if (k), if (!k), k ?,
# k != nullptr, k == nullptr
_GUARD_TEMPLATES = (
    r"if\s*\(\s*!?\s*{p}\s*[)&|]",
    r"\b{p}\s*\?",
    r"\b{p}\s*[!=]=\s*nullptr",
    r"\bnullptr\s*[!=]=\s*{p}\b",
)

_DEPTH_CAP_RE = re.compile(r"\bdepth\s*>=?\s*(\w+)")
_CONST_DEF_RE = re.compile(
    r"\bconstexpr\s+(?:int|size_t|unsigned|long)\s+(\w+)\s*=\s*(\d+)")


def _brace_depth_per_line(lines):
    """Cumulative brace depth AFTER each line (comments/strings are not
    stripped — good enough for this codebase's formatting)."""
    depth = 0
    out = []
    for text in lines:
        # ignore braces in line comments
        code = text.split("//", 1)[0]
        depth += code.count("{") - code.count("}")
        out.append(depth)
    return out


def check_na01(nf: NativeFile) -> list[Violation]:
    """nullptr-reachable .assign(): a pointer initialised to nullptr in
    the current function and passed to string::assign() without any
    intervening null check. assign(nullptr, 0) is UB even though
    mainstream stdlibs tolerate it."""
    out = []
    depths = _brace_depth_per_line(nf.lines)
    tracked: dict = {}   # name -> (decl line 1-based, decl brace depth)
    for i, text in enumerate(nf.lines):
        lineno = i + 1
        # drop pointers whose enclosing scope has closed
        for name, (_dl, dd) in list(tracked.items()):
            if depths[i] < dd:
                tracked.pop(name)
        for m in _NULLPTR_DECL_RE.finditer(text):
            tracked[m.group(1)] = (lineno, depths[i])
        for name in list(tracked):
            if re.search(_REBIND_RE.pattern % re.escape(name), text) \
                    and "nullptr" not in text:
                # direct rebinding does not prove non-null (maybe(&k)
                # style writes go through &k, which we keep tainted) —
                # only drop the taint for `k = <expr>;` assignments
                tracked.pop(name, None)
        m = _ASSIGN_RE.search(text)
        if not m:
            continue
        p = m.group(1)
        if p not in tracked:
            continue
        decl = tracked[p][0]
        window = "\n".join(nf.lines[decl - 1:lineno])
        guarded = any(
            re.search(t.format(p=re.escape(p)), window)
            for t in _GUARD_TEMPLATES)
        if not guarded:
            out.append(Violation(
                nf.path, lineno, "NA01",
                f"`{p}` can still be nullptr here (initialised to "
                f"nullptr on line {decl}, never null-checked) — "
                ".assign(nullptr, n) is undefined behaviour; guard "
                "the pointer"))
    return out


def check_na02(nf: NativeFile, ctx, config: dict) -> list[Violation]:
    """Recursion-cap parity with the Python fallback decoder. The
    depth cap in PbReader::skip must (a) be a named constant, not a
    magic literal, and (b) equal the Python-side parity constant
    (PB_SKIP_MAX_DEPTH in ssf/framing.py) so the two decoders draw the
    fallback boundary at the same depth."""
    out = []
    consts = {}
    for i, text in enumerate(nf.lines):
        for m in _CONST_DEF_RE.finditer(text):
            consts[m.group(1)] = (int(m.group(2)), i + 1)
    py_name = config["na02_py_constant"]
    for i, text in enumerate(nf.lines):
        m = _DEPTH_CAP_RE.search(text.split("//", 1)[0])
        if not m:
            continue
        lineno = i + 1
        cap = m.group(1)
        if cap.isdigit():
            out.append(Violation(
                nf.path, lineno, "NA02",
                f"magic recursion cap {cap} — name it (constexpr) and "
                f"mirror it as {py_name} beside the Python fallback "
                "decoder so the parity boundary has one definition"))
            continue
        if cap not in consts:
            continue   # named elsewhere (another TU); nothing to prove
        value = consts[cap][0]
        if ctx.na02_value is None:
            out.append(Violation(
                nf.path, lineno, "NA02",
                f"recursion cap {cap}={value} has no Python-side "
                f"{py_name} constant in the scanned tree — the native "
                "and fallback decoders must share the boundary"))
        elif ctx.na02_value != value:
            out.append(Violation(
                nf.path, lineno, "NA02",
                f"recursion cap {cap}={value} diverges from "
                f"{py_name}={ctx.na02_value} ({ctx.na02_path}) — the "
                "native parser and the Python fallback decoder draw "
                "the fallback boundary at different depths"))
    return out


# constexpr <type> kName = <whole numbers, *, <<, +, 0x..>;
_CONST_EXPR_RE = re.compile(
    r"\bconstexpr\s+[\w:]+\s+(\w+)\s*=\s*([0-9a-fA-Fx\s*+<()]+);")


def _constexprs(nf: NativeFile, names) -> dict:
    """name -> (value, line) of the file's whole-number constexprs
    among `names`."""
    found = {}
    for i, text in enumerate(nf.lines):
        for m in _CONST_EXPR_RE.finditer(text.split("//", 1)[0]):
            if m.group(1) in names:
                value = int_expr(ast.parse(m.group(2).strip(),
                                           mode="eval").body)
                if value is not None:
                    found[m.group(1)] = (value, i + 1)
    return found


def check_na03(nf: NativeFile, ctx, config: dict) -> list[Violation]:
    """Frame-layout parity of an SSF stream. The bridge cuts framed
    streams itself, so each constant of the layout exists twice: here
    and in ssf/framing.py, which the Python loop, the clients and the
    tests use. Each native constant named in `na03_pairs` must equal
    its Python twin, and a file that defines one of them defines all."""
    pairs = config["na03_pairs"]
    found = _constexprs(nf, pairs)
    if not found:
        return []
    out = []
    first = min(line for _v, line in found.values())
    for cpp_name, py_name in pairs.items():
        if cpp_name not in found:
            out.append(Violation(
                nf.path, first, "NA03",
                f"the frame layout is defined here without {cpp_name} "
                f"(the twin of {py_name}): all of "
                f"{', '.join(pairs)} belong together"))
            continue
        value, lineno = found[cpp_name]
        twin = ctx.na03_values.get(py_name)
        if twin is None:
            out.append(Violation(
                nf.path, lineno, "NA03",
                f"{cpp_name}={value} has no Python-side {py_name} in the "
                "scanned tree: the native stream reader and "
                "ssf/framing.py must cut frames by one layout"))
        elif twin[0] != value:
            out.append(Violation(
                nf.path, lineno, "NA03",
                f"{cpp_name}={value} diverges from {py_name}={twin[0]} "
                f"({twin[1]}): the native stream reader and the Python "
                "frame loop would cut the same bytes into different "
                "frames"))
    return out


_STATS_FN_RE = re.compile(r"^void\s+vtpu_stats\s*\(")
# out[7] = ...;   out[20 + i] = ...;   (a loop over the banks)
_STATS_OUT_RE = re.compile(r"\bout\[\s*(\d+)\s*(\+\s*i\s*)?\]\s*=")


def check_na04(nf: NativeFile, ctx, config: dict) -> list[Violation]:
    """Layout parity of the bridge's stats array. `vtpu_stats` fills a
    caller's array by index and `NativeBridge.stats()` names the fields
    by position, so a field added on one side alone shifts or drops
    silently. The native constant of `na04_pairs` (the array's length)
    must equal its Python twin, which sizes the array and is held to
    the names' count where they are zipped, and must be one more than
    the highest index `vtpu_stats` writes (`out[n + i]` counting a loop
    over NUM_BANKS)."""
    out = []
    consts = _constexprs(nf, set(config["na04_pairs"]) | {"NUM_BANKS"})
    for cpp_name, py_name in config["na04_pairs"].items():
        if cpp_name not in consts:
            continue
        value, lineno = consts[cpp_name]
        twin = ctx.na03_values.get(py_name)
        if twin is None:
            out.append(Violation(
                nf.path, lineno, "NA04",
                f"{cpp_name}={value} has no Python-side {py_name} in the "
                "scanned tree: the stats array is filled by index here "
                "and named by position there"))
        elif twin[0] != value:
            out.append(Violation(
                nf.path, lineno, "NA04",
                f"{cpp_name}={value} diverges from {py_name}={twin[0]} "
                f"({twin[1]}): a stats field exists on one side only"))
        banks = consts.get("NUM_BANKS", (1, 0))[0]
        written, inside = [], False
        for text in nf.lines:
            if _STATS_FN_RE.match(text):
                inside = True
            elif inside and text.startswith("}"):
                break
            if inside:
                written += [int(n) + (banks - 1 if loop else 0)
                            for n, loop in _STATS_OUT_RE.findall(
                                text.split("//", 1)[0])]
        if written and max(written) + 1 != value:
            out.append(Violation(
                nf.path, lineno, "NA04",
                f"vtpu_stats writes up to out[{max(written)}] and "
                f"{cpp_name} is {value}"))
    return out


# LocalStage st;   thread_local LocalStage st;
_STAGE_DECL_RE = re.compile(r"\bLocalStage\s+(\w+)\s*;")
# handle_buffer(br, &st, ...)   handle_ssf(br, &st, ...)
_STAGE_PARSE = r"\bhandle_(?:buffer|ssf)\(\s*\w+\s*,\s*&%s\b"
_STAGE_STAMP = r"\b%s\.order\s*="


def check_na05(nf: NativeFile) -> list[Violation]:
    """Arrival stamp before staging. A gauge is its last write by the
    bridge-wide arrival order, which a gauge sample carries from its
    stage's `order` (stage_parsed reads it, for statsd lines and SSF
    samples alike). So every function that owns a LocalStage and hands
    it to handle_buffer or handle_ssf sets `<stage>.order` on an
    earlier line of the stage's scope: a new transport that forgets
    stages every gauge under the last datagram's number, or 0."""
    out = []
    depths = _brace_depth_per_line(nf.lines)
    for i, text in enumerate(nf.lines):
        m = _STAGE_DECL_RE.search(text.split("//", 1)[0])
        if m is None:
            continue
        parse = re.compile(_STAGE_PARSE % m.group(1))
        stamp = re.compile(_STAGE_STAMP % m.group(1))
        stamped = False
        for j in range(i + 1, len(nf.lines)):
            if depths[j] < depths[i]:
                break       # the stage's scope has closed
            code = nf.lines[j].split("//", 1)[0]
            stamped = stamped or bool(stamp.search(code))
            if parse.search(code) and not stamped:
                out.append(Violation(
                    nf.path, j + 1, "NA05",
                    f"{m.group(1)} is parsed into before its `order` is "
                    "set: gauges staged here carry no arrival number of "
                    "their own, and last-write-wins across readers "
                    "breaks"))
    return out


def check_file(nf: NativeFile, ctx, config: dict) -> list[Violation]:
    return (check_na01(nf) + check_na02(nf, ctx, config)
            + check_na03(nf, ctx, config) + check_na04(nf, ctx, config)
            + check_na05(nf))
