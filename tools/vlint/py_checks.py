"""Python AST passes: JX01, JX02, JX03, TH01, CF01, RS01, SR02, DR01,
DR02, TL01, OV01, SK01, DS01, QT01, PK01, GC01.

All checks are intentionally conservative: they resolve only what can
be resolved statically within the project (local jit wrappers, module
level donating jits reached through import aliases, intra-class call
graphs) and stay silent where they cannot prove a binding. The goal is
a zero-false-positive tier-1 gate, not exhaustive inference — the
check-specific limits are documented in tools/vlint/README.md.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field

from .core import (PyModule, Project, Violation, dotted, int_expr,
                   is_jit_expr,
                   jit_call_keywords, literal_ints, literal_strs,
                   param_names)

_SYNC_SUFFIXES = ("device_get", "block_until_ready", "copy_to_host_async")
_NP_LEAK_FNS = ("asarray", "array", "frombuffer", "fromiter")
_CAST_BUILTINS = ("float", "int", "bool")


@dataclass
class Donating:
    """A callable known to donate arguments: positional indices and/or
    parameter names (either may be empty when unresolvable)."""
    positions: tuple = ()
    names: tuple = ()


@dataclass
class Context:
    """Cross-module facts, built once per run."""
    # method/function name -> parameter names (self/cls stripped) and
    # the set of params that carry defaults; first definition wins
    signatures: dict = field(default_factory=dict)
    # module basename -> {module-level callable name -> Donating}
    donating_modules: dict = field(default_factory=dict)
    # NA02: value of the Python-side recursion-cap parity constant
    na02_value: int | None = None
    na02_path: str | None = None
    # NA03, NA04: Python-side twins of native constants (the frame
    # layout, the stats array's length), name -> (value, path)
    na03_values: dict = field(default_factory=dict)


def _module_basename(path: str) -> str:
    return os.path.splitext(os.path.basename(path))[0]


def build_context(proj: Project, config: dict) -> Context:
    ctx = Context()
    const_name = config["na02_py_constant"]
    na03_names = (set(config["na03_pairs"].values())
                  | set(config["na04_pairs"].values()))
    for mod in proj.py_modules:
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                params = param_names(node)
                if params and params[0] in ("self", "cls"):
                    params = params[1:]
                ctx.signatures.setdefault(node.name, tuple(params))
        ctx.donating_modules[_module_basename(mod.path)] = \
            _module_donating(mod.tree)
        for node in mod.tree.body:
            if (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id == const_name
                    and isinstance(node.value, ast.Constant)
                    and isinstance(node.value.value, int)):
                ctx.na02_value = node.value.value
                ctx.na02_path = mod.path
            if (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id in na03_names):
                value = int_expr(node.value)
                if value is not None:
                    ctx.na03_values[node.targets[0].id] = (value, mod.path)
    return ctx


# ------------------------------------------------------------- jit discovery

def _np_aliases(tree: ast.AST) -> set:
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    out.add(a.asname or "numpy")
    return out


def _partial_jit_aliases(tree: ast.AST) -> dict:
    """Names bound to functools.partial(jax.jit, **kw): name -> the
    partial's keywords (donation/static config ride along)."""
    out = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call):
            v = node.value
            if dotted(v.func) in ("functools.partial", "partial") \
                    and v.args and is_jit_expr(v.args[0]):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out[t.id] = list(v.keywords)
    return out


def _jitted_functions(tree: ast.AST):
    """Every FunctionDef/Lambda the module jit-compiles: via decorator,
    via jax.jit(fn, ...)/partial(jax.jit, ...)(fn) call, or via a
    partial-jit alias applied to a def/lambda."""
    defs_by_name: dict = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(node.name, []).append(node)
    jitted = []
    for fns in defs_by_name.values():
        for fn in fns:
            if any(is_jit_expr(dec) for dec in fn.decorator_list):
                jitted.append(fn)
    aliases = _partial_jit_aliases(tree)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        is_jit_call = is_jit_expr(node.func)
        is_alias_call = (isinstance(node.func, ast.Name)
                         and node.func.id in aliases)
        if not (is_jit_call or is_alias_call) or not node.args:
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Lambda):
            jitted.append(arg)
        else:
            d = dotted(arg)
            if d is not None:
                jitted.extend(defs_by_name.get(d.split(".")[-1], ()))
    # dedupe, preserve order
    seen, out = set(), []
    for fn in jitted:
        if id(fn) not in seen:
            seen.add(id(fn))
            out.append(fn)
    return out


# ------------------------------------------------------------------- JX01

def check_jx01(mod: PyModule) -> list[Violation]:
    """Tracer leaks: host-forcing calls inside jit-compiled functions.
    `.item()`/`.tolist()` and numpy materialisation are flagged
    unconditionally; float()/int()/bool() only when their argument
    references a traced parameter (static shape math like
    int(math.ceil(...)) over closure config is legal and common)."""
    out = []
    np_names = _np_aliases(mod.tree)
    flagged = set()
    for fn in _jitted_functions(mod.tree):
        params = set(param_names(fn))
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            key = (node.lineno, node.col_offset)
            if key in flagged:
                continue
            f = node.func
            if isinstance(f, ast.Attribute) and not node.args \
                    and f.attr in ("item", "tolist"):
                flagged.add(key)
                out.append(Violation(
                    mod.path, node.lineno, "JX01",
                    f".{f.attr}() inside a jitted function forces a "
                    "host sync per trace and breaks under jit — "
                    "compute on-device instead"))
                continue
            d = dotted(f)
            if d and "." in d:
                root, leaf = d.split(".", 1)[0], d.rsplit(".", 1)[-1]
                if root in np_names and leaf in _NP_LEAK_FNS:
                    flagged.add(key)
                    out.append(Violation(
                        mod.path, node.lineno, "JX01",
                        f"{d}() materialises a tracer to host numpy "
                        "inside a jitted function — use jnp"))
                    continue
            if isinstance(f, ast.Name) and f.id in _CAST_BUILTINS \
                    and node.args:
                refs = {n.id for a in node.args
                        for n in ast.walk(a) if isinstance(n, ast.Name)}
                if refs & params:
                    flagged.add(key)
                    out.append(Violation(
                        mod.path, node.lineno, "JX01",
                        f"{f.id}() applied to a traced argument inside "
                        "a jitted function concretises the tracer — "
                        "keep it as an array"))
    return out


# ------------------------------------------------------------------- JX02

def _donating_from_assign(node: ast.Assign, defs_by_name: dict,
                          aliases: dict) -> Donating | None:
    """X = jax.jit(f, donate_*=...) / partial(jax.jit, donate_*=..)(f)
    / alias(f) where alias is a partial-jit with donation."""
    v = node.value
    if not isinstance(v, ast.Call) or not v.args:
        return None
    kws = []
    if is_jit_expr(v.func):
        kws = list(v.keywords) + jit_call_keywords(v.func)
    elif isinstance(v.func, ast.Name) and v.func.id in aliases:
        kws = list(v.keywords) + list(aliases[v.func.id])
    else:
        return None
    return _donation_of(kws, v.args[0], defs_by_name)


def _donation_of(kws, wrapped, defs_by_name) -> Donating | None:
    positions, names = [], []
    for kw in kws:
        if kw.arg == "donate_argnums":
            positions.extend(literal_ints(kw.value) or ())
        elif kw.arg == "donate_argnames":
            names.extend(literal_strs(kw.value) or ())
    if not positions and not names:
        return None
    # resolve names -> positions when the wrapped def is in reach
    fn = None
    if isinstance(wrapped, ast.Lambda):
        fn = wrapped
    else:
        d = dotted(wrapped) if wrapped is not None else None
        if d is not None:
            cands = defs_by_name.get(d.split(".")[-1])
            fn = cands[0] if cands else None
    if fn is not None:
        plist = param_names(fn)
        for n in names:
            if n in plist and plist.index(n) not in positions:
                positions.append(plist.index(n))
    return Donating(tuple(sorted(set(positions))), tuple(names))


def _module_donating(tree: ast.AST) -> dict:
    """Module-level callables that donate: decorated defs and
    module-level assigns of donating jit wrappers."""
    defs_by_name: dict = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(node.name, []).append(node)
    aliases = _partial_jit_aliases(tree)
    out: dict = {}
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if is_jit_expr(dec):
                    don = _donation_of(jit_call_keywords(dec), node,
                                       defs_by_name)
                    if don:
                        out[node.name] = don
        elif isinstance(node, ast.Assign):
            don = _donating_from_assign(node, defs_by_name, aliases)
            if don:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out[t.id] = don
    return out


def _import_aliases(tree: ast.AST) -> dict:
    """Local name -> imported module basename (for resolving
    alias.func() against the cross-module donation table)."""
    out = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                base = a.name.rsplit(".", 1)[-1]
                out[a.asname or a.name.split(".")[0]] = base
        elif isinstance(node, ast.ImportFrom):
            for a in node.names:
                out[a.asname or a.name] = a.name
    return out


def _parent_map(tree: ast.AST) -> dict:
    parents = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


def _enclosing(node, parents, kinds):
    cur = parents.get(node)
    while cur is not None and not isinstance(cur, kinds):
        cur = parents.get(cur)
    return cur


def check_jx02(mod: PyModule, ctx: Context) -> list[Violation]:
    """Donation-use-after-dispatch: an argument expression passed in a
    donated position must not be read again in the same scope after the
    call, unless the call statement itself rebinds it. Tracks local
    wrappers (`f = jax.jit(g, donate_argnums=(0,))`), decorated defs,
    and imported module-level donating jits (`tdigest.compress`)."""
    tree = mod.tree
    defs_by_name: dict = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(node.name, []).append(node)
    aliases = _partial_jit_aliases(tree)
    imports = _import_aliases(tree)
    local: dict = dict(_module_donating(tree))
    # function-local wrapper assigns (any depth), incl. self.attr targets
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            don = _donating_from_assign(node, defs_by_name, aliases)
            if don:
                for t in node.targets:
                    d = dotted(t)
                    if d:
                        local[d] = don
    # decorated defs at class level too
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if is_jit_expr(dec):
                    don = _donation_of(jit_call_keywords(dec), node,
                                       defs_by_name)
                    if don:
                        local.setdefault(node.name, don)

    parents = _parent_map(tree)
    out = []
    for call in ast.walk(tree):
        if not isinstance(call, ast.Call):
            continue
        d = dotted(call.func)
        don = None
        callee_params = None
        if d in local:
            don = local[d]
        elif d and "." in d:
            root, leaf = d.split(".", 1)[0], d.rsplit(".", 1)[-1]
            table = ctx.donating_modules.get(imports.get(root, ""))
            if table and leaf in table:
                don = table[leaf]
                sig = ctx.signatures.get(leaf)
                callee_params = list(sig) if sig else None
        if don is None:
            continue
        donated_exprs = []
        for pos in don.positions:
            if pos < len(call.args):
                donated_exprs.append(call.args[pos])
        for name in don.names:
            for kw in call.keywords:
                if kw.arg == name:
                    donated_exprs.append(kw.value)
            if callee_params and name in callee_params:
                i = callee_params.index(name)
                if i < len(call.args) and i not in don.positions:
                    donated_exprs.append(call.args[i])
        for expr in donated_exprs:
            target = dotted(expr)
            if target is None:
                continue
            v = _read_after_donation(call, target, parents)
            if v is not None:
                out.append(Violation(
                    mod.path, v, "JX02",
                    f"`{target}` was donated to `{d}` and is read "
                    "again before being rebound — the buffer is dead "
                    "after dispatch (donate_argnums)"))
    # dedupe
    seen, uniq = set(), []
    for v in out:
        k = (v.line, v.message)
        if k not in seen:
            seen.add(k)
            uniq.append(v)
    return uniq


def _read_after_donation(call, target: str, parents) -> int | None:
    """Line of the first read of `target` after `call` in the enclosing
    scope, before any rebinding store. None if rebound first (or the
    call statement itself rebinds it)."""
    stmt = _enclosing(call, parents, ast.stmt)
    if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
        targets = stmt.targets if isinstance(stmt, ast.Assign) \
            else [stmt.target]
        if any(dotted(t) == target for t in targets):
            return None   # rebound by the dispatch statement
    scope = _enclosing(call, parents,
                       (ast.FunctionDef, ast.AsyncFunctionDef,
                        ast.Lambda, ast.Module))
    if scope is None:
        return None
    call_end = (call.end_lineno, call.end_col_offset)
    call_start = (call.lineno, call.col_offset)
    events = []
    for node in ast.walk(scope):
        if not isinstance(node, (ast.Name, ast.Attribute)):
            continue
        d = dotted(node)
        if d is None:
            continue
        pos = (node.lineno, node.col_offset)
        if call_start <= pos <= call_end:
            continue   # part of the dispatch expression itself
        if isinstance(node.ctx, ast.Store):
            if d == target:
                events.append((pos, "store"))
        elif isinstance(node.ctx, ast.Load):
            if d == target or d.startswith(target + "."):
                events.append((pos, "load"))
    events.sort()
    for pos, kind in events:
        if pos <= call_end:
            continue
        if kind == "store":
            return None
        return pos[0]
    return None


# ------------------------------------------------------------------- JX03

def check_jx03(mod: PyModule, config: dict) -> list[Violation]:
    """Host synchronisation outside the flush/fetch layer. device_get /
    block_until_ready / copy_to_host_async stall the dispatch
    pipeline; every legitimate sync point lives in the allowlisted modules or carries an
    inline suppression explaining itself."""
    if any(mod.path.endswith(a) for a in config["jx03_allow"]):
        return []
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        if d and d.rsplit(".", 1)[-1] in _SYNC_SUFFIXES:
            fn = d.rsplit(".", 1)[-1]
            out.append(Violation(
                mod.path, node.lineno, "JX03",
                f"{fn}() outside the flush/fetch modules — host sync "
                "in serving code stalls the dispatch pipeline; move it "
                "into the flush module (models/pipeline.py) or suppress with "
                "a reason"))
    return out


# ------------------------------------------------------------------- TH01

def _lockish(expr: ast.AST) -> bool:
    d = dotted(expr)
    if d is None and isinstance(expr, ast.Call):
        d = dotted(expr.func)
    return bool(d) and "lock" in d.lower()


def check_th01(mod: PyModule, config: dict) -> list[Violation]:
    """Unguarded shared-state writes: in the threaded server files, a
    method reachable from two or more thread roots (thread targets +
    public entry points) must hold a lock around writes to self.*
    state. Methods named *_locked run under the caller's lock by
    project convention."""
    if os.path.basename(mod.path) not in config["th01_files"]:
        return []
    out = []
    suffixes = tuple(config["th01_locked_suffixes"])
    for cls in ast.walk(mod.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        methods = {n.name: n for n in cls.body
                   if isinstance(n, ast.FunctionDef)}
        edges: dict = {m: set() for m in methods}
        targets = set()
        for mname, fn in methods.items():
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                d = dotted(node.func)
                if d and d.startswith("self.") and \
                        d.count(".") == 1 and d[5:] in methods:
                    edges[mname].add(d[5:])
                if d and d.rsplit(".", 1)[-1] == "Thread":
                    for kw in node.keywords:
                        if kw.arg == "target":
                            td = dotted(kw.value)
                            if td and td.startswith("self.") \
                                    and td[5:] in methods:
                                targets.add(td[5:])
        roots = targets | {m for m in methods if not m.startswith("_")}
        reached_by: dict = {m: set() for m in methods}
        for root in roots:
            stack, seen = [root], set()
            while stack:
                cur = stack.pop()
                if cur in seen:
                    continue
                seen.add(cur)
                reached_by[cur].add(root)
                stack.extend(edges.get(cur, ()))
        for mname, fn in methods.items():
            if mname == "__init__" or mname.endswith(suffixes):
                continue
            if len(reached_by[mname]) < 2:
                continue
            out.extend(_th01_writes(mod.path, mname, fn))
    return out


def _th01_writes(path: str, mname: str, fn: ast.FunctionDef
                 ) -> list[Violation]:
    out = []

    def self_attr_of(t):
        """self.X or self.X[...] target -> attribute name X."""
        if isinstance(t, ast.Subscript):
            t = t.value
        if isinstance(t, ast.Attribute) and \
                isinstance(t.value, ast.Name) and t.value.id == "self":
            return t.attr
        return None

    def visit(node, locked):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            locked = locked or any(_lockish(item.context_expr)
                                   for item in node.items)
        elif isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                attr = self_attr_of(t)
                if attr is not None and not locked:
                    out.append(Violation(
                        path, node.lineno, "TH01",
                        f"write to self.{attr} in `{mname}` — the "
                        "method is reachable from multiple threads "
                        "and the write is not under a lock"))
        for child in ast.iter_child_nodes(node):
            visit(child, locked)

    visit(fn, False)
    return out


# ------------------------------------------------------------------- CF01

def _cfg_fields(expr: ast.AST) -> set:
    """cfg field names referenced by an expression: cfg.X / self.cfg.X /
    anything.cfg.X."""
    out = set()
    for node in ast.walk(expr):
        if isinstance(node, ast.Attribute):
            base = dotted(node.value)
            if base is not None and (base == "cfg"
                                     or base.endswith(".cfg")):
                out.add(node.attr)
    return out


def check_cf01(mod: PyModule, ctx: Context, config: dict
               ) -> list[Violation]:
    """Config-plumbing parity: within a sibling family (same receiver,
    same method-name prefix), a cfg-derived value passed for parameter
    P at one call site must be passed at every sibling whose signature
    also accepts P — the exact class of the start_ssf_udp rcvbuf bug."""
    prefixes = tuple(config["cf01_prefixes"])
    groups: dict = {}
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call) or \
                not isinstance(node.func, ast.Attribute):
            continue
        recv = dotted(node.func.value)
        mname = node.func.attr
        if recv is None or mname.split("_")[0] not in prefixes:
            continue
        groups.setdefault((recv, mname.split("_")[0]), []).append(node)

    out = []
    for (recv, _prefix), calls in groups.items():
        if len(calls) < 2:
            continue
        bound = []   # (call, mname, params, {param: cfg_fields})
        for call in calls:
            mname = call.func.attr
            sig = ctx.signatures.get(mname)
            if sig is None:
                continue
            params = list(sig)
            binding: dict = {}
            for i, a in enumerate(call.args):
                if i < len(params):
                    f = _cfg_fields(a)
                    if f:
                        binding[params[i]] = f
            explicit = {params[i] for i in range(min(len(call.args),
                                                     len(params)))}
            for kw in call.keywords:
                if kw.arg is not None:
                    explicit.add(kw.arg)
                    f = _cfg_fields(kw.value)
                    if f:
                        binding[kw.arg] = f
            bound.append((call, mname, params, explicit, binding))
        for (ca, na, pa, ea, ba) in bound:
            for param, fields in ba.items():
                for (cb, nb, pb, eb, _bb) in bound:
                    if cb is ca or param not in pb or param in eb:
                        continue
                    fld = ",".join(sorted(fields))
                    out.append(Violation(
                        mod.path, cb.lineno, "CF01",
                        f"sibling `{recv}.{na}` passes cfg.{fld} as "
                        f"`{param}` but `{nb}` leaves it at its "
                        "default — config plumbing must reach every "
                        "sibling listener"))
    # dedupe (two siblings can each accuse the same omission)
    seen, uniq = set(), []
    for v in out:
        k = (v.line, v.message)
        if k not in seen:
            seen.add(k)
            uniq.append(v)
    return uniq


# ------------------------------------------------------------------- RS01

_RS01_GRPC_LEAVES = ("insecure_channel", "secure_channel")


def check_rs01(mod: PyModule, config: dict) -> list[Violation]:
    """Raw egress bypassing the resilience layer: a direct
    urllib.request.urlopen call or grpc channel construction anywhere
    but `veneur_tpu/resilience.py` (the layer's own transport) skips
    the retry/backoff/circuit-breaker treatment every network egress
    must receive. Route HTTP through Egress.post/fetch and channels
    through resilience.grpc_channel; intentional raw calls (e.g. the
    crash-path sentry reporter) carry an inline suppression."""
    if any(mod.path.endswith(a) for a in config["rs01_allow"]):
        return []
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        if d is None:
            continue
        leaf = d.rsplit(".", 1)[-1]
        if leaf == "urlopen":
            out.append(Violation(
                mod.path, node.lineno, "RS01",
                "raw urlopen() bypasses the egress-resilience layer "
                "(no retry/backoff, no circuit breaker, no deadline "
                "budget) — route through resilience.Egress.post/fetch "
                "or suppress with a reason"))
        elif leaf in _RS01_GRPC_LEAVES and (d == leaf
                                            or d.startswith("grpc.")):
            out.append(Violation(
                mod.path, node.lineno, "RS01",
                f"raw {leaf}() bypasses the egress-resilience layer — "
                "create channels via resilience.grpc_channel (and wrap "
                "calls in Egress.call) or suppress with a reason"))
    return out


# ------------------------------------------------------------------- SR02

_SR02_FIELDS = ("mean", "weight")


def check_sr02(mod: PyModule, config: dict) -> list[Violation]:
    """Sorted-prefix invariant protection: TDigestBank.mean/weight rows
    must stay exactly as ops/tdigest.py's cluster core emits them
    (positive-weight means non-decreasing, zero-weight empties last) —
    the merge-path compress depends on that order for CORRECTNESS, not
    just speed. Any construction of those fields outside the owning
    module is flagged: `TDigestBank(...)` calls binding mean/weight
    (positionally or by keyword) and `<x>._replace(mean=.../weight=...)`
    — `_replace` with those field names is unambiguous in this codebase
    (no other bank NamedTuple carries them). Code that provably
    preserves the order (e.g. an all-zeros prefix) suppresses with a
    documented reason."""
    if any(mod.path.endswith(a) for a in config["sr02_allow"]):
        return []
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        if d is not None and d.rsplit(".", 1)[-1] == "TDigestBank":
            # kw.arg is None is a **kwargs expansion: statically opaque,
            # so treated as binding mean/weight (like positional args) —
            # an invariant gate must not be dodgeable by spelling
            binds = node.args or any(
                kw.arg is None or kw.arg in _SR02_FIELDS
                for kw in node.keywords)
            if binds:
                out.append(Violation(
                    mod.path, node.lineno, "SR02",
                    "TDigestBank construction outside ops/tdigest.py "
                    "writes mean/weight — the merge-path compress "
                    "REQUIRES cluster order on those rows; build banks "
                    "through the ops module or suppress with a reason "
                    "proving the order holds"))
        elif isinstance(node.func, ast.Attribute) \
                and node.func.attr == "_replace":
            fields = sorted(kw.arg for kw in node.keywords
                            if kw.arg in _SR02_FIELDS)
            # a **kwargs expansion is statically opaque — it may carry
            # mean/weight, so it is flagged like an explicit binding
            # (no such call exists on the clean tree; a non-TDigestBank
            # one would suppress with its reason)
            if not fields and any(kw.arg is None for kw in node.keywords):
                fields = ["**"]
            if fields:
                out.append(Violation(
                    mod.path, node.lineno, "SR02",
                    f"._replace({', '.join(fields)}=...) outside "
                    "ops/tdigest.py rewrites t-digest centroid rows — "
                    "the merge-path compress requires their cluster "
                    "order; route the write through ops/tdigest.py or "
                    "suppress with a reason proving the order holds"))
    return out


# ------------------------------------------------------------------- DR01

_DR01_WRITE_MODE_CHARS = set("wax+")
_DR01_PATH_WRITERS = ("write_bytes", "write_text")


def check_dr01(mod: PyModule, config: dict) -> list[Violation]:
    """Durable-state write discipline: every on-disk mutation inside
    the durability package must go through the Journal append/snapshot
    API (`dr01_allow` names the one module that owns the raw file I/O —
    the framing/fsync/atomic-rename contract lives there). A stray
    `open(..., 'w')`, `os.open`, `os.write`, or `Path.write_*` anywhere
    else under `dr01_scope` could write un-CRC'd, un-framed, or
    non-atomically-renamed bytes into the recovery path, silently
    breaking the torn-write tolerance recovery depends on. Reads are
    fine; intentional raw writes suppress with a reason."""
    if not any(m in mod.path for m in config["dr01_scope"]):
        return []
    if any(mod.path.endswith(a) for a in config["dr01_allow"]):
        return []
    out = []

    _OPAQUE = object()

    def _mode_of(call: ast.Call):
        """The open() mode: its literal value, None when omitted (the
        read-only default), or _OPAQUE when present but not statically
        resolvable — which is flagged like the os.open branch flags
        unresolvable flags (a gate must not be dodgeable by spelling)."""
        node = call.args[1] if len(call.args) >= 2 else None
        for kw in call.keywords:
            if kw.arg == "mode":
                node = kw.value
        if node is None:
            return None
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value
        return _OPAQUE

    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        leaf = (d.rsplit(".", 1)[-1] if d is not None
                else getattr(node.func, "attr", None))
        if d in ("open", "io.open", "builtins.open"):
            mode = _mode_of(node)
            if mode is _OPAQUE or (isinstance(mode, str) and (
                    _DR01_WRITE_MODE_CHARS & set(mode))):
                shown = "<unresolvable>" if mode is _OPAQUE else repr(mode)
                out.append(Violation(
                    mod.path, node.lineno, "DR01",
                    f"open(..., {shown}) writes durable state outside "
                    "the journal/snapshot API — route the bytes through "
                    "Journal.append/snapshot (CRC32C framing, fsync "
                    "policy, atomic rename) or suppress with a reason"))
        elif d == "os.open":
            # reads are unrestricted: flag only when the flags
            # expression names a write-capable O_* constant, or when
            # it is statically opaque (a gate must not be dodgeable
            # by an unresolvable spelling)
            flags_node = None
            if len(node.args) >= 2:
                flags_node = node.args[1]
            else:
                for kw in node.keywords:
                    if kw.arg == "flags":
                        flags_node = kw.value
            names = {n.attr for n in ast.walk(flags_node)
                     if isinstance(n, ast.Attribute)} \
                if flags_node is not None else set()
            write_flags = names & {"O_WRONLY", "O_RDWR", "O_CREAT",
                                   "O_APPEND", "O_TRUNC", "O_EXCL",
                                   "O_TMPFILE"}
            readonly = names and not write_flags and all(
                n.startswith("O_") for n in names)
            if not readonly:
                out.append(Violation(
                    mod.path, node.lineno, "DR01",
                    "os.open() with write-capable (or unresolvable) "
                    "flags under the durability package bypasses the "
                    "journal/snapshot API's framing and fsync "
                    "discipline — use Journal.append/snapshot or "
                    "suppress with a reason"))
        elif d == "os.write":
            out.append(Violation(
                mod.path, node.lineno, "DR01",
                "os.write() under the durability package writes "
                "unframed bytes the recovery scan cannot validate — "
                "use Journal.append/snapshot or suppress with a reason"))
        elif leaf in _DR01_PATH_WRITERS and isinstance(
                node.func, ast.Attribute):
            out.append(Violation(
                mod.path, node.lineno, "DR01",
                f".{leaf}() under the durability package bypasses the "
                "journal/snapshot API — use Journal.append/snapshot or "
                "suppress with a reason"))
    return out


# ------------------------------------------------------------------- DR02

def check_dr02(mod: PyModule, config: dict) -> list[Violation]:
    """Engine-state serialization discipline (the ISSUE 9 counterpart
    of DR01's write discipline): within the engine/ops/cluster/
    durability layers, raw numpy byte moves — `<arr>.tobytes()` and
    `np.frombuffer(...)` — are single-homed in durability/records.py,
    whose codecs are the ONLY place bank leaves may become bytes. A
    stray tobytes/frombuffer elsewhere could serialize bank rows
    through a lossy path (float formatting, zero-weight dropping,
    re-ordering) and silently break the kill-restart bit-identity the
    engine checkpoint guarantees. Legitimate non-bank byte moves (the
    HLL wire row in cluster/wire.py, the CRC lane fold in journal.py)
    suppress with a documented reason."""
    if not any(m in mod.path for m in config["dr02_scope"]):
        return []
    if any(mod.path.endswith(a) for a in config["dr02_allow"]):
        return []
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        leaf = (d.rsplit(".", 1)[-1] if d is not None
                else getattr(node.func, "attr", None))
        if leaf == "tobytes" and isinstance(node.func, ast.Attribute):
            out.append(Violation(
                mod.path, node.lineno, "DR02",
                ".tobytes() outside durability/records.py — engine-"
                "state byte codecs are single-homed there (bit-exact "
                "leaf framing); route the array through a records.py "
                "codec or suppress with a reason naming what non-bank "
                "bytes these are"))
        elif leaf == "frombuffer" and isinstance(node.func,
                                                ast.Attribute):
            out.append(Violation(
                mod.path, node.lineno, "DR02",
                "frombuffer() outside durability/records.py — engine-"
                "state byte codecs are single-homed there; decode "
                "through a records.py codec or suppress with a reason "
                "naming what non-bank bytes these are"))
    return out


# ------------------------------------------------------------------- TL01

_TL01_PREFIX = "veneur."


def _docstring_ids(tree: ast.AST) -> set:
    """ids of Constant nodes that are docstrings (the first statement
    of a module/class/def) — literal-scanning checks exempt them."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            body = getattr(node, "body", [])
            if body and isinstance(body[0], ast.Expr) and isinstance(
                    body[0].value, ast.Constant) and isinstance(
                    body[0].value.value, str):
                out.add(id(body[0].value))
    return out


def check_tl01(mod: PyModule, config: dict) -> list[Violation]:
    """Self-metric naming monopoly: every `veneur.*` self-metric name
    in the serving tree must be minted by the unified telemetry
    registry (observe/registry.py — TelemetryRegistry.drain,
    phase_timer_samples, flush_span_name). A string literal starting
    with "veneur." anywhere else is an ad-hoc emission surface — the
    exact three-disjoint-registries drift this check exists to prevent
    (an InterMetric built by hand, a raw dict counter drained with its
    own name mapping, a second span-name spelling). Docstrings are
    exempt (documentation names metrics); deliberate emitters suppress
    with a reason."""
    if not any(m in mod.path for m in config["tl01_scope"]):
        return []
    if any(mod.path.endswith(a) for a in config["tl01_allow"]):
        return []
    # docstring Constants: the first statement of a module/class/def
    docstrings = _docstring_ids(mod.tree)
    # constants living inside an f-string report via their JoinedStr
    fstring_parts = {id(v) for node in ast.walk(mod.tree)
                     if isinstance(node, ast.JoinedStr)
                     for v in node.values}
    out = []
    for node in ast.walk(mod.tree):
        lit = None
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            if id(node) in docstrings or id(node) in fstring_parts:
                continue
            lit = node.value
        elif isinstance(node, ast.JoinedStr) and node.values and \
                isinstance(node.values[0], ast.Constant) and \
                isinstance(node.values[0].value, str):
            # f"veneur.{name}_total" — the statically-visible head
            lit = node.values[0].value
        if lit is not None and lit.startswith(_TL01_PREFIX):
            out.append(Violation(
                mod.path, node.lineno, "TL01",
                f"ad-hoc veneur.* self-metric name {lit!r} outside the "
                "telemetry registry — veneur.* naming lives in "
                "observe/registry.py (TelemetryRegistry.drain / "
                "phase_timer_samples / flush_span_name); count through "
                "the registry or suppress with a reason"))
    return out


# ------------------------------------------------------------------- TR01

# wire literals of the forward trace context + the envelope's gRPC
# metadata carrier + the delta/full forward-kind marker — matched
# case-insensitively, by prefix, so a re-spelled header
# ("x-veneur-trace-parent") is still caught
_TR01_PREFIXES = ("x-veneur-trace", "x-veneur-interval-close",
                  "x-veneur-forward-kind", "veneur-envelope-bin")


def check_tr01(mod: PyModule, config: dict) -> list[Violation]:
    """Trace-context wire-encoding monopoly: the header/metadata
    literals that carry the forward trace context (and the envelope's
    serialized-Envelope metadata key) may appear ONLY in
    cluster/wire.py — the same single-home discipline as the envelope
    codecs, for the same reason: two spellings of the encode/decode
    mapping is how the sender and receiver drift apart silently (a
    header renamed on one side reads as 'legacy peer, no trace' on the
    other, and the span tree quietly falls in half). Docstrings are
    exempt (documentation names headers)."""
    if not any(m in mod.path for m in config["tr01_scope"]):
        return []
    if any(mod.path.endswith(a) for a in config["tr01_allow"]):
        return []
    docstrings = _docstring_ids(mod.tree)
    out = []
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Constant)
                and isinstance(node.value, str)):
            continue
        if id(node) in docstrings:
            continue
        if node.value.lower().startswith(_TR01_PREFIXES):
            out.append(Violation(
                mod.path, node.lineno, "TR01",
                f"trace-context wire literal {node.value!r} outside "
                "cluster/wire.py — the envelope/trace header and "
                "metadata encodings are single-homed there (use the "
                "wire.* codec helpers), or suppress with a reason"))
    return out


# ------------------------------------------------------------------- WC01

# wire spellings of the quantized-centroid row: the jsonmetric-v1 key
# and the metricpb TDigest bytes field. Touching either outside
# cluster/wire.py means re-implementing the quantization /
# dequantization math (or half of it) somewhere the golden-bytes tests
# don't look.
_WC01_LITERALS = ("centroids_q16", "packed_centroids")


def check_wc01(mod: PyModule, config: dict) -> list[Violation]:
    """Centroid quantization single-homing (the TR01 literal-scan
    precedent, applied to the q16 codec): the quantized-centroid wire
    row's spellings — the "centroids_q16" JSON key and the
    `packed_centroids` pb field — may appear ONLY in cluster/wire.py,
    as string literals OR attribute access (reading `td.
    packed_centroids` elsewhere IS decoding outside the codec). Two
    homes for an affine-quantization grid is how a sender and receiver
    end up on different grids while every roundtrip test passes:
    encode and dequantize must share one scale expression. Docstrings
    are exempt (documentation names wire keys)."""
    if not any(m in mod.path for m in config["wc01_scope"]):
        return []
    if any(mod.path.endswith(a) for a in config["wc01_allow"]):
        return []
    docstrings = _docstring_ids(mod.tree)
    out = []
    for node in ast.walk(mod.tree):
        name = None
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            if id(node) in docstrings:
                continue
            if node.value.lower().startswith(_WC01_LITERALS):
                name = node.value
        elif isinstance(node, ast.Attribute) and \
                node.attr in _WC01_LITERALS:
            name = node.attr
        if name is not None:
            out.append(Violation(
                mod.path, node.lineno, "WC01",
                f"quantized-centroid wire spelling {name!r} outside "
                "cluster/wire.py — the q16 encode/decode math and its "
                "carriers are single-homed there (use wire."
                "encode_q16_centroids / td_centroids / "
                "histogram_wire_fragment / "
                "histogram_centroids_from_json), or suppress with a "
                "reason"))
    return out


# ------------------------------------------------------------------- OV01

_OV01_COUNT_METHODS = ("incr", "mark")


def _ov01_counts(node: ast.AST) -> bool:
    """Does this subtree contain a registry counter update (an
    `.incr(...)`/`.mark(...)` method call)?"""
    for n in ast.walk(node):
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) \
                and n.func.attr in _OV01_COUNT_METHODS:
            return True
    return False


def check_ov01(mod: PyModule, config: dict) -> list[Violation]:
    """Counted-degradation discipline (the overload-defense layer's
    core contract): inside the admission scope, any function whose name
    starts with admit/fold/shed is a degradation DECISION function, and
    a drop verdict — `return None` (or a bare `return`) — must be
    accompanied by a registry counter update in the same branch. The
    "branch" is the innermost enclosing if/loop/try statement (its
    whole subtree, so a conditional count like `if changed: incr(...)`
    preceding the return qualifies), or the function body for a
    top-level return. An uncounted drop is a silent-degradation bug:
    the accounting identity `received == applied + counted_degraded`
    the soak harness asserts can only hold if every verdict counts."""
    if not any(m in mod.path for m in config["ov01_scope"]):
        return []
    prefixes = tuple(config["ov01_decision_prefixes"])
    parents = _parent_map(mod.tree)
    out = []
    for fn in ast.walk(mod.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not fn.name.lstrip("_").startswith(prefixes):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Return):
                continue
            v = node.value
            is_drop = v is None or (isinstance(v, ast.Constant)
                                    and v.value is None)
            if not is_drop:
                continue
            # the innermost enclosing branch statement WITHIN this
            # function; the function body when the return is top-level
            branch: ast.AST = fn
            cur = parents.get(node)
            while cur is not None and cur is not fn:
                if isinstance(cur, (ast.If, ast.For, ast.While,
                                    ast.Try)):
                    branch = cur
                    break
                cur = parents.get(cur)
            if not _ov01_counts(branch):
                out.append(Violation(
                    mod.path, node.lineno, "OV01",
                    f"drop verdict in decision function `{fn.name}` "
                    "without a registry counter in the same branch — "
                    "degradation must be COUNTED (incr/mark) where it "
                    "is decided, or the accounting identity "
                    "`received == applied + counted_degraded` breaks "
                    "silently"))
    return out


# ------------------------------------------------------------------- SK01

_SK01_BANKS = ("TDigestBank", "HLLBank", "ULLBank", "REQBank")
# module tails that ARE sketch implementations: importing one outside
# the registry boundary is direct sketch-math access
_SK01_MODULES = ("ops.tdigest", "ops.hll",
                 "sketches.ull", "sketches.req",
                 "sketches.tdigest_engine", "sketches.hll_engine",
                 "kernels.hll_stats")
_SK01_LEAF_NAMES = ("tdigest", "hll", "ull", "req",
                    "tdigest_engine", "hll_engine",
                    "hll_stats")


def check_sk01(mod: PyModule, config: dict) -> list[Violation]:
    """Sketch-engine registry boundary (ISSUE 10): sketch banks and
    sketch math are owned by veneur_tpu/sketches/ (the engine registry)
    and the blessed veneur_tpu/ops/ kernels. Outside those, code must
    hold an ENGINE OBJECT from the registry — flagged here are (a)
    imports of the sketch implementation modules (ops.tdigest, ops.hll,
    sketches.ull, ...; a direct import is how a call site grows a
    hard-wired dependency on one engine's math and silently breaks the
    other backend) and (b) construction of the bank NamedTuples
    (TDigestBank/HLLBank/ULLBank/REQBank — a bank built outside the
    owning engine bypasses its invariants: cluster order, register
    packing, level layout). The mesh engine (parallel/) is allowed by
    config — it owns sharded banks and the backend selection refuses
    non-default engines there; intentional exceptions elsewhere
    suppress with a reason."""
    if not any(m in mod.path for m in config["sk01_scope"]):
        return []
    if any(a in mod.path for a in config["sk01_allow"]):
        return []
    out = []
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ImportFrom):
            module = node.module or ""
            hit = any(module.endswith(t) or module == t.rsplit(".")[-1]
                      for t in _SK01_MODULES)
            names = {a.name for a in node.names}
            # `from ..ops import tdigest, hll` / `from ..sketches
            # import ull` / `from ..kernels import hll_stats` forms:
            # the module is the parent package and the implementation
            # rides in the names list
            if not hit and (module.endswith("ops")
                            or module.endswith("sketches")
                            or module.endswith("kernels")):
                hit = bool(names & set(_SK01_LEAF_NAMES))
            if hit:
                out.append(Violation(
                    mod.path, node.lineno, "SK01",
                    f"direct sketch-module import ({module!r}) outside "
                    "the registry boundary — obtain an engine object "
                    "from veneur_tpu.sketches (histogram_engine/"
                    "set_engine) instead, or suppress with a reason"))
        elif isinstance(node, ast.Import):
            for a in node.names:
                if any(a.name.endswith(t) for t in _SK01_MODULES):
                    out.append(Violation(
                        mod.path, node.lineno, "SK01",
                        f"direct sketch-module import ({a.name!r}) "
                        "outside the registry boundary — obtain an "
                        "engine object from veneur_tpu.sketches "
                        "instead, or suppress with a reason"))
        elif isinstance(node, ast.Call):
            d = dotted(node.func)
            if d is not None and d.rsplit(".", 1)[-1] in _SK01_BANKS:
                out.append(Violation(
                    mod.path, node.lineno, "SK01",
                    f"{d.rsplit('.', 1)[-1]} constructed outside "
                    "veneur_tpu/sketches/ + the blessed ops/ kernels — "
                    "banks built outside the owning engine bypass its "
                    "invariants (cluster order, register packing, "
                    "level layout); build through the engine object or "
                    "suppress with a reason"))
    return out


# ------------------------------------------------------------------- PK01


def _pk01_pallas_imports(tree: ast.AST) -> list:
    """(lineno, spelling) for every import of a pallas module."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            module = node.module or ""
            if "pallas" in module:
                out.append((node.lineno, module))
            elif module.endswith("jax.experimental") or \
                    module == "jax.experimental":
                for a in node.names:
                    if a.name == "pallas":
                        out.append((node.lineno, module + ".pallas"))
        elif isinstance(node, ast.Import):
            for a in node.names:
                if "pallas" in a.name:
                    out.append((node.lineno, a.name))
    return out


def _pk01_counts_fallback(fn: ast.AST) -> bool:
    """Does this function call THE fallback counter, count_fallback?
    Exact-match on the final name component: a function that merely
    READS the counter (fallback_total, a /debug getter) has no
    degradation branch and must not pass for one."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            d = dotted(node.func)
            if d is not None and \
                    d.rsplit(".", 1)[-1] == "count_fallback":
                return True
    return False


def _pk01_functions(tree: ast.AST):
    """Module-level functions AND class methods (sync + async) — the
    entry-point surface leg (b) disciplines. Nested closures are
    excluded: kernel-body helpers defined inside an entry are part of
    that entry's own accounting."""
    for n in tree.body:
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield n
        elif isinstance(n, ast.ClassDef):
            for m in n.body:
                if isinstance(m, (ast.FunctionDef,
                                  ast.AsyncFunctionDef)):
                    yield m


def check_pk01(mod: PyModule, config: dict) -> list[Violation]:
    """Pallas-kernel containment (ISSUE 15). Two legs:

    (a) OUTSIDE veneur_tpu/kernels/, importing a pallas module or
        calling `pallas_call` is flagged — every pl.* primitive is
        single-homed in the kernels package, next to the counted
        fallback that makes a shape a kernel cannot serve degrade
        loudly instead of crashing a serving executable.
    (b) INSIDE the kernels package, every PUBLIC function that reaches
        a `pallas_call` (directly or through module-local helpers)
        must contain a counted fallback branch — a call to the
        `count_fallback` helper (veneur.kernels.fallback_total) — so
        no kernel entry point can silently lack the degradation path.
        A function that is no serving entry point suppresses with a
        reason."""
    in_kernels = any(k in mod.path
                     for k in config["pk01_kernel_paths"])
    in_scope = any(s in mod.path for s in config["pk01_scope"])
    if not (in_scope or in_kernels):
        return []
    out = []
    if not in_kernels:
        for lineno, spelling in _pk01_pallas_imports(mod.tree):
            out.append(Violation(
                mod.path, lineno, "PK01",
                f"pallas import ({spelling!r}) outside "
                "veneur_tpu/kernels/ — kernels are single-homed there "
                "behind the arm/probe/fallback machinery; move the "
                "kernel or suppress with a reason"))
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                d = dotted(node.func)
                if d is not None and \
                        d.rsplit(".", 1)[-1] == "pallas_call":
                    out.append(Violation(
                        mod.path, node.lineno, "PK01",
                        "pallas_call outside veneur_tpu/kernels/ — "
                        "kernel invocations live in the kernels "
                        "package (counted-fallback discipline); move "
                        "it or suppress with a reason"))
        return out

    # leg (b): entry-point fallback discipline inside the package
    funcs = {n.name: n for n in _pk01_functions(mod.tree)}
    direct = {}
    calls_local = {}
    for name, fn in funcs.items():
        has = False
        called = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                d = dotted(node.func)
                if d is None:
                    continue
                leaf = d.rsplit(".", 1)[-1]
                if leaf == "pallas_call":
                    has = True
                # match module-local callees by final name component
                # so `self.helper()` / `cls.helper()` resolve too
                if leaf in funcs:
                    called.add(leaf)
        direct[name] = has
        calls_local[name] = called
    reaches = dict(direct)
    for _ in range(len(funcs)):      # fixed-point over the call graph
        changed = False
        for name in funcs:
            if not reaches[name] and any(reaches[c]
                                         for c in calls_local[name]):
                reaches[name] = True
                changed = True
        if not changed:
            break
    # a function is protected when it counts the fallback itself, or
    # every kernel it reaches is reached THROUGH a protected callee
    # (a delegating entry point inherits the branch from the one
    # entry that owns it)
    protected = {name: _pk01_counts_fallback(fn)
                 for name, fn in funcs.items()}
    for _ in range(len(funcs)):
        changed = False
        for name in funcs:
            if protected[name] or direct[name]:
                continue
            kernel_callees = [c for c in calls_local[name]
                              if reaches[c]]
            if kernel_callees and all(protected[c]
                                      for c in kernel_callees):
                protected[name] = True
                changed = True
        if not changed:
            break
    for name, fn in funcs.items():
        if name.startswith("_") or not reaches[name]:
            continue
        if not protected[name]:
            out.append(Violation(
                mod.path, fn.lineno, "PK01",
                f"kernel entry point {name!r} reaches pallas_call "
                "without a counted fallback branch — every public "
                "kernel entry must degrade to the XLA program through "
                "count_fallback (veneur.kernels.fallback_total) when "
                "the backend refuses, or suppress with a reason"))
    return out


# ------------------------------------------------------------------- DS01

_DS01_BANK_ATTRS = ("histo_bank", "counter_bank", "gauge_bank",
                    "set_bank")
# method leaves that LAND data into a bank without assigning a bank
# attribute (the pure landing cores return banks to their caller)
_DS01_LANDING_LEAVES = ("merge_rows", "merge_centroids",
                        "merge_scalars", "counter_merge", "gauge_set")
_DS01_MARK_LEAVES = ("_mark_dirty", "_mark_dirty_into")


def _ds01_direct_mark(fn: ast.AST) -> bool:
    """Does this function mark a dirty bitmap directly — a
    *_mark_dirty(_into) call, or a subscript STORE whose base chain
    names something dirty (`dirty[0][ids] = True`,
    `self._dirty[kind][ids] = True`)?"""
    for n in ast.walk(fn):
        if isinstance(n, ast.Call):
            d = dotted(n.func)
            if d is not None and \
                    d.rsplit(".", 1)[-1] in _DS01_MARK_LEAVES:
                return True
        elif isinstance(n, (ast.Assign, ast.AugAssign)):
            targets = (n.targets if isinstance(n, ast.Assign)
                       else [n.target])
            for t in targets:
                if not isinstance(t, ast.Subscript):
                    continue
                base = t
                while isinstance(base, ast.Subscript):
                    base = base.value
                name = (base.attr if isinstance(base, ast.Attribute)
                        else base.id if isinstance(base, ast.Name)
                        else "")
                if "dirty" in name:
                    return True
    return False


def _ds01_landing_lines(fn: ast.AST) -> list[int]:
    """Line numbers of device-landing bank writes inside `fn`: an
    assignment binding a `*_bank` attribute, a `self._kern[...]`
    kernel dispatch, or a call to one of the bank-landing method
    leaves (merge_rows & co — the cores that return updated banks)."""
    lines = []
    for n in ast.walk(fn):
        if isinstance(n, ast.Assign):
            targets = []
            for t in n.targets:
                targets.extend(t.elts if isinstance(
                    t, (ast.Tuple, ast.List)) else [t])
            if any(isinstance(t, ast.Attribute)
                   and t.attr in _DS01_BANK_ATTRS for t in targets):
                lines.append(n.lineno)
        elif isinstance(n, ast.Call):
            if isinstance(n.func, ast.Subscript) and isinstance(
                    n.func.value, ast.Attribute) \
                    and n.func.value.attr == "_kern":
                lines.append(n.lineno)
            else:
                d = dotted(n.func)
                if d is not None and \
                        d.rsplit(".", 1)[-1] in _DS01_LANDING_LEAVES:
                    lines.append(n.lineno)
    return sorted(set(lines))


def check_ds01(mod: PyModule, config: dict) -> list[Violation]:
    """Dirty-bitmap marking discipline (ISSUE 11): the dirty-slot
    bitmap feeds BOTH the delta checkpoints and the incremental flush
    — an unmarked device-landing write silently drops data from the
    next flush AND the next checkpoint, so marking is a machine-
    checked invariant, not folklore. Inside the scope (the pipeline
    module owning the banks), every function containing a device-
    landing bank write must mark a dirty bitmap: directly
    (*_mark_dirty(_into) call, or a subscript store on a dirty
    bitmap), or by calling — transitively, within the module — a
    function that does. Non-landing bank writes (the fresh-bank swap,
    warmup's all-padding batches, initial setup) suppress with a
    documented reason. One finding per function, at its first landing
    line."""
    if not any(m in mod.path for m in config["ds01_scope"]):
        return []
    fns = [n for n in ast.walk(mod.tree)
           if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    marking = {fn.name for fn in fns if _ds01_direct_mark(fn)}
    # transitive closure over intra-module calls: a function that
    # calls a marking function (by leaf name) is itself marking —
    # wrappers delegate to the landing cores that own the mark
    changed = True
    while changed:
        changed = False
        for fn in fns:
            if fn.name in marking:
                continue
            for n in ast.walk(fn):
                if isinstance(n, ast.Call):
                    d = dotted(n.func)
                    if d is not None and \
                            d.rsplit(".", 1)[-1] in marking:
                        marking.add(fn.name)
                        changed = True
                        break
    out = []
    for fn in fns:
        lines = _ds01_landing_lines(fn)
        if not lines or fn.name in marking:
            continue
        out.append(Violation(
            mod.path, lines[0], "DS01",
            f"device-landing bank write in `{fn.name}` without a "
            "dirty-bitmap mark — the bitmap feeds the incremental "
            "flush AND delta checkpoints, so an unmarked landing "
            "silently drops the slot from both; mark via "
            "_mark_dirty(_into) (or a marking helper), or suppress "
            "with a reason proving this write is not a data landing"))
    return out


# ------------------------------------------------------------------- QT01

_QT01_BANK_ATTRS = ("histo_bank", "counter_bank", "gauge_bank",
                    "set_bank")


def check_qt01(mod: PyModule, config: dict) -> list[Violation]:
    """Read-path isolation for the time-travel query tier (ISSUE 14):
    code under the query/read path (qt01_scope — durability/history.py
    and the check's own fixture) must never acquire an engine's
    ingest/flush lock (`with <x>.lock:`, `<x>.lock.acquire()`) or
    write a bank attribute (`<x>.histo_bank = ...` and siblings). The
    query tier works exclusively on SCRATCH engines minted by its
    factory, through their public restore/import/flush surface — a
    stray lock acquisition here could stall admit/flush behind a heavy
    historical query (the estimate-outside-the-lock discipline
    /debug/flush established), and a bank write could corrupt live
    state a query must only read. Machine-checked so the isolation
    stays an invariant, not review folklore."""
    if not any(m in mod.path for m in config["qt01_scope"]):
        return []
    out = []
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.With):
            for item in node.items:
                ctx_expr = item.context_expr
                if isinstance(ctx_expr, ast.Attribute) \
                        and ctx_expr.attr == "lock":
                    out.append(Violation(
                        mod.path, node.lineno, "QT01",
                        "query-path code acquires an engine lock "
                        "(`with <x>.lock:`) — the read tier must never "
                        "take the ingest/flush lock; go through the "
                        "scratch engine's public surface or suppress "
                        "with a reason naming the non-engine lock"))
        elif isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr == "acquire" \
                    and isinstance(f.value, ast.Attribute) \
                    and f.value.attr == "lock":
                out.append(Violation(
                    mod.path, node.lineno, "QT01",
                    "query-path code calls <x>.lock.acquire() — the "
                    "read tier must never take the ingest/flush lock; "
                    "suppress with a reason naming the non-engine "
                    "lock"))
        elif isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) \
                    else [t]
                for e in elts:
                    if isinstance(e, ast.Attribute) \
                            and e.attr in _QT01_BANK_ATTRS:
                        out.append(Violation(
                            mod.path, node.lineno, "QT01",
                            f"query-path code writes `<x>.{e.attr}` — "
                            "the read tier must never write live "
                            "banks; restore into a scratch engine via "
                            "restore_checkpoint instead"))
    return out


# ------------------------------------------------------------------- GC01

_GC01_SWITCHES = ("disable", "enable", "freeze", "set_threshold")


def check_gc01(mod: PyModule, config: dict) -> list[Violation]:
    """The collector's switch has one home (ISSUE 50): `gc.disable`,
    `gc.enable`, `gc.freeze` and `gc.set_threshold` may appear only
    inside the counted, re-entrant guard (`gc01_home`: a file and the
    class in it) that holds generational collection off while a
    frame's rows are built. A second caller anywhere under veneur_tpu/
    would switch collection back on under a thread still inside the
    guard, or leave it off for a process that never asked: the guard
    counts its holders and restores what it found, a stray call does
    neither. Flagged: an attribute of any name `import gc [as x]`
    bound, called or not, and `from gc import <switch>`."""
    if not any(s in mod.path for s in config["gc01_scope"]):
        return []
    home_path, home_class = config["gc01_home"]
    inside = set()
    if mod.path.endswith(home_path):
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ClassDef) and node.name == home_class:
                inside.update(id(n) for n in ast.walk(node))
    aliases = {"gc"}
    out = []
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            aliases.update(a.asname for a in node.names
                           if a.name == "gc" and a.asname)
        elif isinstance(node, ast.ImportFrom) and node.module == "gc":
            for a in node.names:
                if a.name in _GC01_SWITCHES or a.name == "*":
                    out.append(Violation(
                        mod.path, node.lineno, "GC01",
                        f"`from gc import {a.name}` — the collector's "
                        f"switch lives in {home_path}:{home_class} "
                        "alone"))
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Attribute) \
                and node.attr in _GC01_SWITCHES \
                and isinstance(node.value, ast.Name) \
                and node.value.id in aliases \
                and id(node) not in inside:
            out.append(Violation(
                mod.path, node.lineno, "GC01",
                f"gc.{node.attr} outside {home_path}:{home_class} — "
                "the guard counts its holders across threads and "
                "restores what it found; a second caller breaks both. "
                "Build rows through MetricFrame.to_list() or suppress "
                "with a reason"))
    out.sort(key=lambda v: v.line)
    return out


# ------------------------------------------------------------------- driver

def check_module(mod: PyModule, ctx: Context, config: dict
                 ) -> list[Violation]:
    out = []
    out.extend(check_jx01(mod))
    out.extend(check_jx02(mod, ctx))
    out.extend(check_jx03(mod, config))
    out.extend(check_th01(mod, config))
    out.extend(check_cf01(mod, ctx, config))
    out.extend(check_rs01(mod, config))
    out.extend(check_sr02(mod, config))
    out.extend(check_dr01(mod, config))
    out.extend(check_dr02(mod, config))
    out.extend(check_tl01(mod, config))
    out.extend(check_tr01(mod, config))
    out.extend(check_wc01(mod, config))
    out.extend(check_ov01(mod, config))
    out.extend(check_sk01(mod, config))
    out.extend(check_ds01(mod, config))
    out.extend(check_qt01(mod, config))
    out.extend(check_pk01(mod, config))
    out.extend(check_gc01(mod, config))
    return out
