"""tpu_lint rules R1–R5.

Every rule is a pure function over the :class:`~.model.Project` +
:class:`~.callgraph.CallGraph`; findings carry the trace-entry chain that
makes the site reachable and a fix hint. The shared *taint* machinery
marks values that are traced (function parameters of reachable-under-trace
code, minus jit statics and config-flag defaults, propagated through
assignments) or *lazy* (results of dispatching a compiled program, which
are device futures until something forces them).

- **R1 host-sync**: explicit sync primitives (``jax.device_get`` /
  ``jax.block_until_ready`` / ``.item()``) anywhere — every one is either
  a bug or deserves a written justification; plus implicit syncs on
  traced values in trace-reachable code (``int()``/``float()``/``bool()``
  / ``np.asarray`` / ``print``) and on lazy dispatch results in hot paths.
- **R2 retrace hazard**: Python branching on traced values, formatting a
  tracer into a string, re-jitting inside hot code or loops, and
  unhashable literals fed to static jit parameters.
- **R3 donation-after-use**: an argument at a donated position of a
  compiled call read again afterwards (or reused across loop iterations
  without being reassigned from the call's results).
- **R4 PRNG key reuse**: one key consumed by ≥2 random ops (or by one
  random op across loop iterations) without an interleaving
  ``split``/``fold_in`` rebind. Branch-exclusive consumption (an ``if``
  arm that returns) does not count twice.
- **R5 unguarded shared state**: in classes that own threads, attributes
  guarded by a lock at most sites but accessed bare at others
  (majority-use lock inference, with lock context inherited by private
  helpers only ever called under the lock).
"""
from __future__ import annotations

import ast
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .callgraph import CallGraph, dotted_path
from .model import ClassInfo, Finding, FunctionInfo, Project

__all__ = ["run_rules", "RulesOutput", "FileTimer", "RULE_DOCS"]

RULE_DOCS = {
    "R0": "suppression policy / parse errors (reasons are mandatory)",
    "R1": "host sync in trace-reachable or hot dispatch code",
    "R2": "retrace hazard (branch on traced value, tracer formatting, "
          "jit in hot code, unhashable static)",
    "R3": "donated buffer read after the donating call",
    "R4": "PRNG key consumed by >=2 random ops without split/fold_in",
    "R5": "shared attribute bypassing its majority-use lock in a "
          "threaded class",
    "R6": "lock-order cycle across the interprocedural acquisition "
          "graph, or re-entry through a non-reentrant Lock",
    "R7": "blocking operation (host sync, compiled dispatch, buffer "
          "update, sleep, unbounded wait/get/join, file I/O, rpc) "
          "inside a held-lock region",
    "R8": "mesh-axis/sharding discipline (undeclared PartitionSpec "
          "axis, frozen program-axis resize, shard_map arity, "
          "donated-input reshard)",
    "R9": "resource-lifecycle leak: an acquire (BlockPool lookup, "
          "AdapterStore acquire, pin, staged .tmp file) with an "
          "unreachable release on some path (incl. raise paths)",
    "R10": "SPMD collective divergence: collective under a "
           "rank-tainted branch/loop, or branch-asymmetric collective "
           "sequences — a cross-rank deadlock",
    "R11": "rpc discipline: unbounded rpc call, non-idempotent fn "
           "under transport retry, or a swallowed transport error",
}


class FileTimer:
    """Per-file wall-clock accounting for the ``--json`` timing block.

    ``parse`` is exact (one entry per file parse); ``lint`` accumulates
    the per-function/per-class rule passes attributed to the defining
    file (the dominant cost — whole-project passes like the callgraph
    BFS are reported in the rule totals instead)."""

    def __init__(self):
        self.parse: Dict[str, float] = {}
        self.lint: Dict[str, float] = {}

    def add(self, rel: str, dt: float) -> None:
        self.lint[rel] = self.lint.get(rel, 0.0) + dt

    def timed(self, items, rel_of):
        for x in items:
            t0 = time.perf_counter()
            yield x
            self.add(rel_of(x), time.perf_counter() - t0)

    def files_ms(self) -> Dict[str, dict]:
        out: Dict[str, dict] = {}
        for rel, dt in self.parse.items():
            out.setdefault(rel, {})["parse_ms"] = round(dt * 1e3, 3)
        for rel, dt in self.lint.items():
            out.setdefault(rel, {})["lint_ms"] = round(dt * 1e3, 3)
        return out

_SYNC_TERMINALS = {"device_get", "block_until_ready"}
_HOST_CASTS = {"int", "float", "bool"}
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "sharding", "device",
                 "aval", "weak_type"}
# params with these names are config plumbing, never traced arrays
# (padding/stride/kernel geometry joined the set in the PR-7 baseline
# re-audit: `_pool`'s ceil-mode branch was a taint FP on them)
_UNTAINTED_PARAM_NAMES = {"dtype", "name", "data_format", "mode",
                          "padding", "pad", "kernel_size", "stride",
                          "dilation", "groups"}
_HOST_RESULT_CALLS = {"asarray", "array", "device_get", "item", "int",
                      "float", "bool", "len", "isinstance", "hasattr",
                      "getattr", "repr", "str", "format"}
_RANDOM_DERIVERS = {"split", "fold_in", "PRNGKey", "key", "key_data",
                    "wrap_key_data", "clone", "key_impl", "random_seed"}


def _numpy_rooted(fi: FunctionInfo, path: Tuple[str, ...]) -> bool:
    if path is None or len(path) < 2:
        return False
    alias = fi.file.aliases.get(path[0])
    root = alias[1] if alias and alias[0] == "module" else path[0]
    return root == "numpy" or path[0] in ("np", "numpy")


def _jax_rooted(fi: FunctionInfo, path: Tuple[str, ...]) -> bool:
    if not path:
        return False
    alias = fi.file.aliases.get(path[0])
    root = alias[1] if alias and alias[0] == "module" else path[0]
    return root.split(".")[0] == "jax"


# =========================================================== taint engine
class Taint:
    """Flow-insensitive tainted-name set for ONE function."""

    def __init__(self, fi: FunctionInfo, seeds: Set[str]):
        self.fi = fi
        self.names: Set[str] = set(seeds)
        # name -> line of an `isinstance(x, ...Tracer)` guard that raises
        self.tracer_guards: Dict[str, int] = {}
        # (name, start_line, end_line) regions where name is PROVEN
        # concrete by a `not isinstance(x, Tracer)` test
        self.concrete_regions: List[Tuple[str, int, int]] = []
        self._propagate()

    def _assignments(self):
        for node in ast.walk(self.fi.node):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not self.fi.node:
                continue
            if isinstance(node, ast.Assign):
                yield node.value, node.targets
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                yield node.value, [node.target]
            elif isinstance(node, ast.AugAssign):
                yield node.value, [node.target]
            elif isinstance(node, ast.For):
                yield node.iter, [node.target]
            elif isinstance(node, ast.withitem) and node.optional_vars:
                yield node.context_expr, [node.optional_vars]
            elif isinstance(node, ast.NamedExpr):
                yield node.value, [node.target]

    def _target_names(self, t) -> List[str]:
        """Plain names a tainted RHS taints. Attribute/Subscript targets
        (``self.x = v``, ``d[k] = v``) taint NOTHING — the base object is
        a container, not the value (tainting `self` here poisoned every
        ``self.*`` read)."""
        if isinstance(t, ast.Name):
            return [t.id]
        if isinstance(t, (ast.Tuple, ast.List)):
            out = []
            for e in t.elts:
                out.extend(self._target_names(e))
            return out
        if isinstance(t, ast.Starred):
            return self._target_names(t.value)
        return []

    def _propagate(self) -> None:
        self._find_guards()
        for _ in range(10):
            changed = False
            for value, targets in self._assignments():
                names: List[str] = []
                for t in targets:
                    names.extend(self._target_names(t))
                if not names or not self.expr(value):
                    continue
                # `for k, v in tainted.items():` — the KEYS are strings
                if len(names) == 2 and isinstance(value, ast.Call) \
                        and isinstance(value.func, ast.Attribute) \
                        and value.func.attr == "items":
                    names = names[1:]
                for n in names:
                    if n not in self.names:
                        self.names.add(n)
                        changed = True
            if not changed:
                break

    @staticmethod
    def _isinstance_tracer(e) -> Optional[str]:
        """Name N when ``e`` is ``isinstance(N, ...Tracer)``."""
        if isinstance(e, ast.Call) and isinstance(e.func, ast.Name) \
                and e.func.id == "isinstance" and len(e.args) == 2 \
                and isinstance(e.args[0], ast.Name):
            types = dotted_path(e.args[1]) or ()
            if types and types[-1] == "Tracer":
                return e.args[0].id
        return None

    def _find_guards(self) -> None:
        """Tracer guards prove a value concrete: after an
        ``isinstance(x, Tracer): raise/return``, inside the body of
        ``if not isinstance(x, Tracer):`` (also as an ``and`` operand),
        and in the ``else`` of ``if isinstance(x, Tracer):``."""
        for node in ast.walk(self.fi.node):
            if not isinstance(node, ast.If):
                continue
            t = node.test
            end = getattr(node, "end_lineno", node.lineno)
            n = self._isinstance_tracer(t)
            if n is not None:
                if node.body and isinstance(node.body[-1],
                                            (ast.Raise, ast.Return)):
                    self.tracer_guards.setdefault(n, node.lineno)
                if node.orelse:
                    self.concrete_regions.append(
                        (n, node.orelse[0].lineno, end))
                continue
            neg = []
            if isinstance(t, ast.UnaryOp) and isinstance(t.op, ast.Not):
                n = self._isinstance_tracer(t.operand)
                if n is not None:
                    neg.append(n)
            elif isinstance(t, ast.BoolOp) and isinstance(t.op, ast.And):
                for v in t.values:
                    if isinstance(v, ast.UnaryOp) \
                            and isinstance(v.op, ast.Not):
                        n = self._isinstance_tracer(v.operand)
                        if n is not None:
                            neg.append(n)
            if neg and node.body:
                body_end = getattr(node.body[-1], "end_lineno", end)
                for n in neg:
                    self.concrete_regions.append(
                        (n, node.body[0].lineno, body_end))

    def guarded(self, name: str, line: int) -> bool:
        g = self.tracer_guards.get(name)
        if g is not None and g < line:
            return True
        return any(n == name and s <= line <= e
                   for n, s, e in self.concrete_regions)

    # ------------------------------------------------------------- expr
    def expr(self, e: Optional[ast.AST]) -> bool:
        if e is None or isinstance(e, (ast.Constant, ast.Lambda)):
            return False
        if isinstance(e, ast.BoolOp):
            # `isinstance(x, int) and x == 0` — the guard proves x is a
            # host scalar for the rest of the chain (classic static/traced
            # dispatch idiom, e.g. prefill-vs-decode on position_offset)
            guarded: Set[str] = set()
            for v in e.values:
                if isinstance(v, ast.Call) and isinstance(v.func, ast.Name) \
                        and v.func.id == "isinstance" and v.args \
                        and isinstance(v.args[0], ast.Name):
                    guarded.add(v.args[0].id)
                    continue
                removed = guarded & self.names
                self.names -= removed
                try:
                    if self.expr(v):
                        return True
                finally:
                    self.names |= removed
            return False
        if isinstance(e, ast.Name):
            return e.id in self.names
        if isinstance(e, ast.Attribute):
            if e.attr in _STATIC_ATTRS:
                return False
            return self.expr(e.value)
        if isinstance(e, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in e.ops):
                return False
            return self.expr(e.left) or any(self.expr(c)
                                            for c in e.comparators)
        if isinstance(e, ast.Call):
            f = e.func
            if isinstance(f, ast.Name) and f.id in _HOST_RESULT_CALLS:
                return False
            path = dotted_path(f)
            if path and path[-1] in ("asarray", "array", "device_get",
                                     "item", "stack", "tolist") \
                    and _numpy_rooted(self.fi, path):
                return False
            if path and path[-1] in _SYNC_TERMINALS:
                return False
            return (any(self.expr(a) for a in e.args)
                    or any(self.expr(k.value) for k in e.keywords)
                    or self.expr(f))
        return any(self.expr(c) for c in ast.iter_child_nodes(e)
                   if isinstance(c, ast.expr))


def _default_seeds(fi: FunctionInfo) -> Set[str]:
    out: Set[str] = set()
    for p in fi.params:
        if p in ("self", "cls") or p in fi.statics \
                or p in _UNTAINTED_PARAM_NAMES:
            continue
        d = fi.defaults.get(p)
        if isinstance(d, ast.Constant) and isinstance(d.value, (bool, str)):
            continue
        out.add(p)
    return out


def _map_call_args(call: ast.Call, callee: FunctionInfo,
                   bound: bool) -> Optional[Dict[str, ast.AST]]:
    """Positional+keyword call args mapped onto callee param names.
    ``bound``: the call was ``self.m(...)`` / ``obj.m(...)`` so the
    callee's leading ``self`` is not in the arg list. None when *args
    makes the mapping unreliable."""
    params = callee.params
    if params[:1] in (["self"], ["cls"]):
        if not bound:
            return None
        params = params[1:]
    out: Dict[str, ast.AST] = {}
    for i, a in enumerate(call.args):
        if isinstance(a, ast.Starred):
            break
        if i < len(params):
            out[params[i]] = a
    for kw in call.keywords:
        if kw.arg is not None:
            out[kw.arg] = kw.value
    return out


def build_taints(project: Project, cg: CallGraph) -> Dict[str, Taint]:
    """Taint for every trace-reachable function, with one round of
    interprocedural refinement: a non-root callee param that every
    resolved traced caller feeds an untraced value (e.g. ``top_k``
    threaded down from a jit static) is cleared."""
    reach = [f for f in project.functions.values() if f.trace_reachable]
    seeds = {f.qualname: _default_seeds(f) for f in reach}
    taints = {f.qualname: Taint(f, seeds[f.qualname]) for f in reach}
    for _ in range(2):
        passed_tainted: Dict[str, Set[str]] = {}
        passed_any: Dict[str, Set[str]] = {}
        for caller, call, callee in cg.call_edges:
            if not (caller.trace_reachable and callee.trace_reachable
                    and not callee.trace_root):
                continue
            bound = isinstance(call.func, ast.Attribute)
            mapping = _map_call_args(call, callee, bound)
            if mapping is None:
                # unknown mapping: keep every default-tainted param tainted
                passed_tainted.setdefault(callee.qualname, set()).update(
                    seeds[callee.qualname])
                passed_any.setdefault(callee.qualname, set()).update(
                    seeds[callee.qualname])
                continue
            t = taints[caller.qualname]
            for p, expr in mapping.items():
                passed_any.setdefault(callee.qualname, set()).add(p)
                if t.expr(expr):
                    passed_tainted.setdefault(callee.qualname,
                                              set()).add(p)
        changed = False
        for f in reach:
            if f.trace_root or f.qualname not in passed_any:
                continue
            base = _default_seeds(f)
            # a param no resolved caller passes holds its default, and a
            # constant default (``top_k=0``, ``use_top_p=None``) is no tracer
            new = {p for p in base
                   if p in passed_tainted.get(f.qualname, set())
                   or (p not in passed_any[f.qualname]
                       and not isinstance(f.defaults.get(p), ast.Constant))}
            if new != seeds[f.qualname]:
                seeds[f.qualname] = new
                taints[f.qualname] = Taint(f, new)
                changed = True
        if not changed:
            break
    return taints


def _dispatch_seeds(fi: FunctionInfo, cg: CallGraph) -> Set[str]:
    """Names assigned from a compiled-program call — lazy device values."""
    calls = {id(dc.node) for dc in cg.dispatch_calls.get(fi.qualname, ())}
    out: Set[str] = set()
    if not calls:
        return out
    def names(t) -> List[str]:
        # plain Name targets only — `self.attr = call()` must NOT taint
        # `self` (that poisoned every later `self.*` read in the function)
        if isinstance(t, ast.Name):
            return [t.id]
        if isinstance(t, (ast.Tuple, ast.List)):
            return [n for e in t.elts for n in names(e)]
        if isinstance(t, ast.Starred):
            return names(t.value)
        return []

    for node in ast.walk(fi.node):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call) \
                and id(node.value) in calls:
            for t in node.targets:
                out.update(names(t))
    return out


def _finding(rule: str, fi: FunctionInfo, line: int, msg: str,
             hint: str = "", chain: Tuple[str, ...] = ()) -> Finding:
    return Finding(rule, fi.file.rel, line, msg, symbol=fi.short,
                   snippet=fi.file.snippet(line), chain=chain, hint=hint)


# ================================================================== R1
def run_r1(project: Project, cg: CallGraph,
           taints: Dict[str, Taint]) -> List[Finding]:
    out: List[Finding] = []
    for fi in _timed_functions(project):
        chain = fi.trace_chain if fi.trace_reachable else ()
        ctx = ("inside trace-reachable code — this would sync (or fail) "
               "at trace time" if fi.trace_reachable
               else "in a compiled-dispatch hot path"
               if fi.dispatch else "host sync")
        # --- explicit sync primitives, everywhere
        for call in cg.own_calls(fi):
            path = dotted_path(call.func)
            if path and path[-1] in _SYNC_TERMINALS \
                    and _jax_rooted(fi, path):
                out.append(_finding(
                    "R1", fi, call.lineno,
                    f"`{'.'.join(path)}` {ctx}",
                    hint="move the sync out of the hot path, batch it "
                         "with other reads, or suppress with a reason",
                    chain=chain))
            elif isinstance(call.func, ast.Attribute) \
                    and call.func.attr == "item" and not call.args \
                    and not call.keywords:
                out.append(_finding(
                    "R1", fi, call.lineno,
                    f"`.item()` {ctx} — one scalar per round-trip",
                    hint="batch reads via one jax.device_get, or "
                         "suppress with a reason", chain=chain))
            elif isinstance(call.func, ast.Attribute) \
                    and call.func.attr == "block_until_ready":
                # method form `arr.block_until_ready()` — same sync as
                # the jax.block_until_ready function form
                out.append(_finding(
                    "R1", fi, call.lineno,
                    f"`.block_until_ready()` {ctx}",
                    hint="move the sync out of the hot path, batch it "
                         "with other reads, or suppress with a reason",
                    chain=chain))
        # --- implicit syncs on traced values
        if fi.trace_reachable:
            t = taints.get(fi.qualname)
            if t is not None:
                out.extend(_implicit_syncs(fi, t, chain, traced=True))
        elif cg.dispatch_calls.get(fi.qualname):
            lazy = _dispatch_seeds(fi, cg)
            if lazy:
                t = Taint(fi, lazy)
                out.extend(_implicit_syncs(fi, t, (), traced=False))
    return out


def _implicit_syncs(fi: FunctionInfo, t: Taint, chain, traced: bool):
    out: List[Finding] = []
    what = "traced value" if traced else "lazy value from a compiled call"
    for call in cg_own_calls_cached(fi):
        f = call.func
        args_tainted = [a for a in call.args if t.expr(a)]
        # every tainted NAME reaching the call proven concrete by a Tracer
        # guard (`int(jnp.max(lengths))` under `if not isinstance(lengths,
        # Tracer):` — the tainted arg is a Call, the guarded name inside)
        names_tainted = [n.id for a in args_tainted for n in ast.walk(a)
                         if isinstance(n, ast.Name) and n.id in t.names]
        if names_tainted and all(t.guarded(n, call.lineno)
                                 for n in names_tainted):
            continue
        if isinstance(f, ast.Name) and f.id in _HOST_CASTS and args_tainted:
            out.append(_finding(
                "R1", fi, call.lineno,
                f"`{f.id}()` on {what} `{ast.unparse(args_tainted[0])}` "
                f"forces a host sync",
                hint="keep the value on device (jnp ops / jnp.where), or "
                     "read it lazily in a batched device_get",
                chain=chain))
            continue
        path = dotted_path(f)
        if path and path[-1] in ("asarray", "array") \
                and _numpy_rooted(fi, path) and args_tainted:
            out.append(_finding(
                "R1", fi, call.lineno,
                f"`{'.'.join(path)}` on {what} "
                f"`{ast.unparse(args_tainted[0])}` forces a host transfer",
                hint="use jnp.asarray under trace; for dispatch results "
                     "batch all reads into ONE jax.device_get",
                chain=chain))
            continue
        if traced and isinstance(f, ast.Name) and f.id == "print" \
                and args_tainted:
            out.append(_finding(
                "R1", fi, call.lineno,
                "`print` of a traced value runs at trace time (or syncs); "
                "use jax.debug.print",
                hint="jax.debug.print(\"{x}\", x=...) stays in-graph",
                chain=chain))
    return out


_OWN_CALLS_CACHE: Dict[str, List[ast.Call]] = {}
_CG_REF: Optional[CallGraph] = None
_TIMER: Optional[FileTimer] = None


def cg_own_calls_cached(fi: FunctionInfo) -> List[ast.Call]:
    got = _OWN_CALLS_CACHE.get(fi.qualname)
    if got is None:
        got = _OWN_CALLS_CACHE[fi.qualname] = _CG_REF.own_calls(fi)
    return got


def _timed_functions(project: Project):
    items = project.functions.values()
    if _TIMER is None:
        return iter(items)
    return _TIMER.timed(items, lambda fi: fi.file.rel)


# ================================================================== R2
def run_r2(project: Project, cg: CallGraph,
           taints: Dict[str, Taint]) -> List[Finding]:
    out: List[Finding] = []
    for fi in _timed_functions(project):
        t = taints.get(fi.qualname)
        if fi.trace_reachable and t is not None:
            out.extend(_branch_hazards(fi, t))
        out.extend(_jit_in_hot_code(fi, cg))
        out.extend(_unhashable_statics(fi, cg))
    return out


def _branch_hazards(fi: FunctionInfo, t: Taint) -> List[Finding]:
    out: List[Finding] = []
    chain = fi.trace_chain

    def tainted_names(e) -> List[str]:
        return [n.id for n in ast.walk(e) if isinstance(n, ast.Name)
                and n.id in t.names]

    def ok(e, line) -> bool:
        names = tainted_names(e)
        return bool(names) and all(t.guarded(n, line) for n in names)

    for node in ast.walk(fi.node):
        if isinstance(node, (ast.If, ast.While)) and t.expr(node.test) \
                and not ok(node.test, node.lineno):
            kind = "if" if isinstance(node, ast.If) else "while"
            out.append(_finding(
                "R2", fi, node.lineno,
                f"Python `{kind}` branches on a traced value — every "
                f"distinct value retraces (or fails to trace at all)",
                hint="use jnp.where / lax.cond / lax.select, or hoist the "
                     "decision to a static argument", chain=chain))
        elif isinstance(node, ast.IfExp) and t.expr(node.test) \
                and not ok(node.test, node.lineno):
            out.append(_finding(
                "R2", fi, node.lineno,
                "conditional expression branches on a traced value",
                hint="jnp.where(cond, a, b)", chain=chain))
        elif isinstance(node, ast.Assert) and t.expr(node.test):
            out.append(_finding(
                "R2", fi, node.lineno,
                "assert on a traced value concretizes it at trace time",
                hint="use checkify / debug.check, or assert on .shape",
                chain=chain))
        elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                               ast.GeneratorExp)):
            for gen in node.generators:
                for cond in gen.ifs:
                    if t.expr(cond):
                        out.append(_finding(
                            "R2", fi, cond.lineno,
                            "comprehension filters on a traced value",
                            hint="mask with jnp.where instead of "
                                 "filtering", chain=chain))
        elif isinstance(node, ast.JoinedStr):
            for v in node.values:
                if isinstance(v, ast.FormattedValue) and t.expr(v.value):
                    out.append(_finding(
                        "R2", fi, node.lineno,
                        "f-string formats a traced value (concretizes at "
                        "trace time; bakes ONE traced repr per compile)",
                        hint="format after a device_get outside the "
                             "traced code, or use jax.debug.print",
                        chain=chain))
                    break
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "format" \
                and isinstance(node.func.value, (ast.Constant,
                                                 ast.JoinedStr)) \
                and any(t.expr(a) for a in node.args):
            out.append(_finding(
                "R2", fi, node.lineno,
                "str.format of a traced value concretizes it",
                chain=chain))
    return out


def _jit_in_hot_code(fi: FunctionInfo, cg: CallGraph) -> List[Finding]:
    out: List[Finding] = []

    def walk(node, loop_depth):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            d = loop_depth + (1 if isinstance(child, (ast.For, ast.While))
                              else 0)
            if isinstance(child, ast.Call) \
                    and cg.is_jit_callee(fi, child.func):
                if loop_depth > 0:
                    out.append(_finding(
                        "R2", fi, child.lineno,
                        "jax.jit called inside a loop — a fresh compiled "
                        "callable (and cache entry) per iteration",
                        hint="hoist the jit() out of the loop and reuse "
                             "the compiled callable"))
                elif fi.trace_reachable:
                    out.append(_finding(
                        "R2", fi, child.lineno,
                        "jax.jit called inside trace-reachable code",
                        hint="compile once at construction time",
                        chain=fi.trace_chain))
            walk(child, d)

    walk(fi.node, 0)
    return out


def _unhashable_statics(fi: FunctionInfo, cg: CallGraph) -> List[Finding]:
    out: List[Finding] = []
    for dc in cg.dispatch_calls.get(fi.qualname, ()):
        info = dc.compiled
        if not info.statics:
            continue
        target = info.target
        mapping = None
        if target is not None:
            mapping = _map_call_args(dc.node, target, bound=True)
        if mapping is None:
            mapping = {kw.arg: kw.value for kw in dc.node.keywords
                       if kw.arg}
        for name, expr in mapping.items():
            if name in info.statics and isinstance(
                    expr, (ast.List, ast.Dict, ast.Set)):
                out.append(_finding(
                    "R2", fi, expr.lineno,
                    f"unhashable literal passed for static jit arg "
                    f"`{name}` — raises (or defeats the compile cache)",
                    hint="pass a tuple / frozen value"))
    return out


# ================================================================== R3
def run_r3(project: Project, cg: CallGraph) -> List[Finding]:
    out: List[Finding] = []
    for qual, dcalls in cg.dispatch_calls.items():
        fi = project.functions[qual]
        donating = [dc for dc in dcalls if dc.compiled.donate]
        if donating:
            out.extend(_donation_scan(fi, donating))
    return out


@dataclass
class _VarUse:
    line: int
    write: bool


def _var_id(expr) -> Optional[Tuple[str, str]]:
    if isinstance(expr, ast.Name):
        return ("local", expr.id)
    if isinstance(expr, ast.Attribute) and isinstance(expr.value, ast.Name) \
            and expr.value.id == "self":
        return ("attr", expr.attr)
    return None


def _collect_uses(fi: FunctionInfo) -> Dict[Tuple[str, str], List[_VarUse]]:
    uses: Dict[Tuple[str, str], List[_VarUse]] = {}
    for node in ast.walk(fi.node):
        vid = _var_id(node) if isinstance(node, (ast.Name,
                                                 ast.Attribute)) else None
        if vid is None:
            continue
        if isinstance(node, ast.Attribute) and not isinstance(
                node.ctx, (ast.Load, ast.Store, ast.Del)):
            continue
        write = isinstance(node.ctx, (ast.Store, ast.Del))
        uses.setdefault(vid, []).append(_VarUse(node.lineno, write))
    return uses


def _donation_scan(fi: FunctionInfo, dcalls) -> List[Finding]:
    out: List[Finding] = []
    uses = _collect_uses(fi)
    # map call node id -> (enclosing stmt, loop ancestors)
    ctx: Dict[int, Tuple[ast.stmt, List[ast.stmt]]] = {}

    def walk(node, stmt, loops):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            s = child if isinstance(child, ast.stmt) else stmt
            lp = loops + ([child] if isinstance(child,
                                                (ast.For, ast.While)) else [])
            if isinstance(child, ast.Call):
                ctx[id(child)] = (s, loops)
            walk(child, s, lp)

    walk(fi.node, None, [])
    for dc in dcalls:
        call = dc.node
        stmt, loops = ctx.get(id(call), (None, []))
        if stmt is None:
            continue
        stored: Set[Tuple[str, str]] = set()
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                for n in ast.walk(t):
                    vid = _var_id(n)
                    if vid:
                        stored.add(vid)
        end = getattr(stmt, "end_lineno", stmt.lineno)
        for pos in sorted(dc.compiled.donate):
            if pos >= len(call.args):
                continue
            vid = _var_id(call.args[pos])
            if vid is None:
                continue
            later = [u for u in uses.get(vid, ()) if u.line > end]
            reads = [u.line for u in later if not u.write]
            writes = [u.line for u in later if u.write]
            if vid not in stored and reads and (
                    not writes or min(writes) > min(reads)):
                out.append(_finding(
                    "R3", fi, min(reads),
                    f"`{vid[1]}` was donated to the compiled call at line "
                    f"{call.lineno} (donate_argnums={sorted(dc.compiled.donate)}, "
                    f"{dc.compiled.site}) and is read again here — the "
                    f"buffer may already be overwritten",
                    hint="rebind the name from the call's results, or "
                         "drop it from donate_argnums"))
            if loops and vid not in stored:
                innermost = loops[-1]
                loop_stores = False
                for n in ast.walk(innermost):
                    if isinstance(n, (ast.Name, ast.Attribute)) \
                            and isinstance(getattr(n, "ctx", None),
                                           ast.Store) \
                            and _var_id(n) == vid:
                        loop_stores = True
                        break
                if not loop_stores:
                    out.append(_finding(
                        "R3", fi, call.lineno,
                        f"`{vid[1]}` is donated inside a loop but never "
                        f"reassigned in the loop body — iteration 2 "
                        f"dispatches a donated (dead) buffer",
                        hint="rebind it from the call results each "
                             "iteration"))
    return out


# ================================================================== R4
def _random_consumer_arg(fi: FunctionInfo, call: ast.Call):
    """The key expr if ``call`` is a jax.random sampling op. Recognizes
    every import form: ``jax.random.normal``, ``from jax import random;
    random.normal``, and ``from jax.random import normal; normal``."""
    path = dotted_path(call.func)
    if not path:
        return None
    alias = fi.file.aliases.get(path[0])
    if alias is None:
        head = (path[0],)
    elif alias[0] == "module":
        head = (alias[1],)
    else:   # ("symbol", module, name)
        head = (alias[1], alias[2])
    dotted = ".".join(head + path[1:])
    if not dotted.startswith("jax.random."):
        return None
    name = path[-1]
    if name in _RANDOM_DERIVERS:
        return None
    for kw in call.keywords:
        if kw.arg == "key":
            return kw.value
    if call.args:
        return call.args[0]
    return None


def _consuming_params(project: Project, cg: CallGraph) -> Dict[str, Set[str]]:
    consuming: Dict[str, Set[str]] = {}
    for _ in range(4):
        changed = False
        for fi in project.functions.values():
            mine = consuming.setdefault(fi.qualname, set())
            for call in cg_own_calls_cached(fi):
                arg = _random_consumer_arg(fi, call)
                if isinstance(arg, ast.Name) and arg.id in fi.params \
                        and arg.id not in mine:
                    mine.add(arg.id)
                    changed = True
        for caller, call, callee in cg.call_edges:
            callee_cons = consuming.get(callee.qualname)
            if not callee_cons:
                continue
            bound = isinstance(call.func, ast.Attribute)
            mapping = _map_call_args(call, callee, bound)
            if not mapping:
                continue
            mine = consuming.setdefault(caller.qualname, set())
            for p, expr in mapping.items():
                if p in callee_cons and isinstance(expr, ast.Name) \
                        and expr.id in caller.params \
                        and expr.id not in mine:
                    mine.add(expr.id)
                    changed = True
        if not changed:
            break
    return consuming


class _R4Scanner:
    """Path-aware consumption counting for one function."""

    def __init__(self, fi: FunctionInfo, project: Project, cg: CallGraph,
                 consuming: Dict[str, Set[str]]):
        self.fi = fi
        self.project = project
        self.cg = cg
        self.consuming = consuming
        self.findings: List[Finding] = []
        self._emitted: Set[Tuple[int, str]] = set()

    def run(self) -> List[Finding]:
        self._scan(self.fi.node.body, {})
        return self.findings

    # state: name -> (count, first_line)
    def _consumptions(self, expr) -> List[Tuple[str, int]]:
        out = []
        for node in ast.walk(expr):
            if not isinstance(node, ast.Call):
                continue
            arg = _random_consumer_arg(self.fi, node)
            if isinstance(arg, ast.Name):
                out.append((arg.id, node.lineno))
                continue
            # project calls whose params are (transitively) key-consuming
            callees = self.cg.resolve_call(self.fi, node)
            for callee in callees:
                cons = self.consuming.get(callee.qualname) or set()
                if not cons:
                    continue
                mapping = _map_call_args(
                    node, callee, isinstance(node.func, ast.Attribute))
                if not mapping:
                    continue
                for p, e in mapping.items():
                    if p in cons and isinstance(e, ast.Name):
                        out.append((e.id, node.lineno))
        return out

    def _consume(self, expr, state, in_loop: bool) -> None:
        if expr is None:
            return
        for name, line in self._consumptions(expr):
            count, first = state.get(name, (0, None))
            count += 1
            if count == 1:
                state[name] = (1, line)
                continue
            state[name] = (count, first)
            if (line, name) in self._emitted:
                continue
            self._emitted.add((line, name))
            if first == line and in_loop:
                msg = (f"PRNG key `{name}` is consumed inside a loop "
                       f"without being split/folded per iteration — every "
                       f"iteration draws the SAME randomness")
            else:
                msg = (f"PRNG key `{name}` already consumed at line "
                       f"{first} is consumed again without an "
                       f"interleaving split/fold_in — the two draws "
                       f"correlate")
            self.findings.append(_finding(
                "R4", self.fi, line, msg,
                hint="key, sub = jax.random.split(key) (or fold_in a "
                     "step/row index) before each use",
                chain=self.fi.trace_chain))

    def _rebind(self, targets, state) -> None:
        for t in targets:
            for n in ast.walk(t):
                if isinstance(n, ast.Name):
                    state[n.id] = (0, None)

    def _scan(self, stmts: Sequence[ast.stmt], state,
              in_loop: bool = False) -> bool:
        """Returns False when the block terminates (return/raise/...)."""
        for s in stmts:
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                continue
            if isinstance(s, (ast.Return, ast.Raise)):
                self._consume(getattr(s, "value", None) or
                              getattr(s, "exc", None), state, in_loop)
                return False
            if isinstance(s, (ast.Break, ast.Continue)):
                return False
            if isinstance(s, ast.Assign):
                self._consume(s.value, state, in_loop)
                self._rebind(s.targets, state)
            elif isinstance(s, ast.AugAssign):
                self._consume(s.value, state, in_loop)
                self._rebind([s.target], state)
            elif isinstance(s, ast.AnnAssign):
                if s.value is not None:
                    self._consume(s.value, state, in_loop)
                    self._rebind([s.target], state)
            elif isinstance(s, ast.Expr):
                self._consume(s.value, state, in_loop)
            elif isinstance(s, ast.If):
                self._consume(s.test, state, in_loop)
                s1 = dict(state)
                s2 = dict(state)
                f1 = self._scan(s.body, s1, in_loop)
                f2 = self._scan(s.orelse, s2, in_loop)
                if f1 and f2:
                    merged = {}
                    for k in set(s1) | set(s2):
                        c1, l1 = s1.get(k, (0, None))
                        c2, l2 = s2.get(k, (0, None))
                        merged[k] = (max(c1, c2), l1 if c1 >= c2 else l2)
                    state.clear()
                    state.update(merged)
                elif f1:
                    state.clear()
                    state.update(s1)
                elif f2:
                    state.clear()
                    state.update(s2)
                else:
                    return False
            elif isinstance(s, (ast.For, ast.While)):
                if isinstance(s, ast.For):
                    self._consume(s.iter, state, in_loop)
                    self._rebind([s.target], state)
                else:
                    self._consume(s.test, state, in_loop)
                # two symbolic iterations: a key consumed but not rebound
                # inside the body trips the counter on pass 2
                self._scan(s.body, state, in_loop=True)
                self._scan(s.body, state, in_loop=True)
            elif isinstance(s, (ast.With, ast.AsyncWith)):
                for item in s.items:
                    self._consume(item.context_expr, state, in_loop)
                    if item.optional_vars is not None:
                        self._rebind([item.optional_vars], state)
                if not self._scan(s.body, state, in_loop):
                    return False
            elif isinstance(s, ast.Try):
                self._scan(s.body, state, in_loop)
                for h in s.handlers:
                    self._scan(h.body, dict(state), in_loop)
                self._scan(s.finalbody, state, in_loop)
            else:
                for child in ast.iter_child_nodes(s):
                    if isinstance(child, ast.expr):
                        self._consume(child, state, in_loop)
        return True


def run_r4(project: Project, cg: CallGraph) -> List[Finding]:
    consuming = _consuming_params(project, cg)
    out: List[Finding] = []
    for fi in _timed_functions(project):
        out.extend(_R4Scanner(fi, project, cg, consuming).run())
    return out


# ================================================================== R5
@dataclass
class _Access:
    attr: str
    method: FunctionInfo
    line: int
    write: bool
    locks: frozenset


def _method_accesses(ci: ClassInfo, fi: FunctionInfo):
    """(accesses, intra-class calls with held locks) for one method."""
    accesses: List[_Access] = []
    calls: List[Tuple[str, frozenset]] = []

    def walk_stmt(node, held):
        # one statement subtree under a lock context
        if isinstance(node, ast.With):
            locks = set(held)
            for item in node.items:
                e = item.context_expr
                if isinstance(e, ast.Attribute) \
                        and isinstance(e.value, ast.Name) \
                        and e.value.id == "self" \
                        and e.attr in ci.lock_attrs:
                    locks.add(e.attr)
            for st in node.body:
                walk_stmt(st, frozenset(locks))
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id == "self" \
                and node.func.attr in ci.methods:
            calls.append((node.func.attr, held))
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name) \
                and node.value.id == "self" \
                and node.attr not in ci.lock_attrs \
                and node.attr not in ci.methods \
                and not node.attr.isupper():
            accesses.append(_Access(
                node.attr, fi, node.lineno,
                isinstance(node.ctx, (ast.Store, ast.Del)), held))
        for child in ast.iter_child_nodes(node):
            walk_stmt(child, held)

    for st in fi.node.body:
        walk_stmt(st, frozenset())
    return accesses, calls


def run_r5(project: Project, cg: CallGraph) -> List[Finding]:
    out: List[Finding] = []
    for ci in project.classes.values():
        if not ci.lock_attrs:
            continue
        involved = ci.qualname in cg.threaded_classes or any(
            m.thread_reachable for m in ci.methods.values())
        if not involved:
            continue
        per_method: Dict[str, Tuple[List[_Access], list]] = {}
        for name, fi in ci.methods.items():
            if name == "__init__":
                continue
            per_method[name] = _method_accesses(ci, fi)
        # lock context inherited by private helpers only ever called
        # (intra-class) with the lock held
        inherited: Dict[str, frozenset] = {m: frozenset()
                                           for m in per_method}
        for _ in range(3):
            call_locks: Dict[str, List[frozenset]] = {}
            for caller, (_, calls) in per_method.items():
                for callee, held in calls:
                    eff = held | inherited.get(caller, frozenset())
                    call_locks.setdefault(callee, []).append(eff)
            new = dict(inherited)
            for m, sites in call_locks.items():
                fi = ci.methods.get(m)
                if fi is None or not m.startswith("_") or fi.thread_root:
                    continue
                ctx = frozenset.intersection(*[frozenset(s)
                                               for s in sites])
                new[m] = ctx
            if new == inherited:
                break
            inherited = new
        # verdicts per attribute
        by_attr: Dict[str, List[_Access]] = {}
        for m, (accesses, _) in per_method.items():
            extra = inherited.get(m, frozenset())
            for a in accesses:
                a = _Access(a.attr, a.method, a.line, a.write,
                            a.locks | extra)
                by_attr.setdefault(a.attr, []).append(a)
        for attr, sites in by_attr.items():
            methods = {a.method.name for a in sites}
            if len(methods) < 2 or not any(a.write for a in sites):
                continue
            for lock in ci.lock_attrs:
                guarded = [a for a in sites if lock in a.locks]
                unguarded = [a for a in sites if lock not in a.locks]
                if len(guarded) < 2 or len(guarded) <= len(unguarded):
                    continue
                for a in unguarded:
                    out.append(Finding(
                        "R5", ci.file.rel, a.line,
                        f"`self.{attr}` is accessed under `self.{lock}` "
                        f"at {len(guarded)} site(s) in {ci.name} but "
                        f"without it here, and {ci.name} runs a "
                        f"background thread — torn read/lost update risk",
                        symbol=f"{ci.name}.{a.method.name}",
                        snippet=ci.file.snippet(a.line),
                        hint=f"take `with self.{lock}:` around this "
                             f"access (majority-use lock inference)"))
                break
    return out


# ============================================================== driver
@dataclass
class RulesOutput:
    findings: List[Finding] = field(default_factory=list)
    lock_graph: dict = field(default_factory=dict)
    lifecycle_graph: dict = field(default_factory=dict)
    rule_ms: Dict[str, float] = field(default_factory=dict)


def run_rules(project: Project, cg: CallGraph,
              timer: Optional[FileTimer] = None) -> RulesOutput:
    from .lifecycle import analyze_lifecycle
    from .locks import analyze_locks
    from .rpccheck import analyze_rpc
    from .sharding import analyze_sharding
    from .spmd import analyze_spmd

    global _CG_REF, _TIMER
    _CG_REF = cg
    _TIMER = timer
    _OWN_CALLS_CACHE.clear()
    out = RulesOutput()

    def staged(rule: str, fn):
        t0 = time.perf_counter()
        got = fn()
        out.rule_ms[rule] = round(
            out.rule_ms.get(rule, 0.0)
            + (time.perf_counter() - t0) * 1e3, 3)
        return got

    taints = staged("taint", lambda: build_taints(project, cg))
    out.findings.extend(staged("R1", lambda: run_r1(project, cg, taints)))
    out.findings.extend(staged("R2", lambda: run_r2(project, cg, taints)))
    out.findings.extend(staged("R3", lambda: run_r3(project, cg)))
    out.findings.extend(staged("R4", lambda: run_r4(project, cg)))
    out.findings.extend(staged("R5", lambda: run_r5(project, cg)))
    locks = staged("R6+R7", lambda: analyze_locks(project, cg))
    out.findings.extend(locks.findings)
    out.lock_graph = locks.lock_graph()
    out.findings.extend(staged("R8",
                               lambda: analyze_sharding(project, cg)))
    life = staged("R9", lambda: analyze_lifecycle(project, cg))
    out.findings.extend(life.findings)
    out.lifecycle_graph = life.lifecycle_graph()
    out.findings.extend(staged("R10", lambda: analyze_spmd(project, cg)))
    out.findings.extend(staged("R11", lambda: analyze_rpc(project, cg)))
    _TIMER = None
    return out
