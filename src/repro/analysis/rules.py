"""The pluggable rule registry and the five core slablint rules.

A rule is a callable ``run(project) -> list[Finding]`` registered via
``@rule``. Adding a rule means writing one function; the CLI, baseline
and JSON plumbing pick it up automatically.

Precision notes (shared by HS001/RT001, which use the taint engine):

* Taint is intra-procedural and flow-insensitive across branches but
  forward in program order (loop bodies get two passes so taint
  introduced late in a body reaches sinks earlier in it).
* Sources: calls rooted at ``jnp``/``jax``/``lax``, calls to the
  curated device-producing surface (:data:`DEVICE_FNS`), calls to any
  function the project knows is jax.jit-wrapped, method calls on
  tainted values, and the device-buffer attributes
  (:data:`TAINTED_ATTRS`). Function *parameters* are not tainted — a
  deliberate precision tradeoff documented in docs/static_analysis.md.
* Sinks lexically inside ``with deliberate_sync(...):`` are skipped:
  the static view and the runtime guard (:mod:`repro.analysis.guards`)
  agree on what a deliberate sync is.
"""
from __future__ import annotations

import ast
from typing import Callable, Dict, List, Optional, Set, Tuple

from repro.analysis.callgraph import (FunctionInfo, ModuleInfo, Project,
                                      _dotted, is_jit_expr)
from repro.analysis.findings import Finding

RULES: Dict[str, dict] = {}


def rule(rule_id: str, name: str, hint: str) -> Callable:
    def register(fn: Callable) -> Callable:
        RULES[rule_id] = {"id": rule_id, "name": name, "hint": hint,
                          "run": fn}
        return fn
    return register


def run_rules(project: Project,
              only: Optional[Set[str]] = None) -> List[Finding]:
    out: List[Finding] = []
    for rid, r in sorted(RULES.items()):
        if only and rid not in only:
            continue
        out.extend(r["run"](project))
    out.sort(key=lambda f: (f.path, f.line, f.rule_id))
    return out


# ---------------------------------------------------------------------------
# Taint engine (HS001 + RT001c share it)
# ---------------------------------------------------------------------------

# Curated device-producing call surface: the dispatch-discipline APIs
# whose results live in accelerator memory.
DEVICE_FNS = {
    "histogram_distance_device", "_dense_distance", "drift_gate_fleet",
    "fold_gate_fleet",
    "waste_eval", "waste_eval_fleet", "waste_eval_pallas",
    "waste_eval_fleet_pallas", "waste_eval_ref", "waste_eval_fleet_ref",
    "sketch_update", "sketch_update_pallas", "sketch_update_ref",
    "sketch_window_pallas", "sketch_window_ref", "flush_window",
    "observe_window", "slab_decode_attention",
    "slab_decode_attention_pallas", "slab_decode_attention_ref",
    "waste_jax", "waste_batch_jax",
}
DEVICE_ROOTS = {"jnp", "jax", "lax", "_jnp"}
TAINTED_ATTRS = {"weights_device", "support_device", "_weights"}
SHAPE_FNS = {"zeros", "ones", "full", "empty", "arange", "tile",
             "repeat", "broadcast_to", "reshape", "eye", "linspace"}
HOST_CASTS = {"float", "int", "bool"}
ITEM_SINKS = {"item", "tolist"}


def _call_root(func: ast.AST) -> Optional[str]:
    d = _dotted(func)
    return d.split(".")[0] if d else None


def _bare_callee(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


class TaintWalk:
    """One pass over a function body: tainted names, sinks, shape
    hazards. ``sinks`` entries are ``(node, symbol, tainted_ok)``."""

    def __init__(self, mod: ModuleInfo, jitted: Set[str]):
        self.mod = mod
        self.jitted = jitted
        self.tainted: Set[str] = set()
        self.host_derived: Set[str] = set()   # int(x)/float(x) of tainted
        self.sinks: List[Tuple[ast.AST, str]] = []
        self.shape_hazards: List[Tuple[ast.AST, str]] = []
        self.allow = 0                        # deliberate_sync depth
        self._seen_sinks: Set[int] = set()    # loop bodies scan twice

    # -- expression taint -------------------------------------------------
    def is_tainted(self, e: ast.AST) -> bool:
        if isinstance(e, ast.Name):
            return e.id in self.tainted
        if isinstance(e, ast.Attribute):
            if e.attr in TAINTED_ATTRS:
                return True
            return self.is_tainted(e.value)
        if isinstance(e, ast.Subscript):
            return self.is_tainted(e.value)
        if isinstance(e, (ast.BinOp,)):
            return self.is_tainted(e.left) or self.is_tainted(e.right)
        if isinstance(e, ast.UnaryOp):
            return self.is_tainted(e.operand)
        if isinstance(e, ast.Compare):
            return self.is_tainted(e.left) or any(
                self.is_tainted(c) for c in e.comparators)
        if isinstance(e, (ast.Tuple, ast.List)):
            return any(self.is_tainted(x) for x in e.elts)
        if isinstance(e, ast.IfExp):
            return self.is_tainted(e.body) or self.is_tainted(e.orelse)
        if isinstance(e, ast.Starred):
            return self.is_tainted(e.value)
        if isinstance(e, ast.Call):
            return self.call_produces_device(e)
        return False

    def call_produces_device(self, call: ast.Call) -> bool:
        root = _call_root(call.func)
        if root in DEVICE_ROOTS and not self._is_device_get(call.func):
            return True
        name = _bare_callee(call.func)
        if name in DEVICE_FNS or (name in self.jitted):
            return True
        # curried transforms: jax.vmap(f)(x), jit(f)(x) — the outer
        # call's result is device-valued iff the inner factory is
        if isinstance(call.func, ast.Call):
            return self.call_produces_device(call.func)
        # method on a tainted value stays tainted (x.sum(), x.astype())
        if isinstance(call.func, ast.Attribute) and self.is_tainted(
                call.func.value):
            return name not in ITEM_SINKS
        return False

    @staticmethod
    def _is_device_get(func: ast.AST) -> bool:
        d = _dotted(func)
        return bool(d and d.split(".")[-1] == "device_get")

    # -- sinks ------------------------------------------------------------
    def _check_call(self, call: ast.Call) -> None:
        name = _bare_callee(call.func)
        root = _call_root(call.func)
        args_tainted = any(self.is_tainted(a) for a in call.args)
        if isinstance(call.func, ast.Name) and name in HOST_CASTS \
                and args_tainted:
            self._sink(call, name)
        elif isinstance(call.func, ast.Attribute) \
                and name in ITEM_SINKS \
                and self.is_tainted(call.func.value):
            self._sink(call, name)
        elif root == "np" and name in ("asarray", "array") and args_tainted:
            self._sink(call, f"np.{name}")
        elif self._is_device_get(call.func) and args_tainted:
            self._sink(call, "device_get")
        elif root in DEVICE_ROOTS and name in SHAPE_FNS:
            for a in list(call.args) + [k.value for k in call.keywords]:
                if any(isinstance(n, ast.Name)
                       and n.id in self.host_derived
                       for n in ast.walk(a)):
                    if id(call) not in self._seen_sinks:
                        self._seen_sinks.add(id(call))
                        self.shape_hazards.append((call, name))
                    break

    def _sink(self, node: ast.AST, symbol: str) -> None:
        if not self.allow and id(node) not in self._seen_sinks:
            self._seen_sinks.add(id(node))
            self.sinks.append((node, symbol))

    def _scan_exprs(self, stmt: ast.stmt) -> None:
        """Sink-check every call in ``stmt`` that is not inside a nested
        function definition (those are separate functions)."""
        for node in ast.walk(stmt):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)) and node is not stmt:
                continue
            if isinstance(node, ast.Call):
                self._check_call(node)

    # -- statements -------------------------------------------------------
    def _assign_target(self, target: ast.AST, tainted: bool,
                       host: bool) -> None:
        if isinstance(target, ast.Name):
            (self.tainted.add if tainted else
             self.tainted.discard)(target.id)
            if host:
                self.host_derived.add(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for el in target.elts:
                self._assign_target(el, tainted, host)

    def _value_is_host_cast(self, value: ast.AST) -> bool:
        return (isinstance(value, ast.Call)
                and isinstance(value.func, ast.Name)
                and value.func.id in HOST_CASTS
                and any(self.is_tainted(a) for a in value.args))

    def run(self, body: List[ast.stmt]) -> None:
        for stmt in body:
            self._stmt(stmt)

    def _stmt(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return
        if isinstance(stmt, ast.With):
            deliberate = any(
                isinstance(item.context_expr, ast.Call)
                and (_dotted(item.context_expr.func) or "").split(".")[-1]
                == "deliberate_sync"
                for item in stmt.items)
            if deliberate:
                self.allow += 1
            for s in stmt.body:
                self._stmt(s)
            if deliberate:
                self.allow -= 1
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._scan_exprs(ast.Expr(stmt.iter))
            if self.is_tainted(stmt.iter):
                self._assign_target(stmt.target, True, False)
            for _ in range(2):            # crude fixpoint for carried taint
                for s in stmt.body:
                    self._stmt(s)
            for s in stmt.orelse:
                self._stmt(s)
            return
        if isinstance(stmt, ast.While):
            self._scan_exprs(ast.Expr(stmt.test))
            for _ in range(2):
                for s in stmt.body:
                    self._stmt(s)
            for s in stmt.orelse:
                self._stmt(s)
            return
        if isinstance(stmt, (ast.If,)):
            self._scan_exprs(ast.Expr(stmt.test))
            for s in stmt.body + stmt.orelse:
                self._stmt(s)
            return
        if isinstance(stmt, ast.Try):
            for s in (stmt.body + stmt.orelse + stmt.finalbody
                      + [h for hh in stmt.handlers for h in hh.body]):
                self._stmt(s)
            return
        # leaf statements: sink-check, then propagate assignment taint
        self._scan_exprs(stmt)
        if isinstance(stmt, ast.Assign):
            t = self.is_tainted(stmt.value)
            h = self._value_is_host_cast(stmt.value)
            for target in stmt.targets:
                self._assign_target(target, t, h)
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            self._assign_target(stmt.target, self.is_tainted(stmt.value),
                                self._value_is_host_cast(stmt.value))
        elif isinstance(stmt, ast.AugAssign):
            if self.is_tainted(stmt.value):
                self._assign_target(stmt.target, True, False)


def _taint_function(fn: FunctionInfo, project: Project,
                    jitted: Set[str]) -> TaintWalk:
    walk = TaintWalk(project.modules[fn.path], jitted)
    walk.run(fn.node.body)
    return walk


# ---------------------------------------------------------------------------
# HS001 — host sync in hot path
# ---------------------------------------------------------------------------

@rule("HS001", "host-sync-in-hot-path",
      "wrap a deliberate cadence-boundary readback in "
      "`with deliberate_sync(...):` (repro.analysis.guards), or move the "
      "scalar pull off the hot path")
def host_sync_in_hot_path(project: Project) -> List[Finding]:
    out: List[Finding] = []
    hot = project.hot_reachable()
    for key in sorted(hot):
        fn = project.functions[key]
        walk = _taint_function(fn, project,
                               project.jitted_names(fn.path))
        for node, symbol in walk.sinks:
            out.append(Finding(
                rule_id="HS001", path=fn.path, line=node.lineno,
                qualname=fn.qualname, symbol=symbol,
                message=(f"`{symbol}` materialises a traced/device value "
                         f"on host inside hot path `{fn.qualname}`"),
                hint=RULES["HS001"]["hint"]))
    return out


# ---------------------------------------------------------------------------
# DN001 — donation
# ---------------------------------------------------------------------------

# First-positional-parameter names that denote a large carried device
# buffer: jitting such a function without donation doubles its live
# footprint and forces a copy per dispatch.
CARRY_PARAMS = {"state", "carry", "buf", "buffers", "sketch", "fleet"}


def _first_param(node) -> Optional[str]:
    args = node.args
    pos = list(args.posonlyargs) + list(args.args)
    if pos and pos[0].arg in ("self", "cls"):
        pos = pos[1:]
    return pos[0].arg if pos else None


@rule("DN001", "undonated-carry-buffer",
      "pass donate_argnums=(0,) to jax.jit (or baseline it if every "
      "caller genuinely retains the input buffer)")
def donation(project: Project) -> List[Finding]:
    out: List[Finding] = []

    def check(fn_node, mod: ModuleInfo, qual: str, donates: bool) -> None:
        first = _first_param(fn_node)
        if donates or first not in CARRY_PARAMS:
            return
        out.append(Finding(
            rule_id="DN001", path=mod.path, line=fn_node.lineno,
            qualname=qual, symbol=getattr(fn_node, "name", "<lambda>"),
            message=(f"jax.jit of `{qual}` carries buffer param "
                     f"`{first}` without donate_argnums"),
            hint=RULES["DN001"]["hint"]))

    for fn in project.functions.values():
        if fn.jitted:
            check(fn.node, project.modules[fn.path], fn.qualname,
                  fn.jit_donates)
    # call-form: jax.jit(local_fn, ...) / jax.jit(lambda: ...)
    for mod in project.modules.values():
        local_defs = {f.name: f for f in mod.functions}
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            j, donates, call = is_jit_expr(node, mod.aliases)
            if not j or call is not node or not node.args:
                continue
            wrapped = node.args[0]
            if isinstance(wrapped, ast.Name) and wrapped.id in local_defs:
                f = local_defs[wrapped.id]
                if f.jitted:       # decorator form already checked
                    continue
                check(f.node, mod, f.qualname, donates)
            elif isinstance(wrapped, ast.Lambda):
                check(wrapped, mod, "<lambda>", donates)
    # de-dup (a def can be reached via decorator and call form)
    seen: Set[str] = set()
    uniq = []
    for f in out:
        if f.fingerprint not in seen:
            seen.add(f.fingerprint)
            uniq.append(f)
    return uniq


# ---------------------------------------------------------------------------
# RT001 — retrace hazards
# ---------------------------------------------------------------------------

@rule("RT001", "retrace-hazard",
      "hoist jit out of the loop behind a keyed cache, close over "
      "hashable config only, and keep runtime-derived scalars out of "
      "shapes/static_argnums")
def retrace_hazard(project: Project) -> List[Finding]:
    out: List[Finding] = []

    # (a) jax.jit applied inside a loop body: a fresh callable (and a
    # fresh trace) per iteration.
    for mod in project.modules.values():
        loops: List[ast.AST] = [n for n in ast.walk(mod.tree)
                                if isinstance(n, (ast.For, ast.While))]
        for loop in loops:
            for node in ast.walk(loop):
                if isinstance(node, ast.Call):
                    j, _, call = is_jit_expr(node, mod.aliases)
                    if j and call is node:
                        out.append(Finding(
                            rule_id="RT001", path=mod.path,
                            line=node.lineno, qualname="<loop>",
                            symbol="jit-in-loop",
                            message=("jax.jit applied inside a loop "
                                     "body retraces every iteration"),
                            hint=RULES["RT001"]["hint"]))

    # (b) jitted closure capturing an enclosing mutable literal: the
    # trace bakes in a snapshot; later mutation is silently ignored (or
    # forces a retrace under static hashing).
    for fn in project.functions.values():
        node = fn.node
        mutable_locals: Set[str] = set()
        for stmt in ast.walk(node):
            if isinstance(stmt, ast.Assign) and isinstance(
                    stmt.value, (ast.List, ast.Dict, ast.Set,
                                 ast.ListComp, ast.DictComp, ast.SetComp)):
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        mutable_locals.add(t.id)
        if not mutable_locals:
            continue
        for inner in ast.walk(node):
            if inner is node or not isinstance(
                    inner, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not any(is_jit_expr(d, project.modules[fn.path].aliases)[0]
                       for d in inner.decorator_list):
                continue
            params = {a.arg for a in (inner.args.posonlyargs
                                      + inner.args.args
                                      + inner.args.kwonlyargs)}
            inner_locals = {t.id for s in ast.walk(inner)
                            if isinstance(s, ast.Assign)
                            for t in s.targets if isinstance(t, ast.Name)}
            for ref in ast.walk(inner):
                if isinstance(ref, ast.Name) and isinstance(
                        ref.ctx, ast.Load) \
                        and ref.id in mutable_locals \
                        and ref.id not in params \
                        and ref.id not in inner_locals:
                    out.append(Finding(
                        rule_id="RT001", path=fn.path, line=inner.lineno,
                        qualname=f"{fn.qualname}.{inner.name}",
                        symbol=f"closure:{ref.id}",
                        message=(f"jitted closure `{inner.name}` "
                                 f"captures mutable `{ref.id}` from "
                                 f"`{fn.qualname}` — trace won't see "
                                 "mutations"),
                        hint=RULES["RT001"]["hint"]))
                    break

    # (c) runtime-derived host scalar flowing into a shape: every new
    # value is a new static shape, i.e. a silent retrace.
    hot = project.hot_reachable()
    for key in sorted(hot):
        fn = project.functions[key]
        walk = _taint_function(fn, project,
                               project.jitted_names(fn.path))
        for node, symbol in walk.shape_hazards:
            out.append(Finding(
                rule_id="RT001", path=fn.path, line=node.lineno,
                qualname=fn.qualname, symbol=f"shape:{symbol}",
                message=(f"runtime-derived scalar feeds `{symbol}` "
                         f"shape in hot path `{fn.qualname}` — "
                         "retraces on every new value"),
                hint=RULES["RT001"]["hint"]))
    return out


# ---------------------------------------------------------------------------
# KC001 — kernel contract
# ---------------------------------------------------------------------------

def _param_names(node) -> Tuple[List[str], List[str]]:
    a = node.args
    pos = [p.arg for p in list(a.posonlyargs) + list(a.args)]
    kw = [p.arg for p in a.kwonlyargs]
    return pos, kw


def _index_map_exprs(call: ast.Call, local_defs: Dict[str, ast.AST]
                     ) -> List[ast.AST]:
    """Return-expression nodes of a BlockSpec's index map, if any."""
    cand: Optional[ast.AST] = None
    if len(call.args) >= 2:
        cand = call.args[1]
    for k in call.keywords:
        if k.arg == "index_map":
            cand = k.value
    if cand is None:
        return []
    if isinstance(cand, ast.Lambda):
        return [cand.body]
    if isinstance(cand, ast.Name) and cand.id in local_defs:
        return [r.value for r in ast.walk(local_defs[cand.id])
                if isinstance(r, ast.Return) and r.value is not None]
    return []


def _element_unclamped(el: ast.AST) -> bool:
    """Arithmetic in an index-map coordinate without a clamp can run
    past the declared BlockSpec bounds."""
    has_arith = any(isinstance(n, ast.BinOp)
                    and not isinstance(n.op, (ast.Mod, ast.FloorDiv))
                    for n in ast.walk(el))
    if not has_arith:
        return False
    for n in ast.walk(el):
        if isinstance(n, ast.Call):
            name = _bare_callee(n.func)
            if name in ("minimum", "min", "clip", "clamp", "mod",
                        "remainder", "where"):
                return False
        if isinstance(n, ast.BinOp) and isinstance(n.op, ast.Mod):
            return False
    return True


@rule("KC001", "kernel-contract",
      "every *_pallas kernel needs an interpret= fallback, a *_ref jnp "
      "oracle with a matching signature, and clamped index-map "
      "arithmetic (jnp.minimum/clip/%)")
def kernel_contract(project: Project) -> List[Finding]:
    out: List[Finding] = []
    kernel_mods = {p: m for p, m in project.modules.items()
                   if "kernels/" in p or p.startswith("kernels")
                   or "/kernels/" in f"/{p}"}
    if not kernel_mods:
        return out
    # all _ref defs anywhere in the kernel package(s)
    refs: Dict[str, ast.AST] = {}
    for mod in kernel_mods.values():
        for fn in mod.functions:
            if fn.name.endswith("_ref") and fn.class_name is None:
                refs[fn.name] = fn.node
    for path, mod in kernel_mods.items():
        for fn in mod.functions:
            if not fn.name.endswith("_pallas") or fn.class_name:
                continue
            pos, kw = _param_names(fn.node)
            if "interpret" not in pos + kw:
                out.append(Finding(
                    rule_id="KC001", path=path, line=fn.node.lineno,
                    qualname=fn.qualname, symbol="interpret",
                    message=(f"kernel `{fn.name}` has no interpret= "
                             "fallback parameter"),
                    hint=RULES["KC001"]["hint"]))
            ref_name = fn.name[:-len("_pallas")] + "_ref"
            ref = refs.get(ref_name)
            if ref is None:
                out.append(Finding(
                    rule_id="KC001", path=path, line=fn.node.lineno,
                    qualname=fn.qualname, symbol="ref-missing",
                    message=(f"kernel `{fn.name}` has no `{ref_name}` "
                             "jnp oracle in the kernels package"),
                    hint=RULES["KC001"]["hint"]))
            else:
                rpos, rkw = _param_names(ref)
                if rpos != pos or not set(rkw) <= set(kw):
                    out.append(Finding(
                        rule_id="KC001", path=path, line=fn.node.lineno,
                        qualname=fn.qualname, symbol="ref-signature",
                        message=(f"`{ref_name}` signature ({rpos}, "
                                 f"kwonly {rkw}) does not match "
                                 f"`{fn.name}` ({pos}, kwonly {kw})"),
                        hint=RULES["KC001"]["hint"]))
            # index-map bounds inside this kernel wrapper
            local_defs = {n.name: n for n in ast.walk(fn.node)
                          if isinstance(n, ast.FunctionDef)}
            for node in ast.walk(fn.node):
                if not (isinstance(node, ast.Call)
                        and _bare_callee(node.func) == "BlockSpec"):
                    continue
                for ret in _index_map_exprs(node, local_defs):
                    elements = (ret.elts if isinstance(ret, ast.Tuple)
                                else [ret])
                    for el in elements:
                        if _element_unclamped(el):
                            out.append(Finding(
                                rule_id="KC001", path=path,
                                line=node.lineno, qualname=fn.qualname,
                                symbol="index-map-bounds",
                                message=("BlockSpec index map does "
                                         "arithmetic without a clamp — "
                                         "can exceed declared bounds"),
                                hint=RULES["KC001"]["hint"]))
    return out


# ---------------------------------------------------------------------------
# CC001 — counter coverage
# ---------------------------------------------------------------------------

COUNTER_MODULES = ("observe", "controller", "arbiter", "fleet",
                   "forecast", "slab_allocator", "kv_slab_pool",
                   "scheduler", "serve")
COUNTER_SUFFIXES = ("_syncs", "_dispatches", "_launches", "_count")


def _is_counter_name(name: str) -> bool:
    return name.startswith("n_") or name.endswith(COUNTER_SUFFIXES)


@rule("CC001", "counter-coverage",
      "read the counter from a test or scenarios/invariants.py checker "
      "(an unread counter is an unenforced contract), or delete it")
def counter_coverage(project: Project) -> List[Finding]:
    out: List[Finding] = []
    corpus = project.reader_corpus
    for path, mod in project.modules.items():
        stem = path.rsplit("/", 1)[-1][:-3]
        if not any(tag in stem for tag in COUNTER_MODULES):
            continue
        counters: Dict[str, Tuple[int, str]] = {}   # name -> (line, qual)
        declared: Dict[str, Tuple[str, int]] = {}   # @hot_path counters
        for fn in mod.functions:
            for c in fn.hot_counters:
                declared[c] = (fn.qualname, fn.node.lineno)
            if fn.name != "__init__" and fn.class_name is None:
                continue
            for stmt in ast.walk(fn.node):
                if isinstance(stmt, ast.Assign) \
                        and isinstance(stmt.value, ast.Constant) \
                        and stmt.value.value == 0:
                    for t in stmt.targets:
                        if isinstance(t, ast.Attribute) \
                                and isinstance(t.value, ast.Name) \
                                and t.value.id == "self" \
                                and _is_counter_name(t.attr):
                            counters.setdefault(
                                t.attr,
                                (stmt.lineno, fn.class_name or ""))
        for node in mod.tree.body:       # dataclass-style class counters
            if not isinstance(node, ast.ClassDef):
                continue
            for stmt in node.body:
                if isinstance(stmt, ast.AnnAssign) \
                        and isinstance(stmt.target, ast.Name) \
                        and isinstance(stmt.value, ast.Constant) \
                        and stmt.value.value == 0 \
                        and _is_counter_name(stmt.target.id):
                    counters.setdefault(stmt.target.id,
                                        (stmt.lineno, node.name))
        for name, (line, cls) in sorted(counters.items()):
            if name not in corpus:
                out.append(Finding(
                    rule_id="CC001", path=path, line=line,
                    qualname=cls or "<module>", symbol=name,
                    message=(f"counter `{name}` is never read by any "
                             "test or invariants checker"),
                    hint=RULES["CC001"]["hint"]))
        for name, (qual, line) in sorted(declared.items()):
            # the annotation itself is one occurrence; a backing counter
            # (self.x = 0 / x += 1) means the name appears again
            if name not in counters and mod.source.count(name) <= 1:
                out.append(Finding(
                    rule_id="CC001", path=path, line=line,
                    qualname=qual, symbol=name,
                    message=(f"@hot_path declares guard counter "
                             f"`{name}` that does not exist in "
                             f"{path}"),
                    hint="fix the counters=() annotation"))
    return out
