"""tpulint pass 3: symbolic shape-flow analysis over the device data plane.

Passes 1 and 2 know *where* traced code is (the call-graph fixpoints) and
*what statements* it contains (the per-file rule visitors). Neither knows
what the values flowing through it look like — and the whole eager-scoring
economy rests on value-shape invariants no syntactic rule can check:

* every device program is **statically shaped** — a host dimension that
  reaches a jit static argument or a cached program factory must come
  from a *bounded* universe (pow2 buckets), or every distinct request
  compiles a distinct program (the recompile storm the program
  observatory's shape-key census measures at runtime);
* every variable dimension is **pow2-padded** — which means every array
  entering a mesh program carries *padding lanes*, and a reduction over
  them (`sum`/`max`/`top_k`/`segment_sum`/`psum`) is only sound under a
  dominating validity mask (`jnp.where`, a mask multiply, a live/length
  mask) — otherwise padded lanes leak into scores;
* every MXU matmul runs in its **intended dtype** — bf16 sweeps and f32
  re-ranks mix only at declared cast points, and a stray float64/int64
  spelling in traced code silently promotes the whole path.

This module is an abstract interpreter over the pass-1 project index that
propagates a small shape/dtype lattice through the code and gates those
invariants as four rules:

**The dim lattice (R017).** Host-side integer values classify as::

      Unknown  <  Concrete  <  PaddedPow2  <  DataDependent

  - ``Concrete`` — literals and closure constants (`k = 10`);
  - ``PaddedPow2`` — produced by the padding helpers (`pow2_bucket`,
    `half_step_bucket`, `round_up` — utils/shapes.py; `chunk_count_bucket`,
    which picks one of the two — search/context.py) or joins of padded
    values (`max` of buckets is a bucket: the `Tmax` accumulation idiom);
  - ``DataDependent`` — derived from `len()`, `.shape`/`.size` of host
    data, dict sizes: an unbounded universe;
  - ``Unknown`` — no evidence either way (never alarms).

  Joins take the higher classification, except that the padding helpers
  are *bucketing points*: ``pow2_bucket(anything)`` is PaddedPow2 — the
  `Q = len(qs); Q = pow2_bucket(Q)` rebinding idiom converges to padded,
  not data-dependent. Dim values propagate interprocedurally: a worklist
  fixpoint joins call-site actuals into callee parameters and callee
  return summaries back into call expressions, over the same resolver
  pass 1 uses — so ``Q = len(bodies)`` in search/batch.py is visible at
  the `_bm25_program(..., Q=Q, ...)` edge in parallel/executor.py even
  though no single file shows both.

  **R017 (recompile storm)** fires where a DataDependent value reaches a
  *program factory* call (a function that registers its result with the
  AOT executable cache — `aot.wrap` — the executor's `_*_program`
  family) or a jit static argument, from host code. This generalizes
  R001's third arm (a syntactically-direct `len()` static argument)
  through dataflow: the storm is just as real two assignments and one
  call away. The program observatory's shape-key census is the dynamic
  ground truth this rule approximates statically — a key family the
  census saw vary at runtime must never be classified Concrete here
  (tests/unit cross-validates exactly that on a live node).

**The padded-lane taint (R018).** Inside *collective program bodies*
  (shard_map/`wrap` roots — the mesh invariant says every array entering
  one is pow2-padded), array values classify as::

      Unknown | Tainted | Mask | Validated

  Parameters enter Tainted (padding lanes present, unmasked); parameters
  with mask-like names (`live`, `mask`, ...) and comparison results are
  Mask; `jnp.where(cond, x, y)` and mask multiplies/ands produce
  Validated; elementwise/shape ops propagate; calls the analysis cannot
  see into produce Unknown (no false alarms through helpers).
  **R018 (padding soundness)** fires when a reduction (`sum`/`max`/
  `top_k`/`topk_auto`/`segment_sum`/`psum`/...) consumes a Tainted
  operand: padded lanes reach the reduction with no dominating mask.

**The dtype lattice (R019).** Inside traced functions, local dtypes are
  tracked through `dtype=` keywords and `.astype(...)`; **R019 (dtype
  discipline)** fires on (a) a float64/int64 dtype spelling in traced
  code — the silent-promotion trap — and (b) a matmul (`jnp.dot`/
  `matmul`/`einsum`/`@`/`lax.dot_general`) whose operands are known to
  mix bf16 and f32 outside a declared cast point.

**Reservation release paths (R020).** The resource-accounting twin of
  R015: an acquisition of breaker/residency budget (`track`/`put_array`/
  `force`/`break_or_reserve`/`_reserve`, resolved against the project
  symbol table so arbitrary `.track()` methods don't match) followed by
  fallible calls *before* the token/charge is stored, returned, or
  released, with no enclosing `try` whose handler/finally releases —
  an exception on that path strands the reservation and wedges admission
  control (the breaker counts bytes nobody holds). The clean exemplars
  are residency.py's own `put_array`/`_rehydrate` try/except-release
  pattern.

Contracts: three annotations declare the invariants the interpreter
cannot derive (each a targeted `allow`): ``# tpulint: bucketed`` (R017 —
the dim is bounded/padded by construction upstream), ``# tpulint:
masked`` (R018 — padded lanes are neutral for this reduction: zero-
padded, repeat-padded, or pre-masked upstream), ``# tpulint: cast``
(R019 — a declared MXU cast point).

Everything stays stdlib-``ast`` (no JAX import, no device); the whole-
project pass shares the tier-1 <30s budget with passes 1 and 2, and the
report (`analyze(index)`) carries reach/classification stats for the
bench `analysis` record and the census cross-validation test.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple, Union

from tools.tpulint.analyzer import Violation, snippet_at
from tools.tpulint.project import (FnSymbol, ModuleRecord, ProjectIndex,
                                   _Resolver, _attr_chain, _fn_params,
                                   _name)

# ---------------------------------------------------------------------------
# the dim lattice
# ---------------------------------------------------------------------------

UNKNOWN, CONCRETE, PADDED, DATADEP = 0, 1, 2, 3
KIND_NAMES = {UNKNOWN: "Unknown", CONCRETE: "Concrete",
              PADDED: "PaddedPow2", DATADEP: "DataDependent"}


@dataclass(frozen=True)
class Dim:
    """One abstract host-side integer (a candidate shape dim)."""
    kind: int
    origin: str = ""  # provenance of the classification, for messages

    def join(self, other: "Dim") -> "Dim":
        if other.kind > self.kind:
            return other
        if self.kind == other.kind and not self.origin:
            return Dim(self.kind, other.origin)
        return self


DIM_UNKNOWN = Dim(UNKNOWN)
DIM_CONCRETE = Dim(CONCRETE)

#: value of a local can be a single dim or a tuple of dims (a function
#: returning ``(starts, lens, P)`` keeps P's classification addressable
#: through the caller's tuple unpack)
DimVal = Union[Dim, Tuple[Dim, ...]]

# The padding helpers: calling one of these IS the bucketing point, so
# the result is PaddedPow2 regardless of the operand (utils/shapes.py;
# name-matched so fixtures and future helpers with the same contract
# participate without central registration).
PAD_PRODUCER_NAMES = {"pow2_bucket", "half_step_bucket", "chunk_count_bucket",
                      "round_up"}
# min/max/arithmetic join operand classifications (max of pow2 buckets
# is a pow2 bucket; min(k, D) is bounded by both operands' universes —
# the join keeps the worst one, which is the conservative direction).
DIM_JOIN_CALLS = {"min", "max"}
DIM_TRANSPARENT_CALLS = {"int", "abs"}  # int(x) keeps x's classification


def _join_all(dims: Sequence[Dim]) -> Dim:
    out = DIM_UNKNOWN
    for d in dims:
        out = out.join(d)
    return out


def _as_single(v: DimVal) -> Dim:
    if isinstance(v, tuple):
        return _join_all(v)
    return v


# ---------------------------------------------------------------------------
# the array-taint lattice (R018) and dtype lattice (R019)
# ---------------------------------------------------------------------------

ARR_UNKNOWN, ARR_VALIDATED, ARR_MASK, ARR_TAINT = 0, 1, 2, 3

import re as _re

# parameter/operand names that denote validity masks rather than payload
# arrays: `live`, `mask`, `valid`, `keep`, `exists`, bitvec lanes
_MASKY_RE = _re.compile(r"(?:^|_)(?:mask|live|valid|keep|exists|bits?|"
                        r"sel|hit)s?(?:$|_)", _re.IGNORECASE)

# reductions whose padded-lane soundness R018 gates. Exact-name matched
# on the call chain tail (or the method name): jnp/np reductions, lax
# top-k, segment reductions, mesh collectives, and the in-repo top-k
# dispatcher that takes no mask (`topk_auto` — its mask-aware siblings
# `knn_topk_auto`/`merge_candidate_topk` carry the live mask explicitly
# and are deliberately absent).
REDUCTION_NAMES = {
    "sum", "max", "min", "mean", "prod", "amax", "amin", "argmax",
    "argmin", "nansum", "nanmax", "nanmin", "top_k", "segment_sum",
    "segment_max", "psum", "pmax", "pmin", "pmean", "topk_auto",
    "cumsum", "median", "average",
}
# elementwise / shape ops that PRESERVE the operand's taint state (the
# padding lanes travel along)
_ELEMENTWISE_NAMES = {
    "exp", "log", "log1p", "sqrt", "abs", "negative", "square", "tanh",
    "sigmoid", "clip", "maximum", "minimum", "power", "astype",
    "reshape", "transpose", "ravel", "flatten", "squeeze", "expand_dims",
    "broadcast_to", "swapaxes", "asarray", "array", "take_along_axis",
    "sort", "argsort", "flip", "roll", "copy", "bitcast_convert_type",
    "convert_element_type",
}
# dtype spellings → canonical short names (the R019 vocabulary)
_DTYPE_CANON = {
    "bfloat16": "bf16", "float16": "f16", "float32": "f32",
    "float64": "f64", "int8": "i8", "int16": "i16", "int32": "i32",
    "int64": "i64", "uint32": "u32", "uint8": "u8", "bool_": "b1",
    "bool": "b1",
}
_WIDE_DTYPES = {"f64", "i64"}
_MATMUL_NAMES = {"dot", "matmul", "einsum", "tensordot", "dot_general",
                 "vdot"}

# ---------------------------------------------------------------------------
# R020 vocabulary
# ---------------------------------------------------------------------------

# Acquisition method names, valid only when the resolved owner looks
# like the resource-accounting layer (class or module named *Residency*/
# *Breaker*/*residency*/*breakers*): a reservation of budget that must be
# paired with a release on every path until ownership transfers.
ACQUIRE_NAMES = {"track", "put_array", "force", "break_or_reserve",
                 "_reserve"}
_ACQ_OWNER_RE = _re.compile(r"(?:residency|breaker|Registry)",
                            _re.IGNORECASE)
# Release spellings an except/finally (or the liability region itself)
# can use to discharge the reservation
RELEASE_NAMES = {"close", "release", "_release", "_untrack", "evict",
                 "rollback", "unreserve", "untrack"}
# Builtins that cannot raise in a way that strands a reservation (pure
# conversions / container peeks) — anything else between an acquisition
# and its escape is a fallible call
_SAFE_CALLS = {
    "len", "int", "float", "str", "bool", "list", "dict", "tuple", "set",
    "frozenset", "sorted", "min", "max", "sum", "abs", "round", "repr",
    "isinstance", "issubclass", "getattr", "hasattr", "id", "iter",
    "next", "enumerate", "zip", "range", "print", "format", "type",
    "any", "all", "map", "filter", "reversed", "hash",
}
# method spellings that are container/string peeks, not fallible work —
# `self._cache.items()` between an acquisition and its store is not a
# path that can strand the reservation
_SAFE_METHODS = {
    "items", "keys", "values", "get", "append", "extend", "add",
    "pop", "popitem", "move_to_end", "setdefault", "discard", "copy",
    "sort", "reverse", "count", "index", "strip", "split", "join",
    "startswith", "endswith", "lower", "upper", "format", "update",
}


# ---------------------------------------------------------------------------
# per-function summaries and the report
# ---------------------------------------------------------------------------

@dataclass
class FnSummary:
    """Interprocedural dim facts for one function."""
    param_in: Dict[str, Dim] = field(default_factory=dict)
    ret: DimVal = DIM_UNKNOWN
    env: Dict[str, DimVal] = field(default_factory=dict)


@dataclass
class ShapeFlowReport:
    """The pass-3 result: violations plus the coverage/classification
    stats the bench `analysis` record and the census test consume."""
    violations: List[Violation] = field(default_factory=list)
    functions: int = 0            # fns the dim fixpoint evaluated
    factories: List[str] = field(default_factory=list)   # factory sids
    collective_bodies: int = 0    # fns in R018 scope
    traced_fns: int = 0           # fns in R019 scope
    dims_classified: Dict[str, int] = field(
        default_factory=lambda: {n: 0 for n in KIND_NAMES.values()})
    #: factory sid -> {param: lattice kind name} — the join over every
    #: resolvable call site's actuals (the census cross-validation view:
    #: a dim the runtime census saw VARY must not be Concrete here)
    factory_param_dims: Dict[str, Dict[str, str]] = field(
        default_factory=dict)


# ---------------------------------------------------------------------------
# helpers over the pass-1 index
# ---------------------------------------------------------------------------

def _chain_tail(chain: Optional[str]) -> str:
    if not chain:
        return ""
    return chain.rpartition(".")[2]


def _sid_qual(sid: str) -> str:
    return sid.partition(":")[2]


def _sid_module(sid: str) -> str:
    return sid.partition(":")[0]


class _FnScope:
    """One function's resolution context: record, symbol, resolver."""

    def __init__(self, index: ProjectIndex, sym: FnSymbol):
        self.index = index
        self.sym = sym
        self.rec: ModuleRecord = index.records[sym.module]
        self.res = _Resolver(index, self.rec)

    def resolve_call(self, call: ast.Call) -> Optional[FnSymbol]:
        """Callee symbol for a call expression, or None. Mirrors the
        pass-1 resolution order: self-attr methods, module-local names,
        import chains (incl. module singletons)."""
        fn = call.func
        bare = _name(fn)
        if bare is not None:
            local = self.rec.symbols.get(bare)
            if local is not None:
                return local
            # Class() -> __init__
            if bare in self.rec.classes:
                init = self.rec.symbols.get(f"{bare}.__init__")
                if init is not None:
                    return init
            sid = self.res.resolve_chain(bare)
            return self.index.symbols.get(sid) if sid else None
        chain = _attr_chain(fn)
        if chain is None:
            return None
        if chain.startswith("self.") and chain.count(".") == 1:
            sid = self.res.resolve_self_attr(self.sym.cls, chain[5:])
            if sid is None and self.sym.cls is not None:
                # typed instance attribute: self.<attr>.<meth> handled
                # below; plain self.<meth> unresolved stays None
                pass
            return self.index.symbols.get(sid) if sid else None
        if chain.startswith("self.") and chain.count(".") == 2:
            _self, attr, meth = chain.split(".")
            tgt = self.res.attr_type_of(self.rec, self.sym.cls, attr)
            if tgt is not None:
                sid = self.res.resolve_method(tgt[0], tgt[1], meth)
                return self.index.symbols.get(sid) if sid else None
            return None
        sid = self.res.resolve_chain(chain)
        return self.index.symbols.get(sid) if sid else None


def _map_actuals(callee: FnSymbol,
                 call: ast.Call) -> List[Tuple[str, ast.AST]]:
    """(callee_param, actual expression) pairs for a call, skipping
    ``self`` for method callees (attribute calls never pass it)."""
    params = list(callee.params)
    if callee.cls is not None and params and params[0] in ("self", "cls"):
        params = params[1:]
    out: List[Tuple[str, ast.AST]] = []
    for i, a in enumerate(call.args):
        if isinstance(a, ast.Starred):
            break
        if i < len(params):
            out.append((params[i], a))
    pset = set(params)
    for kw in call.keywords:
        if kw.arg is not None and kw.arg in pset:
            out.append((kw.arg, kw.value))
    return out


def _assign_targets(t: ast.AST, out: List[str]) -> None:
    if isinstance(t, ast.Name):
        out.append(t.id)
    elif isinstance(t, (ast.Tuple, ast.List)):
        for e in t.elts:
            _assign_targets(e, out)
    elif isinstance(t, ast.Starred):
        _assign_targets(t.value, out)


def _stmts_in_order(node: ast.AST) -> List[ast.stmt]:
    """Every statement of a function body in document order, not
    descending into nested function/class definitions."""
    out: List[ast.stmt] = []

    def walk(body: Sequence[ast.stmt]) -> None:
        for stmt in body:
            out.append(stmt)
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            for fname in ("body", "orelse", "finalbody"):
                sub = getattr(stmt, fname, None)
                if sub:
                    walk(sub)
            for h in getattr(stmt, "handlers", []) or []:
                walk(h.body)

    walk(node.body)
    return out


# ---------------------------------------------------------------------------
# the interprocedural dim fixpoint (R017 substrate)
# ---------------------------------------------------------------------------

class _DimFlow:
    """Worklist fixpoint over every project function: per-function local
    dim environments, callee parameter joins, return summaries."""

    def __init__(self, index: ProjectIndex):
        self.index = index
        self.summaries: Dict[str, FnSummary] = {}
        self.scopes: Dict[str, _FnScope] = {}
        self.callers: Dict[str, Set[str]] = {}
        for sid, sym in index.symbols.items():
            self.summaries[sid] = FnSummary(
                param_in={p: DIM_UNKNOWN for p in sym.params})
            self.scopes[sid] = _FnScope(index, sym)
            for e in sym.edges:
                self.callers.setdefault(e.callee, set()).add(sid)
        self._dirty: Set[str] = set()

    # -- expression evaluation ----------------------------------------------

    def _dim_of(self, expr: ast.AST, sid: str,
                env: Dict[str, DimVal]) -> DimVal:
        scope = self.scopes[sid]
        summ = self.summaries[sid]
        if isinstance(expr, ast.Constant):
            return DIM_CONCRETE if isinstance(expr.value, (int, bool)) \
                else DIM_UNKNOWN
        if isinstance(expr, ast.Name):
            if expr.id in env:
                return env[expr.id]
            return summ.param_in.get(expr.id, DIM_UNKNOWN)
        if isinstance(expr, ast.Attribute):
            # host .shape/.size/.nbytes of anything is data-dependent —
            # R017 only *checks* in host code, so the trace-time-static
            # reading of these never reaches a verdict
            if expr.attr in ("shape", "size", "nbytes"):
                return Dim(DATADEP, ".%s at %s:%d" % (
                    expr.attr, scope.rec.path,
                    getattr(expr, "lineno", 0)))
            return DIM_UNKNOWN
        if isinstance(expr, ast.Tuple):
            return tuple(_as_single(self._dim_of(e, sid, env))
                         for e in expr.elts)
        if isinstance(expr, ast.Subscript):
            base = self._dim_of(expr.value, sid, env)
            if isinstance(base, tuple):
                sl = expr.slice
                if isinstance(sl, ast.Constant) and \
                        isinstance(sl.value, int) and \
                        -len(base) <= sl.value < len(base):
                    return base[sl.value]
                return _join_all(base)
            if isinstance(base, Dim) and base.kind == DATADEP:
                return base  # x.shape[0], x.shape[1:]
            return DIM_UNKNOWN
        if isinstance(expr, ast.BinOp):
            return _as_single(self._dim_of(expr.left, sid, env)).join(
                _as_single(self._dim_of(expr.right, sid, env)))
        if isinstance(expr, ast.UnaryOp):
            return self._dim_of(expr.operand, sid, env)
        if isinstance(expr, ast.IfExp):
            return _as_single(self._dim_of(expr.body, sid, env)).join(
                _as_single(self._dim_of(expr.orelse, sid, env)))
        if isinstance(expr, ast.Call):
            return self._dim_of_call(expr, sid, env)
        return DIM_UNKNOWN

    def _dim_of_call(self, call: ast.Call, sid: str,
                     env: Dict[str, DimVal]) -> DimVal:
        scope = self.scopes[sid]
        chain = _attr_chain(call.func)
        tail = _chain_tail(chain) or (_name(call.func) or "")
        if tail in PAD_PRODUCER_NAMES:
            return Dim(PADDED, "%s at %s:%d" % (
                tail, scope.rec.path, call.lineno))
        if tail == "len":
            return Dim(DATADEP, "len() at %s:%d" % (
                scope.rec.path, call.lineno))
        if tail in DIM_TRANSPARENT_CALLS and len(call.args) == 1:
            return self._dim_of(call.args[0], sid, env)
        if tail in DIM_JOIN_CALLS:
            return _join_all([_as_single(self._dim_of(a, sid, env))
                              for a in call.args
                              if not isinstance(a, ast.Starred)])
        callee = scope.resolve_call(call)
        if callee is None:
            return DIM_UNKNOWN
        # propagate actuals into the callee's parameter joins
        csum = self.summaries.get(callee.sid)
        if csum is None:
            return DIM_UNKNOWN
        for pname, aexpr in _map_actuals(callee, call):
            d = _as_single(self._dim_of(aexpr, sid, env))
            old = csum.param_in.get(pname, DIM_UNKNOWN)
            new = old.join(d)
            if new != old:
                csum.param_in[pname] = new
                self._dirty.add(callee.sid)
        return csum.ret

    # -- per-function evaluation --------------------------------------------

    def _eval_fn(self, sid: str) -> None:
        sym = self.index.symbols[sid]
        summ = self.summaries[sid]
        env: Dict[str, DimVal] = dict(summ.env)
        ret: DimVal = DIM_UNKNOWN
        stmts = _stmts_in_order(sym.node)
        for _round in range(4):
            changed = False
            rets: List[DimVal] = []
            for stmt in stmts:
                if isinstance(stmt, ast.Assign):
                    v = self._dim_of(stmt.value, sid, env)
                    for t in stmt.targets:
                        changed |= self._bind(t, v, env)
                elif isinstance(stmt, ast.AnnAssign) and stmt.value:
                    v = self._dim_of(stmt.value, sid, env)
                    changed |= self._bind(stmt.target, v, env)
                elif isinstance(stmt, ast.AugAssign):
                    names: List[str] = []
                    _assign_targets(stmt.target, names)
                    v = _as_single(self._dim_of(stmt.value, sid, env))
                    for n in names:
                        old = _as_single(env.get(n, DIM_UNKNOWN))
                        new = old.join(v)
                        if new != old:
                            env[n] = new
                            changed = True
                elif isinstance(stmt, ast.Return) and stmt.value:
                    rets.append(self._dim_of(stmt.value, sid, env))
                elif isinstance(stmt, ast.Expr):
                    self._dim_of(stmt.value, sid, env)  # edge effects
            if rets:
                ret = self._join_rets(rets)
            if not changed:
                break
        old_ret = summ.ret
        summ.env = env
        summ.ret = ret
        if ret != old_ret:
            for caller in self.callers.get(sid, ()):
                self._dirty.add(caller)

    @staticmethod
    def _join_rets(rets: List[DimVal]) -> DimVal:
        tuples = [r for r in rets if isinstance(r, tuple)]
        if len(tuples) == len(rets) and tuples and \
                len({len(t) for t in tuples}) == 1:
            width = len(tuples[0])
            return tuple(_join_all([t[i] for t in tuples])
                         for i in range(width))
        return _join_all([_as_single(r) for r in rets])

    @staticmethod
    def _bind(target: ast.AST, v: DimVal, env: Dict[str, DimVal]) -> bool:
        changed = False
        if isinstance(target, ast.Name):
            if env.get(target.id) != v:
                env[target.id] = v
                changed = True
        elif isinstance(target, (ast.Tuple, ast.List)):
            elts = target.elts
            vals: Sequence[DimVal]
            if isinstance(v, tuple) and len(v) == len(elts) and \
                    not any(isinstance(e, ast.Starred) for e in elts):
                vals = v
            else:
                vals = [_as_single(v)] * len(elts)
            for e, ev in zip(elts, vals):
                changed |= _DimFlow._bind(e, ev, env)
        elif isinstance(target, ast.Starred):
            changed |= _DimFlow._bind(target.value, _as_single(v), env)
        return changed

    # -- the fixpoint --------------------------------------------------------

    def run(self) -> None:
        work = sorted(self.summaries)
        seen_rounds = 0
        while work and seen_rounds < 12:
            seen_rounds += 1
            self._dirty = set()
            for sid in work:
                self._eval_fn(sid)
            work = sorted(self._dirty)


# ---------------------------------------------------------------------------
# R017: recompile-storm detection over the dim fixpoint
# ---------------------------------------------------------------------------

def _wrap_sids(index: ProjectIndex) -> Set[str]:
    """sids of the AOT registration point: ``wrap`` in an ``aot``
    module (parallel/aot.py in the real tree; any `aot.py` in
    fixtures)."""
    out = set()
    for sid in index.symbols:
        mod, qual = _sid_module(sid), _sid_qual(sid)
        if qual == "wrap" and (mod == "aot" or mod.endswith(".aot")):
            out.add(sid)
    return out


def _factory_sids(index: ProjectIndex) -> Set[str]:
    """Program factories: functions whose body registers a compiled
    program with the AOT cache (a resolved call edge to `aot:wrap`)."""
    wraps = _wrap_sids(index)
    if not wraps:
        return set()
    return {sym.sid for sym in index.symbols.values()
            if any(e.callee in wraps and e.kind == "call"
                   for e in sym.edges)}


class _R017Checker(ast.NodeVisitor):
    """One host-side function: flag factory/static call edges whose
    actual dims are DataDependent."""

    def __init__(self, flow: _DimFlow, sid: str, factories: Set[str],
                 out: List[Violation]):
        self.flow = flow
        self.sid = sid
        self.scope = flow.scopes[sid]
        self.env = flow.summaries[sid].env
        self.factories = factories
        self.out = out

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        pass  # nested defs are their own symbols

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node: ast.Call) -> None:
        self.generic_visit(node)
        callee = self.scope.resolve_call(node)
        if callee is None:
            return
        is_factory = callee.sid in self.factories
        statics = callee.statics
        if not is_factory and not statics:
            return
        for pname, aexpr in _map_actuals(callee, node):
            if not is_factory and pname not in statics:
                continue
            d = _as_single(self.flow._dim_of(aexpr, self.sid, self.env))
            if d.kind != DATADEP:
                continue
            kind = ("program factory '%s'" % callee.qual) if is_factory \
                else ("jit static argument '%s' of '%s'"
                      % (pname, callee.qual))
            origin = (" (%s)" % d.origin) if d.origin else ""
            rec = self.scope.rec
            self.out.append(Violation(
                "R017", rec.path, node.lineno, node.col_offset,
                "recompile storm: argument '%s' to %s is data-dependent"
                "%s — every distinct value compiles and caches a new "
                "program (unbounded shape-key census); bucket it "
                "(pow2_bucket/round_up) or declare the call "
                "`# tpulint: bucketed`" % (pname, kind, origin),
                snippet_at(rec.lines, node.lineno)))


def _check_r017(index: ProjectIndex, flow: _DimFlow,
                factories: Set[str], out: List[Violation]) -> None:
    traced = set(index.traced)
    for sid, sym in index.symbols.items():
        # only HOST code builds programs; a factory-shaped call inside a
        # traced body is trace-time-static by construction
        if sid in traced or sym.is_root:
            continue
        checker = _R017Checker(flow, sid, factories, out)
        for stmt in sym.node.body:
            checker.visit(stmt)


def _factory_param_view(flow: _DimFlow,
                        factories: Set[str]) -> Dict[str, Dict[str, str]]:
    out: Dict[str, Dict[str, str]] = {}
    for sid in sorted(factories):
        summ = flow.summaries.get(sid)
        if summ is None:
            continue
        out[sid] = {p: KIND_NAMES[d.kind]
                    for p, d in sorted(summ.param_in.items())}
    return out


# ---------------------------------------------------------------------------
# R018: padded-lane taint inside collective program bodies
# ---------------------------------------------------------------------------

class _TaintEval:
    """Flow-sensitive (document-order) array-taint evaluation of one
    collective body."""

    def __init__(self, scope: _FnScope, out: List[Violation]):
        self.scope = scope
        self.out = out
        self.check = False
        self.env: Dict[str, int] = {}
        sym = scope.sym
        params = _fn_params(sym.node)
        for p in params:
            if p in ("self", "cls"):
                continue
            self.env[p] = ARR_MASK if _MASKY_RE.search(p) else ARR_TAINT

    # -- expression states ---------------------------------------------------

    def state_of(self, expr: ast.AST) -> int:
        if isinstance(expr, ast.Name):
            return self.env.get(expr.id, ARR_UNKNOWN)
        if isinstance(expr, ast.Constant):
            return ARR_VALIDATED
        if isinstance(expr, ast.Compare):
            return ARR_MASK
        if isinstance(expr, ast.UnaryOp):
            return self.state_of(expr.operand)
        if isinstance(expr, ast.Subscript):
            return self.state_of(expr.value)
        if isinstance(expr, ast.IfExp):
            return max(self.state_of(expr.body),
                       self.state_of(expr.orelse))
        if isinstance(expr, ast.BinOp):
            ls, rs = self.state_of(expr.left), self.state_of(expr.right)
            if isinstance(expr.op, (ast.Mult, ast.BitAnd)):
                # a mask multiply/and validates the other operand
                if ls == ARR_MASK or rs == ARR_MASK or \
                        self._masky(expr.left) or self._masky(expr.right):
                    if ls == ARR_MASK and rs == ARR_MASK:
                        return ARR_MASK
                    return ARR_VALIDATED
            if ls == ARR_TAINT or rs == ARR_TAINT:
                return ARR_TAINT
            if ls == ARR_UNKNOWN or rs == ARR_UNKNOWN:
                return ARR_UNKNOWN
            return max(ls, rs)
        if isinstance(expr, ast.Call):
            return self._call_state(expr)
        if isinstance(expr, (ast.Tuple, ast.List)):
            sts = [self.state_of(e) for e in expr.elts]
            if any(s == ARR_TAINT for s in sts):
                return ARR_TAINT
            return ARR_UNKNOWN
        if isinstance(expr, ast.Attribute):
            return ARR_UNKNOWN
        return ARR_UNKNOWN

    @staticmethod
    def _masky(expr: ast.AST) -> bool:
        n = _name(expr)
        if n is not None and _MASKY_RE.search(n):
            return True
        if isinstance(expr, ast.Subscript):
            return _TaintEval._masky(expr.value)
        return isinstance(expr, ast.Compare)

    def _operand(self, call: ast.Call) -> Optional[ast.AST]:
        if call.args and not isinstance(call.args[0], ast.Starred):
            return call.args[0]
        return None

    def _call_state(self, call: ast.Call) -> int:
        chain = _attr_chain(call.func)
        tail = _chain_tail(chain) or (_name(call.func) or "")
        # the reduction check itself happens in visit(); here we only
        # compute the VALUE state of the call expression
        if tail == "where" and len(call.args) == 3:
            return ARR_VALIDATED
        if tail in ("pad", "pad_to"):
            return ARR_TAINT  # fresh padding lanes
        if tail == "astype" or tail in _ELEMENTWISE_NAMES:
            # receiver method (x.astype) or jnp.op(x, ...): propagate
            if isinstance(call.func, ast.Attribute) and \
                    tail not in ("asarray", "array") and \
                    not self._jnp_rooted(chain):
                return self.state_of(call.func.value)
            op = self._operand(call)
            return self.state_of(op) if op is not None else ARR_UNKNOWN
        if tail in ("all_gather", "concatenate", "stack", "hstack",
                    "vstack"):
            op = self._operand(call)
            return self.state_of(op) if op is not None else ARR_UNKNOWN
        if tail in REDUCTION_NAMES:
            return ARR_VALIDATED  # a reduction's OUTPUT has no pad lanes
        return ARR_UNKNOWN  # helper the analysis can't see into

    def _jnp_rooted(self, chain: Optional[str]) -> bool:
        if not chain:
            return False
        return chain.split(".")[0] in self.scope.rec.info.jnp | \
            {"lax", "jax", "np"}

    # -- the walk ------------------------------------------------------------

    def run(self) -> None:
        # round 1 stabilizes the environment (forward-declared names,
        # loop-carried state) with checks off; round 2 reports
        stmts = _stmts_in_order(self.scope.sym.node)
        self.check = False
        for stmt in stmts:
            self._stmt(stmt)
        self.check = True
        for stmt in stmts:
            self._stmt(stmt)

    def _stmt(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, ast.Assign):
            v = self._value_with_checks(stmt.value)
            for t in stmt.targets:
                self._bind(t, v)
        elif isinstance(stmt, ast.AnnAssign) and stmt.value:
            self._bind(stmt.target, self._value_with_checks(stmt.value))
        elif isinstance(stmt, ast.AugAssign):
            self._value_with_checks(stmt.value)
        elif isinstance(stmt, ast.Return) and stmt.value:
            self._value_with_checks(stmt.value)
        elif isinstance(stmt, ast.Expr):
            self._value_with_checks(stmt.value)

    def _bind(self, target: ast.AST, state: int) -> None:
        if isinstance(target, ast.Name):
            self.env[target.id] = state
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._bind(e, state)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, state)

    def _value_with_checks(self, expr: ast.AST) -> int:
        if self.check:
            for call in [n for n in ast.walk(expr)
                         if isinstance(n, ast.Call)]:
                self._check_reduction(call)
        return self.state_of(expr)

    def _check_reduction(self, call: ast.Call) -> None:
        chain = _attr_chain(call.func)
        tail = _chain_tail(chain) or (_name(call.func) or "")
        if tail not in REDUCTION_NAMES:
            return
        if isinstance(call.func, ast.Attribute) and \
                not self._jnp_rooted(chain):
            operand: Optional[ast.AST] = call.func.value  # x.sum()
        else:
            operand = self._operand(call)
        if operand is None:
            return
        if self.state_of(operand) != ARR_TAINT:
            return
        rec = self.scope.rec
        self.out.append(Violation(
            "R018", rec.path, call.lineno, call.col_offset,
            "padding soundness: reduction '%s' consumes an operand "
            "carrying pow2-padded lanes with no dominating validity "
            "mask — padded lanes leak into the result; mask first "
            "(jnp.where / mask multiply) or declare the operand "
            "`# tpulint: masked`" % tail,
            snippet_at(rec.lines, call.lineno)))


def _r018_scope(index: ProjectIndex) -> List[str]:
    """Collective program bodies: functions handed whole to shard_map/
    `wrap`. The mesh invariant — every array entering one is pow2-padded
    on its variable axes — holds exactly there, so parameters are
    born Tainted. Inner roots (scan/cond/pallas bodies) see tiles and
    accumulators whose padding story belongs to their enclosing
    program, not to them — tainting their params would indict every
    online-softmax accumulator, so they stay out of scope."""
    return sorted(sid for sid, sym in index.symbols.items()
                  if sym.is_collective_root)


def _check_r018(index: ProjectIndex, out: List[Violation]) -> List[str]:
    scope_sids = _r018_scope(index)
    for sid in scope_sids:
        sym = index.symbols[sid]
        _TaintEval(_FnScope(index, sym), out).run()
    return scope_sids


# ---------------------------------------------------------------------------
# R019: dtype discipline inside traced code
# ---------------------------------------------------------------------------

def _dtype_of_expr(expr: ast.AST) -> Optional[str]:
    """Canonical dtype named by a dtype-position expression
    (`jnp.bfloat16`, `np.float64`, `"float32"`), else None."""
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return _DTYPE_CANON.get(expr.value)
    if isinstance(expr, ast.Attribute):
        return _DTYPE_CANON.get(expr.attr)
    if isinstance(expr, ast.Name):
        return _DTYPE_CANON.get(expr.id)
    if isinstance(expr, ast.Call):  # jnp.dtype("float64")
        if expr.args and not isinstance(expr.args[0], ast.Starred):
            return _dtype_of_expr(expr.args[0])
    return None


class _DtypeChecker(ast.NodeVisitor):
    """One traced function: local dtype tracking + the two R019 arms."""

    def __init__(self, scope: _FnScope, out: List[Violation]):
        self.scope = scope
        self.out = out
        self.env: Dict[str, str] = {}

    def visit_FunctionDef(self, node):
        pass  # nested defs are their own symbols

    visit_AsyncFunctionDef = visit_FunctionDef

    def _flag_wide(self, expr: ast.AST, where: str) -> None:
        d = _dtype_of_expr(expr)
        if d in _WIDE_DTYPES:
            rec = self.scope.rec
            self.out.append(Violation(
                "R019", rec.path, expr.lineno, expr.col_offset,
                "dtype discipline: %s spelling in traced code (%s) — "
                "silent f64/i64 promotion widens the whole device path; "
                "use the 32-bit dtype, or declare an intended cast "
                "`# tpulint: cast`" % (
                    "float64" if d == "f64" else "int64", where),
                snippet_at(rec.lines, expr.lineno)))

    def _operand_dtype(self, expr: ast.AST) -> Optional[str]:
        if isinstance(expr, ast.Name):
            return self.env.get(expr.id)
        if isinstance(expr, ast.Call) and \
                isinstance(expr.func, ast.Attribute) and \
                expr.func.attr == "astype" and expr.args:
            return _dtype_of_expr(expr.args[0])
        if isinstance(expr, ast.Attribute) and expr.attr == "T":
            return self._operand_dtype(expr.value)
        if isinstance(expr, ast.Subscript):
            return self._operand_dtype(expr.value)
        return None

    def _check_matmul(self, node: ast.AST, lhs: ast.AST,
                      rhs: ast.AST, opname: str) -> None:
        dl, dr = self._operand_dtype(lhs), self._operand_dtype(rhs)
        if dl is None or dr is None or dl == dr:
            return
        if {dl, dr} == {"bf16", "f32"}:
            rec = self.scope.rec
            self.out.append(Violation(
                "R019", rec.path, node.lineno, node.col_offset,
                "dtype discipline: MXU matmul '%s' mixes bf16 and f32 "
                "operands — the implicit promotion costs the bf16 "
                "throughput win and hides the intended precision; cast "
                "both sides explicitly at a declared cast point "
                "(`# tpulint: cast`)" % opname,
                snippet_at(rec.lines, node.lineno)))

    def visit_Assign(self, node: ast.Assign) -> None:
        self.generic_visit(node)
        d = self._operand_dtype(node.value)
        if isinstance(node.value, ast.Call):
            for kw in node.value.keywords:
                if kw.arg == "dtype":
                    d = _dtype_of_expr(kw.value) or d
        if d is not None:
            names: List[str] = []
            for t in node.targets:
                _assign_targets(t, names)
            for n in names:
                self.env[n] = d

    def visit_BinOp(self, node: ast.BinOp) -> None:
        self.generic_visit(node)
        if isinstance(node.op, ast.MatMult):
            self._check_matmul(node, node.left, node.right, "@")

    def visit_Call(self, node: ast.Call) -> None:
        self.generic_visit(node)
        chain = _attr_chain(node.func)
        tail = _chain_tail(chain) or (_name(node.func) or "")
        if tail == "astype" and node.args:
            self._flag_wide(node.args[0], ".astype(...)")
        for kw in node.keywords:
            if kw.arg == "dtype":
                self._flag_wide(kw.value, "dtype= keyword")
        if tail in _MATMUL_NAMES:
            args = [a for a in node.args
                    if not isinstance(a, ast.Starred)]
            if tail == "einsum" and len(args) >= 3:
                self._check_matmul(node, args[1], args[2], tail)
            elif tail != "einsum" and len(args) >= 2:
                self._check_matmul(node, args[0], args[1], tail)


def _check_r019(index: ProjectIndex, out: List[Violation]) -> int:
    scope_sids = sorted(set(index.traced) |
                        {sid for sid, s in index.symbols.items()
                         if s.is_root})
    for sid in scope_sids:
        sym = index.symbols.get(sid)
        if sym is None:
            continue
        checker = _DtypeChecker(_FnScope(index, sym), out)
        for stmt in sym.node.body:
            checker.visit(stmt)
    return len(scope_sids)


# ---------------------------------------------------------------------------
# R020: reservation-leak (release-path) checking
# ---------------------------------------------------------------------------

def _release_in(body: Sequence[ast.stmt]) -> bool:
    for stmt in body:
        for n in ast.walk(stmt):
            if isinstance(n, ast.Call):
                tail = _chain_tail(_attr_chain(n.func)) or \
                    (_name(n.func) or "")
                if tail in RELEASE_NAMES:
                    return True
    return False


@dataclass
class _OrderedStmt:
    stmt: ast.stmt
    protected: bool  # inside a try whose handler/finally releases


def _flatten_protected(node: ast.AST) -> List[_OrderedStmt]:
    out: List[_OrderedStmt] = []

    def walk(body: Sequence[ast.stmt], protected: bool) -> None:
        for stmt in body:
            out.append(_OrderedStmt(stmt, protected))
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            if isinstance(stmt, ast.Try):
                covered = protected or _release_in(
                    [s for h in stmt.handlers for s in h.body]
                    + list(stmt.finalbody))
                walk(stmt.body, covered)
                for h in stmt.handlers:
                    walk(h.body, protected)
                walk(stmt.orelse, protected)
                walk(stmt.finalbody, protected)
                continue
            for fname in ("body", "orelse", "finalbody"):
                sub = getattr(stmt, fname, None)
                if sub:
                    walk(sub, protected)
            for h in getattr(stmt, "handlers", []) or []:
                walk(h.body, protected)

    walk(node.body, False)
    return out


def _acquire_call(scope: _FnScope,
                  stmt: ast.stmt) -> Optional[Tuple[ast.Call, str]]:
    """(call, acquisition name) when this statement's value is a
    resolved breaker/residency acquisition."""
    value = None
    if isinstance(stmt, (ast.Assign, ast.AnnAssign)) and \
            getattr(stmt, "value", None) is not None:
        value = stmt.value
    elif isinstance(stmt, ast.Expr):
        value = stmt.value
    if not isinstance(value, ast.Call):
        return None
    chain = _attr_chain(value.func)
    tail = _chain_tail(chain)
    if tail not in ACQUIRE_NAMES:
        return None
    callee = scope.resolve_call(value)
    if callee is None:
        return None
    qual, mod = _sid_qual(callee.sid), _sid_module(callee.sid)
    owner = qual.rpartition(".")[0] or mod.rpartition(".")[2]
    if not (_ACQ_OWNER_RE.search(owner) or
            _ACQ_OWNER_RE.search(mod.rpartition(".")[2])):
        return None
    return value, tail


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _scan_nodes(stmt: ast.stmt) -> List[ast.AST]:
    """AST regions a liability scan may attribute to THIS flattened
    entry: a compound statement contributes only its header expressions
    (its children re-appear later in document order — judging the whole
    subtree here would see the body before it runs)."""
    if isinstance(stmt, (ast.If, ast.While)):
        return [stmt.test]
    if isinstance(stmt, (ast.For, ast.AsyncFor)):
        return [stmt.iter]
    if isinstance(stmt, (ast.With, ast.AsyncWith)):
        out: List[ast.AST] = []
        for item in stmt.items:
            out.append(item.context_expr)
        return out
    if isinstance(stmt, ast.Try):
        return []
    return [stmt]


def _is_risky(stmt: ast.stmt, token: Optional[str]) -> bool:
    """Does this statement contain a fallible call that is NOT a
    release/method on the token itself and not a safe builtin?"""
    for region in _scan_nodes(stmt):
        for n in ast.walk(region):
            if not isinstance(n, ast.Call):
                continue
            if isinstance(n.func, ast.Attribute):
                recv = _name(n.func.value)
                if token is not None and recv == token:
                    continue  # tok.close() / tok.anything
                if n.func.attr in _SAFE_METHODS:
                    continue
                return True
            fname = _name(n.func) or ""
            if fname in _SAFE_CALLS:
                continue
            return True
    return False


def _token_fate(stmt: ast.stmt, token: str) -> Optional[str]:
    """'escape' (stored/returned/passed — ownership transferred),
    'release' (closed/released), or None (no mention / plain read)."""
    mentions = False
    for region in _scan_nodes(stmt):
        for n in ast.walk(region):
            if isinstance(n, ast.Call):
                if isinstance(n.func, ast.Attribute) and \
                        _name(n.func.value) == token and \
                        n.func.attr in RELEASE_NAMES:
                    return "release"
                for a in list(n.args) + [kw.value for kw in n.keywords]:
                    if token in _names_in(a):
                        return "escape"  # ownership transferred
            if isinstance(n, ast.Name) and n.id == token:
                mentions = True
    if not mentions:
        return None
    if isinstance(stmt, (ast.Return,)) and stmt.value is not None and \
            token in _names_in(stmt.value):
        return "escape"
    if isinstance(stmt, ast.Assign) and token in _names_in(stmt.value):
        return "escape"  # stored into a container/attribute
    if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Yield):
        return "escape"
    return None


def _commit_stmt(stmt: ast.stmt) -> bool:
    """A void acquisition's liability ends when the guarded state is
    committed: a store into instance state (`self._x[...] = h` /
    `self._x = h`) or a return."""
    if isinstance(stmt, ast.Return):
        return True
    if isinstance(stmt, (ast.Assign, ast.AnnAssign)):
        targets = stmt.targets if isinstance(stmt, ast.Assign) \
            else [stmt.target]
        for t in targets:
            base = t
            while isinstance(base, ast.Subscript):
                base = base.value
            if isinstance(base, ast.Attribute):
                return True
    return False


def _bound_token(stmt: ast.stmt) -> Optional[str]:
    if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
        t = stmt.targets[0]
        if isinstance(t, ast.Name):
            return t.id
    if isinstance(stmt, ast.AnnAssign) and \
            isinstance(stmt.target, ast.Name):
        return stmt.target.id
    return None


def _check_r020_fn(index: ProjectIndex, sym: FnSymbol,
                   out: List[Violation]) -> None:
    scope = _FnScope(index, sym)
    ordered = _flatten_protected(sym.node)
    for i, ostmt in enumerate(ordered):
        acq = _acquire_call(scope, ostmt.stmt)
        if acq is None:
            continue
        call, acq_name = acq
        # the acquisition implementation itself (ResidencyRegistry.track
        # calling breaker.force) is the primitive being modeled — its own
        # internal calls are covered by analyzing ITS callers; still
        # checked here like any other caller.
        token = _bound_token(ostmt.stmt)
        risky_line = 0
        leaked = False
        for later in ordered[i + 1:]:
            stmt = later.stmt
            if token is not None:
                fate = _token_fate(stmt, token)
                if fate is not None:
                    break  # escaped or released: liability over
            else:
                # void charge: released / committed ends liability
                done = False
                for region in _scan_nodes(stmt):
                    for n in ast.walk(region):
                        if isinstance(n, ast.Call):
                            tail = _chain_tail(_attr_chain(n.func)) or \
                                (_name(n.func) or "")
                            if tail in RELEASE_NAMES:
                                done = True
                                break
                    if done:
                        break
                if done or _commit_stmt(stmt):
                    break
            if not later.protected and _is_risky(stmt, token):
                leaked = True
                if not risky_line:
                    risky_line = getattr(stmt, "lineno", 0)
        if not leaked:
            continue
        rec = scope.rec
        what = "token" if token is not None else "charge"
        out.append(Violation(
            "R020", rec.path, call.lineno, call.col_offset,
            "reservation leak: '%s' acquires breaker/residency budget "
            "but a fallible call (line %d) runs before the %s is "
            "stored, returned, or released, outside any try whose "
            "except/finally releases it — an exception on that path "
            "strands the reservation and wedges admission control"
            % (acq_name, risky_line, what),
            snippet_at(rec.lines, call.lineno)))


def _check_r020(index: ProjectIndex, out: List[Violation]) -> None:
    for sid in sorted(index.symbols):
        sym = index.symbols[sid]
        # acquisition implementations police their own callees; skip the
        # defining methods so `def track(self): self.breaker.force(n)`
        # doesn't flag itself acquiring-within-acquire
        tail = _sid_qual(sym.sid).rpartition(".")[2]
        if tail in ACQUIRE_NAMES or tail in RELEASE_NAMES:
            continue
        _check_r020_fn(index, sym, out)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def analyze(index: ProjectIndex) -> ShapeFlowReport:
    """Run pass 3 over a built project index. Memoized on the index:
    lint_index, the bench `analysis` record, and the census test share
    one evaluation."""
    cached = getattr(index, "_shapeflow_report", None)
    if cached is not None:
        return cached
    report = ShapeFlowReport()
    flow = _DimFlow(index)
    flow.run()
    report.functions = len(flow.summaries)
    factories = _factory_sids(index)
    report.factories = sorted(factories)
    report.factory_param_dims = _factory_param_view(flow, factories)
    for summ in flow.summaries.values():
        for v in summ.env.values():
            report.dims_classified[KIND_NAMES[_as_single(v).kind]] += 1
    _check_r017(index, flow, factories, report.violations)
    report.collective_bodies = len(
        _check_r018(index, report.violations))
    report.traced_fns = _check_r019(index, report.violations)
    _check_r020(index, report.violations)
    report.violations.sort(
        key=lambda v: (v.path, v.line, v.col, v.rule))
    index._shapeflow_report = report  # type: ignore[attr-defined]
    return report


def shapeflow_violations(index: ProjectIndex) -> List[Violation]:
    """The pass-3 findings for lint_index (suppressions applied by the
    caller per record, like every other pass)."""
    return list(analyze(index).violations)
