"""jaxpr -> CiM IR: the eligibility front end of the lowering compiler.

`trace(fn, *args)` stages a JAX function with `jax.make_jaxpr`, flattens
nested `jit` calls, and classifies every equation into the ADRA cost model:

  single — elementwise integer ops one asymmetric dual-row access computes:
           add / sub / compare (lt, le, gt, ge, eq, ne) / bitwise
           and-or-xor / min / max / neg / abs.
  multi  — ops the macro planner (repro.cim.planner) lowers to explicit
           access schedules: mul (shift-and-add), integer dot_general in
           the canonical [*B,M,K]x[*B,K,N] form — 2-D or batched, the
           batch dims flattening onto the word/tile axis of the broadcast
           contraction layout — full reduce_sum (log-stride tree),
           population_count (pairwise plane tree).
  free   — zero-access peripheral wiring that keeps a fused region in the
           packed domain: int<->int convert_element_type (plane truncate /
           sign-extend), reshape, bitwise not (SA output complement),
           select_n on a predicate bitmap (predicated writeback), scalar
           broadcast_in_dim (row-buffer fanout).
  host   — everything else (floats, gathers, control flow, ...).

Each eligible equation carries its planner `Schedule`, its access count and
the operand word count one access covers — the SAME numbers the executor
(repro.cim.lower) will charge to the ledger and the offload estimator
(repro.core.offload, source="jaxpr") projects from. One classification,
three consumers: the estimator and the executor can never disagree about
eligibility.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.core import DropVar, ShapedArray
from jax.extend.core import ClosedJaxpr, Literal, Var

from . import planner

#: jaxpr comparison primitive -> (engine predicate op, complement-at-periphery)
CMP_PRIMS: Dict[str, Tuple[str, bool]] = {
    "lt": ("lt", False), "gt": ("gt", False), "eq": ("eq", False),
    "ge": ("lt", True), "le": ("gt", True), "ne": ("eq", True),
}

#: elementwise single-access primitives (besides the comparisons)
SINGLE_PRIMS = ("add", "sub", "and", "or", "xor", "min", "max", "neg", "abs")

#: multi-access primitives lowered through the macro planner
MULTI_PRIMS = ("mul", "dot_general", "reduce_sum", "population_count")

#: zero-access peripheral primitives (free inside a fused region)
FREE_PRIMS = ("convert_element_type", "reshape", "select_n", "not",
              "broadcast_in_dim")


@dataclasses.dataclass(frozen=True)
class ConstVal:
    """A closed-over constant routed into the flat eqn list (the lowering
    analogue of a jaxpr constvar binding)."""

    val: Any

    @property
    def aval(self):
        v = self.val
        return ShapedArray(np.shape(v), jnp.result_type(v))


def aval_of(atom) -> ShapedArray:
    """aval of a Var, Literal, or ConstVal operand."""
    return atom.aval


@dataclasses.dataclass
class TracedOp:
    """One flattened jaxpr equation plus its ADRA classification."""

    prim: Any                      # jax Primitive (None for _alias passthrough)
    params: Dict[str, Any]
    invars: Tuple[Any, ...]        # Var | Literal | ConstVal
    outvars: Tuple[Any, ...]
    name: str = ""                 # normalized op name
    kind: str = "host"             # single | multi | free | host
    n_bits: int = 0                # operand word width the access works at
    accesses: int = 0              # planned ADRA accesses (0 for free/host)
    words: int = 0                 # operand words one access covers
    schedule: Optional[planner.Schedule] = None
    why_host: str = ""             # ineligibility reason (diagnostics)

    @property
    def eligible(self) -> bool:
        return self.kind != "host"


@dataclasses.dataclass
class Trace:
    """The flattened, classified eqn list of one staged function."""

    closed: ClosedJaxpr
    ops: List[TracedOp]
    out_shape: Any                 # pytree of ShapeDtypeStruct (output tree)

    @property
    def eligible_ops(self) -> int:
        return sum(1 for op in self.ops if op.eligible and op.accesses)

    @property
    def adra_accesses(self) -> int:
        """Total planned accesses — what a lowered execution's ledger shows
        (unbanked); banked placement multiplies per-eqn by its tile count."""
        return sum(op.accesses for op in self.ops)


# ---------------------------------------------------------------------------
# dtype helpers
# ---------------------------------------------------------------------------


def dtype_bits(dtype) -> int:
    """Word width of an integer/bool dtype (int4 -> 4, bool -> 1)."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.bool_:
        return 1
    return jnp.iinfo(dtype).bits


def dtype_signed(dtype) -> bool:
    dtype = jnp.dtype(dtype)
    if dtype == jnp.bool_:
        return False
    return jnp.issubdtype(dtype, jnp.signedinteger)


def _intlike(aval) -> bool:
    return (aval.dtype == jnp.bool_
            or jnp.issubdtype(aval.dtype, jnp.integer))


def host_flops(op: "TracedOp") -> int:
    """Scalar-op count an XLA host execution of this eqn performs — the
    roofline numerator for the cost model (repro.cim.cost). Elementwise
    ops count one op per output element; dot_general counts the standard
    2*(out elements)*K."""
    if op.prim is None or not op.outvars:
        return 0
    out = aval_of(op.outvars[0])
    if op.name == "dot_general":
        k = int(aval_of(op.invars[0]).shape[-1])
        return 2 * _numel(out.shape) * k
    return _numel(out.shape)


def host_io_bits(op: "TracedOp") -> int:
    """Bits moved through HBM if this eqn ran alone on the host: every
    operand read once plus every result written once, at true element
    widths (accumulate bits, round to bytes ONCE at the consumer — the
    PR-4 sub-byte-dtype convention)."""
    bits = 0
    for v in tuple(op.invars) + tuple(op.outvars):
        if not hasattr(v, "aval"):
            continue
        aval = aval_of(v)
        if not hasattr(aval, "shape"):
            continue
        try:
            b = dtype_bits(aval.dtype)
        except Exception:
            b = aval.dtype.itemsize * 8
        bits += _numel(aval.shape) * b
    return bits


def _numel(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------


def _host(op: TracedOp, why: str) -> None:
    op.kind, op.why_host = "host", why


def _elementwise_shapes_ok(op: TracedOp) -> bool:
    """Operand shapes must equal the output shape or be scalar (the lax
    weak-literal broadcast the executor replays at pack time)."""
    out = aval_of(op.outvars[0]).shape
    return all(aval_of(v).shape in (out, ()) for v in op.invars)


def classify(op: TracedOp) -> None:
    """Fill in kind / n_bits / accesses / words / schedule for one eqn."""
    name = op.name
    if op.prim is None:                      # _alias passthrough
        _host(op, "alias")
        return
    if name not in SINGLE_PRIMS + MULTI_PRIMS + tuple(CMP_PRIMS) + FREE_PRIMS:
        _host(op, f"unsupported primitive {name!r}")
        return
    avals_in = [aval_of(v) for v in op.invars]
    avals_out = [aval_of(v) for v in op.outvars]
    if not all(_intlike(a) for a in avals_in + avals_out):
        _host(op, "non-integer operand or result")
        return

    out = avals_out[0]
    words = _numel(out.shape)

    # -- free peripheral ops ------------------------------------------------
    if name == "convert_element_type":
        src, dst = avals_in[0].dtype, out.dtype
        if dst == jnp.bool_ and src != jnp.bool_:
            _host(op, "int->bool convert is a != 0 test, not a truncation")
            return
        op.kind, op.n_bits = "free", dtype_bits(dst)
        return
    if name == "reshape":
        if op.params.get("dimensions") is not None:
            _host(op, "reshape with dimension permutation")
            return
        op.kind = "free"
        return
    if name == "not":
        op.kind, op.n_bits = "free", dtype_bits(out.dtype)
        return
    if name == "select_n":
        if len(op.invars) != 3:
            _host(op, "select_n with more than two cases")
            return
        if avals_in[0].dtype != jnp.bool_:
            _host(op, "select_n predicate is not boolean")
            return
        if not _elementwise_shapes_ok(op):
            _host(op, "select_n operand shapes differ from output")
            return
        op.kind = "free"
        return
    if name == "broadcast_in_dim":
        if avals_in[0].shape != ():
            _host(op, "only scalar broadcast is peripheral fanout")
            return
        op.kind = "free"
        return

    # -- single-access elementwise ops --------------------------------------
    if name in SINGLE_PRIMS or name in CMP_PRIMS:
        if not _elementwise_shapes_ok(op):
            _host(op, "operand shapes differ from output")
            return
        ref = next((a for a in avals_in if a.shape != ()), avals_in[0])
        n = dtype_bits(ref.dtype)
        op.kind, op.n_bits, op.words, op.accesses = "single", n, words, 1
        if name in ("add", "sub"):
            op.schedule = planner.plan_elementwise((name,), n + 1, macro=name)
        elif name in ("and", "or", "xor"):
            op.schedule = planner.plan_elementwise((name,), n, macro=name)
        elif name in CMP_PRIMS:
            base, _ = CMP_PRIMS[name]
            op.schedule = planner.plan_elementwise((base,), 1, macro=name)
        elif name == "min":
            op.schedule = planner.plan_minimum(n)
        elif name == "max":
            op.schedule = planner.plan_maximum(n)
        elif name == "neg":
            op.schedule = planner.plan_neg(n)
        elif name == "abs":
            op.schedule = planner.plan_abs(n)
        op.accesses = op.schedule.accesses
        return

    # -- multi-access macro ops ---------------------------------------------
    if name == "mul":
        if not _elementwise_shapes_ok(op):
            _host(op, "operand shapes differ from output")
            return
        n = dtype_bits(out.dtype)
        op.schedule = planner.plan_multiply(
            n, n, signed_b=dtype_signed(out.dtype))
        op.kind, op.n_bits, op.words = "multi", n, words
        op.accesses = op.schedule.accesses
        return
    if name == "population_count":
        n = dtype_bits(out.dtype)
        if n < 2:
            _host(op, "popcount of a 1-bit word is the identity")
            return
        op.schedule = planner.plan_popcount(n)
        op.kind, op.n_bits, op.words = "multi", n, words
        op.accesses = op.schedule.accesses
        return
    if name == "reduce_sum":
        src = avals_in[0]
        if tuple(op.params.get("axes", ())) != tuple(range(len(src.shape))):
            _host(op, "partial reductions not lowered (full-tree only)")
            return
        n_elems = _numel(src.shape)
        if n_elems < 2:
            _host(op, "reduction over fewer than two elements")
            return
        n = dtype_bits(src.dtype)
        op.schedule = planner.plan_reduce_sum(n_elems, stride=1, n_bits=n)
        op.kind, op.n_bits, op.words = "multi", n, n_elems
        op.accesses = op.schedule.accesses
        return
    if name == "dot_general":
        lhs, rhs = avals_in
        dims = op.params["dimension_numbers"]
        (lc, rc), (lb, rb) = dims
        nb = len(lb)
        # canonical (possibly batched) form: [*B, M, K] x [*B, K, N] with
        # the batch dims leading on BOTH sides, the lhs contracting last and
        # the rhs contracting second-to-last — exactly what jnp.matmul emits
        # for stacked operands. Batch dims map onto the word/tile axis of
        # the broadcast layout, so the plan's access count is independent of
        # batch size per tile (see planner.plan_batched_matmul).
        if (len(lhs.shape), len(rhs.shape)) != (nb + 2, nb + 2) or \
                tuple(lb) != tuple(range(nb)) or \
                tuple(rb) != tuple(range(nb)) or \
                tuple(lc) != (nb + 1,) or tuple(rc) != (nb,):
            _host(op, "only canonical [*B,M,K]x[*B,K,N] contractions "
                      "are lowered")
            return
        if lhs.dtype != rhs.dtype:
            _host(op, "mixed-dtype contraction")
            return
        batch = _numel(lhs.shape[:nb])
        m, k = int(lhs.shape[nb]), int(lhs.shape[nb + 1])
        n_cols = int(rhs.shape[nb + 1])
        n = dtype_bits(lhs.dtype)
        k_pad = 1 << planner._log2_ceil(k)
        if nb:
            op.schedule = planner.plan_batched_matmul(
                batch, k, n_cols, n_bits=n, signed=dtype_signed(lhs.dtype))
        else:
            op.schedule = planner.plan_matmul(
                k, n_cols, n_bits=n, signed=dtype_signed(lhs.dtype))
        op.kind, op.n_bits = "multi", n
        op.words = batch * m * k_pad * n_cols
        op.accesses = op.schedule.accesses
        return
    _host(op, f"unhandled primitive {name!r}")   # pragma: no cover


# ---------------------------------------------------------------------------
# jaxpr flattening (nested jit inlining)
# ---------------------------------------------------------------------------


def _flatten(jaxpr, subst: Dict[Any, Any]) -> List[TracedOp]:
    """Flatten a jaxpr into TracedOps, inlining jit calls so regions can
    fuse across `jnp.where`-style wrappers. `subst` maps this jaxpr's vars
    (invars of an inlined call, constvars) to outer atoms."""

    def res(atom):
        if isinstance(atom, Literal):
            return atom
        return subst.get(atom, atom)

    ops: List[TracedOp] = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "jit":
            inner = eqn.params["jaxpr"]          # ClosedJaxpr
            inner_subst = dict(
                zip(inner.jaxpr.invars, (res(v) for v in eqn.invars)))
            inner_subst.update(
                (cv, ConstVal(c))
                for cv, c in zip(inner.jaxpr.constvars, inner.consts))
            inner_ops = _flatten(inner.jaxpr, inner_subst)
            # remap each inner output var to the outer eqn's outvar; a
            # passthrough (literal / invar / duplicated) output becomes an
            # explicit _alias op the executor runs as identity
            out_map: Dict[Any, Any] = {}
            aliases: List[Tuple[Any, Any]] = []
            for iv, ov in zip(inner.jaxpr.outvars, eqn.outvars):
                if isinstance(ov, DropVar):
                    continue
                if isinstance(iv, Literal):
                    aliases.append((iv, ov))
                elif iv in inner_subst:
                    aliases.append((inner_subst[iv], ov))
                elif iv in out_map:
                    aliases.append((out_map[iv], ov))
                else:
                    out_map[iv] = ov
            for op in inner_ops:
                op.outvars = tuple(out_map.get(v, v) for v in op.outvars)
                # consumers INSIDE the inlined jaxpr must follow the rename
                # (an inner output can also feed further inner eqns)
                op.invars = tuple(
                    out_map.get(v, v) if isinstance(v, Var) else v
                    for v in op.invars)
            ops.extend(inner_ops)
            ops.extend(
                TracedOp(prim=None, params={}, invars=(src,), outvars=(dst,),
                         name="_alias")
                for src, dst in aliases)
        else:
            ops.append(TracedOp(
                prim=eqn.primitive, params=dict(eqn.params),
                invars=tuple(res(v) for v in eqn.invars),
                outvars=tuple(eqn.outvars),
                name=eqn.primitive.name))
    return ops


def trace(fn, *args) -> Trace:
    """Stage `fn` on example `args` and classify every eqn (see module doc).

    Positional arguments only; pytrees are allowed and flattened the same
    way `jax.make_jaxpr` flattens them.
    """
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
    subst = {cv: ConstVal(c)
             for cv, c in zip(closed.jaxpr.constvars, closed.consts)}
    ops = _flatten(closed.jaxpr, subst)
    for op in ops:
        classify(op)
    return Trace(closed=closed, ops=ops, out_shape=out_shape)
