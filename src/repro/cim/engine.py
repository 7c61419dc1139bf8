"""The unified CiM engine: one dispatch point for every ADRA operation.

`execute` runs any subset of the op catalogue (opset.ALL_OPS) over two
PlanePacks in ONE simulated memory access on the selected backend, returning
PlanePacks — so chained ops stay in the packed bit-plane domain with zero
intermediate pack/unpack. `execute_unfused` is the near-memory baseline (one
access per pass) the paper argues against; benchmarks compare the two.

Integer-level convenience wrappers (add / sub / compare / boolean) pack,
execute, and unpack for call sites that live in ordinary integer arrays.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax

from . import opset
from .accounting import LEDGER
from .backends import get_backend
from .planepack import PlanePack

Outputs = Dict[str, PlanePack]


def _wrap(op: str, raw: jax.Array, n_bits: int,
          shape: Tuple[int, ...]) -> PlanePack:
    rows = opset.out_rows(op, n_bits)
    assert raw.shape[0] == rows, (op, raw.shape, rows)
    return PlanePack(planes=raw, n_bits=rows, signed=opset.out_signed(op),
                     shape=shape)


def prepare_operands(a: PlanePack, b: PlanePack, ops: Sequence[str]
                     ) -> Tuple[PlanePack, PlanePack, Tuple[str, ...]]:
    """Validate an op request and align its operands in the packed domain.

    Shared by the single-array `execute` below and the banked tiling
    dispatcher (repro.cim.dispatch), so both paths see identical widening."""
    ops = opset.validate_ops(tuple(ops))
    if a.shape != b.shape:
        raise opset.CimOpError(f"operand shapes differ: {a.shape} vs {b.shape}")
    a, b = a.align(b)
    if (opset.needs_add_chain(ops) or opset.needs_sub_chain(ops)) \
            and not (a.signed and b.signed):
        # the ripple chains interpret operands as two's complement (the
        # overflow module sign-extends the MSB plane); widen by one plane —
        # zero for unsigned, sign replica for signed — so unsigned magnitudes
        # with the top bit set cannot be misread as negative
        n = a.n_bits + 1
        a, b = a.extend_to(n), b.extend_to(n)
    return a, b, ops


def execute_traced(a: PlanePack, b: PlanePack, ops: Sequence[str],
                   backend: Optional[str] = None,
                   charges: Optional[list] = None,
                   n_words: Optional[int] = None) -> Outputs:
    """The side-effect-free inner form of `execute`: pure computation, no
    ledger mutation, so a whole schedule of these can be traced into ONE
    jitted XLA program (repro.cim.macro.run_schedule_program).

    With `charges`, the access this call represents is appended as a
    charge-from-plan record — ("access", ops, n_bits, n_words) at the
    post-alignment width, exactly what `execute` would have charged — for
    the compiled program to replay per invocation (accounting.PlannedCharges).
    `n_words` charges the modelled access's words where the caller computes
    fewer than it (the matmul's halving fold, `macro._fold_reduce`); by
    default the operands' own words are charged.
    """
    a, b, ops = prepare_operands(a, b, ops)
    bk = get_backend(backend)
    raws = bk(a.planes, b.planes, ops)
    if charges is not None:
        charges.append(("access", ops, a.n_bits,
                        a.n_words if n_words is None else n_words))
    return {op: _wrap(op, raw, a.n_bits, a.shape)
            for op, raw in zip(ops, raws)}


def execute(a: PlanePack, b: PlanePack, ops: Sequence[str],
            backend: Optional[str] = None) -> Outputs:
    """One ADRA access: every requested op from a single streamed pass.

    Operands of different widths are sign/zero-extended in the packed domain
    first. Returns {op: PlanePack}; predicates come back as 1-plane unsigned
    packs (unpack() gives 0/1 per word).
    """
    a, b = _fault_overlay(a, b)
    charges: list = []
    out = execute_traced(a, b, ops, backend=backend, charges=charges)
    for _, c_ops, n_bits, n_words in charges:
        LEDGER.charge(c_ops, n_bits, n_words, accesses=1)
    return out


def _fault_overlay(a: PlanePack, b: PlanePack
                   ) -> Tuple[PlanePack, PlanePack]:
    """Transient BER injection on the streamed operands of one eager
    access (the untiled path has no bank placement, so stuck-at rows do
    not apply here). Concrete values only — tracers pass untouched."""
    from . import faults as faults_mod

    fm = faults_mod.active()
    if fm is None or fm.config.ber <= 0.0:
        return a, b
    if isinstance(a.planes, jax.core.Tracer) \
            or isinstance(b.planes, jax.core.Tracer):
        return a, b
    import dataclasses as _dc

    import jax.numpy as jnp
    import numpy as np

    pa, na = fm.corrupt_streamed(np.asarray(a.planes))
    pb, nb = fm.corrupt_streamed(np.asarray(b.planes))
    if na:
        a = _dc.replace(a, planes=jnp.asarray(pa))
    if nb:
        b = _dc.replace(b, planes=jnp.asarray(pb))
    return a, b


def execute_unfused(a: PlanePack, b: PlanePack,
                    passes: Sequence[Sequence[str]],
                    backend: Optional[str] = None) -> Outputs:
    """Near-memory baseline: one FULL access per pass, operands re-streamed
    each time (the paper's two-access execution, generalized to k passes)."""
    out: Outputs = {}
    for ops in passes:
        out.update(execute(a, b, ops, backend=backend))
    return out


# ---------------------------------------------------------------------------
# integer-level wrappers
# ---------------------------------------------------------------------------


class CmpOut(NamedTuple):
    lt: jax.Array
    eq: jax.Array
    gt: jax.Array


def add(x: jax.Array, y: jax.Array, n_bits: int = 32,
        backend: Optional[str] = None) -> jax.Array:
    """x + y via one ADRA access. The engine emits the full (n+1)-plane
    result; unpack() materializes it as int32, so values are exact for
    n_bits < 32 and wrap modulo 2^32 at n_bits = 32 (int32 semantics).
    Callers needing the wider planes should use execute() directly."""
    out = execute(PlanePack.pack(x, n_bits), PlanePack.pack(y, n_bits),
                  ("add",), backend=backend)
    return out["add"].unpack()


def sub(x: jax.Array, y: jax.Array, n_bits: int = 32,
        backend: Optional[str] = None) -> jax.Array:
    """x - y via one ADRA access (the paper's non-commutative headline)."""
    out = execute(PlanePack.pack(x, n_bits), PlanePack.pack(y, n_bits),
                  ("sub",), backend=backend)
    return out["sub"].unpack()


def compare(x: jax.Array, y: jax.Array, n_bits: int = 32,
            backend: Optional[str] = None) -> CmpOut:
    """Single-access comparison: lt/eq/gt 0/1 arrays of the operand shape."""
    out = execute(PlanePack.pack(x, n_bits), PlanePack.pack(y, n_bits),
                  ("lt", "eq", "gt"), backend=backend)
    return CmpOut(lt=out["lt"].unpack(), eq=out["eq"].unpack(),
                  gt=out["gt"].unpack())


def boolean(x: jax.Array, y: jax.Array, fn: str, n_bits: int = 32,
            backend: Optional[str] = None) -> jax.Array:
    """Any of the 16 two-input Boolean functions, one access."""
    if fn not in opset.BOOLEAN_OPS:
        raise opset.CimOpError(
            f"unknown Boolean function {fn!r}; valid: {opset.BOOLEAN_OPS}")
    out = execute(PlanePack.pack(x, n_bits), PlanePack.pack(y, n_bits),
                  (fn,), backend=backend)
    return out[fn].unpack()


# ---------------------------------------------------------------------------
# HBM traffic: the roofline argument, modeled and measured
# ---------------------------------------------------------------------------


def traffic_model_bytes(n_bits: int, n_words32: int,
                        ops: Sequence[str] = ("sub", "carry_sub", "lt", "eq"),
                        baseline_passes: Optional[Sequence[Sequence[str]]] = None,
                        ) -> Dict[str, float]:
    """HBM bytes of one fused pass vs per-pass baseline re-reads.

    The memory-roofline analogue of the paper's one-vs-two access argument:
    the baseline re-streams both operand stacks for every pass."""
    ops = opset.validate_ops(tuple(ops))
    if baseline_passes is None:
        baseline_passes = tuple((op,) for op in ops)
    plane_bytes = 4 * n_words32
    ops_in = 2 * n_bits * plane_bytes
    out_bytes = {op: opset.out_rows(op, n_bits) * plane_bytes for op in ops}
    fused = ops_in + sum(out_bytes.values())
    baseline = sum(ops_in + sum(out_bytes[o] for o in p)
                   for p in baseline_passes)
    return {"fused": float(fused), "baseline": float(baseline),
            "ratio": baseline / fused}


def measured_traffic_bytes(a: PlanePack, b: PlanePack, ops: Sequence[str],
                           baseline_passes: Optional[Sequence[Sequence[str]]] = None,
                           backend: Optional[str] = None) -> Dict[str, float]:
    """Like traffic_model_bytes, but measured from the buffers the backend
    program ACTUALLY streams: operand + result bytes per pass, read off the
    abstractly-evaluated backend call (no execution, no ledger charge)."""
    ops = opset.validate_ops(tuple(ops))
    if baseline_passes is None:
        baseline_passes = tuple((op,) for op in ops)
    a, b = a.align(b)
    in_bytes = a.planes.nbytes + b.planes.nbytes
    bk = get_backend(backend)

    def pass_bytes(pass_ops):
        outs = jax.eval_shape(
            lambda ap, bp: bk(ap, bp, tuple(pass_ops)), a.planes, b.planes)
        out_bytes = 0
        for o in jax.tree_util.tree_leaves(outs):
            n = 1
            for d in o.shape:
                n *= int(d)
            out_bytes += n * o.dtype.itemsize
        return in_bytes + out_bytes

    fused = pass_bytes(ops)
    baseline = sum(pass_bytes(p) for p in baseline_passes)
    return {"fused": float(fused), "baseline": float(baseline),
            "ratio": baseline / fused}
