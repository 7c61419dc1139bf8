"""CiM backend registry: one dispatch point for every ADRA execution model.

A backend is a callable over packed bit-planes:

    fn(a_planes uint32[n, W], b_planes uint32[n, W], ops: tuple[str, ...])
        -> tuple[jax.Array, ...]   # one output per op, opset shape rules

Registered backends:

  pallas-tpu       — the fused single-pass Pallas kernel, compiled (TPU)
  pallas-interpret — same kernel through the Pallas interpreter (CPU tests)
  jnp-boolean      — pure-jnp plane math, ideal SAs (fast portable path and
                     the dry-run lowering fallback)
  analog-oracle    — per-bit senseline currents from the calibrated FeFET
                     device model, thresholded against the SA references
                     (repro.core.adra mode="analog"): the slow path that IS
                     the paper, used to validate every other backend

This replaces the ad-hoc `_on_tpu()` checks that used to be scattered through
kernels/ops.py: resolution order is explicit argument > REPRO_CIM_BACKEND
env var > set_default_backend() > platform default.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import opset
from .fused_kernel import fused_planes_op

Planes = jax.Array
BackendFn = Callable[[Planes, Planes, Tuple[str, ...]], Tuple[jax.Array, ...]]


@dataclasses.dataclass(frozen=True)
class Backend:
    name: str
    fn: BackendFn
    description: str

    def __call__(self, a_planes, b_planes, ops):
        return self.fn(a_planes, b_planes, ops)


_REGISTRY: Dict[str, Backend] = {}
_DEFAULT_OVERRIDE: Optional[str] = None


def register_backend(name: str, fn: BackendFn, description: str = "") -> Backend:
    bk = Backend(name=name, fn=fn, description=description)
    _REGISTRY[name] = bk
    return bk


def available_backends() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def set_default_backend(name: Optional[str]) -> None:
    """Process-wide default (None restores platform-based resolution)."""
    global _DEFAULT_OVERRIDE
    if name is not None and name not in _REGISTRY:
        raise KeyError(f"unknown backend {name!r}; have {available_backends()}")
    _DEFAULT_OVERRIDE = name


def default_backend_name() -> str:
    env = os.environ.get("REPRO_CIM_BACKEND")
    if env:
        return env
    if _DEFAULT_OVERRIDE:
        return _DEFAULT_OVERRIDE
    return "pallas-tpu" if on_tpu() else "jnp-boolean"


def get_backend(name: Optional[str] = None) -> Backend:
    name = name or default_backend_name()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown CiM backend {name!r}; have {available_backends()}") from None


# ---------------------------------------------------------------------------
# pallas-tpu / pallas-interpret
# ---------------------------------------------------------------------------


def _pallas_backend(a_planes, b_planes, ops, *, interpret: bool):
    with jax.named_scope("cim.kernel"):
        return fused_planes_op(a_planes, b_planes, tuple(ops),
                               interpret=interpret)


# ---------------------------------------------------------------------------
# jnp-boolean: the kernel's dataflow in pure jnp (ideal SAs)
# ---------------------------------------------------------------------------


def _jnp_boolean_backend(a_planes, b_planes, ops):
    ops = opset.validate_ops(ops)
    n_bits, w = a_planes.shape
    need_add = opset.needs_add_chain(ops)
    need_sub = opset.needs_sub_chain(ops)
    out: Dict[str, list] = {fn: [] for fn in ops if fn in opset.BOOLEAN_OPS}
    add_planes, sub_planes = [], []

    zeros = jnp.zeros((w,), jnp.uint32)
    carry_a, carry_s, nz = zeros, ~zeros, zeros
    for i in range(n_bits):
        a, b = a_planes[i], b_planes[i]
        or_, and_ = a | b, a & b
        a_rec = opset.oai21_recover_a_planes(or_, and_, b)
        for fn in out:
            out[fn].append(opset.boolean_plane(fn, or_, and_, b, a_rec))
        xor = or_ & ~and_
        if need_add:
            add_planes.append(xor ^ carry_a)
            carry_a = and_ | (carry_a & xor)
        if need_sub:
            xnor = ~xor
            s = xnor ^ carry_s
            sub_planes.append(s)
            carry_s = (or_ & ~b) | (carry_s & xnor)
            nz = nz | s

    a_msb, b_msb = a_planes[n_bits - 1], b_planes[n_bits - 1]
    results: Dict[str, jax.Array] = {}
    if need_add:
        xor = a_msb ^ b_msb
        add_planes.append(xor ^ carry_a)
        results["add"] = jnp.stack(add_planes)
        results["carry_add"] = ((a_msb & b_msb) | (carry_a & xor))[None, :]
    if need_sub:
        nb = ~b_msb
        xnor = a_msb ^ nb
        s_ext = xnor ^ carry_s
        sub_planes.append(s_ext)
        nz = nz | s_ext
        results["sub"] = jnp.stack(sub_planes)
        results["carry_sub"] = ((a_msb & nb) | (carry_s & xnor))[None, :]
        results["lt"] = s_ext[None, :]
        results["eq"] = (~nz)[None, :]
        results["gt"] = (~s_ext & nz)[None, :]
    for fn, planes in out.items():
        results[fn] = jnp.stack(planes)
    # one access per fusion: XLA would otherwise fuse a whole unrolled
    # schedule of plane ops into one loop, which LLVM takes minutes to
    # optimize on the CPU
    return jax.lax.optimization_barrier(tuple(results[op] for op in ops))


# ---------------------------------------------------------------------------
# analog-oracle: the device-model path from repro.core.adra, per bit
# ---------------------------------------------------------------------------


def _planes_to_bits(planes: jax.Array) -> jax.Array:
    """uint32[rows, W] -> int32[W*32, rows] 0/1 bit matrix (word-major)."""
    rows, w = planes.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (planes[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(rows, w * 32).T.astype(jnp.int32)


def _bits_to_planes(bits: jax.Array) -> jax.Array:
    """int32[W*32, rows] 0/1 -> uint32[rows, W] packed planes."""
    n, rows = bits.shape
    assert n % 32 == 0, n
    weights = (1 << jnp.arange(32, dtype=jnp.uint32)).astype(jnp.uint32)
    chunks = bits.T.reshape(rows, n // 32, 32).astype(jnp.uint32)
    return jnp.sum(chunks * weights, axis=-1)


def _analog_oracle_backend(a_planes, b_planes, ops):
    """Unpack to bits, run the sensed analog dataflow, repack. Slow by design
    (evaluates the FeFET device model per bit); use small widths."""
    from repro.core.adra import adra_access
    from repro.core.compute_module import compare_from_sub, ripple_chain

    ops = opset.validate_ops(ops)
    a_bits = _planes_to_bits(a_planes)      # [N, n_bits]
    b_bits = _planes_to_bits(b_planes)
    acc = adra_access(a_bits, b_bits, mode="analog")

    results: Dict[str, jax.Array] = {}
    if opset.needs_add_chain(ops):
        sum_bits, c_out = ripple_chain(acc.or_, acc.and_, acc.b, select=0)
        results["add"] = _bits_to_planes(sum_bits)
        results["carry_add"] = _bits_to_planes(c_out[:, None])
    if opset.needs_sub_chain(ops):
        sum_bits, c_out = ripple_chain(acc.or_, acc.and_, acc.b, select=1)
        results["sub"] = _bits_to_planes(sum_bits)
        results["carry_sub"] = _bits_to_planes(c_out[:, None])
        c = compare_from_sub(sum_bits)
        results["lt"] = _bits_to_planes(c.lt[:, None])
        results["eq"] = _bits_to_planes(c.eq[:, None])
        results["gt"] = _bits_to_planes(c.gt[:, None])
    for fn in ops:
        if fn in opset.BOOLEAN_OPS:
            plane_bits = opset.boolean_plane(
                fn,
                acc.or_.astype(jnp.uint32), acc.and_.astype(jnp.uint32),
                acc.b.astype(jnp.uint32), acc.a.astype(jnp.uint32)) & 1
            results[fn] = _bits_to_planes(plane_bits.astype(jnp.int32))
    return tuple(results[op] for op in ops)


# ---------------------------------------------------------------------------
# registration
# ---------------------------------------------------------------------------

import functools as _functools

register_backend(
    "pallas-tpu", _functools.partial(_pallas_backend, interpret=False),
    "fused single-pass Pallas kernel, compiled")
register_backend(
    "pallas-interpret", _functools.partial(_pallas_backend, interpret=True),
    "fused Pallas kernel through the interpreter (portable tests)")
register_backend(
    "jnp-boolean", _jnp_boolean_backend,
    "pure-jnp plane math with ideal SAs")
register_backend(
    "analog-oracle", _analog_oracle_backend,
    "calibrated FeFET device model + sensed SAs (the paper, per bit)")
