"""Jitted public wrappers for the Pallas kernels, with backend dispatch.

ADRA integer ops route through the unified CiM engine (repro.cim): backend
resolution comes from the registry (pallas-tpu on TPU, jnp-boolean elsewhere,
REPRO_CIM_BACKEND / set_default_backend to override) instead of ad-hoc
platform checks. The legacy `interpret` flag maps onto the pallas-interpret /
pallas-tpu backends for callers that pin the Pallas path explicitly.

Attention / recurrence wrappers keep the same dispatch idea: Pallas on TPU,
interpret mode only where the caller asks for it (tests), pure-jnp reference
elsewhere.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.cim import PlanePack, execute, execute_unfused, macro, on_tpu
from repro.cim.array import ArraySpec
from repro.cim.dispatch import execute_tiled
from repro.cim.planepack import mask_to_ints
from . import ref
from .adra_bitplane import adra_bitplane_op, baseline_bitplane_sub_then_cmp  # noqa: F401
from .flash_attention import flash_attention as _flash
from .rglru import rglru as _rglru


def _resolve_backend(interpret: Optional[bool], backend: Optional[str]) -> Optional[str]:
    """Map the legacy interpret flag to a registry backend name.

    None/None defers to the registry default (platform- or env-resolved)."""
    if backend is not None:
        return backend
    if interpret is None:
        return None
    return "pallas-interpret" if interpret else "pallas-tpu"


# ---------------------------------------------------------------------------
# ADRA integer ops through the CiM engine
# ---------------------------------------------------------------------------


def adra_sub(a: jax.Array, b: jax.Array, n_bits: int = 16,
             interpret: bool | None = None, backend: str | None = None,
             spec: ArraySpec | None = None, mesh=None):
    """Fused single-pass subtraction + comparison over integer arrays.

    Returns (diff int32[...], lt int32[...], eq int32[...]). With `spec`
    the operands are tiled over the banked array substrate (optionally
    shard_mapped over `mesh`); results are identical, the ledger charges
    per-bank activations instead of one infinite-array access.
    """
    bk = _resolve_backend(interpret, backend)
    pa, pb = PlanePack.pack(a, n_bits), PlanePack.pack(b, n_bits)
    if spec is not None or mesh is not None:
        out = execute_tiled(pa, pb, ("sub", "lt", "eq"), spec=spec,
                            backend=bk, mesh=mesh)
    else:
        out = execute(pa, pb, ("sub", "lt", "eq"), backend=bk)
    return out["sub"].unpack(), out["lt"].unpack(), out["eq"].unpack()


def adra_add(a: jax.Array, b: jax.Array, n_bits: int = 16,
             interpret: bool | None = None, backend: str | None = None,
             spec: ArraySpec | None = None, mesh=None):
    bk = _resolve_backend(interpret, backend)
    pa, pb = PlanePack.pack(a, n_bits), PlanePack.pack(b, n_bits)
    if spec is not None or mesh is not None:
        out = execute_tiled(pa, pb, ("add",), spec=spec, backend=bk,
                            mesh=mesh)
    else:
        out = execute(pa, pb, ("add",), backend=bk)
    return out["add"].unpack()


def unpack_bits_mask(bitmap: jax.Array, n: int) -> jax.Array:
    """uint32[1, W] bitmap -> int32[n] of 0/1 (compat; see planepack)."""
    return mask_to_ints(bitmap, (n,))


def baseline_sub_then_cmp(a: jax.Array, b: jax.Array, n_bits: int = 16,
                          interpret: bool | None = None,
                          backend: str | None = None):
    """The paper's near-memory baseline: separate passes (for benchmarks)."""
    bk = _resolve_backend(interpret, backend)
    out = execute_unfused(PlanePack.pack(a, n_bits), PlanePack.pack(b, n_bits),
                          (("sub",), ("lt", "eq")), backend=bk)
    return out["sub"].unpack(), out["lt"].unpack(), out["eq"].unpack()


# ---------------------------------------------------------------------------
# Macro ops (multi-access schedules from the CiM planner)
# ---------------------------------------------------------------------------


def cim_matmul(a: jax.Array, b: jax.Array, n_bits: int = 8,
               interpret: bool | None = None, backend: str | None = None,
               spec: ArraySpec | None = None, mesh=None):
    """Exact intN x intN -> int32 matmul through planned CiM access schedules.

    a [M, K], b [K, N] with entries representable in n_bits signed. The
    LOGICAL access count is (2*n_bits - 1) + ceil(log2 K) — independent of
    M and N; placed on a banked `spec`, each access becomes one activation
    per operand tile and the schedule carries its placement. The whole
    schedule executes as ONE jitted XLA program (repro.cim.macro.
    run_schedule_program): warm calls are a single dispatch with ledger
    charges replayed from the plan.
    """
    return macro.matmul(a, b, n_bits=n_bits,
                        backend=_resolve_backend(interpret, backend),
                        spec=spec, mesh=mesh)


def cim_relu(x: jax.Array, n_bits: int = 16,
             interpret: bool | None = None, backend: str | None = None,
             spec: ArraySpec | None = None, mesh=None):
    """max(x, 0) over integer arrays: ONE access (gt predicate + peripheral
    select) regardless of width."""
    bk = _resolve_backend(interpret, backend)
    return macro.relu(PlanePack.pack(x, n_bits), backend=bk,
                      spec=spec, mesh=mesh).unpack()


def cim_lower(fn, interpret: bool | None = None, backend: str | None = None,
              spec: ArraySpec | None = None, mesh=None):
    """Compile an unmodified JAX function into the hybrid CiM/host callable
    (repro.cim.lower): ADRA-eligible integer subgraphs fuse into planned
    access schedules executed through the banked dispatcher, everything
    else runs on the host. The kernels-level entry point applies the same
    legacy `interpret` flag resolution as the other wrappers here."""
    from repro.cim.lower import lower

    return lower(fn, backend=_resolve_backend(interpret, backend),
                 spec=spec, mesh=mesh)


# ---------------------------------------------------------------------------
# Attention / recurrence with backend dispatch
# ---------------------------------------------------------------------------


def attention(q, k, v, causal: bool = True, use_pallas: bool | None = None,
              interpret: bool = False):
    """GQA attention: Pallas flash kernel on TPU, jnp reference elsewhere.
    The kernel runs in interpret mode only when the caller asks for it."""
    use_pallas = on_tpu() if use_pallas is None else use_pallas
    if use_pallas or interpret:
        return _flash(q, k, v, causal=causal, interpret=interpret)
    return ref.mha_ref(q, k, v, causal=causal)


def rglru_scan(x, r, i, log_lambda, h0=None, c: float = 8.0,
               use_pallas: bool | None = None, interpret: bool = False):
    use_pallas = on_tpu() if use_pallas is None else use_pallas
    if use_pallas or interpret:
        return _rglru(x, r, i, log_lambda, h0=h0, c=c, interpret=interpret)
    return ref.rglru_ref(x, r, i, log_lambda, h0=h0, c=c)
