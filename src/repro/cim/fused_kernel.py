"""Generalized Pallas TPU kernel: ANY subset of the CiM op catalogue from ONE
streamed pass over both bit-plane stacks.

This is the TPU analogue of the paper's full peripheral: the three sense
amplifiers + OAI21 gate expose {OR, AND, B, A} per bit from a single memory
access, and the dual-output compute modules ripple BOTH the addition and the
subtraction chains in the same cycle. Here the plane stacks stream HBM->VMEM
exactly once, and every requested output — add/sub plane stacks, carry-outs,
lt/eq/gt bitmaps, any of the 16 Boolean function plane stacks — is emitted
from that one pass with pure VPU bitwise ops.

The near-memory baseline (what the paper beats) is one pass PER function,
re-reading the operands each time; the engine exposes it for benchmarks via
`repro.cim.engine.execute_unfused`.

Layout:  a_planes, b_planes : uint32[n_bits, n_words32]
Grid:    1-D over lane blocks; the whole bit dim stays resident in VMEM
         (a 33-plane f32-width stack at block_w=512 is ~66 KiB per ref,
         well inside the ~16 MiB VMEM budget; MXU-free, pure VPU).

The op request is STATIC: each distinct subset specializes its own kernel, so
unrequested outputs cost neither VMEM nor HBM writeback.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import opset

DEFAULT_BLOCK_W = 512  # lane-dim block (multiple of 128 for VPU alignment)


def _fused_kernel(a_ref, b_ref, *out_refs, ops: Tuple[str, ...]):
    """One word block: single streamed pass, all requested outputs.

    a_ref/b_ref: uint32[n_bits, bw]; out_refs ordered as `ops`
    (arith: [n_bits+1, bw]; predicates: [1, bw]; boolean fns: [n_bits, bw]).
    """
    n_bits = a_ref.shape[0]
    bw = a_ref.shape[1]
    out = dict(zip(ops, out_refs))
    need_add = opset.needs_add_chain(ops)
    need_sub = opset.needs_sub_chain(ops)
    bool_fns = tuple(o for o in ops if o in opset.BOOLEAN_OPS)

    zeros = jnp.zeros((bw,), jnp.uint32)
    ones = ~zeros

    def module(i, state):
        carry_a, carry_s, nz = state
        a = a_ref[i, :]
        b = b_ref[i, :]
        # the single-access signal set (3 SAs + OAI21), plane-wise
        or_ = a | b
        and_ = a & b
        a_rec = opset.oai21_recover_a_planes(or_, and_, b)
        for fn in bool_fns:
            out[fn][i, :] = opset.boolean_plane(fn, or_, and_, b, a_rec)
        xor = or_ & ~and_                       # half-sum (addition)
        if need_add:
            s = xor ^ carry_a
            if "add" in out:
                out["add"][i, :] = s
            carry_a = and_ | (carry_a & xor)    # generate | propagate
        if need_sub:
            xnor = ~xor                         # half-sum with B inverted
            a_nb = or_ & ~b                     # generate term A * NOT(B)
            s = xnor ^ carry_s
            if "sub" in out:
                out["sub"][i, :] = s
            carry_s = a_nb | (carry_s & xnor)
            nz = nz | s                         # OR tree for the zero detect
        return carry_a, carry_s, nz

    # C_IN(0): 0 for addition, 1 for subtraction (A - B = A + ~B + 1)
    carry_a, carry_s, nz = jax.lax.fori_loop(
        0, n_bits, module, (zeros, ones, zeros))

    # (n+1)-th compute module: sign-extended inputs (paper Sec. III-B)
    a_msb = a_ref[n_bits - 1, :]
    b_msb = b_ref[n_bits - 1, :]
    if need_add:
        xor = a_msb ^ b_msb
        s_ext = xor ^ carry_a
        if "add" in out:
            out["add"][n_bits, :] = s_ext
        if "carry_add" in out:
            out["carry_add"][0, :] = (a_msb & b_msb) | (carry_a & xor)
    if need_sub:
        nb = ~b_msb
        xnor = a_msb ^ nb
        s_ext = xnor ^ carry_s
        nz = nz | s_ext
        if "sub" in out:
            out["sub"][n_bits, :] = s_ext
        if "carry_sub" in out:
            out["carry_sub"][0, :] = (a_msb & nb) | (carry_s & xnor)
        if "lt" in out:
            out["lt"][0, :] = s_ext             # sign of the (n+1)-bit A-B
        if "eq" in out:
            out["eq"][0, :] = ~nz               # AND tree over ~SUM bits
        if "gt" in out:
            out["gt"][0, :] = ~s_ext & nz       # not lt, not eq


@functools.partial(jax.jit, static_argnames=("ops", "block_w", "interpret"))
def fused_planes_op(
    a_planes: jax.Array,
    b_planes: jax.Array,
    ops: Tuple[str, ...],
    block_w: int = DEFAULT_BLOCK_W,
    interpret: bool = False,
) -> Tuple[jax.Array, ...]:
    """Run the fused kernel; returns one array per requested op, in order."""
    ops = opset.validate_ops(ops)
    n_bits, w = a_planes.shape
    assert b_planes.shape == (n_bits, w), (a_planes.shape, b_planes.shape)
    pad = (-w) % block_w
    if pad:
        with jax.named_scope("cim.kernel_pad"):
            a_planes = jnp.pad(a_planes, ((0, 0), (0, pad)))
            b_planes = jnp.pad(b_planes, ((0, 0), (0, pad)))
    wp = a_planes.shape[1]

    grid = (wp // block_w,)
    rows = [opset.out_rows(op, n_bits) for op in ops]
    out_shapes = tuple(
        jax.ShapeDtypeStruct((r, wp), jnp.uint32) for r in rows)
    plane_spec = pl.BlockSpec((n_bits, block_w), lambda i: (0, i))
    out_specs = tuple(
        pl.BlockSpec((r, block_w), lambda i: (0, i)) for r in rows)

    # no named scope around the call: the TPU custom call takes its name
    # from the innermost scope, and a profile finds the kernel by the name
    # `fused_planes_op` (callers scope the whole call as `cim.kernel`)
    outs = pl.pallas_call(
        functools.partial(_fused_kernel, ops=ops),
        grid=grid,
        in_specs=[plane_spec, plane_spec],
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(a_planes, b_planes)
    if not isinstance(outs, (tuple, list)):
        outs = (outs,)
    with jax.named_scope("cim.kernel_pad"):
        return tuple(o[:, :w] for o in outs)
