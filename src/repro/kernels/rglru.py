"""Pallas TPU kernel for the RG-LRU recurrence (Griffin / RecurrentGemma).

    a_t = exp(-c * softplus(log_lambda) * sigmoid(r_t))
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (sigmoid(i_t) * x_t)

Time is blocked along a sequential grid dimension; the hidden state h is
carried across time blocks in VMEM scratch (the TPU analogue of keeping the
recurrence register-resident instead of round-tripping HBM per step). Feature
dim is blocked lane-aligned (multiples of 128). Validated in interpret mode
against ref.rglru_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(x_ref, r_ref, i_ref, ll_ref, h0_ref, y_ref, hout_ref, h_ref, *, c: float):
    ti = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = h0_ref[0, :][None, :].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)            # [bt, bd]
    r = r_ref[0].astype(jnp.float32)
    i = i_ref[0].astype(jnp.float32)
    ll = ll_ref[0].astype(jnp.float32)          # [bd]

    decay = jax.nn.softplus(ll)[None, :]
    a = jnp.exp(-c * decay * jax.nn.sigmoid(r))  # [bt, bd]
    gated = jax.nn.sigmoid(i) * x
    mult = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12))

    def step(h, xs):
        a_t, g_t, m_t = xs
        h = a_t * h + m_t * g_t
        return h, h

    h0 = h_ref[0, :]
    h_last, ys = jax.lax.scan(step, h0, (a, gated, mult))
    y_ref[0] = ys.astype(y_ref.dtype)
    h_ref[...] = h_last[None, :]

    @pl.when(ti == nt - 1)
    def _emit_state():
        hout_ref[0, :] = h_last.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("c", "block_t", "block_d", "interpret"))
def rglru(
    x: jax.Array,            # [B, T, D]
    r: jax.Array,            # [B, T, D]
    i: jax.Array,            # [B, T, D]
    log_lambda: jax.Array,   # [D]
    h0: jax.Array | None = None,   # [B, D]
    c: float = 8.0,
    block_t: int = 128,
    block_d: int = 128,
    interpret: bool = False,
):
    """Returns (y [B,T,D], h_T [B,D])."""
    b, t, d = x.shape
    if h0 is None:
        h0 = jnp.zeros((b, d), jnp.float32)
    block_t = min(block_t, t)
    block_d = min(block_d, d)
    assert t % block_t == 0 and d % block_d == 0, (t, d, block_t, block_d)

    grid = (b, d // block_d, t // block_t)
    seq_spec = pl.BlockSpec((1, block_t, block_d), lambda b_, di, ti: (b_, ti, di))
    out = pl.pallas_call(
        functools.partial(_rglru_kernel, c=c),
        grid=grid,
        in_specs=[
            seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, block_d), lambda b_, di, ti: (0, di)),
            pl.BlockSpec((1, block_d), lambda b_, di, ti: (b_, di)),
        ],
        out_specs=(
            seq_spec,
            pl.BlockSpec((1, block_d), lambda b_, di, ti: (b_, di)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, t, d), x.dtype),
            jax.ShapeDtypeStruct((b, d), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, r, i, log_lambda[None, :], h0)
    return out
