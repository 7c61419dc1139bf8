"""Pallas TPU kernel for the sLSTM recurrence with VMEM-RESIDENT recurrent
weights (EXPERIMENTS.md §Perf B2).

sLSTM is inherently sequential (h_{t-1} feeds the gate pre-activations), so
XLA re-streams the recurrent matrix R [D, 4, D] from HBM every timestep:
9.4 MB x 4096 steps x 3 layers ~ 116 GB of redundant traffic per xlstm-125m
train step. R fits VMEM (9.4 MB f32 < 16 MiB), so this kernel pins it there
for the whole sequence: traffic becomes read-once + O(T) activations.

Grid: (B_blocks, T) with T sequential ("arbitrary"); the (h, c, n, m) state
is carried across timesteps in VMEM scratch. Per step: one [bb, D] x [D, 4D]
MXU matmul + elementwise gating.

    pre = wx_t + h R + b;  z = tanh(pre_0); i = pre_1; f = log_sigmoid(pre_2)
    m' = max(f + m, i);  c = e^{f+m-m'} c + e^{i-m'} z;  n = e^{f+m-m'} n + e^{i-m'}
    h = sigmoid(pre_3) * c / max(n, 1e-6)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _slstm_kernel(wx_ref, r_ref, b_ref, h0_ref, c0_ref, n0_ref, m0_ref,
                  y_ref, hout_ref, cout_ref, nout_ref, mout_ref, state_ref):
    t = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t == 0)
    def _init():
        state_ref[0] = h0_ref[...].astype(jnp.float32)
        state_ref[1] = c0_ref[...].astype(jnp.float32)
        state_ref[2] = n0_ref[...].astype(jnp.float32)
        state_ref[3] = m0_ref[...].astype(jnp.float32)

    h = state_ref[0]                                     # [bb, D]
    c = state_ref[1]
    n = state_ref[2]
    m = state_ref[3]

    d = h.shape[-1]
    r = r_ref[...].reshape(d, 4 * d)                     # VMEM-resident
    rec = jax.lax.dot_general(h, r, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    rec = rec.reshape(h.shape[0], 4, d)
    pre = wx_ref[:, 0].astype(jnp.float32) + rec + b_ref[...][None]

    z = jnp.tanh(pre[:, 0])
    i_t = pre[:, 1]
    f_t = jax.nn.log_sigmoid(pre[:, 2])
    o = jax.nn.sigmoid(pre[:, 3])
    m_new = jnp.maximum(f_t + m, i_t)
    i_eff = jnp.exp(i_t - m_new)
    f_eff = jnp.exp(f_t + m - m_new)
    c = f_eff * c + i_eff * z
    n = f_eff * n + i_eff
    h = o * c / jnp.maximum(n, 1e-6)

    state_ref[0], state_ref[1], state_ref[2], state_ref[3] = h, c, n, m_new
    y_ref[:, 0] = h.astype(y_ref.dtype)

    @pl.when(t == nt - 1)
    def _emit():
        hout_ref[...] = h.astype(hout_ref.dtype)
        cout_ref[...] = c.astype(cout_ref.dtype)
        nout_ref[...] = n.astype(nout_ref.dtype)
        mout_ref[...] = m_new.astype(mout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def slstm_scan(
    wx: jax.Array,          # [B, T, 4, D] input-projected gate pre-activations
    r_gates: jax.Array,     # [D, 4, D] recurrent weights (pinned in VMEM)
    b_gates: jax.Array,     # [4, D]
    h0: jax.Array, c0: jax.Array, n0: jax.Array, m0: jax.Array,  # [B, D]
    block_b: int = 8,
    interpret: bool = False,
):
    """Returns (y [B,T,D], (h,c,n,m) [B,D] final state)."""
    b, t, four, d = wx.shape
    assert four == 4
    block_b = min(block_b, b)
    pad = (-b) % block_b
    if pad:
        wx = jnp.pad(wx, ((0, pad), (0, 0), (0, 0), (0, 0)))
        h0, c0, n0, m0 = (jnp.pad(a, ((0, pad), (0, 0))) for a in (h0, c0, n0, m0))
    bp = wx.shape[0]
    grid = (bp // block_b, t)

    state_spec = pl.BlockSpec((block_b, d), lambda i, tt: (i, 0))
    outs = pl.pallas_call(
        _slstm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, 1, 4, d), lambda i, tt: (i, tt, 0, 0)),
            pl.BlockSpec((d, 4, d), lambda i, tt: (0, 0, 0)),
            pl.BlockSpec((4, d), lambda i, tt: (0, 0)),
            state_spec, state_spec, state_spec, state_spec,
        ],
        out_specs=(
            pl.BlockSpec((block_b, 1, d), lambda i, tt: (i, tt, 0)),
            state_spec, state_spec, state_spec, state_spec,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bp, t, d), wx.dtype),
            jax.ShapeDtypeStruct((bp, d), jnp.float32),
            jax.ShapeDtypeStruct((bp, d), jnp.float32),
            jax.ShapeDtypeStruct((bp, d), jnp.float32),
            jax.ShapeDtypeStruct((bp, d), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((4, block_b, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(wx, r_gates, b_gates, h0, c0, n0, m0)
    y, h, c, n, m = outs
    return y[:b], (h[:b], c[:b], n[:b], m[:b])
