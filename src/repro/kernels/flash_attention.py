"""Pallas TPU flash attention (GQA-aware, causal), with online softmax.

Blocked q/k streaming with running (m, l, acc) statistics held in VMEM
scratch across the innermost (sequential) k-block grid dimension. Block
shapes are MXU-aligned (q/k blocks multiples of 128 where the head_dim
allows). Used for the prefill hot spot; validated in interpret mode against
ref.mha_ref. The XLA path (ref) is used for dry-run lowering on non-TPU
backends — see DESIGN.md §7.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    seq_q: int, seq_k: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, :, 0, :].astype(jnp.float32) * scale          # [bq, d]
    k = k_ref[0, :, 0, :].astype(jnp.float32)                  # [bk, d]
    v = v_ref[0, :, 0, :].astype(jnp.float32)                  # [bk, d]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq, bk]

    # causal mask in global coordinates (q aligned to the END of the kv span)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    if causal:
        mask = (q_pos + (seq_k - seq_q)) >= k_pos
    else:
        mask = jnp.ones((block_q, block_k), jnp.bool_)
    mask = mask & (k_pos < seq_k)                              # kv padding
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, 0]                                       # [bq]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)

    l_new = alpha * l_ref[:, 0] + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, :, 0, :] = (acc_ref[...] / safe_l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "scale"),
)
def flash_attention(
    q: jax.Array,                  # [B, Tq, Hq, D]
    k: jax.Array,                  # [B, Tk, Hkv, D]
    v: jax.Array,                  # [B, Tk, Hkv, D]
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, tq, hq, d = q.shape
    _, tk, hkv, _ = k.shape
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    pad_q = (-tq) % block_q
    pad_k = (-tk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    tqp, tkp = q.shape[1], k.shape[1]

    grid = (b, hq, tqp // block_q, tkp // block_k)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_q=tq, seq_k=tk,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1, d), lambda b_, h, qi, ki: (b_, qi, h, 0)),
            pl.BlockSpec((1, block_k, 1, d), lambda b_, h, qi, ki: (b_, ki, h // group, 0)),
            pl.BlockSpec((1, block_k, 1, d), lambda b_, h, qi, ki: (b_, ki, h // group, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, d), lambda b_, h, qi, ki: (b_, qi, h, 0)),
        out_shape=jax.ShapeDtypeStruct((b, tqp, hq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :tq]
