"""Blockwise (FlashAttention-style) attention for the XLA path, with a
custom VJP so neither forward nor backward ever materializes the T x T
score matrix.

This is the memory substrate that makes train_4k / prefill_32k fit on a
16 GB/chip pod (the naive _sdpa stores B*H*T^2 logits: ~1.3 TB/device for
qwen3-14b train_4k). The Pallas kernel covers real-TPU execution; this
covers every jnp/dry-run path with the same asymptotics:

  fwd : scan over kv blocks, carry (m, l, acc); save (q, k, v, o, lse)
  bwd : FlashAttention-2 recomputation — D = rowsum(dO*O), one scan over
        kv blocks accumulating dq and emitting (dk_j, dv_j) per block.

Supports GQA (q heads grouped over kv heads), causal masking with
end-aligned query positions, and an optional local window.
"""
from __future__ import annotations

import functools
from collections import OrderedDict

import jax
import jax.numpy as jnp

NEG = -1e30

#: bounded LRU of lowered batched-matmul callables (see layers._lru_get)
_LOWERED_BMM: "OrderedDict" = OrderedDict()


def _mask(tq, tk, kj0, bq, bk, causal, window):
    """[bq, bk] bool for q rows 0..tq and kv cols kj0.. (end-aligned causal)."""
    q_pos = jnp.arange(bq)[:, None] + (tk - tq)
    k_pos = kj0 + jnp.arange(bk)[None, :]
    m = k_pos < tk
    if causal:
        m = m & (q_pos >= k_pos)
    if window:
        m = m & (q_pos - k_pos < window)
    return m


def _pad_kv(k, v, bk):
    pad = (-k.shape[1]) % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return k, v


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def blockwise_attention(q, k, v, causal=True, scale=None, window=0, block_k=512):
    out, _ = _fwd(q, k, v, causal, scale, window, block_k)
    return out


def _fwd(q, k, v, causal, scale, window, block_k):
    b, tq, hq, d = q.shape
    _, tk, hkv, _ = k.shape
    dv = v.shape[-1]
    g = hq // hkv
    scale_v = scale if scale is not None else 1.0 / d ** 0.5
    bk = min(block_k, tk) if tk % min(block_k, tk) == 0 else block_k
    kp, vp = _pad_kv(k, v, bk)
    nk = kp.shape[1] // bk

    qg = (q.astype(jnp.float32) * scale_v).reshape(b, tq, hkv, g, d)
    ks = kp.astype(jnp.float32).reshape(b, nk, bk, hkv, d)
    vs = vp.astype(jnp.float32).reshape(b, nk, bk, hkv, dv)

    def body(carry, xs):
        m_run, l_run, acc = carry
        kb, vb, j = xs                                     # [B,bk,Hkv,D]
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kb)        # [B,Hkv,G,Tq,bk]
        msk = _mask(tq, tk, j * bk, tq, bk, causal, window)
        s = jnp.where(msk[None, None, None], s, NEG)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_run - m_new)
        l_new = alpha * l_run + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, vb)
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, hkv, g, tq), NEG, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, tq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, tq, dv), jnp.float32)
    (m_f, l_f, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (ks.swapaxes(0, 1), vs.swapaxes(0, 1), jnp.arange(nk)))

    safe_l = jnp.where(l_f == 0.0, 1.0, l_f)
    o = acc / safe_l[..., None]                                  # [B,Hkv,G,Tq,D]
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, tq, hq, dv).astype(q.dtype)
    lse = m_f + jnp.log(safe_l)                                  # [B,Hkv,G,Tq]
    return o, (q, k, v, o, lse)


def _bwd(causal, scale, window, block_k, res, do):
    q, k, v, o, lse = res
    b, tq, hq, d = q.shape
    _, tk, hkv, _ = k.shape
    dv = v.shape[-1]
    g = hq // hkv
    scale_v = scale if scale is not None else 1.0 / d ** 0.5
    bk = min(block_k, tk) if tk % min(block_k, tk) == 0 else block_k
    kp, vp = _pad_kv(k, v, bk)
    nk = kp.shape[1] // bk

    qg = (q.astype(jnp.float32) * scale_v).reshape(b, tq, hkv, g, d)
    dog = do.astype(jnp.float32).reshape(b, tq, hkv, g, dv)
    og = o.astype(jnp.float32).reshape(b, tq, hkv, g, dv)
    delta = jnp.einsum("bqhgd,bqhgd->bhgq", dog, og)             # [B,Hkv,G,Tq]

    ks = kp.astype(jnp.float32).reshape(b, nk, bk, hkv, d)
    vs = vp.astype(jnp.float32).reshape(b, nk, bk, hkv, dv)

    def body(dq_acc, xs):
        kb, vb, j = xs
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kb)
        msk = _mask(tq, tk, j * bk, tq, bk, causal, window)
        s = jnp.where(msk[None, None, None], s, NEG)
        p = jnp.exp(s - lse[..., None])                          # [B,Hkv,G,Tq,bk]
        dv_j = jnp.einsum("bhgqk,bqhgd->bkhd", p, dog)
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", dog, vb)
        ds = p * (dp - delta[..., None])
        dq_acc = dq_acc + jnp.einsum("bhgqk,bkhd->bqhgd", ds, kb)
        dk_j = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qg)
        return dq_acc, (dk_j, dv_j)

    dq0 = jnp.zeros((b, tq, hkv, g, d), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(
        body, dq0, (ks.swapaxes(0, 1), vs.swapaxes(0, 1), jnp.arange(nk)))

    dq = (dq * scale_v).reshape(b, tq, hq, d).astype(q.dtype)
    dk = dks.swapaxes(0, 1).reshape(b, nk * bk, hkv, d)[:, :tk].astype(k.dtype)
    dv_out = dvs.swapaxes(0, 1).reshape(b, nk * bk, hkv, dv)[:, :tk].astype(v.dtype)
    return dq, dk, dv_out


def _fwd_rule(q, k, v, causal, scale, window, block_k):
    return _fwd(q, k, v, causal, scale, window, block_k)


blockwise_attention.defvjp(_fwd_rule, _bwd)


# ---------------------------------------------------------------------------
# Quantized blockwise attention (host reference + CiM-lowered execution)
# ---------------------------------------------------------------------------


def blockwise_attention_quantized(q, k, v, causal=True, scale=None, window=0,
                                  block_k=512, n_bits=8, bmm=None):
    """Forward-only quantized blockwise attention with a pluggable batched
    matmul.

    Same online-softmax recurrence as `_fwd`, but the per-block QK^T and AV
    contractions go through `bmm(a, b)` on canonical [B*, M, K] x [B*, K, N]
    operands — `quantized_batched_matmul` when `bmm` is None (the float-
    quantized host reference), or a `lower()`-compiled twin of it for CiM
    execution (`blockwise_attention_cim`). The kv loop is a Python loop over
    FIXED block shapes, not a scan: every block (and every layer sharing the
    config) presents the same two operand signatures, so the lowered bmm
    compiles exactly two programs and replays them 2 x n_blocks times."""
    if bmm is None:
        def bmm(a, bb):
            from .layers import quantized_batched_matmul
            return quantized_batched_matmul(a, bb, n_bits)
    b, tq, hq, d = q.shape
    _, tk, hkv, _ = k.shape
    dv = v.shape[-1]
    g = hq // hkv
    scale_v = scale if scale is not None else 1.0 / d ** 0.5
    bk = min(block_k, tk) if tk % min(block_k, tk) == 0 else block_k
    kp, vp = _pad_kv(k, v, bk)
    nk = kp.shape[1] // bk

    qm = (q.astype(jnp.float32) * scale_v).reshape(b, tq, hkv, g, d) \
        .transpose(0, 2, 3, 1, 4).reshape(b, hkv, g * tq, d)
    m_run = jnp.full((b, hkv, g, tq), NEG, jnp.float32)
    l_run = jnp.zeros((b, hkv, g, tq), jnp.float32)
    acc = jnp.zeros((b, hkv, g, tq, dv), jnp.float32)
    for j in range(nk):
        kb = kp[:, j * bk:(j + 1) * bk].astype(jnp.float32)  # [B,bk,Hkv,D]
        vb = vp[:, j * bk:(j + 1) * bk].astype(jnp.float32)
        s = bmm(qm, kb.transpose(0, 2, 3, 1)) \
            .reshape(b, hkv, g, tq, bk)                      # [B,Hkv,G,Tq,bk]
        msk = _mask(tq, tk, j * bk, tq, bk, causal, window)
        s = jnp.where(msk[None, None, None], s, NEG)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_run - m_new)
        l_run = alpha * l_run + jnp.sum(p, axis=-1)
        pv = bmm(p.reshape(b, hkv, g * tq, bk),
                 vb.transpose(0, 2, 1, 3)).reshape(b, hkv, g, tq, dv)
        acc = acc * alpha[..., None] + pv
        m_run = m_new
    safe_l = jnp.where(l_run == 0.0, 1.0, l_run)
    o = acc / safe_l[..., None]
    return o.transpose(0, 3, 1, 2, 4).reshape(b, tq, hq, dv).astype(q.dtype)


def blockwise_attention_cim(q, k, v, causal=True, scale=None, window=0,
                            block_k=512, n_bits=8, backend=None, spec=None,
                            mesh=None, resident=False):
    """Blockwise attention whose integer contractions execute in the CiM
    array: bit-exact with `blockwise_attention_quantized` on the same
    operands, 2 dispatches per kv block, and (by the structural region key)
    ONE compiled program per contraction shape shared across all blocks and
    all layers."""
    from .layers import _lru_get, quantized_batched_matmul

    def make():
        from repro.cim import array
        from repro.cim.lower import lower

        return lower(lambda a, bb: quantized_batched_matmul(a, bb, n_bits),
                     backend=backend, spec=spec, mesh=mesh,
                     resident_argnums=(1,) if resident else (),
                     resident_set=array.resident_set(spec)
                     if resident else None, name="attn_bmm")

    bmm = _lru_get(_LOWERED_BMM, (n_bits, backend, spec, mesh, resident),
                   make)
    return blockwise_attention_quantized(q, k, v, causal, scale, window,
                                         block_k, n_bits, bmm=bmm)
