"""Attention variants: GQA/MQA (+qk-norm, RoPE), sliding-window local
attention with a ring-buffer cache, and DeepSeek-V2 MLA with a latent cache.

Each variant exposes:
  *_init(key, cfg)                      -> params
  *_apply(p, cfg, x, positions)         -> y                       (train/prefill, no cache)
  *_prefill(p, cfg, x, positions)       -> (y, cache)
  *_decode(p, cfg, x, cache, positions) -> (y, cache)              (T == 1)

Caches are plain pytrees so they shard/checkpoint like params.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops as kops
from .blockwise_attention import blockwise_attention
from .layers import (
    _dense_init,
    _lru_get,
    apply_rope,
    quantized_batched_matmul,
    rmsnorm,
    rmsnorm_init,
)

#: sequences at or above this length use the blockwise custom-VJP attention
#: (never materializes T x T); shorter ones use the exact dense path.
BLOCKWISE_MIN_LEN = 1024

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# dense attention core (shared by GQA & local)
# ---------------------------------------------------------------------------


def _sdpa(q, k, v, mask, scale) -> jax.Array:
    """[B,Tq,H,D] x [B,Tk,Hkv,D] grouped attention with explicit mask.

    Operands stay in their storage dtype (bf16 on the production path) with
    f32 ACCUMULATION via preferred_element_type — upcasting the KV operands
    to f32 would double decode's dominant HBM term (the full-cache read) and
    materialize an f32 copy of the cache (measured on llama decode_32k:
    6.2 -> 2.9 GB/partition, EXPERIMENTS.md §Perf)."""
    b, tq, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    qg = (q * jnp.asarray(scale, q.dtype)).reshape(b, tq, hkv, group, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32)
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, tq, hq, d).astype(q.dtype)


def _sdpa_quantized_core(qs, k, v, mask, n_bits: int) -> jax.Array:
    """Quantized SDPA body staged through the CiM lowering pass.

    `qs` is the PRE-SCALED query [B,Tq,Hq,D] (scale applied by the caller so
    the lowered trace is keyed only on shapes/n_bits, never on a closed-over
    float). Both contractions are canonical batched dot_generals — batch
    dims (B, Hkv) map onto CiM tile rows, the grouped-query axis folds into
    the matmul M axis — so `plan_batched_matmul` covers QK^T and AV with a
    per-tile access count independent of batch and head count. Everything
    between them (mask select, softmax, the layout transposes) is a host
    island."""
    b, tq, hq, d = qs.shape
    tk, hkv, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = hq // hkv
    qg = qs.reshape(b, tq, hkv, g, d).transpose(0, 2, 3, 1, 4) \
        .reshape(b, hkv, g * tq, d)
    kt = k.astype(jnp.float32).transpose(0, 2, 3, 1)           # [B,Hkv,D,Tk]
    logits = quantized_batched_matmul(qg, kt, n_bits) \
        .reshape(b, hkv, g, tq, tk)
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    vt = v.astype(jnp.float32).transpose(0, 2, 1, 3)           # [B,Hkv,Tk,Dv]
    out = quantized_batched_matmul(
        probs.reshape(b, hkv, g * tq, tk), vt, n_bits)
    return out.reshape(b, hkv, g, tq, dv).transpose(0, 3, 1, 2, 4) \
        .reshape(b, tq, hq, dv)


def _sdpa_quantized(q, k, v, mask, scale, n_bits: int = 8) -> jax.Array:
    """Plain-JAX quantized twin of `_sdpa` — the un-lowered reference that
    `sdpa_cim` must match bit-for-bit."""
    qs = q.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)
    return _sdpa_quantized_core(qs, k, v, mask, n_bits).astype(q.dtype)


#: bounded LRU of lowered SDPA callables (see layers._LOWERED_LINEAR)
_LOWERED_SDPA: "OrderedDict" = OrderedDict()


def _lowered_sdpa(n_bits: int, backend, spec, mesh, resident: bool = False,
                  policy: str | None = None):
    from repro.cim import array
    from repro.cim.lower import lower

    return _lru_get(
        _LOWERED_SDPA, (n_bits, backend, spec, mesh, resident, policy),
        lambda: lower(
            lambda qs, k, v, mask: _sdpa_quantized_core(qs, k, v, mask,
                                                        n_bits),
            backend=backend, spec=spec, mesh=mesh,
            resident_argnums=(1, 2) if resident else (),
            resident_set=array.resident_set(spec) if resident else None,
            policy=policy, name="sdpa"))


def sdpa_cim(q, k, v, mask, scale, n_bits: int = 8,
             backend: str | None = None, spec=None, mesh=None,
             resident: bool = False, policy: str | None = None) -> jax.Array:
    """Grouped SDPA with QK^T and AV executed as planned CiM schedules.

    Two fused regions per call (one per contraction) — warm calls are
    exactly two dispatches regardless of batch, heads, or context length.
    `resident=True` pins the packed K^T/V planes by array identity: pass
    the SAME k/v arrays across calls to skip their entry packs (decode with
    a functionally-updated cache gets fresh arrays each step, so the serve
    path streams KV instead — see `gqa_decode_cim`). `policy` is the
    lowering's offload policy (see `layers.mlp_cim`)."""
    qs = q.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)
    lf = _lowered_sdpa(n_bits, backend, spec, mesh, resident, policy)
    return lf(qs, k, v, mask).astype(q.dtype)


def _causal_mask(tq: int, tk: int) -> jax.Array:
    # query block aligned to the END of the key span
    return jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)[None]


# ---------------------------------------------------------------------------
# GQA / MQA global attention
# ---------------------------------------------------------------------------


def gqa_init(key, cfg: ArchConfig, dtype) -> Params:
    ks = jax.random.split(key, 4)
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": _dense_init(ks[0], (d, h, hd), d, dtype),
        "wk": _dense_init(ks[1], (d, hkv, hd), d, dtype),
        "wv": _dense_init(ks[2], (d, hkv, hd), d, dtype),
        "wo": _dense_init(ks[3], (h, hd, d), h * hd, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def _gqa_qkv(p, cfg: ArchConfig, x, positions):
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"], preferred_element_type=jnp.float32).astype(x.dtype)
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"], preferred_element_type=jnp.float32).astype(x.dtype)
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"], preferred_element_type=jnp.float32).astype(x.dtype)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attend(q, k, v, scale, window: int = 0):
    """Dense for short sequences (exact), blockwise custom-VJP for long."""
    if q.shape[1] >= BLOCKWISE_MIN_LEN:
        return blockwise_attention(q, k, v, True, scale, window, 512)
    tq, tk = q.shape[1], k.shape[1]
    mask = _causal_mask(tq, tk)
    if window:
        qpos = jnp.arange(tq)[:, None] + (tk - tq)
        kpos = jnp.arange(tk)[None, :]
        mask = mask & (qpos - kpos < window)[None]
    return _sdpa(q, k, v, mask, scale)


def gqa_apply(p, cfg: ArchConfig, x, positions, use_flash: bool = False) -> jax.Array:
    q, k, v = _gqa_qkv(p, cfg, x, positions)
    if use_flash:
        o = kops.attention(q, k, v, causal=True)
    else:
        o = _attend(q, k, v, 1.0 / cfg.head_dim ** 0.5)
    return jnp.einsum("bthk,hkd->btd", o, p["wo"],
                      preferred_element_type=jnp.float32).astype(x.dtype)


def gqa_make_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> Params:
    shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gqa_prefill(p, cfg: ArchConfig, x, positions, max_len: int) -> Tuple[jax.Array, Params]:
    q, k, v = _gqa_qkv(p, cfg, x, positions)
    o = _attend(q, k, v, 1.0 / cfg.head_dim ** 0.5)
    y = jnp.einsum("bthk,hkd->btd", o, p["wo"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    t = k.shape[1]
    cache = gqa_make_cache(cfg, x.shape[0], max_len, x.dtype)
    cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0)),
    }
    return y, cache


def gqa_decode(p, cfg: ArchConfig, x, cache: Params, positions) -> Tuple[jax.Array, Params]:
    """x: [B, 1, D]; positions: [B] = index of the new token."""
    from .moe import _hint

    pos2 = positions[:, None]
    q, k, v = _gqa_qkv(p, cfg, x, pos2)
    # align the attention compute layout with the cache layout (batch on DP,
    # head_dim on "model") — otherwise GSPMD reshards the WHOLE cache to the
    # projections' head-sharded layout every step (SPMD 'involuntary full
    # rematerialization': a full-cache copy per layer per token)
    q = _hint(q, ("DP", None, None, "model"))
    k = _hint(k, ("DP", None, None, "model"))
    v = _hint(v, ("DP", None, None, "model"))
    bidx = jnp.arange(x.shape[0])
    ck = cache["k"].at[bidx, positions].set(k[:, 0])
    cv = cache["v"].at[bidx, positions].set(v[:, 0])
    t_max = ck.shape[1]
    valid = jnp.arange(t_max)[None, :] <= positions[:, None]        # [B, Tmax]
    o = _sdpa(q, ck, cv, valid[:, None, :], 1.0 / cfg.head_dim ** 0.5)
    y = jnp.einsum("bthk,hkd->btd", o, p["wo"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return y, {"k": ck, "v": cv}


def gqa_decode_cim(p, cfg: ArchConfig, x, cache: Params, positions
                   ) -> Tuple[jax.Array, Params]:
    """`gqa_decode` with the attention core routed through the jaxpr->CiM
    lowering: QK^T and AV execute as planned batched schedules (two region
    dispatches per layer per step), while rotary, softmax, and the cache
    update stay on the host. Quantization width comes from
    `cfg.cim_attention_bits`. KV streams into the banks each step — the
    functional cache update makes a fresh array per token, so identity-
    fingerprinted resident pins would churn, never hit (resident KV reuse
    is exercised where the arrays are stable: `sdpa_cim(resident=True)`
    with a fixed cache, as in the bench's attention section). Its host
    phases run under `model.qkv`, `model.kv_write` and `model.out_proj`
    spans."""
    from .moe import _hint

    span = jax.profiler.TraceAnnotation
    with span("model.qkv"):           # projections, qk-norm and rotary
        pos2 = positions[:, None]
        q, k, v = _gqa_qkv(p, cfg, x, pos2)
        q = _hint(q, ("DP", None, None, "model"))
        k = _hint(k, ("DP", None, None, "model"))
        v = _hint(v, ("DP", None, None, "model"))
    with span("model.kv_write"):
        bidx = jnp.arange(x.shape[0])
        ck = cache["k"].at[bidx, positions].set(k[:, 0])
        cv = cache["v"].at[bidx, positions].set(v[:, 0])
        t_max = ck.shape[1]
        valid = jnp.arange(t_max)[None, :] <= positions[:, None]
    o = sdpa_cim(q, ck, cv, valid[:, None, :], 1.0 / cfg.head_dim ** 0.5,
                 n_bits=cfg.cim_attention_bits, policy=cfg.cim_policy)
    with span("model.out_proj"):
        y = jnp.einsum("bthk,hkd->btd", o, p["wo"],
                       preferred_element_type=jnp.float32).astype(x.dtype)
    return y, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Sliding-window local attention with a RING-BUFFER cache
# (cache is O(window), not O(context) — required for long_500k decode)
# ---------------------------------------------------------------------------


def local_apply(p, cfg: ArchConfig, x, positions) -> jax.Array:
    q, k, v = _gqa_qkv(p, cfg, x, positions)
    o = _attend(q, k, v, 1.0 / cfg.head_dim ** 0.5, window=cfg.local_window)
    return jnp.einsum("bthk,hkd->btd", o, p["wo"],
                      preferred_element_type=jnp.float32).astype(x.dtype)


def local_make_cache(cfg: ArchConfig, batch: int, dtype) -> Params:
    w = cfg.local_window
    shape = (batch, w, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def local_prefill(p, cfg: ArchConfig, x, positions) -> Tuple[jax.Array, Params]:
    y = local_apply(p, cfg, x, positions)
    # recompute the last-window K/V into the ring buffer
    q, k, v = _gqa_qkv(p, cfg, x, positions)
    w = cfg.local_window
    t = k.shape[1]
    if t >= w:
        k_tail, v_tail = k[:, t - w:], v[:, t - w:]
        # ring layout: slot = pos % w
        slots = (jnp.arange(t - w, t)) % w
        ck = jnp.zeros_like(k_tail).at[:, slots].set(k_tail)
        cv = jnp.zeros_like(v_tail).at[:, slots].set(v_tail)
    else:
        ck = jnp.zeros((k.shape[0], w) + k.shape[2:], k.dtype).at[:, :t].set(k)
        cv = jnp.zeros((v.shape[0], w) + v.shape[2:], v.dtype).at[:, :t].set(v)
    return y, {"k": ck, "v": cv}


def local_decode(p, cfg: ArchConfig, x, cache: Params, positions) -> Tuple[jax.Array, Params]:
    q, k, v = _gqa_qkv(p, cfg, x, positions[:, None])
    w = cfg.local_window
    slot = positions % w
    bidx = jnp.arange(x.shape[0])
    ck = cache["k"].at[bidx, slot].set(k[:, 0])
    cv = cache["v"].at[bidx, slot].set(v[:, 0])
    # slot s holds absolute position: valid iff within window of `positions`
    slot_ids = jnp.arange(w)[None, :]
    # absolute position stored in slot s (given current head at `positions`):
    # pos_s = positions - ((positions - slot_ids) mod w)
    offset = (positions[:, None] - slot_ids) % w
    abs_pos = positions[:, None] - offset
    valid = (abs_pos >= 0) & (abs_pos >= positions[:, None] - (w - 1))
    o = _sdpa(q, ck, cv, valid[:, None, :], 1.0 / cfg.head_dim ** 0.5)
    y = jnp.einsum("bthk,hkd->btd", o, p["wo"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return y, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# DeepSeek-V2 Multi-head Latent Attention (MLA)
# ---------------------------------------------------------------------------


def mla_init(key, cfg: ArchConfig, dtype) -> Params:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 6)
    qd = m.qk_nope_dim + m.qk_rope_dim
    return {
        "wq": _dense_init(ks[0], (d, h, qd), d, dtype),
        "w_kv_a": _dense_init(ks[1], (d, m.kv_lora_rank + m.qk_rope_dim), d, dtype),
        "kv_a_norm": rmsnorm_init(m.kv_lora_rank, dtype),
        "w_uk": _dense_init(ks[2], (m.kv_lora_rank, h, m.qk_nope_dim), m.kv_lora_rank, dtype),
        "w_uv": _dense_init(ks[3], (m.kv_lora_rank, h, m.v_head_dim), m.kv_lora_rank, dtype),
        "wo": _dense_init(ks[4], (h, m.v_head_dim, d), h * m.v_head_dim, dtype),
    }


def _mla_project(p, cfg: ArchConfig, x, positions):
    m = cfg.mla
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"], preferred_element_type=jnp.float32).astype(x.dtype)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    kv_a = jnp.einsum("btd,dr->btr", x, p["w_kv_a"],
                      preferred_element_type=jnp.float32).astype(x.dtype)
    c_kv, k_rope = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(p["kv_a_norm"], c_kv, cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, c_kv, k_rope


def _mla_attend(p, cfg: ArchConfig, q_nope, q_rope, c_kv, k_rope, mask):
    """Absorbed-form attention: score via the 512-d latent, never expanding
    per-head K for the whole context (the MLA memory win)."""
    m = cfg.mla
    scale = 1.0 / (m.qk_nope_dim + m.qk_rope_dim) ** 0.5
    # fold W_uk into q: q_lat [B,Tq,H,R]
    q_lat = jnp.einsum("bthk,rhk->bthr", q_nope.astype(jnp.float32),
                       p["w_uk"].astype(jnp.float32))
    s_nope = jnp.einsum("bthr,bsr->bhts", q_lat, c_kv.astype(jnp.float32))
    s_rope = jnp.einsum("bthk,bsk->bhts", q_rope.astype(jnp.float32),
                        k_rope.astype(jnp.float32))
    logits = (s_nope + s_rope) * scale
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    # attend in latent space then decompress once per query
    o_lat = jnp.einsum("bhts,bsr->bthr", probs, c_kv.astype(jnp.float32))
    o = jnp.einsum("bthr,rhv->bthv", o_lat, p["w_uv"].astype(jnp.float32))
    return o


def _mla_attend_blockwise(p, cfg, q_nope, q_rope, c_kv, k_rope):
    """EXPLICIT (non-absorbed) MLA for prefill/train: decompress per-head
    K_nope/V from the latent once, then flash attention over 192-dim heads.

    The absorbed form (decode's win: score via the 1088-dim [c_kv, k_rope])
    costs 2*S^2*h*(R+rope) + 2*S^2*h*R score/combine FLOPs — ~5.7x the
    explicit form's 2*S^2*h*(nope+rope) at kv_lora=512. Absorption pays when
    S^2 work is small relative to the per-token decompression (decode);
    prefill at 32k is the opposite regime (EXPERIMENTS §Perf D). DeepSeek-V2
    itself trains in the explicit form and absorbs only for inference."""
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"],
                        preferred_element_type=c_kv.dtype)
    v = jnp.einsum("bsr,rhv->bshv", c_kv, p["w_uv"],
                   preferred_element_type=c_kv.dtype)
    h = k_nope.shape[2]
    k_cat = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  k_rope.shape[:2] + (h, k_rope.shape[-1]))],
        axis=-1)
    q_cat = jnp.concatenate([q_nope, q_rope], axis=-1)
    m = cfg.mla
    scale = 1.0 / (m.qk_nope_dim + m.qk_rope_dim) ** 0.5
    return blockwise_attention(q_cat, k_cat, v, True, scale, 0, 512)


def mla_apply(p, cfg: ArchConfig, x, positions) -> jax.Array:
    q_nope, q_rope, c_kv, k_rope = _mla_project(p, cfg, x, positions)
    if x.shape[1] >= BLOCKWISE_MIN_LEN:
        o = _mla_attend_blockwise(p, cfg, q_nope, q_rope, c_kv, k_rope)
    else:
        mask = _causal_mask(x.shape[1], x.shape[1])
        o = _mla_attend(p, cfg, q_nope, q_rope, c_kv, k_rope, mask)
    return jnp.einsum("bthv,hvd->btd", o.astype(x.dtype), p["wo"],
                      preferred_element_type=jnp.float32).astype(x.dtype)


def mla_make_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> Params:
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.qk_rope_dim), dtype),
    }


def mla_prefill(p, cfg: ArchConfig, x, positions, max_len: int):
    q_nope, q_rope, c_kv, k_rope = _mla_project(p, cfg, x, positions)
    if x.shape[1] >= BLOCKWISE_MIN_LEN:
        o = _mla_attend_blockwise(p, cfg, q_nope, q_rope, c_kv, k_rope)
    else:
        mask = _causal_mask(x.shape[1], x.shape[1])
        o = _mla_attend(p, cfg, q_nope, q_rope, c_kv, k_rope, mask)
    y = jnp.einsum("bthv,hvd->btd", o.astype(x.dtype), p["wo"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    cache = mla_make_cache(cfg, x.shape[0], max_len, x.dtype)
    cache = {
        "c_kv": jax.lax.dynamic_update_slice(cache["c_kv"], c_kv, (0, 0, 0)),
        "k_rope": jax.lax.dynamic_update_slice(cache["k_rope"], k_rope, (0, 0, 0)),
    }
    return y, cache


def mla_decode(p, cfg: ArchConfig, x, cache, positions):
    q_nope, q_rope, c_kv, k_rope = _mla_project(p, cfg, x, positions[:, None])
    bidx = jnp.arange(x.shape[0])
    cc = cache["c_kv"].at[bidx, positions].set(c_kv[:, 0])
    cr = cache["k_rope"].at[bidx, positions].set(k_rope[:, 0])
    valid = jnp.arange(cc.shape[1])[None, :] <= positions[:, None]
    o = _mla_attend(p, cfg, q_nope, q_rope, cc, cr, valid[:, None, :])
    y = jnp.einsum("bthv,hvd->btd", o.astype(x.dtype), p["wo"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return y, {"c_kv": cc, "k_rope": cr}
