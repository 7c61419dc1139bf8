"""Shared model layers: norms, rotary embeddings, MLPs, embedding tables.

Pure-functional (params are plain pytrees of jnp arrays); initializers take an
explicit PRNG key. Matmul-bearing layers compute in the config activation
dtype with f32 accumulation via preferred_element_type.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict

import jax
import jax.numpy as jnp

Params = Dict[str, jax.Array]


def _dense_init(key, shape, in_axis_size, dtype):
    scale = 1.0 / jnp.sqrt(jnp.asarray(in_axis_size, jnp.float32))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Interleaved (adjacent-pair) RoPE: x [B, T, H, D], positions [B, T].

    The pair (2i, 2i+1) layout keeps every rotation WITHIN a shard when the
    head_dim axis is model-sharded (the half-split layout splits the sharded
    axis and forces SPMD to fully replicate — observed as 'involuntary full
    rematerialization' costing 100s of GB/device on qwen/gemma)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                         # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    xr = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    x1, x2 = xr[..., 0], xr[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def hint_batch_sharding(x: jax.Array) -> jax.Array:
    """Best-effort sharding hint: leading (batch) dim on the DP axes.

    GSPMD occasionally drops batch sharding through scan carries / reshapes;
    this re-pins it. No-op when no mesh is in scope (CPU unit tests)."""
    from jax.sharding import PartitionSpec as P

    for dp in (("pod", "data"), "data"):
        try:
            return jax.lax.with_sharding_constraint(
                x, P(*((dp,) + (None,) * (x.ndim - 1))))
        except Exception:
            continue
    return x


def hint_activation_sharding(x: jax.Array) -> jax.Array:
    """Layer-boundary activation hint: batch on DP axes AND sequence on the
    model axis (sequence parallelism, Korthikanti et al.): the per-group
    saved carries of the layer scan are the dominant train-time residency
    (n_groups x [B, S, d]); 2-D sharding cuts them by the model-axis width.
    Falls back to batch-only for short sequences / decode steps."""
    from jax.sharding import PartitionSpec as P

    if x.ndim >= 3 and x.shape[1] >= 64:
        for dp in (("pod", "data"), "data"):
            try:
                return jax.lax.with_sharding_constraint(
                    x, P(*((dp, "model") + (None,) * (x.ndim - 2))))
            except Exception:
                continue
    return hint_batch_sharding(x)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / plain GELU)
# ---------------------------------------------------------------------------


def mlp_init(key, d_model: int, d_ff: int, gating: str, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "w_in": _dense_init(k1, (d_model, d_ff), d_model, dtype),
        "w_out": _dense_init(k2, (d_ff, d_model), d_ff, dtype),
    }
    if gating in ("swiglu", "geglu"):
        p["w_gate"] = _dense_init(k3, (d_model, d_ff), d_model, dtype)
    return p


def mlp(p: Params, x: jax.Array, gating: str) -> jax.Array:
    h = jnp.einsum("btd,df->btf", x, p["w_in"], preferred_element_type=jnp.float32)
    if gating == "swiglu":
        g = jnp.einsum("btd,df->btf", x, p["w_gate"], preferred_element_type=jnp.float32)
        h = jax.nn.silu(g) * h
    elif gating == "geglu":
        g = jnp.einsum("btd,df->btf", x, p["w_gate"], preferred_element_type=jnp.float32)
        h = jax.nn.gelu(g) * h
    else:
        h = jax.nn.gelu(h)
    h = h.astype(x.dtype)
    return jnp.einsum("btf,fd->btd", h, p["w_out"],
                      preferred_element_type=jnp.float32).astype(x.dtype)


# ---------------------------------------------------------------------------
# Opt-in CiM-quantized linear path (compiled through the lowering pass)
# ---------------------------------------------------------------------------


def quantize_symmetric(x: jax.Array, n_bits: int = 8):
    """Per-tensor symmetric quantization: x ~ q * scale, q in intN range."""
    qmax = float(2 ** (n_bits - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), 1e-8) / qmax
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -qmax, qmax)
    return q.astype(jnp.int32), scale


def _cim_int_dtype(n_bits: int):
    """Narrowest jnp integer dtype holding symmetric n_bits quantized values
    — the dtype IS the eligibility signal the lowering compiler reads."""
    if n_bits <= 8:
        return jnp.int8
    if n_bits <= 16:
        return jnp.int16
    return jnp.int32


def _quantized_linear(x: jax.Array, w: jax.Array, n_bits: int) -> jax.Array:
    """Pure-jnp quantized linear: fake-quantize both operands, contract
    EXACTLY in narrow integers, rescale. This is the function the lowering
    compiler stages — its integer `dot_general` is the CiM-eligible eqn;
    the float quantize/rescale stays on the host."""
    d, f = w.shape
    lead = x.shape[:-1]
    xq, sx = quantize_symmetric(x, n_bits)
    wq, sw = quantize_symmetric(w, n_bits)
    dt = _cim_int_dtype(n_bits)
    y = jnp.matmul(xq.reshape(-1, d).astype(dt), wq.astype(dt),
                   preferred_element_type=jnp.int32)
    return (y.astype(jnp.float32) * (sx * sw)).reshape(lead + (f,))


def quantized_batched_matmul(a: jax.Array, b: jax.Array,
                             n_bits: int = 8) -> jax.Array:
    """Per-tensor-quantized batched matmul: [*B,M,K] x [*B,K,N] -> f32.

    Built on an EXPLICIT `lax.dot_general` with canonical batch dims —
    `jnp.matmul` rewrites singleton batch axes into squeeze + transpose
    around a non-canonical contraction, which the lowering classifier
    (correctly) rejects. The canonical form is what `plan_batched_matmul`
    lowers with a per-tile access count independent of the batch size."""
    nb = a.ndim - 2
    aq, sa = quantize_symmetric(a, n_bits)
    bq, sb = quantize_symmetric(b, n_bits)
    dt = _cim_int_dtype(n_bits)
    y = jax.lax.dot_general(
        aq.astype(dt), bq.astype(dt),
        (((nb + 1,), (nb,)), (tuple(range(nb)), tuple(range(nb)))),
        preferred_element_type=jnp.int32)
    return y.astype(jnp.float32) * (sa * sb)


def _mlp_quantized(p: Params, x: jax.Array, gating: str,
                   n_bits: int) -> jax.Array:
    """The quantized MLP as one plain JAX function — the un-lowered
    reference `mlp_cim` must match bit-for-bit."""
    h = _quantized_linear(x, p["w_in"], n_bits)
    if gating == "swiglu":
        g = _quantized_linear(x, p["w_gate"], n_bits)
        h = jax.nn.silu(g) * h
    elif gating == "geglu":
        g = _quantized_linear(x, p["w_gate"], n_bits)
        h = jax.nn.gelu(g) * h
    else:
        h = jax.nn.gelu(h)
    return _quantized_linear(h, p["w_out"], n_bits).astype(x.dtype)


#: bounded LRU caches of lowered callables, keyed by everything that shapes
#: the trace (each LoweredFunction additionally LRU-bounds its per-shape
#: signature traces — no layer of this path grows without limit)
_LOWERED_CACHE_CAPACITY = 32
_LOWERED_LINEAR: "OrderedDict" = OrderedDict()
_LOWERED_MLP: "OrderedDict" = OrderedDict()


def _lru_get(cache, key, make):
    lf = cache.get(key)
    if lf is None:
        lf = cache[key] = make()
        while len(cache) > _LOWERED_CACHE_CAPACITY:
            cache.popitem(last=False)
    else:
        cache.move_to_end(key)
    return lf


def _lowered_linear(n_bits: int, backend, spec, mesh, resident: bool = False):
    from repro.cim.lower import lower

    # resident_set stays None: the lowered callable resolves the registry
    # set per execution, so clear_resident()/set_resident_ecc()/failover
    # are honored even though this LRU outlives them
    return _lru_get(
        _LOWERED_LINEAR, (n_bits, backend, spec, mesh, resident),
        lambda: lower(lambda x, w: _quantized_linear(x, w, n_bits),
                      backend=backend, spec=spec, mesh=mesh,
                      resident_argnums=(1,) if resident else (),
                      name="linear"))


def _lowered_mlp(gating: str, n_bits: int, backend, spec, mesh,
                 resident: bool = False, policy: str | None = None):
    from repro.cim.lower import lower

    return _lru_get(
        _LOWERED_MLP, (gating, n_bits, backend, spec, mesh, resident, policy),
        lambda: lower(lambda p, x: _mlp_quantized(p, x, gating, n_bits),
                      backend=backend, spec=spec, mesh=mesh,
                      resident_argnums=(0,) if resident else (),
                      policy=policy, name="mlp"))


def cim_linear(x: jax.Array, w: jax.Array, n_bits: int = 8,
               backend: str | None = None, spec=None, mesh=None,
               resident: bool = False) -> jax.Array:
    """Opt-in CiM execution of x @ w via intN symmetric quantization.

    x [..., D], w [D, F] -> f32 [..., F]. A `lower()` application: the
    quantized-linear function is staged once per argument signature and its
    integer contraction executes through the planner's access schedules
    (banked/tiled when `spec` is given) while quantize/rescale run on the
    host — bit-exact with the un-lowered function. Each fused region is
    ONE compiled XLA program (warm calls: one dispatch per region, zero
    retrace). Still a functional-simulation path for model-scale integer
    offload studies, not a fast path: the packed broadcast layout
    materializes M*K*N words, so use it on reduced configs / layer slices.

    `resident=True` pins the int8 weight planes in the array's resident
    region at first call: warm calls skip the weight-side entry pack (and
    its quantization eqns) entirely — the paper's stored-operand execution.
    Pass the SAME `w` array object each call to stay warm.

    `spec=None` resolves through `array.spec_override()` — the failover
    lever: installing a degraded spec re-routes every subsequent call
    through the degraded geometry (fresh lowered callables, fresh pins);
    with no override installed, None keeps meaning unbanked lowering.
    """
    if spec is None:
        from repro.cim import array
        spec = array.spec_override()
    return _lowered_linear(n_bits, backend, spec, mesh, resident)(x, w)


def mlp_cim(p: Params, x: jax.Array, gating: str, n_bits: int = 8,
            backend: str | None = None, spec=None, mesh=None,
            resident: bool = False, policy: str | None = None) -> jax.Array:
    """The MLP compiled through the jaxpr->CiM lowering pass: every integer
    matmul executes in the CiM array, every float op (quantization scales,
    SiLU/GELU gating) on the host — the opt-in twin of `mlp` for offload
    studies on reduced configs. `resident=True` pins the int8 weight planes
    across calls (see cim_linear). `spec=None` resolves through
    `array.spec_override()` — bank failover re-routes here too. `policy` is
    the lowering's offload policy (repro.cim.cost; "never" keeps every eqn
    on the host: the bit-exact host twin)."""
    if spec is None:
        from repro.cim import array
        spec = array.spec_override()
    return _lowered_mlp(gating, n_bits, backend, spec, mesh, resident,
                        policy)(p, x)


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------


def embed_init(key, vocab: int, d_model: int, dtype) -> Params:
    return {"table": _dense_init(key, (vocab, d_model), d_model, dtype)}


def embed(p: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0)


def lm_head_init(key, d_model: int, vocab: int, dtype) -> Params:
    return {"w": _dense_init(key, (d_model, vocab), d_model, dtype)}


def lm_head(p: Params, x: jax.Array, tied_table: jax.Array | None = None) -> jax.Array:
    w = tied_table.T if tied_table is not None else p["w"]
    return jnp.einsum("btd,dv->btv", x, w, preferred_element_type=jnp.float32)


def chunked_lm_loss(
    x: jax.Array,            # [B, S, D] final hidden states
    w_head: jax.Array,       # [D, V_padded]
    targets: jax.Array,      # [B, S]
    real_vocab: int,
    chunk: int = 512,
) -> jax.Array:
    """Mean CE without ever materializing the full [B, S, V] logits.

    Scans over sequence chunks; each chunk's logits are recomputed in the
    backward pass (jax.checkpoint), so peak memory is one chunk's logits.
    Padded vocab columns (Megatron-style padding) are masked to -inf.
    """
    b, s, d = x.shape
    v = w_head.shape[-1]
    c = chunk
    while s % c:
        c -= 1
    n_chunks = s // c
    pad_mask = (jnp.arange(v) >= real_vocab) * (-1e30)

    def body(total, xs):
        xc, tc = xs                                     # [B, c, D], [B, c]
        logits = jnp.einsum("btd,dv->btv", xc, w_head,
                            preferred_element_type=jnp.float32) + pad_mask
        total = total + jnp.sum(cross_entropy(logits, tc))
        return total, None

    xs = (
        jnp.moveaxis(x.reshape(b, n_chunks, c, d), 1, 0),
        jnp.moveaxis(targets.reshape(b, n_chunks, c), 1, 0),
    )
    total, _ = jax.lax.scan(jax.checkpoint(body, prevent_cse=False),
                            jnp.zeros((), jnp.float32), xs)
    return total / (b * s)


def cross_entropy(logits_f32: jax.Array, targets: jax.Array) -> jax.Array:
    """Sharded-vocab-safe CE: the target logit is extracted with an
    iota==target mask (elementwise + reduce stays sharded under GSPMD;
    a gather would force an all-gather of the vocab axis)."""
    v = logits_f32.shape[-1]
    m = jnp.max(logits_f32, axis=-1, keepdims=True)
    shifted = logits_f32 - jax.lax.stop_gradient(m)
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    onehot_sel = (
        jax.lax.broadcasted_iota(jnp.int32, logits_f32.shape, logits_f32.ndim - 1)
        == targets[..., None]
    )
    tgt = jnp.sum(jnp.where(onehot_sel, logits_f32, 0.0), axis=-1)
    return lse - tgt
