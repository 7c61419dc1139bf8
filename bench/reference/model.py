"""Plain float32 reference of the benchmark's decoder models.

A pre-norm decoder written in straightforward `jax.numpy`, one layer per
jitted call, with no cache and no batching: RMSNorm, grouped-query attention
with RoPE over adjacent pairs, causal softmax, and a SwiGLU or GELU MLP,
then a final RMSNorm and an untied LM head. Every matrix product runs at
`jax.default_matmul_precision("highest")`. It imports nothing of the system
under test; it reads the benchmark's weights (`bench.model_io`).

Departures from the published models follow the system's own equations and
are listed in each configuration file under `departures`.

`control` names a lower precision for the comparison's control: every
operand of every matrix product is quantized per tensor before the product,
either to float8 e4m3 ("fp8", with a scale that maps the tensor's largest
magnitude to 448) or to symmetric integers ("int4", "int8").
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.model_io import Sizes, layer_weights

BLOCK = 256          # sequences are padded to a multiple of this


def _quant(x, control: Optional[str]):
    if control is None:
        return x
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
    if control == "fp8":
        s = amax / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    bits = int(control.removeprefix("int"))
    qmax = 2.0 ** (bits - 1) - 1
    s = amax / qmax
    return jnp.clip(jnp.round(x / s), -qmax, qmax) * s


def _mm(spec, a, b, control):
    return jnp.einsum(spec, _quant(a, control), _quant(b, control),
                      precision=jax.lax.Precision.HIGHEST)


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta):
    t, _, d = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs      # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("s", "control"))
def _layer(x, w, s: Sizes, control):
    f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    t = x.shape[0]
    h = _norm(x, f32["ln1"], s.norm_eps)
    q = _rope(_mm("td,dhk->thk", h, f32["wq"], control), s.rope_theta)
    k = _rope(_mm("td,dhk->thk", h, f32["wk"], control), s.rope_theta)
    v = _mm("td,dhk->thk", h, f32["wv"], control)
    g = s.n_heads // s.n_kv_heads
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scores = _mm("qhd,khd->hqk", q, k, control) / np.sqrt(s.head_dim)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm("hqk,khd->qhd", probs, v, control)
    x = x + _mm("thk,hkd->td", o, f32["wo"], control)
    h = _norm(x, f32["ln2"], s.norm_eps)
    u = _mm("td,df->tf", h, f32["w_in"], control)
    if s.gated:
        u = jax.nn.silu(_mm("td,df->tf", h, f32["w_gate"], control)) * u
    else:
        u = jax.nn.gelu(u, approximate=True)
    return x + _mm("tf,fd->td", u, f32["w_out"], control)


@functools.partial(jax.jit, static_argnames=("s", "control"))
def _head(x, rows, norm_scale, w_head, s: Sizes, control):
    x = _norm(x[rows], norm_scale, s.norm_eps)
    return _mm("td,dv->tv", x, w_head[:, :s.vocab].astype(jnp.float32),
               control)


@jax.jit
def _embed_tokens(table, tokens):
    return table[tokens].astype(jnp.float32)


def logits_at(params, s: Sizes, inputs, rows, control: Optional[str] = None):
    """Reference logits [len(rows), vocab] at sequence positions `rows`.

    `inputs` is a 1-D array of token ids, or [T, d_model] frame embeddings
    for a model whose inputs are embeddings. The sequence is padded with
    zeros to a multiple of `BLOCK`; causal attention keeps the padding out
    of every real position."""
    n = len(rows)
    rows = np.asarray(rows, np.int32)
    rows = jnp.asarray(np.pad(rows, (0, -n % 64), mode="edge"))
    with jax.default_matmul_precision("highest"):
        if s.embed_stub:
            x = jnp.asarray(inputs, jnp.float32)
            t = x.shape[0]
            x = jnp.pad(x, ((0, -t % BLOCK), (0, 0)))
        else:
            tokens = np.asarray(inputs, np.int32)
            tokens = np.pad(tokens, (0, -tokens.shape[0] % BLOCK))
            x = _embed_tokens(params["embed"]["table"], tokens)
        for i in range(s.n_layers):
            x = _layer(x, layer_weights(params, i), s, control)
        return _head(x, rows, params["final_norm"]["scale"],
                     params["lm_head"]["w"], s, control)[:n]
