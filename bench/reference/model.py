"""The dense decoder family: its sizes, weights, FLOP count and plain
float32 reference.

A configuration with no `"reference"` key is of this family
(`bench.model_io.family`). The family's weights are made here, from the
seed, on the device, in one jitted call, in bfloat16 (the type they are
served in), in the program's own parameter layout; the reference reads the
same arrays through `layer_weights`.

The reference is a pre-norm decoder written in straightforward `jax.numpy`,
one layer per jitted call, with no cache and no batching: RMSNorm,
grouped-query attention with RoPE over adjacent pairs, causal softmax, and
a SwiGLU or GELU MLP, then a final RMSNorm and an untied LM head. Every
matrix product runs at `jax.default_matmul_precision("highest")`. It imports
nothing of the system under test.

Departures from the published models follow the system's own equations and
are listed in each configuration file under `departures`.

`control` names a lower precision for the comparison's control: every
operand of every matrix product is quantized per tensor before the product,
either to float8 e4m3 ("fp8", with a scale that maps the tensor's largest
magnitude to 448) or to symmetric integers ("int4", "int8").
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256          # sequences are padded to a multiple of this


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The shapes the benchmark needs, independent of the program."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    vocab_padded: int
    gated: bool          # SwiGLU (three matrices) or plain GELU (two)
    embed_stub: bool     # inputs are frame embeddings, not token ids
    norm_eps: float
    rope_theta: float


def sizes_of(cfg) -> Sizes:
    if cfg.gating not in ("swiglu", "none"):
        raise ValueError(f"gating {cfg.gating!r} has no reference here")
    return Sizes(n_layers=cfg.n_layers, d_model=cfg.d_model,
                 n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                 head_dim=cfg.head_dim, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
                 vocab_padded=cfg.vocab_padded, gated=cfg.gating == "swiglu",
                 embed_stub=cfg.embed_stub, norm_eps=cfg.norm_eps,
                 rope_theta=cfg.rope_theta)


def _make_layer(key, s: Sizes, dtype):
    d, h, hkv, hd, f = s.d_model, s.n_heads, s.n_kv_heads, s.head_dim, s.d_ff
    ks = jax.random.split(key, 9)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    def scale(k):
        return (1.0 + 0.1 * jax.random.normal(k, (d,), jnp.float32)
                ).astype(dtype)

    mlp = {"w_in": dense(ks[0], (d, f), d), "w_out": dense(ks[1], (f, d), f)}
    if s.gated:
        mlp["w_gate"] = dense(ks[2], (d, f), d)
    return {
        "ln1": {"scale": scale(ks[3])},
        "attn": {"wq": dense(ks[4], (d, h, hd), d),
                 "wk": dense(ks[5], (d, hkv, hd), d),
                 "wv": dense(ks[6], (d, hkv, hd), d),
                 "wo": dense(ks[7], (h, hd, d), h * hd)},
        "ln2": {"scale": scale(ks[8])},
        "mlp": mlp,
    }


def make_params(key, s: Sizes, unstacked: bool, dtype=jnp.bfloat16):
    """Weights in the program's parameter layout, made in one jitted call.

    `unstacked` gives the layers as `group_layers` (one tuple per layer, how
    the lowered path serves them); otherwise they are stacked under `groups`
    for the scanned path. Both hold the same values for one key."""

    def build(key):
        k_emb, k_layers, k_norm, k_head = jax.random.split(key, 4)
        layers = [_make_layer(jax.random.fold_in(k_layers, i), s, dtype)
                  for i in range(s.n_layers)]
        p = {"first_dense": [], "rem": [],
             "final_norm": {"scale": (1.0 + 0.1 * jax.random.normal(
                 k_norm, (s.d_model,), jnp.float32)).astype(dtype)},
             "lm_head": {"w": (jax.random.normal(
                 k_head, (s.d_model, s.vocab_padded), jnp.float32)
                 * s.d_model ** -0.5).astype(dtype)}}
        if not s.embed_stub:
            p["embed"] = {"table": jax.random.normal(
                k_emb, (s.vocab_padded, s.d_model), jnp.float32
            ).astype(dtype)}
        if unstacked:
            p["group_layers"] = [(layer,) for layer in layers]
        else:
            p["groups"] = (jax.tree.map(lambda *xs: jnp.stack(xs), *layers),)
        return p

    return jax.jit(build)(key)


def layer_weights(params, i: int) -> dict:
    """Layer `i`'s weights as a flat dict, from either layout."""
    if "group_layers" in params:
        lp = params["group_layers"][i][0]
    else:
        lp = jax.tree.map(lambda a: a[i], params["groups"][0])
    out = {"ln1": lp["ln1"]["scale"], "ln2": lp["ln2"]["scale"]}
    out.update(lp["attn"])
    out.update(lp["mlp"])
    return out


def decode_token_flops(s: Sizes, context: int) -> float:
    """Model FLOPs of one decoded token at `context` cached positions: two
    per weight of every matrix product, and the attention's QK and AV."""
    per_layer = (s.d_model * s.n_heads * s.head_dim
                 + 2 * s.d_model * s.n_kv_heads * s.head_dim
                 + s.n_heads * s.head_dim * s.d_model
                 + (3 if s.gated else 2) * s.d_model * s.d_ff)
    weights = s.n_layers * per_layer + s.d_model * s.vocab
    return 2.0 * weights + 4.0 * s.n_layers * s.n_heads * s.head_dim * context


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
