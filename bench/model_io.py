"""Configurations and weights of the benchmark's models.

A configuration is a JSON file under `bench/configs/`, found by its name. Its
`program` block says how the system under test is set up for it: a registry
architecture and the fields replaced on it. Weights are made here, from the
seed, on the device, in one jitted call, in bfloat16 (the type they are served
in). The reference reads the same arrays through `layer_weights`.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp

BENCH = Path(__file__).resolve().parent


def load_config(name: str) -> dict:
    path = BENCH / "configs" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration file {path}")
    return json.loads(path.read_text())


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


def arch_config(conf: dict, cim_bits: int = 0, cim_resident: bool = False):
    """The program's `ArchConfig` for a configuration file."""
    from repro.configs import get_config

    prog = dict(conf["program"])
    cfg = get_config(prog.pop("arch"))
    cfg = dataclasses.replace(cfg, **prog)
    cfg = dataclasses.replace(cfg, param_dtype=cfg.dtype)
    if cim_bits:
        cfg = dataclasses.replace(cfg, cim_mlp_bits=cim_bits,
                                  cim_attention_bits=cim_bits,
                                  cim_unroll_groups=True,
                                  cim_resident=cim_resident)
    return cfg


def sizes_of(cfg) -> Sizes:
    if cfg.gating not in ("swiglu", "none"):
        raise ValueError(f"gating {cfg.gating!r} has no reference here")
    return Sizes(n_layers=cfg.n_layers, d_model=cfg.d_model,
                 n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                 head_dim=cfg.head_dim, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
                 vocab_padded=cfg.vocab_padded, gated=cfg.gating == "swiglu",
                 embed_stub=cfg.embed_stub, norm_eps=cfg.norm_eps,
                 rope_theta=cfg.rope_theta)


def _layer(key, s: Sizes, dtype):
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
        layers = [_layer(jax.random.fold_in(k_layers, i), s, dtype)
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


def seed_key(seed: int):
    """A PRNG key for any whole-number seed, including ones past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
