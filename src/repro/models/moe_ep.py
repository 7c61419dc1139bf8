"""Expert-parallel MoE via shard_map (the EXPERIMENTS §Perf 6b follow-up).

The in-model scatter dispatch keeps the expert dim unsharded (a
data-dependent scatter across a sharded dim makes GSPMD replicate), paying
an expert-weight all-gather per layer instead. This module provides the true
EP execution: each "model"-axis shard OWNS n_experts/ep experts, tokens are
model-replicated per data shard, every shard routes its tokens to its LOCAL
experts only, and one psum over "model" combines the outputs.

Collective cost per layer: psum of [N_tokens, D] activations
vs the scatter design's all-gather of the layer's expert weights — EP wins
when expert params/layer exceed the token bytes (grok-1: 9.7 GB weights vs
~4 GB bf16 tokens at train_4k => ~2.4x less collective traffic).

Semantics note: capacity is enforced per (data-shard, expert) rather than
globally, so token drops can differ from the reference under saturation; in
the no-drop regime (capacity_factor high enough) outputs are identical —
asserted by tests/test_sharding.py::test_moe_ep_matches_reference.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig

Params = Dict[str, Any]


def _local_moe(router, w_in, w_gate, w_out, xf, *, cfg: ArchConfig,
               e_local: int, axis: str):
    """Per-shard body: route local tokens to LOCAL experts, psum the combine.

    xf: [N_loc, D] (this data-shard's tokens, replicated over `axis`);
    w_*: [E_loc, ...] (this shard's experts). Output [N_loc, D], combined.
    """
    m = cfg.moe
    n, d = xf.shape
    e, k = m.n_experts, m.top_k
    shard = jax.lax.axis_index(axis)
    e0 = shard * e_local

    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, k)
    if m.router_renorm:
        weights = weights / jnp.maximum(jnp.sum(weights, -1, keepdims=True), 1e-9)

    # keep only choices routed to THIS shard's experts
    local = (idx >= e0) & (idx < e0 + e_local)              # [N, k]
    lidx = jnp.where(local, idx - e0, 0)

    cap = max(int(m.capacity_factor * k * n / e), 1)
    onehot = jax.nn.one_hot(lidx, e_local, dtype=jnp.int32) * local[..., None]
    flat = onehot.reshape(n * k, e_local)
    pos = jnp.cumsum(flat, axis=0) - flat
    pos = jnp.sum(pos.reshape(n, k, e_local) * onehot, axis=-1)
    keep = (local & (pos < cap)).astype(xf.dtype)

    fe = lidx.reshape(n * k)
    fp = jnp.minimum(pos.reshape(n * k), cap - 1)
    fk = keep.reshape(n * k)
    src = jnp.repeat(xf, k, axis=0) * fk[:, None]
    xe = jnp.zeros((e_local, cap, d), xf.dtype).at[fe, fp].add(src)

    h = jnp.einsum("ecd,edf->ecf", xe, w_in, preferred_element_type=jnp.float32)
    g = jnp.einsum("ecd,edf->ecf", xe, w_gate, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * h).astype(xf.dtype)
    ye = jnp.einsum("ecf,efd->ecd", h, w_out,
                    preferred_element_type=jnp.float32).astype(xf.dtype)

    back = ye[fe, fp] * fk[:, None]
    back = back.reshape(n, k, d) * weights[..., None].astype(xf.dtype)
    y = jnp.sum(back, axis=1)
    # ONE collective: combine expert outputs across the expert-parallel axis
    return jax.lax.psum(y, axis)


def moe_apply_ep(p: Params, cfg: ArchConfig, x: jax.Array, mesh: Mesh,
                 axis: str = "model") -> jax.Array:
    """Routed-expert output under true expert parallelism (shared experts and
    the aux loss are computed by the caller / standard path)."""
    m = cfg.moe
    ep = mesh.shape[axis]
    assert m.n_experts % ep == 0, (m.n_experts, ep)
    e_local = m.n_experts // ep
    b, t, d = x.shape
    dp = "data" if "data" in mesh.axis_names else mesh.axis_names[0]

    body = functools.partial(_local_moe, cfg=cfg, e_local=e_local, axis=axis)
    # replication checking off: the psum combine is the only collective
    # and its spec is explicit
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(axis, None, None), P(axis, None, None),
                  P(axis, None, None), P(dp, None)),
        out_specs=P(dp, None), check_vma=False,
    )
    y = fn(p["router"], p["w_in"], p["w_gate"], p["w_out"], x.reshape(b * t, d))
    return y.reshape(b, t, d)
