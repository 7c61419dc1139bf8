"""Model assembly: layer stacks (grouped lax.scan), caches, train/prefill/
decode paths for every assigned architecture family.

Layer-stack layout (supports mixed block patterns a la Griffin/xLSTM while
keeping a scannable structure):

  params = {
    "embed":       token table                  (absent for embed-stub archs)
    "first_dense": [layer, ...]                 (unscanned; e.g. DeepSeek layer 0)
    "groups":      (stack_p0, ..., stack_p{P-1})  each stacked over G groups
    "rem":         [layer, ...]                 (pattern remainder, unscanned)
    "final_norm", "lm_head"
  }

The pattern period P repeats G = (n_layers - first_dense) // P times; one
scan step applies one full period (P heterogeneous layers), so heterogeneous
stacks (rec,rec,local / m,m,m,s) still compile as a single rolled loop.

Caches mirror the same structure; every cache/state is a plain pytree.

The phases of a step run under profiler spans (`model.embed`,
`model.layer` holding `model.cast`, `model.norm`, `model.attn` and
`model.mlp`, `model.cache_slice` and `model.cache_stack` in an unrolled
stack, `model.head`), on the clock of the profile's device plane. Inside a
jitted step they mark tracing alone; in an unjitted (CiM-lowered) step
they name what the host runs between the lowered calls.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from . import attention as attn
from . import moe as moe_lib
from . import recurrent as rec_lib
from . import xlstm as xlstm_lib
from .layers import (
    chunked_lm_loss,
    embed,
    embed_init,
    lm_head,
    lm_head_init,
    mlp,
    mlp_cim,
    mlp_init,
    rmsnorm,
    rmsnorm_init,
)

Params = Dict[str, Any]


def _span(name: str, **kwargs):
    return jax.profiler.TraceAnnotation(name, **kwargs)


# ---------------------------------------------------------------------------
# per-layer init / apply
# ---------------------------------------------------------------------------


def _apply_mlp(cfg: ArchConfig, p: Params, h, mode: str):
    """Dense-MLP dispatch: the jaxpr->CiM lowered quantized path when the
    config opts in (cim_mlp_bits > 0), the plain dense path otherwise.
    Weight planes are pinned for decode only: the broadcast [M, K_pad, N]
    layout pins one plane stack per row count, and a prefill's rows (its
    prompt) would pin another beside the decode batch's."""
    if cfg.cim_mlp_bits:
        return mlp_cim(p, h, cfg.gating, n_bits=cfg.cim_mlp_bits,
                       resident=cfg.cim_resident and mode == "decode",
                       policy=cfg.cim_policy)
    return mlp(p, h, cfg.gating)


def _layer_init(key, cfg: ArchConfig, kind: str, layer_idx: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    p: Params = {"ln1": rmsnorm_init(cfg.d_model, dtype)}
    if kind in ("attn", "local"):
        p["attn"] = (attn.mla_init(ks[0], cfg, dtype) if cfg.mla
                     else attn.gqa_init(ks[0], cfg, dtype))
        p["ln2"] = rmsnorm_init(cfg.d_model, dtype)
        if cfg.moe is not None and layer_idx >= cfg.first_dense_layers:
            p["mlp"] = moe_lib.moe_init(ks[1], cfg, dtype)
        else:
            width = cfg.d_ff_first_dense or cfg.d_ff
            p["mlp"] = mlp_init(ks[1], cfg.d_model, width, cfg.gating, dtype)
    elif kind == "rec":
        p["rec"] = rec_lib.rglru_block_init(ks[0], cfg, dtype)
        p["ln2"] = rmsnorm_init(cfg.d_model, dtype)
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.gating, dtype)
    elif kind == "mlstm":
        p["cell"] = xlstm_lib.mlstm_init(ks[0], cfg, dtype)
    elif kind == "slstm":
        p["cell"] = xlstm_lib.slstm_init(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)
    return p


def _layer_cache(cfg: ArchConfig, kind: str, batch: int, max_len: int, dtype):
    if kind == "attn":
        if cfg.mla:
            return attn.mla_make_cache(cfg, batch, max_len, dtype)
        return attn.gqa_make_cache(cfg, batch, max_len, dtype)
    if kind == "local":
        return attn.local_make_cache(cfg, batch, dtype)
    if kind == "rec":
        return rec_lib.rglru_make_state(cfg, batch, dtype)
    if kind == "mlstm":
        return xlstm_lib.mlstm_make_state(cfg, batch)
    if kind == "slstm":
        return xlstm_lib.slstm_make_state(cfg, batch)
    raise ValueError(kind)


_KEEP_F32 = ("router",)  # routing logits stay full precision


def _compute_cast(p: Params, act_dtype) -> Params:
    """Mixed precision at compute time: f32 master weights are cast to the
    activation dtype before every matmul. Without this, bf16 x f32 einsums
    promote to f32 dots and the per-layer tensor-parallel all-reduces move
    f32 partial sums — 2x the collective bytes (measured: EXPERIMENTS.md
    §Perf, xlstm prefill cell)."""
    if act_dtype == jnp.float32:
        return p

    def cast(path, a):
        name = str(getattr(path[-1], "key", ""))
        if a.dtype == jnp.float32 and a.ndim >= 2 and name not in _KEEP_F32:
            return a.astype(act_dtype)
        return a

    return jax.tree_util.tree_map_with_path(cast, p)


def _layer_apply(
    p: Params, cfg: ArchConfig, kind: str, layer_idx: int,
    x: jax.Array, positions: jax.Array, mode: str,
    cache, max_len: Optional[int],
):
    """Returns (x, aux_loss, new_cache)."""
    with _span("model.layer"):
        return _layer_phases(p, cfg, kind, layer_idx, x, positions, mode,
                             cache, max_len)


def _layer_phases(p, cfg, kind, layer_idx, x, positions, mode, cache,
                  max_len):
    with _span("model.cast"):
        p = _compute_cast(p, cfg.activation_dtype())
        aux = jnp.zeros((), jnp.float32)
    with _span("model.norm"):
        h = rmsnorm(p["ln1"], x, cfg.norm_eps)

    if kind in ("attn", "local"):
        with _span("model.attn"):
            y, new_cache = _attn_apply(p, cfg, kind, h, positions, mode,
                                       cache, max_len)
            x = x + y
        with _span("model.norm"):
            h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
        with _span("model.mlp"):
            if cfg.moe is not None and layer_idx >= cfg.first_dense_layers:
                y2, aux = moe_lib.moe_apply(p["mlp"], cfg, h2)
            else:
                y2 = _apply_mlp(cfg, p["mlp"], h2, mode)
            return x + y2, aux, new_cache

    if kind == "rec":
        state = cache if mode == "decode" else None
        y, new_state = rec_lib.rglru_block_apply(p["rec"], cfg, h, state)
        x = x + y
        h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
        x = x + _apply_mlp(cfg, p["mlp"], h2, mode)
        new_cache = new_state if mode in ("prefill", "decode") else None
        return x, aux, new_cache

    if kind in ("mlstm", "slstm"):
        state = cache if mode == "decode" else None
        fn = xlstm_lib.mlstm_apply if kind == "mlstm" else xlstm_lib.slstm_apply
        y, new_state = fn(p["cell"], cfg, h, state)
        new_cache = new_state if mode in ("prefill", "decode") else None
        return x + y, aux, new_cache

    raise ValueError(kind)


def _attn_apply(p, cfg, kind, h, positions, mode, cache, max_len):
    """The attention of one "attn" or "local" layer: (y, new_cache)."""
    if cfg.mla:
        if mode == "train":
            return attn.mla_apply(p["attn"], cfg, h, positions), None
        if mode == "prefill":
            return attn.mla_prefill(p["attn"], cfg, h, positions, max_len)
        return attn.mla_decode(p["attn"], cfg, h, cache, positions)
    if kind == "local":
        if mode == "train":
            return attn.local_apply(p["attn"], cfg, h, positions), None
        if mode == "prefill":
            return attn.local_prefill(p["attn"], cfg, h, positions)
        return attn.local_decode(p["attn"], cfg, h, cache, positions)
    if mode == "train":
        return attn.gqa_apply(p["attn"], cfg, h, positions), None
    if mode == "prefill":
        return attn.gqa_prefill(p["attn"], cfg, h, positions, max_len)
    if cfg.cim_attention_bits:
        return attn.gqa_decode_cim(p["attn"], cfg, h, cache, positions)
    return attn.gqa_decode(p["attn"], cfg, h, cache, positions)


# ---------------------------------------------------------------------------
# the Model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StackLayout:
    pattern: Tuple[str, ...]
    n_first_dense: int
    n_groups: int
    n_rem: int

    @classmethod
    def from_config(cls, cfg: ArchConfig) -> "StackLayout":
        p = cfg.block_pattern
        body = cfg.n_layers - cfg.first_dense_layers
        return cls(pattern=p, n_first_dense=cfg.first_dense_layers,
                   n_groups=body // len(p), n_rem=body % len(p))


class Model:
    """Functional model wrapper for one ArchConfig."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.layout = StackLayout.from_config(cfg)
        # memoized per-group param slices for the unrolled (resident) stack:
        # the SAME jax.Arrays must be handed to every call so the lowered
        # MLPs' identity fingerprints stay warm across decode steps
        self._group_slices: Dict[int, Tuple[Any, list]] = {}

    # -- init ---------------------------------------------------------------

    def init(self, key) -> Params:
        cfg = self.cfg
        lay = self.layout
        dtype = jnp.float32 if cfg.param_dtype == "float32" else jnp.bfloat16
        keys = jax.random.split(key, 8)
        params: Params = {}
        if not cfg.embed_stub:
            # padded vocab (multiple of 256): model-axis shardable; pad rows
            # are never indexed and pad logits are masked to -inf
            params["embed"] = embed_init(keys[0], cfg.vocab_padded, cfg.d_model, dtype)

        params["first_dense"] = [
            _layer_init(jax.random.fold_in(keys[1], i), cfg, "attn", i, dtype)
            for i in range(lay.n_first_dense)
        ]

        def make_stack(pos_in_period: int):
            kind = lay.pattern[pos_in_period]

            def one(i):
                li = lay.n_first_dense + i * len(lay.pattern) + pos_in_period
                return _layer_init(
                    jax.random.fold_in(keys[2], li), cfg, kind, li, dtype)

            layers = [one(i) for i in range(lay.n_groups)]
            return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)

        params["groups"] = tuple(make_stack(p) for p in range(len(lay.pattern)))
        params["rem"] = [
            _layer_init(
                jax.random.fold_in(keys[3], 10_000 + r), cfg, lay.pattern[r],
                lay.n_first_dense + lay.n_groups * len(lay.pattern) + r, dtype)
            for r in range(lay.n_rem)
        ]
        params["final_norm"] = rmsnorm_init(cfg.d_model, dtype)
        if not (cfg.tie_embeddings and not cfg.embed_stub):
            params["lm_head"] = lm_head_init(keys[4], cfg.d_model, cfg.vocab_padded, dtype)
        return params

    # -- caches ---------------------------------------------------------------

    def init_caches(self, batch: int, max_len: int) -> Params:
        cfg, lay = self.cfg, self.layout
        dtype = cfg.activation_dtype()

        def stack_cache(pos: int):
            kind = lay.pattern[pos]
            one = _layer_cache(cfg, kind, batch, max_len, dtype)
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (lay.n_groups,) + x.shape), one)

        return {
            "first_dense": [
                _layer_cache(cfg, "attn", batch, max_len, dtype)
                for _ in range(lay.n_first_dense)
            ],
            "groups": tuple(stack_cache(p) for p in range(len(lay.pattern))),
            "rem": [
                _layer_cache(cfg, lay.pattern[r], batch, max_len, dtype)
                for r in range(lay.n_rem)
            ],
        }

    # -- stack execution ------------------------------------------------------

    def _embed_inputs(self, params: Params, inputs: Dict[str, jax.Array]):
        cfg = self.cfg
        if cfg.embed_stub:
            x = inputs["embeds"].astype(cfg.activation_dtype())
        else:
            x = embed(params["embed"], inputs["tokens"]).astype(cfg.activation_dtype())
        return x

    def _positions(self, inputs, x, mode):
        if mode == "decode":
            return inputs["positions"]
        b, t = x.shape[0], x.shape[1]
        return jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))

    def _run_stack(self, params, x, positions, mode, caches=None, max_len=None):
        cfg, lay = self.cfg, self.layout
        aux_total = jnp.zeros((), jnp.float32)
        new_caches: Dict[str, Any] = {"first_dense": [], "groups": [], "rem": []}

        def get_cache(part, idx):
            if caches is None:
                return None
            return caches[part][idx]

        for i, p in enumerate(params["first_dense"]):
            x, aux, nc = _layer_apply(p, cfg, "attn", i, x, positions, mode,
                                      get_cache("first_dense", i), max_len)
            aux_total += aux
            new_caches["first_dense"].append(nc)

        period = len(lay.pattern)

        def period_body(carry, xs):
            x, aux_acc = carry
            from .layers import hint_activation_sharding
            if mode == "train":
                x = hint_activation_sharding(x)   # 2-D (batch x seq) residency
            group_params, group_caches = xs
            ncs = []
            for pos in range(period):
                kind = lay.pattern[pos]
                # any group layer is past the first_dense prefix, so the
                # moe-vs-dense choice is static: use n_first_dense + pos
                li = lay.n_first_dense + pos
                c = None if group_caches is None else group_caches[pos]
                x, aux, nc = _layer_apply(group_params[pos], cfg, kind, li,
                                          x, positions, mode, c, max_len)
                aux_acc = aux_acc + aux
                ncs.append(nc)
            return (x, aux_acc), tuple(ncs)

        body = period_body
        if cfg.remat and mode == "train":
            body = jax.checkpoint(period_body, prevent_cse=False)

        if lay.n_groups > 0:
            # resident serving unrolls the group scan: inside lax.scan the
            # per-layer params are Tracers, whose identity is per-trace, so
            # the lowered MLPs could never hold a warm pin. The unrolled
            # path hands each layer the SAME param slice every call: the
            # per-group params of `unstack_groups`, or else memoized slices
            # of the stacked ones (train keeps the scan: remat + compile
            # time matter more)
            unstacked = "group_layers" in params
            if unstacked or ((cfg.cim_resident or cfg.cim_unroll_groups)
                             and mode != "train"):
                carry = (x, aux_total)
                ncs_stacked = []
                slices = (params["group_layers"] if unstacked else
                          self._group_param_slices(params["groups"]))
                for g, gp in enumerate(slices):
                    with _span("model.cache_slice"):
                        gc = (jax.tree.map(lambda a: a[g], caches["groups"])
                              if caches is not None else None)
                    carry, ncs = body(carry, (gp, gc))
                    ncs_stacked.append(ncs)
                x, aux_total = carry
                with _span("model.cache_stack"):
                    new_caches["groups"] = jax.tree.map(
                        lambda *xs_: jnp.stack(xs_), *ncs_stacked)
            else:
                xs = (params["groups"],
                      caches["groups"] if caches is not None else None)
                (x, aux_total), group_caches_new = jax.lax.scan(
                    body, (x, aux_total), xs)
                new_caches["groups"] = group_caches_new

        base = lay.n_first_dense + lay.n_groups * period
        for r in range(lay.n_rem):
            x, aux, nc = _layer_apply(params["rem"][r], cfg, lay.pattern[r],
                                      base + r, x, positions, mode,
                                      get_cache("rem", r), max_len)
            aux_total += aux
            new_caches["rem"].append(nc)

        with _span("model.norm"):
            x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return x, aux_total, new_caches

    def _group_param_slices(self, groups):
        """Per-group views of the stacked group params, built ONCE per
        params object and reused verbatim thereafter — the stability the
        resident fingerprints (id-based, see repro.cim.lower) depend on.
        The cache entry keeps a strong reference to the keyed object so a
        recycled id() can never alias a dead pytree. The slices are copies
        held beside the stacked params: at published widths, serve
        `unstack_groups` params instead."""
        key = id(groups)
        hit = self._group_slices.get(key)
        if hit is not None and hit[0] is groups:
            return hit[1]
        slices = [jax.tree.map(lambda a: a[g], groups)
                  for g in range(self.layout.n_groups)]
        self._group_slices[key] = (groups, slices)
        return slices

    def unstack_groups(self, params: Params) -> Params:
        """`params` with the stacked "groups" replaced by "group_layers":
        one tuple of per-layer params per group, which the stack then
        runs unrolled. The stacked arrays are not kept, so once the caller
        drops `params` the group weights are held once, where the memoized
        slices of a stacked tree hold them twice."""
        out = {k: v for k, v in params.items() if k != "groups"}
        out["group_layers"] = [
            jax.tree.map(lambda a: a[g], params["groups"])
            for g in range(self.layout.n_groups)]
        return out

    # -- public paths -----------------------------------------------------------

    def _head_weight(self, params) -> jax.Array:
        cfg = self.cfg
        if cfg.tie_embeddings and not cfg.embed_stub:
            return params["embed"]["table"].T
        return params["lm_head"]["w"]

    def logits(self, params, x_final) -> jax.Array:
        """Full logits over the padded vocab, pad columns masked to -inf."""
        cfg = self.cfg
        out = jnp.einsum("btd,dv->btv", x_final, self._head_weight(params),
                         preferred_element_type=jnp.float32)
        if cfg.vocab_padded != cfg.vocab_size:
            out = out + (jnp.arange(cfg.vocab_padded) >= cfg.vocab_size) * (-1e30)
        return out

    def forward(self, params, inputs) -> Tuple[jax.Array, jax.Array]:
        """Full-sequence forward (train path). Returns (logits_f32, aux)."""
        x = self._embed_inputs(params, inputs)
        positions = self._positions(inputs, x, "train")
        x, aux, _ = self._run_stack(params, x, positions, "train")
        return self.logits(params, x), aux

    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Chunked-CE loss: never materializes the [B, S, V] logits."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        positions = self._positions(batch, x, "train")
        x, aux, _ = self._run_stack(params, x, positions, "train")
        ce = chunked_lm_loss(x, self._head_weight(params), batch["targets"],
                             real_vocab=cfg.vocab_size)
        loss = ce + 0.01 * aux
        return loss, {"ce": ce, "aux": aux}

    def prefill(self, params, inputs, max_len: int):
        """Returns (caches, last_token_logits [B, V])."""
        with _span("model.embed"):
            x = self._embed_inputs(params, inputs)
            positions = self._positions(inputs, x, "prefill")
        x, _, caches = self._run_stack(params, x, positions, "prefill",
                                       caches=None, max_len=max_len)
        with _span("model.head"):
            return caches, self.logits(params, x[:, -1:])[:, 0]

    def decode_step(self, params, caches, inputs):
        """One token step. inputs: tokens/embeds [B,1] + positions [B].
        Returns (new_caches, logits [B, V])."""
        with _span("model.embed"):
            x = self._embed_inputs(params, inputs)
        positions = inputs["positions"]
        x, _, new_caches = self._run_stack(params, x, positions, "decode",
                                           caches=caches)
        with _span("model.head"):
            return new_caches, self.logits(params, x)[:, 0]


def build(cfg: ArchConfig) -> Model:
    return Model(cfg)
