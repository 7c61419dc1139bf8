"""Architecture & shape configuration system.

Every assigned architecture gets one `ArchConfig` (exact published numbers)
plus a `.reduced()` variant for CPU smoke tests. Input shapes are the four
assigned workload cells; `input_specs()` builds ShapeDtypeStruct stand-ins
for the dry-run (no allocation).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int                  # routed experts
    top_k: int
    d_ff_expert: int                # per-expert hidden width
    n_shared: int = 0               # always-on shared experts (DeepSeek)
    capacity_factor: float = 1.25
    router_renorm: bool = True      # renormalize top-k probs


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2 Multi-head Latent Attention."""

    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                     # dense | moe | hybrid | ssm | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int                       # dense-FFN hidden (0 => arch has none)
    vocab_size: int

    gating: str = "swiglu"          # swiglu | geglu | none
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    first_dense_layers: int = 0     # leading dense layers in a MoE stack
    d_ff_first_dense: int = 0       # width of those layers (0 -> d_ff)

    # layer pattern, repeated to fill n_layers. kinds:
    #   attn (global), local (windowed attn), rec (RG-LRU), mlstm, slstm
    block_pattern: Tuple[str, ...] = ("attn",)
    local_window: int = 2048

    embed_stub: bool = False        # audio/vlm: inputs are precomputed embeddings
    tie_embeddings: bool = False
    norm_eps: float = 1e-6

    dtype: str = "bfloat16"         # activation dtype
    param_dtype: str = "float32"
    opt_state_dtype: str = "float32"
    remat: bool = True

    expert_sharding: str = "ep"     # ep | tp (grok: 8 experts < 16-way axis)
    sub_quadratic: bool = False     # can run long_500k
    microbatches: int = 1           # gradient-accumulation factor (train)
    tensor_parallel: bool = True    # False: replicate params across "model"
                                    # (125M-scale: TP all-reduces cost more
                                    # than the replicated weights save)
    cim_mlp_bits: int = 0           # >0: dense MLPs run through the
    #                                 jaxpr->CiM lowering pass at this
    #                                 quantization width (serve --cim-lower)
    cim_attention_bits: int = 0     # >0: GQA decode attention (QK^T + AV)
    #                                 runs through the lowering pass as
    #                                 batched CiM schedules; softmax/rotary
    #                                 stay host islands (serve --cim-lower)
    cim_resident: bool = False      # pin int8 MLP weight planes in the
    #                                 array's resident region across calls
    #                                 (serve --cim-resident): warm decode
    #                                 skips the weight-side entry pack
    cim_unroll_groups: bool = False  # unroll the grouped-layer scan outside
    #                                 training: per-layer params keep a
    #                                 stable identity so eager serving can
    #                                 charge (and pin) per call — the serve
    #                                 engine sets this for BOTH sides of the
    #                                 repack-vs-resident comparison
    cim_policy: str = "edp"         # offload policy of the lowered MLP and
    #                                 attention (repro.cim.cost): "never"
    #                                 runs every lowered eqn on the host —
    #                                 the bit-exact host twin of a CiM run

    # -- derived -----------------------------------------------------------
    @property
    def vocab_padded(self) -> int:
        """Megatron-style vocab padding to a multiple of 256: keeps the
        vocab axis shardable on the 16-wide model axis (granite's 49155 and
        internvl's 92553 are odd); pad columns are masked to -inf in the LM
        head so semantics are unchanged."""
        return ((self.vocab_size + 255) // 256) * 256

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def pattern_layers(self) -> Tuple[str, ...]:
        """The full per-layer kind list (pattern repeated, truncated)."""
        p = self.block_pattern
        reps = -(-self.n_layers // len(p))
        full = (p * reps)[: self.n_layers]
        if self.first_dense_layers:
            # leading dense layers replace the first entries' moe-ness only;
            # kind stays as given (handled by the MoE layer itself)
            pass
        return full

    def activation_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    def reduced(self) -> "ArchConfig":
        """Tiny same-family variant for CPU smoke tests."""
        moe = None
        if self.moe is not None:
            moe = dataclasses.replace(
                self.moe,
                n_experts=max(2, min(4, self.moe.n_experts)),
                top_k=min(2, self.moe.top_k),
                d_ff_expert=64,
                n_shared=min(1, self.moe.n_shared),
            )
        mla = None
        if self.mla is not None:
            mla = MLAConfig(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16)
        period = len(self.block_pattern)
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            n_layers=max(2, 2 * period) if period > 1 else 2,
            d_model=64,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2),
            head_dim=16,
            d_ff=128 if self.d_ff else 0,
            vocab_size=256,
            moe=moe,
            mla=mla,
            local_window=32,
            microbatches=1,
            dtype="float32",
            param_dtype="float32",
            remat=False,
        )


# ---------------------------------------------------------------------------
# Workload shapes (assigned cells)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # train | prefill | decode


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


def shape_applicable(arch: ArchConfig, shape: ShapeSpec) -> Tuple[bool, str]:
    """long_500k needs sub-quadratic attention (DESIGN.md §5)."""
    if shape.name == "long_500k" and not arch.sub_quadratic:
        return False, "full-attention arch: O(S^2) attention at 512k is out of scope (DESIGN.md §5)"
    return True, ""


def input_specs(arch: ArchConfig, shape: ShapeSpec) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    train:   tokens/embeds + targets over the full sequence
    prefill: tokens/embeds (cache is an output)
    decode:  one new token + position (the KV/state cache of seq_len is part
             of the step signature and built abstractly by the caller)
    """
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    act = arch.activation_dtype()
    if shape.kind == "train":
        if arch.embed_stub:
            return {
                "embeds": jax.ShapeDtypeStruct((b, s, arch.d_model), act),
                "targets": jax.ShapeDtypeStruct((b, s), i32),
            }
        return {
            "tokens": jax.ShapeDtypeStruct((b, s), i32),
            "targets": jax.ShapeDtypeStruct((b, s), i32),
        }
    if shape.kind == "prefill":
        if arch.embed_stub:
            return {"embeds": jax.ShapeDtypeStruct((b, s, arch.d_model), act)}
        return {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
    if shape.kind == "decode":
        tok = (
            {"embeds": jax.ShapeDtypeStruct((b, 1, arch.d_model), act)}
            if arch.embed_stub
            else {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}
        )
        tok["positions"] = jax.ShapeDtypeStruct((b,), i32)
        return tok
    raise ValueError(shape.kind)
