"""Bit-plane codecs: integer tensors <-> LSB-first bit-planes / packed planes.

The ADRA array stores an n-bit word as n bits along a row; a CiM access
operates on ALL columns of a row pair at once. The natural TPU layout for the
same computation is the transpose: plane p holds bit p of many words, packed
32 words per uint32 lane element. The codecs here are used by the functional
ADRA ops (repro.core.adra) and by the Pallas bit-plane kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# trace-time codec call counters: the CiM engine's chained-op tests assert
# that PlanePack pipelines never re-enter these between ops
_CODEC_CALLS = {"pack": 0, "unpack": 0}


def codec_call_counts() -> dict:
    return dict(_CODEC_CALLS)


def reset_codec_call_counts() -> None:
    _CODEC_CALLS["pack"] = 0
    _CODEC_CALLS["unpack"] = 0


def int_to_bits(x: jax.Array, n_bits: int) -> jax.Array:
    """Two's-complement LSB-first bit decomposition: [...] -> [..., n_bits]."""
    x = jnp.asarray(x, dtype=jnp.int32)
    shifts = jnp.arange(n_bits, dtype=jnp.int32)
    shifted = x[..., None] >> shifts  # jnp broadcasts; arithmetic shift is fine pre-mask
    return (shifted & 1).astype(jnp.int32)


def bits_to_int(bits: jax.Array, signed: bool = True) -> jax.Array:
    """Inverse of int_to_bits; interprets the MSB as a sign bit if signed.

    Accumulates modulo 2^32 (int32 wrap semantics). Exact for words of up to
    31 value bits (signed) / 32 bits (wrapped); wider chains — e.g. the
    (n+1)-bit output of a 32-bit subtraction — are exact iff the result fits,
    otherwise use the raw bit pattern.
    """
    n = bits.shape[-1]
    k = min(n, 32)
    w = jnp.left_shift(jnp.uint32(1), jnp.arange(k, dtype=jnp.uint32))
    val = jnp.sum(bits[..., :k].astype(jnp.uint32) * w, axis=-1, dtype=jnp.uint32)
    val = val.astype(jnp.int32)
    if signed and n < 32:
        sign = bits[..., -1].astype(jnp.int32)
        # subtract 2^n per sign bit: two's complement sign extension.
        # (for n == 32 the int32 wrap already encodes the sign.)
        val = val - jnp.left_shift(sign, jnp.int32(min(n, 31)))
    return val


def pack_bitplanes(x: jax.Array, n_bits: int) -> jax.Array:
    """[words] int32 -> [n_bits, ceil(words/32)] uint32 packed planes.

    Plane p, lane word w, bit position j holds bit p of element 32*w + j.
    """
    _CODEC_CALLS["pack"] += 1
    x = jnp.asarray(x, dtype=jnp.int32).reshape(-1)
    n = x.shape[0]
    pad = (-n) % 32
    # bits plane-major with the lane axis minor, never an [N, n_bits] bit
    # matrix: that is n_bits words per word, padded on the TPU from a minor
    # axis of n_bits to 128 lanes — GiBs at a 2048 x 16384 operand
    x = jnp.pad(x, (0, pad)).reshape(-1, 32).T           # [32, N/32]
    shifts = jnp.arange(n_bits, dtype=jnp.int32)[:, None, None]
    bits = (x[None] >> shifts) & 1                       # [n_bits, 32, N/32]
    weights = (1 << jnp.arange(32, dtype=jnp.uint32)).astype(jnp.uint32)
    return jnp.sum(bits.astype(jnp.uint32) * weights[:, None], axis=1)


def unpack_bitplanes(planes: jax.Array, n_words: int, signed: bool = True) -> jax.Array:
    """[n_bits, W] uint32 packed planes -> [n_words] int (two's complement)."""
    _CODEC_CALLS["unpack"] += 1
    n_bits, w = planes.shape
    # bits_to_int over the plane axis with the lane axis minor (see
    # pack_bitplanes); only the [32, W] result is transposed to word order.
    # The sign plane of a signed word narrower than 32 bits weighs -2^(n-1)
    # (two's complement); sums wrap modulo 2^32 like int32 arithmetic
    k = min(n_bits, 32)
    weights = np.left_shift(np.int64(1), np.arange(k, dtype=np.int64))
    if signed and n_bits < 32:
        weights[-1] = -weights[-1]
    weights = jnp.asarray(weights.astype(np.uint32).view(np.int32))
    shifts = jnp.arange(32, dtype=jnp.uint32)[:, None]
    bits = ((planes[:k, None, :] >> shifts) & jnp.uint32(1)).astype(jnp.int32)
    val = jnp.sum(bits * weights[:, None, None], axis=0,
                  dtype=jnp.int32)                       # [32, W]
    return val.T.reshape(-1)[:n_words]
