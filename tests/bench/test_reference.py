"""The float32 reference against the system's model at a small size: the
model's prefill followed by cached decode must give the reference's full
forward pass, in float32 both."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model_io
from bench.reference import model as reference
import tiny


@pytest.mark.parametrize("conf", [tiny.TINY, tiny.TINY_STUB],
                         ids=["swiglu-gqa-tokens", "gelu-mha-embeds"])
def test_prefill_then_cached_decode_matches_full_forward(conf):
    from repro.models import build

    cfg = dataclasses.replace(model_io.arch_config(conf), dtype="float32",
                              param_dtype="float32")
    s = reference.sizes_of(cfg)
    model = build(cfg)
    params = reference.make_params(jax.random.PRNGKey(3), s, unstacked=False,
                                   dtype=jnp.float32)
    p, n, max_len = 5, 6, 16
    key = jax.random.PRNGKey(4)
    if s.embed_stub:
        seq = jax.random.normal(key, (p + n, s.d_model)) * 0.5
        inputs = {"embeds": seq[None, :p]}
    else:
        seq = jax.random.randint(key, (p + n,), 0, s.vocab)
        inputs = {"tokens": seq[None, :p]}
    caches, logits = model.prefill(params, inputs, max_len)
    got = [logits[0]]
    for t in range(p, p + n - 1):
        step = ({"embeds": seq[None, None, t]} if s.embed_stub
                else {"tokens": seq[None, None, t]})
        step["positions"] = jnp.array([t], jnp.int32)
        caches, logits = model.decode_step(params, caches, step)
        got.append(logits[0])
    got = np.stack([np.asarray(g[:s.vocab]) for g in got])
    want = np.asarray(reference.logits_at(params, s, np.asarray(seq),
                                          np.arange(p - 1, p + n - 1)))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale, np.abs(got - want).max()


def test_control_precisions_depart_from_the_reference():
    conf = tiny.TINY
    s = reference.sizes_of(model_io.arch_config(conf))
    params = reference.make_params(jax.random.PRNGKey(5), s, unstacked=True)
    seq = np.asarray(jax.random.randint(jax.random.PRNGKey(6), (40,), 0,
                                        s.vocab))
    rows = np.arange(40)
    ref = np.asarray(reference.logits_at(params, s, seq, rows))
    err = {c: np.abs(np.asarray(reference.logits_at(params, s, seq, rows, c))
                     - ref).max() for c in ("int8", "fp8", "int4")}
    assert 0 < err["int8"] < err["fp8"] < err["int4"]
