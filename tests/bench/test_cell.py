"""The harness driven on the CPU at a tiny size, past its look for a chip:
a sound run comes out correct, and a run whose timed path is broken
underneath, or whose reference is a control in a lower precision, does not."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import cell
import tiny

SEED = 2 ** 31 + 77


def run(monkeypatch, conf, mix, seconds=2.0, **kw):
    tiny.patch(monkeypatch, conf, mix)
    return cell.run_cell("tiny-cell", SEED, seconds, False, time.perf_counter(),
                         **kw)


def state_unchanged(engine):
    """Every decode step returns the cache it was given."""
    decode = engine.decode_fn

    def fn(params, caches, step_in):
        kept = jax.tree.map(jnp.copy, caches)     # the plain step donates
        _, logits = decode(params, caches, step_in)
        return kept, logits
    engine.decode_fn = fn


def token_altered(engine):
    """Every tenth sampled batch, from the third, has its first token
    replaced by the least likely one (a warm-up run samples too)."""
    sample, calls = engine.sample, []

    def fn(logits):
        tok = sample(logits)
        calls.append(1)
        if len(calls) % 10 == 3:
            tok = tok.at[0].set(jnp.argmin(logits[0]).astype(tok.dtype))
        return tok
    engine.sample = fn


@pytest.mark.parametrize("which", ["chat", "cim", "cim-embeds"])
def test_sound_run_is_correct(monkeypatch, which):
    conf = tiny.TINY_STUB if which == "cim-embeds" else tiny.TINY
    mix = tiny.CHAT if which == "chat" else tiny.CIM
    r = run(monkeypatch, conf, mix)
    assert r["correct"], r["check"]
    assert r["modelled"]["compared_tokens"] >= 10
    assert r["modelled"]["compiles_in_window"] == 0
    assert r["metrics"]["setup_s"]["value"] > 0
    assert list(r)[-1] == "check"


@pytest.mark.parametrize("fault", [state_unchanged, token_altered],
                         ids=["state-unchanged", "token-altered"])
@pytest.mark.parametrize("which", ["chat", "cim"])
def test_broken_timed_path_is_not_correct(monkeypatch, which, fault):
    mix = tiny.CHAT if which == "chat" else tiny.CIM
    r = run(monkeypatch, tiny.TINY, mix, engine_hook=fault)
    assert not r["correct"], r["check"]
    assert r["failed"] >= 1


def test_controls_are_not_correct(monkeypatch):
    """The controls the chip readings use, at the tiny size: the program's
    4-bit contractions for a CiM cell, the fp8 reference for a plain one."""
    r = run(monkeypatch, tiny.TINY, tiny.CIM, cim_bits=4)
    assert not r["correct"], r["check"]
    r = run(monkeypatch, tiny.TINY, tiny.CHAT, control="fp8")
    assert not r["correct"], r["check"]
