"""What the lowering executor shows a profile: a `cim.call` span a call, a
`cim.host` span an island of host eqns, a `cim.region` span a region, the
`host_eqns` counter, and region programs named after the function; and the
`model.*` spans around the host phases of an unjitted CiM decode step."""
import jax
import jax.numpy as jnp
import pytest

from repro.cim import clear_resident, dispatch, macro
from repro.cim.lower import lower


def _quant_linear(x, w):
    scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-9)
    wq = jnp.clip(jnp.round(w / scale * 127), -127, 127).astype(jnp.int8)
    xq = jnp.clip(jnp.round(x * 8), -127, 127).astype(jnp.int8)
    y = jax.lax.dot_general(xq, wq, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.int32)
    return y.astype(jnp.float32) * scale


X = jax.random.normal(jax.random.PRNGKey(0), (2, 16))
W = jax.random.normal(jax.random.PRNGKey(1), (16, 8))


@pytest.fixture
def entered(monkeypatch):
    """The spans entered, as (name, keyword arguments), in order."""
    seen = []

    class Recording:
        def __init__(self, name, **kwargs):
            seen.append((name, kwargs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
    return seen


def test_host_eqns_counts_cold_and_warm_resident_calls():
    clear_resident()
    dispatch.clear_schedule_cache()
    assert dispatch.cache_stats()["host_eqns"] == 0
    lf = lower(_quant_linear, resident_argnums=(1,))
    comp = lf.trace(X, W)
    assert comp._warm_skip
    lf(X, W)                                  # cold: pins, binds every eqn
    cold = dispatch.cache_stats()["host_eqns"]
    assert cold == comp.host_eqns
    lf(X, W)                                  # warm: skips the weight's eqns
    warm = dispatch.cache_stats()["host_eqns"] - cold
    assert warm == comp.host_eqns - len(comp._warm_skip)
    dispatch.clear_schedule_cache()
    assert dispatch.cache_stats()["host_eqns"] == 0
    clear_resident()


def test_lowered_call_enters_one_span_per_island_and_region(entered):
    lf = lower(_quant_linear, name="qlin")
    lf(X, W)
    comp = lf.trace(X, W)
    assert entered[0] == ("cim.call", {"fn": "qlin"})
    assert [n for n, _ in entered].count("cim.call") == 1
    islands = [kw["eqns"] for n, kw in entered if n == "cim.host"]
    assert sum(islands) == comp.host_eqns
    assert len(islands) < comp.host_eqns     # a span an island, not an eqn
    assert [kw["region"] for n, kw in entered if n == "cim.region"] \
        == [r.index for r in comp.regions]


def test_region_programs_are_named_after_the_lowered_function():
    dispatch.clear_schedule_cache()
    lf = lower(_quant_linear, name="qlin")
    lf(X, W)
    named = sorted(p.fn.__name__ for _, p in dispatch._PROGRAMS.items()
                   if isinstance(p, macro.CompiledSchedule))
    assert named == [f"cim_qlin_r{r.index}"
                     for r in lf.trace(X, W).regions]
    assert lower(_quant_linear).name == "quant_linear"
    assert lower(lambda x: x).name == "lambda"


def test_cim_decode_step_enters_a_span_per_model_phase(entered):
    """Between the lowered calls of an unjitted CiM decode step, every host
    phase of the model runs under a `model.*` span."""
    from repro.configs.base import ArchConfig
    from repro.models import build

    cfg = ArchConfig(name="model-spans-test", family="dense", n_layers=2,
                     d_model=16, n_heads=4, n_kv_heads=2, head_dim=8,
                     d_ff=32, vocab_size=64, dtype="float32",
                     tensor_parallel=False, cim_mlp_bits=8,
                     cim_attention_bits=8, cim_unroll_groups=True)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(1))
    caches = model.init_caches(2, 4)
    inputs = {"tokens": jnp.zeros((2, 1), jnp.int32),
              "positions": jnp.array([1, 2], jnp.int32)}
    model.decode_step(params, caches, inputs)
    names = [n for n, _ in entered]
    assert names[0] == "model.embed" and names[-1] == "model.head"
    assert names.count("model.layer") == 2
    assert {n for n in names if n.startswith("model.")} == {
        "model.embed", "model.layer", "model.cast", "model.norm",
        "model.attn", "model.qkv", "model.kv_write", "model.out_proj",
        "model.mlp", "model.cache_slice", "model.cache_stack", "model.head"}
    # per layer: rotary and the cache write, the lowered attention core,
    # its output projection, then the lowered MLP
    calls = [kw.get("fn") or n for n, kw in entered
             if n in ("model.kv_write", "model.out_proj", "model.mlp",
                      "cim.call")]
    assert calls == 2 * ["model.kv_write", "sdpa", "model.out_proj",
                         "model.mlp", "mlp"]
