"""Compile rehearsals for one TPU v5e, at gemma-2b's published widths.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached: these tests run nothing, but refuse what the
chip's compiler would refuse (a kernel's tiling or VMEM budget, a program
that does not fit device memory) at no chip time. The topology is described
inside a fixture, never while a module is imported, so that every test
worker collects the same tests and only the worker running this file loads
the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.cim import macro
from repro.cim.fused_kernel import fused_planes_op
from repro.cim.lower import LoweredComputation
from repro.cim.trace import aval_of, trace
from repro.configs import get_config
from repro.models import build
from repro.models.layers import _mlp_quantized
from repro.train import make_decode_step

#: gemma-2b at published widths, weights in the activation dtype (as served)
CFG = get_config("gemma-2b")
#: packed lanes of one decode slot's MLP contraction: K_pad * N words / 32
SLOT_LANES = 2048 * 16384 // 32
#: what a decode MLP region of one slot may take beside the 5 GB of bf16
#: weights and the pinned weight planes on a 16 GB chip (about 1.5 GB since
#: the codec builds bits plane-major; it took 5.4 GB through an [N, n_bits]
#: bit matrix)
REGION_TEMP_LIMIT = 2 << 30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(x, sharding):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


@pytest.mark.parametrize("n_bits,ops", [
    (8, ("add", "sub", "lt")),     # the decode MLP's 8-bit operand width
    (27, ("add",)),                # the widest reduction plane stack
])
def test_fused_kernel_compiles_at_decode_width(one_chip, n_bits, ops):
    planes = jax.ShapeDtypeStruct((n_bits, SLOT_LANES), jnp.uint32,
                                  sharding=one_chip)
    compiled = jax.jit(fused_planes_op, static_argnames=("ops",)).lower(
        planes, planes, ops=ops).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _mlp_lowering(rows: int) -> LoweredComputation:
    """The lowered int8 MLP at published widths over `rows` token rows,
    through the pallas-tpu backend."""
    act = jnp.bfloat16
    p = {"w_in": jax.ShapeDtypeStruct((CFG.d_model, CFG.d_ff), act),
         "w_gate": jax.ShapeDtypeStruct((CFG.d_model, CFG.d_ff), act),
         "w_out": jax.ShapeDtypeStruct((CFG.d_ff, CFG.d_model), act)}
    x = jax.ShapeDtypeStruct((1, rows, CFG.d_model), act)
    return LoweredComputation(
        trace(lambda p, x: _mlp_quantized(p, x, CFG.gating, 8), p, x),
        backend="pallas-tpu")


def _compile_region(comp: LoweredComputation, region, sharding):
    """One region's program, as `macro.run_schedule_program` builds it."""
    body = comp._region_body(region)

    def program(*leaves):
        cur = macro.ScheduleCursor(region.schedule, "pallas-tpu", charges=[])
        out = body(cur, *leaves)
        cur.finish()
        return out

    leaves = [_sds(aval_of(a), sharding) for a in region.in_atoms]
    return jax.jit(program).lower(*leaves).compile()


def test_lowered_mlp_region_compiles_at_published_width(one_chip):
    """The first decode MLP region of one slot (quantized x @ w_in, 2048 x
    16384) through the pallas-tpu backend: one kernel per planned access,
    temporaries within REGION_TEMP_LIMIT."""
    comp = _mlp_lowering(1)
    region = comp.regions[0]
    compiled = _compile_region(comp, region, one_chip)
    assert compiled.as_text().count("tpu_custom_call") == region.accesses
    assert compiled.memory_analysis().temp_size_in_bytes < REGION_TEMP_LIMIT


def test_plain_decode_step_compiles_at_published_width(one_chip):
    """The plain server's jitted decode step, 4 slots: weights and caches
    fit the chip."""
    import dataclasses
    cfg = dataclasses.replace(CFG, param_dtype=CFG.dtype)
    model = build(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: model.init_caches(4, 80))
    step_in = {"tokens": jax.ShapeDtypeStruct((4, 1), jnp.int32),
               "positions": jax.ShapeDtypeStruct((4,), jnp.int32)}
    shard = lambda t: jax.tree.map(lambda a: _sds(a, one_chip), t)  # noqa: E731
    compiled = jax.jit(make_decode_step(model), donate_argnums=(1,)).lower(
        shard(params), shard(caches), shard(step_in)).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 16e9


def test_lowered_prefill_compiles_beside_pinned_planes(one_chip):
    """The MLP regions of the `--cim-lower` prefill that `chip_smoke.py`
    admits (a 4-token prompt; the lowered prefill runs unjitted, one region
    program at a time): each region's temporaries fit beside the 5.0 GB of
    weights and the 3.6 GB of weight planes two slots pin, on a 16 GB
    chip."""
    comp = _mlp_lowering(4)
    for region in comp.regions:
        compiled = _compile_region(comp, region, one_chip)
        assert compiled.as_text().count("tpu_custom_call") == region.accesses
        assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30
