"""Whole-schedule compiled execution: one jitted XLA dispatch per macro /
fused region, with ledger charges replayed from the plan.

The contract under test: compiling a schedule into a single XLA program
changes the COST of execution (dispatch count, walltime), never its
semantics or its accounting — results are bit-exact with the eager cursor,
and every field of the ledger (accesses, words32, per-op histogram,
per-bank slots, activated/inter-bank words) is identical to what the eager
per-access charging produced, unbanked and banked, cold cache and warm.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import cim
from repro.cim import PlanePack, dispatch, macro, planner
from repro.cim.accounting import LEDGER, Ledger, PlannedCharges

RNG = np.random.RandomState(5)

#: a small banked geometry: 70-word operands place 3 tiles over 2 banks
SPEC = cim.ArraySpec(banks=2, subarrays=1, rows=256, bitline_words=32)


def _ints(lo, hi, shape):
    return jnp.array(RNG.randint(lo, hi, shape), jnp.int32)


def _ledger_state():
    """Deep snapshot of every ledger counter (dicts copied)."""
    out = {}
    for f in dataclasses.fields(LEDGER):
        if f.name == "enabled":
            continue
        v = getattr(LEDGER, f.name)
        out[f.name] = dict(v) if isinstance(v, dict) else v
    return out


# ---------------------------------------------------------------------------
# dispatch counts: one program per schedule, warm calls hit
# ---------------------------------------------------------------------------


def test_macro_matmul_is_exactly_one_dispatch():
    A = _ints(-128, 128, (8, 16))
    B = _ints(-128, 128, (16, 4))
    C1 = cim.matmul(A, B, n_bits=8, backend="jnp-boolean")  # compile if cold
    mid = dispatch.cache_stats()
    C2 = cim.matmul(A, B, n_bits=8, backend="jnp-boolean")
    after = dispatch.cache_stats()
    assert after["dispatches"] - mid["dispatches"] == 1
    assert after["misses"] == mid["misses"]           # zero retrace warm
    assert after["hits"] >= mid["hits"] + 1
    want = np.array(A, np.int64) @ np.array(B, np.int64)
    np.testing.assert_array_equal(np.array(C1), want)
    np.testing.assert_array_equal(np.array(C2), want)


def test_warm_macro_ledger_and_results_identical_to_cold():
    x = _ints(-100, 100, 66)
    y = _ints(-100, 100, 66)
    pa, pb = PlanePack.pack(x, 8), PlanePack.pack(y, 8)
    LEDGER.reset()
    cold = macro.multiply(pa, pb, backend="jnp-boolean")
    cold_led = _ledger_state()
    LEDGER.reset()
    warm = macro.multiply(pa, pb, backend="jnp-boolean")
    assert _ledger_state() == cold_led
    np.testing.assert_array_equal(np.array(cold.unpack()),
                                  np.array(warm.unpack()))


def test_charges_replay_on_every_invocation():
    x = _ints(-100, 100, 48)
    pa = PlanePack.pack(x, 8)
    plan = planner.plan_popcount(8)
    LEDGER.reset()
    for _ in range(3):
        macro.popcount(pa, backend="jnp-boolean")
    assert LEDGER.accesses == 3 * plan.accesses


# ---------------------------------------------------------------------------
# ledger parity: compiled program vs eager cursor, full field set
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", [None, SPEC], ids=["unbanked", "banked"])
def test_multiply_ledger_matches_eager_cursor(spec):
    x = _ints(-100, 100, 70)
    y = _ints(-100, 100, 70)
    pa, pb = PlanePack.pack(x, 8), PlanePack.pack(y, 8)
    sched = planner.plan_multiply(8, 8)
    if spec is not None:
        sched = sched.placed(spec, pa.n_words)

    LEDGER.reset()
    cur = macro.ScheduleCursor(sched, "jnp-boolean", spec=spec)
    ref = macro._multiply_with(cur, pa, pb)
    cur.finish()
    eager = _ledger_state()

    LEDGER.reset()
    out = cim.multiply(pa, pb, backend="jnp-boolean", spec=spec)
    assert _ledger_state() == eager
    np.testing.assert_array_equal(np.array(out.unpack()),
                                  np.array(ref.unpack()))
    np.testing.assert_array_equal(np.array(out.unpack()),
                                  np.array(x) * np.array(y))


def test_banked_reduce_inter_bank_traffic_matches_eager_cursor():
    """The stride charges of a cross-tile reduction are recorded at trace
    time and replayed — including the fractional inter-bank words."""
    x = _ints(-50, 50, 70)
    pa = PlanePack.pack(x, 8)
    sched = planner.plan_reduce_sum(pa.n_words, stride=1,
                                    n_bits=8).placed(SPEC, pa.n_words)

    LEDGER.reset()
    cur = macro.ScheduleCursor(sched, "jnp-boolean", spec=SPEC)
    ref = macro._reduce_sum_body(cur, pa)
    cur.finish()
    eager = _ledger_state()
    assert eager["inter_bank_words32"] > 0      # strides cross tiles here

    LEDGER.reset()
    out = cim.reduce_sum(pa, backend="jnp-boolean", spec=SPEC)
    assert _ledger_state() == eager
    assert int(out.unpack()) == int(ref.unpack()) == int(np.array(x).sum())


@pytest.mark.parametrize("spec", [None, SPEC], ids=["unbanked", "banked"])
def test_every_macro_charges_exactly_its_plan(spec):
    x = _ints(-100, 100, 70)
    y = _ints(-100, 100, 70)
    pa, pb = PlanePack.pack(x, 8), PlanePack.pack(y, 8)
    cases = [
        (lambda: macro.abs_(pa, backend="jnp-boolean", spec=spec),
         planner.plan_abs(8)),
        (lambda: macro.relu(pa, backend="jnp-boolean", spec=spec),
         planner.plan_relu(8)),
        (lambda: macro.minimum(pa, pb, backend="jnp-boolean", spec=spec),
         planner.plan_minimum(8)),
        (lambda: macro.maximum(pa, pb, backend="jnp-boolean", spec=spec),
         planner.plan_maximum(8)),
        (lambda: macro.popcount(pa, backend="jnp-boolean", spec=spec),
         planner.plan_popcount(8)),
        (lambda: macro.multiply(pa, pb, backend="jnp-boolean", spec=spec),
         planner.plan_multiply(8, 8)),
        (lambda: macro.reduce_sum(pa, backend="jnp-boolean", spec=spec),
         planner.plan_reduce_sum(70, n_bits=8)),
    ]
    for fn, plan in cases:
        if spec is not None:
            plan = plan.placed(spec, 70)
        LEDGER.reset()
        fn()
        assert LEDGER.accesses == plan.placed_accesses, plan.macro


# ---------------------------------------------------------------------------
# lowered regions: one dispatch per region, cold/warm parity, sharing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", [None, SPEC], ids=["unbanked", "banked"])
def test_lowered_region_one_dispatch_cold_warm_parity(spec):
    def fn(a, b):
        return ((a + b) * b) - a

    a = _ints(-60, 60, 70).astype(jnp.int16)
    b = _ints(-60, 60, 70).astype(jnp.int16)
    lf = cim.lower(fn, backend="jnp-boolean", spec=spec)
    comp = lf.trace(a, b)
    assert len(comp.regions) == 1

    LEDGER.reset()
    out1 = lf(a, b)                              # cold: trace + compile
    cold_led = _ledger_state()
    mid = dispatch.cache_stats()
    LEDGER.reset()
    out2 = lf(a, b)                              # warm: cache hit
    after = dispatch.cache_stats()

    assert _ledger_state() == cold_led           # counters move identically
    assert after["dispatches"] - mid["dispatches"] == len(comp.regions) == 1
    assert after["misses"] == mid["misses"]
    np.testing.assert_array_equal(np.array(out1), np.array(fn(a, b)))
    np.testing.assert_array_equal(np.array(out1), np.array(out2))


def test_structurally_identical_regions_share_one_program():
    """Two separate lower() applications of the same function structure
    resolve to the SAME cached region program (structural key): the second
    one's execution is hit-only."""
    def make():
        return cim.lower(lambda a, b: (a + b) ^ a, backend="jnp-boolean")

    a = _ints(-40, 40, 34).astype(jnp.int16)
    b = _ints(-40, 40, 34).astype(jnp.int16)
    lf1 = make()
    want = np.array((a + b) ^ a)
    np.testing.assert_array_equal(np.array(lf1(a, b)), want)
    before = dispatch.cache_stats()
    lf2 = make()                                 # fresh trace, same structure
    np.testing.assert_array_equal(np.array(lf2(a, b)), want)
    after = dispatch.cache_stats()
    assert after["misses"] == before["misses"]
    assert after["hits"] > before["hits"]


def test_identical_regions_within_one_trace_compile_once():
    """Repeated identical regions in a SINGLE lowered function (the
    repeated-layer pattern) share one program too: the region schedule's
    macro name is not positional, so the structural key is the whole key."""
    def fn(a, b):
        t = (a + b) ^ a                          # region, structure S
        f = jnp.floor(t.astype(jnp.float32) / 2.0)   # host island
        q = f.astype(jnp.int16)
        return (q + b) ^ q                       # region, same structure S

    a = _ints(-40, 40, 38).astype(jnp.int16)
    b = _ints(-40, 40, 38).astype(jnp.int16)
    lf = cim.lower(fn, backend="jnp-boolean")
    comp = lf.trace(a, b)
    assert len(comp.regions) == 2
    assert comp.regions[0].key == comp.regions[1].key
    before = dispatch.cache_stats()
    out = lf(a, b)                               # compiles ONE program
    after = dispatch.cache_stats()
    assert after["misses"] - before["misses"] == 1
    assert after["dispatches"] - before["dispatches"] == 2
    np.testing.assert_array_equal(np.array(out), np.array(fn(a, b)))


def test_mesh_macro_compiles_through_shard_map():
    """The shard_map path stays inside the step program: one dispatch, same
    results, per-device ledger intact (single-device mesh smoke)."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("data",))
    x = _ints(-100, 100, 70)
    y = _ints(-100, 100, 70)
    pa, pb = PlanePack.pack(x, 8), PlanePack.pack(y, 8)
    LEDGER.reset()
    out = cim.multiply(pa, pb, backend="jnp-boolean", spec=SPEC, mesh=mesh)
    plan = planner.plan_multiply(8, 8).placed(SPEC, 70)
    assert LEDGER.accesses == plan.placed_accesses
    np.testing.assert_array_equal(np.array(out.unpack()),
                                  np.array(x) * np.array(y))


def test_donation_excludes_caller_and_alias_shared_buffers():
    """Region buffer donation may only name dead intermediates: never the
    caller's arrays, and never vars touching a pjit-inlining `_alias` (the
    alias outvar holds the SAME jax.Array as its source, so donating either
    side would delete a buffer the other may still need)."""
    @jax.jit
    def g(x):
        t = x + 1
        return t, t                          # duplicated output -> _alias

    def fn(x):
        a, b = g(x)
        return a * 2, b                      # region eats a; b lives on

    x = jnp.arange(-8, 8, dtype=jnp.int16)
    comp = cim.lower(fn, backend="jnp-boolean").trace(x)
    assert any(op.name == "_alias" for op in comp.trace.ops)
    add_region, mul_region = comp.regions
    # mul's input is the add result whose buffer the alias outvar shares:
    # dead after the region by liveness, yet it must NOT be donated
    assert mul_region.donatable == ()
    assert add_region.donatable == ()        # consumes caller's x directly
    np.testing.assert_array_equal(
        np.array(cim.lower(fn, backend="jnp-boolean")(x)[0]),
        np.array(fn(x)[0]))


def test_donation_marks_dead_host_intermediates():
    """Positive control: a host-produced intermediate consumed only by the
    region IS donatable (the accumulator-chain reuse case)."""
    def fn(x):
        h = jnp.sin(x.astype(jnp.float32))           # host island
        q = jnp.round(h * 7.0).astype(jnp.int16)     # dead after region
        return q * 2

    x = jnp.arange(-8, 8, dtype=jnp.int16)
    comp = cim.lower(fn, backend="jnp-boolean").trace(x)
    (region,) = comp.regions
    assert len(region.donatable) == 1


def test_donation_only_names_inputs_an_output_can_reuse():
    """A dead intermediate is donated only where the region has an output
    of its shape and dtype: XLA can reuse a donated buffer for nothing
    else, and warns of each one it cannot use."""
    def fn(x):
        h = jnp.sin(x.astype(jnp.float32))           # host island
        q = jnp.round(h * 7.0).astype(jnp.int16)     # dead after region
        return jnp.sum(q * 2)                        # scalar output

    x = jnp.arange(-8, 8, dtype=jnp.int16)
    comp = cim.lower(fn, backend="jnp-boolean").trace(x)
    (region,) = comp.regions
    assert region.donatable == ()
    np.testing.assert_array_equal(
        np.array(cim.lower(fn, backend="jnp-boolean")(x)), np.array(fn(x)))


def test_failed_invocation_charges_nothing():
    """A program whose execution raises must leave the ledger and the
    dispatch counter untouched — accounting follows execution, not intent."""
    pc = PlannedCharges((("access", ("add",), 8, 16),))

    def boom(*_):
        raise RuntimeError("device lost")

    prog = macro.CompiledSchedule(boom, pc)
    LEDGER.reset()
    before = dispatch.cache_stats()["dispatches"]
    with pytest.raises(RuntimeError):
        prog()
    assert LEDGER.accesses == 0
    assert dispatch.cache_stats()["dispatches"] == before


# ---------------------------------------------------------------------------
# PlannedCharges unit behavior
# ---------------------------------------------------------------------------


def test_planned_charges_replays_into_ledger():
    pc = PlannedCharges((
        ("access", ("add",), 8, 16),
        ("banked", ("sub",), 8, 64, SPEC.plan(64), 1),
        ("reduction", 2.5),
    ))
    led = Ledger()
    pc.replay(led)
    assert pc.accesses == 2
    assert led.accesses == 1 + SPEC.plan(64).n_tiles
    assert led.per_op == {"add": 1, "sub": 1}
    assert led.inter_bank_words32 == 2.5
    assert led.words32 == 16 * 8 / 32.0 + 64 * 8 / 32.0


def test_planned_charges_respects_disabled_ledger():
    led = Ledger(enabled=False)
    PlannedCharges((("access", ("add",), 8, 16),)).replay(led)
    assert led.accesses == 0


def test_compiled_program_rejects_unknown_charge_kind():
    with pytest.raises(ValueError):
        PlannedCharges((("bogus", 1),)).replay(Ledger())
