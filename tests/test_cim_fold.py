"""The matmul's halving-fold reduction against the row-buffer shift path.

On a traced unbanked cursor a matmul's tree reduction folds the
[M', K_pad, N] product in halves and computes only the words its result
keeps; banked and eager cursors shift the whole row buffer. The contract:
the same result, the same charge-from-plan record and the same ledger on
both paths — the ledger models the array's full-row access either way.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro import cim
from repro.cim import PlanePack, dispatch, macro, planner
from repro.cim.accounting import LEDGER
from repro.cim.opset import CimOpError

BK = "jnp-boolean"

#: a small banked geometry, as in test_cim_program
SPEC = cim.ArraySpec(banks=2, subarrays=1, rows=256, bitline_words=32)


def _ledger_state():
    out = {}
    for f in dataclasses.fields(LEDGER):
        if f.name == "enabled":
            continue
        v = getattr(LEDGER, f.name)
        out[f.name] = dict(v) if isinstance(v, dict) else v
    return out


def _operands(lead, m, k, n):
    rng = np.random.RandomState(m * 1000 + k * 10 + n)
    a = rng.randint(-8, 8, lead + (m, k))
    b = rng.randint(-8, 8, lead + (k, n))
    return a, b


def _call(batched, resident, a, b):
    """One matmul through its macro wrapper, a cold compile of one program."""
    aj, bj = jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32)
    m = a.shape[-2]
    if batched:
        if resident:
            bp = macro.batched_matmul_rhs_pack(bj, m=m, n_bits=4)
            return macro.batched_matmul(aj, n_bits=4, backend=BK, b_pack=bp)
        return macro.batched_matmul(aj, bj, n_bits=4, backend=BK)
    if resident:
        bp = macro.matmul_rhs_pack(bj, m=m, n_bits=4)
        return macro.matmul(aj, n_bits=4, backend=BK, b_pack=bp)
    return macro.matmul(aj, bj, n_bits=4, backend=BK)


def _run(monkeypatch, shift, batched, resident, a, b):
    """(result, PlannedCharges, ledger after one call, (fold, shift)) of
    the one program the call compiles; `shift` routes the reduction
    through the module's shift path."""
    with monkeypatch.context() as mp:
        if shift:
            mp.setattr(macro, "_matmul_reduce", macro._shift_reduce)
        dispatch.clear_schedule_cache()
        LEDGER.reset()
        out = np.asarray(_call(batched, resident, a, b))
        (prog,) = dispatch._PROGRAMS.values()
        return out, prog.charges, _ledger_state(), prog.reduce_steps


#: (batch dims, M): 2-D matmuls, then batched ones over M' = B * M rows;
#: M' = 40 is more rows than `_FOLD_SLICE_ROWS`, so its fold levels split
#: the rows by a reshape where the others slice them
SHAPES = [((), 1), ((), 2), ((), 8), ((2,), 1), ((2,), 2), ((2,), 8),
          ((5, 4), 2)]


@pytest.mark.parametrize("n", [64, 40], ids=["n64", "n40"])
@pytest.mark.parametrize("k", [16, 7], ids=["k16", "k7"])
@pytest.mark.parametrize("lead,m", SHAPES,
                         ids=[f"b{'x'.join(map(str, b)) or '0'}-m{m}"
                              for b, m in SHAPES])
@pytest.mark.parametrize("resident", [False, True],
                         ids=["streamed", "resident"])
def test_fold_matches_shift_path(monkeypatch, resident, lead, m, k, n):
    batched = bool(lead)
    a, b = _operands(lead, m, k, n)
    out, charges, led, steps = _run(monkeypatch, False, batched, resident,
                                    a, b)
    ref, ref_charges, ref_led, ref_steps = _run(monkeypatch, True, batched,
                                                resident, a, b)
    want = np.asarray(a, np.int64) @ np.asarray(b, np.int64)
    np.testing.assert_array_equal(out, want)
    np.testing.assert_array_equal(ref, want)
    assert charges == ref_charges
    for field in ("accesses", "words32", "activated_words32",
                  "load_words32", "resident_words32"):
        assert led[field] == ref_led[field], field
    assert led == ref_led
    levels = planner._log2_ceil(k)
    assert sum(steps) == levels and ref_steps == (0, levels)
    assert steps[0] > 0                # every case folds its top levels


def _counts():
    cs = dispatch.cache_stats()
    return cs["reduce_fold_steps"], cs["reduce_shift_steps"]


def _matmul(m, k, n, spec=None):
    rng = np.random.RandomState(k + n)
    a = jnp.asarray(rng.randint(-8, 8, (m, k)), jnp.int32)
    b = jnp.asarray(rng.randint(-8, 8, (k, n)), jnp.int32)
    out = macro.matmul(a, b, n_bits=4, backend=BK, spec=spec)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(a) @ np.asarray(b))


@pytest.mark.parametrize("m,k,n,spec,per_call", [
    (2, 16, 64, None, (4, 0)),      # N % 32 == 0: every level folds
    (2, 16, 40, None, (2, 2)),      # H*N = 80, 40 words: shifts below
    (1, 4, 70, SPEC, (0, 2)),       # banked: every level shifts
], ids=["aligned", "mixed", "banked"])
def test_reduce_step_counters(m, k, n, spec, per_call):
    dispatch.clear_schedule_cache()
    assert _counts() == (0, 0)
    _matmul(m, k, n, spec)                                  # cold
    assert _counts() == per_call
    hits = dispatch.cache_stats()["hits"]
    _matmul(m, k, n, spec)                                  # warm hit
    assert dispatch.cache_stats()["hits"] == hits + 1
    assert _counts() == tuple(2 * c for c in per_call)
    dispatch.clear_schedule_cache()
    assert _counts() == (0, 0)


def test_reduce_sum_keeps_the_shift_path():
    dispatch.clear_schedule_cache()
    x = jnp.arange(-20, 44, dtype=jnp.int32)
    out = macro.reduce_sum(PlanePack.pack(x, 8), backend=BK)
    assert int(out.unpack()) == int(np.asarray(x).sum())
    assert _counts() == (0, 6)


@pytest.mark.parametrize("charges,spec", [(None, None), ([], SPEC)],
                         ids=["eager", "banked"])
def test_charge_words_needs_a_traced_unbanked_cursor(charges, spec):
    sched = planner.plan_reduce_sum(64, stride=1, n_bits=8)
    cur = macro.ScheduleCursor(sched, BK, spec=spec, charges=charges)
    p = PlanePack.pack(jnp.zeros(64, jnp.int32), 8)
    with pytest.raises(CimOpError, match="charge_words"):
        cur.execute(p, p, ("add",), charge_words=128)
