"""Serve-engine tests: paged KV block table + continuous batching.

`PagedKV` is pure bookkeeping (block pool + ResidentSet reservations) and
is tested exhaustively; the `ServeEngine` tests run a real reduced model
through the queue and assert the request lifecycle invariants — completion,
monotone timestamps, per-request attribution, slot/block recycling — not
wall-clock numbers, which are machine-dependent and belong to the gated
serve bench.
"""
import jax
import pytest

from repro.cim import CimOpError
from repro.cim.array import ArraySpec, ResidentSet
from repro.configs import get_config
from repro.launch.paged_kv import PagedKV
from repro.launch.serve import ServeEngine, ServeRequest, _percentile
from repro.models import build

SPEC = ArraySpec(banks=2, subarrays=1, rows=64, bitline_words=32)


# ---------------------------------------------------------------------------
# paged KV block table
# ---------------------------------------------------------------------------


class TestPagedKV:
    def test_alloc_extend_free(self):
        kv = PagedKV(spec=SPEC, n_blocks=4, block_tokens=4)
        assert kv.alloc(0, 6)                    # 6 tokens -> 2 blocks
        assert kv.blocks_in_use == 2
        assert kv.extend(0, 2)                   # fills block 2, no claim
        assert kv.blocks_in_use == 2
        assert kv.extend(0, 1)                   # 9th token -> 3rd block
        assert kv.blocks_in_use == 3
        kv.free(0)
        assert kv.blocks_in_use == 0
        assert kv.stats().peak_blocks == 3

    def test_alloc_is_all_or_nothing(self):
        kv = PagedKV(spec=SPEC, n_blocks=2, block_tokens=4)
        assert not kv.alloc(0, 12)               # needs 3 of 2 blocks
        assert kv.blocks_in_use == 0             # partial claim rolled back
        assert kv.stats().failed_allocs == 1
        assert kv.alloc(0, 8)                    # pool still usable

    def test_double_alloc_rejected(self):
        kv = PagedKV(spec=SPEC, n_blocks=4, block_tokens=4)
        kv.alloc(0, 4)
        with pytest.raises(ValueError):
            kv.alloc(0, 4)
        with pytest.raises(ValueError):
            kv.extend(99)

    def test_bank_alignment(self):
        kv = PagedKV(spec=SPEC, n_blocks=8, block_tokens=4)
        assert [kv.bank_of_block(b) for b in range(4)] == [0, 1, 0, 1]

    def test_reservations_drive_resident_rows(self):
        rs = ResidentSet(SPEC)
        kv = PagedKV(spec=SPEC, n_blocks=4, block_tokens=4, kv_bits=16,
                     resident_set=rs)
        assert kv.alloc(0, 8)                    # blocks 0,1 -> banks 0,1
        assert rs.rows_per_bank() == {0: 16, 1: 16}
        kv.free(0)
        assert rs.resident_rows == 0             # reservations released

    def test_failed_reservation_rolls_back_block(self):
        # 3 rows of reserve budget: the 16-row KV reservation cannot fit
        rs = ResidentSet(SPEC, reserve_rows=61)
        kv = PagedKV(spec=SPEC, n_blocks=4, block_tokens=4, kv_bits=16,
                     resident_set=rs)
        assert not kv.alloc(0, 4)
        assert kv.blocks_in_use == 0 and len(rs) == 0
        assert kv.stats().failed_allocs == 1

    def test_reservations_are_not_evictable_by_pins(self):
        from repro.cim import PlanePack
        import jax.numpy as jnp
        rs = ResidentSet(SPEC)
        kv = PagedKV(spec=SPEC, n_blocks=8, block_tokens=4, kv_bits=16,
                     resident_set=rs)
        assert kv.alloc(0, 32)                   # 8 blocks: 64 rows/bank
        with pytest.raises(CimOpError, match="reservation"):
            rs.pin("w", PlanePack.pack(jnp.arange(8), 8, signed=False))
        assert kv.blocks_in_use == 8             # KV untouched

    def test_for_model_sizing(self):
        cfg = get_config("llama3.2-1b").reduced()
        kv = PagedKV.for_model(cfg, spec=SPEC, slots=3, max_len=16)
        words_per_token = 2 * cfg.kv_dim * cfg.n_layers
        expect_bt = max(1, SPEC.tile_words // words_per_token)
        assert kv.block_tokens == expect_bt
        assert kv.n_blocks == 3 * (-(-16 // expect_bt))
        # the pool holds exactly slots * max_len tokens
        assert kv.n_blocks * kv.block_tokens >= 3 * 16


def test_percentile():
    assert _percentile([], 50) == 0.0
    assert _percentile([7.0], 99) == 7.0
    xs = [float(i) for i in range(101)]      # 0..100: index == percentile
    assert _percentile(xs, 50) == 50.0
    assert _percentile(xs, 99) == 99.0
    assert _percentile(xs, 0) == 0.0
    assert _percentile(list(reversed(xs)), 100) == 100.0


# ---------------------------------------------------------------------------
# the engine, end to end on a real reduced model
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("llama3.2-1b").reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _run(model, params, *, slots, reqs, prompt_len=4, gen=3, paged=None,
         warmup_steps=0):
    engine = ServeEngine(model, params, slots=slots,
                         max_len=prompt_len + gen, paged=paged,
                         warmup_steps=warmup_steps)
    requests = [ServeRequest(rid=i, prompt_len=prompt_len, gen=gen)
                for i in range(reqs)]
    return engine.run(requests), requests


def test_engine_completes_all_requests(small_model):
    model, params = small_model
    rep, requests = _run(model, params, slots=2, reqs=3, gen=3)
    assert rep["requests"] == 3 and rep["total_tokens"] == 9
    assert rep["decode_tokens"] == 6          # first token of each: prefill
    for r in requests:
        assert r.done and len(r.tokens) == r.gen
        assert r.first_token_s >= r.arrival_s
        assert r.done_s >= r.first_token_s
        assert r.prefill_ms > 0.0
        assert len(r.token_s) == r.gen
    # 3 requests through 2 slots: the third waited for a retirement
    assert {r.slot for r in requests} == {0, 1}


def test_engine_recycles_slots_and_blocks(small_model):
    model, params = small_model
    cfg = model.cfg
    paged = PagedKV.for_model(cfg, slots=2, max_len=7)
    rep, _ = _run(model, params, slots=2, reqs=4, paged=paged)
    assert rep["kv"]["failed_allocs"] == 0
    assert paged.blocks_in_use == 0           # every retirement freed blocks
    assert rep["kv"]["peak_blocks"] <= paged.n_blocks
    assert rep["requests"] == 4


def test_engine_report_shape(small_model):
    model, params = small_model
    rep, _ = _run(model, params, slots=2, reqs=2)
    for key in ("tok_s_steady", "p50_ms", "p99_ms", "prefill_ms_mean",
                "decode_steps", "wall_s", "per_request"):
        assert key in rep
    assert len(rep["per_request"]) == 2
    for pr in rep["per_request"]:
        assert pr["tokens"] == 3
    assert rep["p99_ms"] >= rep["p50_ms"] >= 0.0


def test_engine_single_token_requests(small_model):
    # gen == 1: the prefill token completes the request, no decode steps
    model, params = small_model
    rep, requests = _run(model, params, slots=2, reqs=2, gen=1)
    assert all(r.done and len(r.tokens) == 1 for r in requests)
    assert rep["decode_tokens"] == 0 and rep["decode_steps"] == 0


def _cim_test_model(name="serve-chaos-test", resident=True):
    from repro.configs.base import ArchConfig

    cfg = ArchConfig(name=name, family="dense", n_layers=1,
                     d_model=16, n_heads=4, n_kv_heads=2, head_dim=8,
                     d_ff=32, vocab_size=64, dtype="float32",
                     tensor_parallel=False, cim_mlp_bits=8,
                     cim_attention_bits=8, cim_unroll_groups=True,
                     cim_resident=resident)
    model = build(cfg)
    return model, model.init(jax.random.PRNGKey(1))


def test_unstacked_group_params_serve_identically():
    """`Model.unstack_groups` params: the same tokens as the stacked
    params they came from, resident pins reused, and no memoized slices
    (the second copy of the weights that unstacking avoids)."""
    from repro.configs.base import ArchConfig

    cfg = ArchConfig(name="serve-unstack-test", family="dense", n_layers=2,
                     d_model=16, n_heads=4, n_kv_heads=2, head_dim=8,
                     d_ff=32, vocab_size=64, dtype="float32",
                     tensor_parallel=False, cim_mlp_bits=8,
                     cim_unroll_groups=True, cim_resident=True)
    stacked = build(cfg).init(jax.random.PRNGKey(1))
    reps = []
    for unstack in (False, True):
        _fresh_cim()
        model = build(cfg)
        params = model.unstack_groups(stacked) if unstack else stacked
        rep, _, _ = _serve_cim(model, params, reqs=3, gen=3)
        reps.append(rep)
    assert "groups" not in params and len(params["group_layers"]) == 2
    assert not model._group_slices
    assert [r["token_ids"] for r in reps[0]["per_request"]] == \
        [r["token_ids"] for r in reps[1]["per_request"]]
    assert reps[1]["ledger"]["resident_reuses"] > 0


def _fresh_cim():
    from repro.cim import clear_schedule_cache
    from repro.cim import cost as cost_mod
    from repro.cim import faults, ledger
    from repro.cim.array import clear_resident, set_current_spec
    ledger().reset()
    clear_resident()
    clear_schedule_cache()
    cost_mod.reset_plan_stats()
    set_current_spec(None)
    faults.uninstall()
    faults.reset_fault_stats()


def _serve_cim(model, params, *, reqs=2, gen=4, spec=None, **kw):
    from repro.cim.array import DEFAULT_SPEC, resident_set
    spec = spec or DEFAULT_SPEC
    rs = resident_set(spec)
    paged = PagedKV.for_model(model.cfg, spec=spec, slots=2,
                              max_len=4 + gen, resident_set=rs)
    engine = ServeEngine(model, params, slots=2, max_len=4 + gen,
                         cim_lower=True, paged=paged, warmup_steps=0,
                         spec=spec, **kw)
    requests = [ServeRequest(rid=i, prompt_len=4, gen=gen)
                for i in range(reqs)]
    return engine.run(requests), requests, engine


def test_engine_report_surfaces_offload_plan_stats():
    """With cim_lower the report carries the cost model's offload decision
    counters (repro.cim.cost.PLAN_STATS): plans were cut for the lowered
    decode, every eligible eqn of the unbanked paths wins under the
    default edp policy, and the counters mirror the module state."""
    from repro.cim import cost as cost_mod
    from repro.configs.base import ArchConfig

    cfg = ArchConfig(name="serve-offload-test", family="dense", n_layers=1,
                     d_model=16, n_heads=4, n_kv_heads=2, head_dim=8,
                     d_ff=32, vocab_size=64, dtype="float32",
                     tensor_parallel=False, cim_mlp_bits=8,
                     cim_attention_bits=8, cim_unroll_groups=True)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(1))
    cost_mod.reset_plan_stats()
    engine = ServeEngine(model, params, slots=1, max_len=4, cim_lower=True,
                         warmup_steps=0)
    rep = engine.run([ServeRequest(rid=0, prompt_len=2, gen=2)])
    off = rep["offload"]
    assert off == cost_mod.PLAN_STATS
    assert off["plans"] > 0
    assert off["eqns_lowered"] > 0
    # unbanked placements always win the edp comparison: nothing demoted
    assert off["eqns_demoted"] == 0 and off["demoted_accesses"] == 0


# ---------------------------------------------------------------------------
# chaos: the self-healing serve loop under injected faults
# ---------------------------------------------------------------------------


class TestChaos:
    def test_bit_exact_under_single_bit_resident_faults(self):
        """Single-bit faults on ECC-protected resident planes: the served
        tokens are bit-identical to the fault-free run, every error
        corrected, zero uncorrected (the tentpole acceptance). BER is set
        high enough that this tiny model's resident footprint sees flips."""
        from repro.cim import faults
        from repro.cim.array import set_resident_ecc

        model, params = _cim_test_model()
        _fresh_cim()
        clean, _, _ = _serve_cim(model, params)
        clean_ids = [r["token_ids"] for r in clean["per_request"]]

        _fresh_cim()
        set_resident_ecc(True)
        try:
            with faults.faults(faults.FaultConfig(
                    seed=11, resident_ber=1e-3,
                    raise_on_uncorrectable=True)) as fm:
                chaos, _, _ = _serve_cim(model, params)
        finally:
            set_resident_ecc(False)
            _fresh_cim()
        assert [r["token_ids"] for r in chaos["per_request"]] == clean_ids
        assert fm.injected > 0 and fm.corrected == fm.injected
        assert fm.uncorrected == 0
        assert chaos["faults"]["corrected"] > 0
        assert chaos["faults"]["uncorrected"] == 0
        assert chaos["faults"]["ecc_uncorrected"] == 0

    def test_uncorrectable_triggers_repair_and_retry(self):
        """A forced double-bit error raises mid-decode; the engine counts
        a repair, re-pins from the host weights, retries the step, and the
        output is STILL bit-identical to the fault-free run."""
        from repro.cim import faults
        from repro.cim.array import set_resident_ecc

        model, params = _cim_test_model()
        _fresh_cim()
        clean, _, _ = _serve_cim(model, params)
        clean_ids = [r["token_ids"] for r in clean["per_request"]]

        _fresh_cim()
        set_resident_ecc(True)
        try:
            with faults.faults(faults.FaultConfig(
                    seed=0, uncorrectable_at_verify=(2,),
                    raise_on_uncorrectable=True)) as fm:
                chaos, _, engine = _serve_cim(model, params)
        finally:
            set_resident_ecc(False)
            _fresh_cim()
        assert engine.repairs >= 1
        assert chaos["faults"]["repairs"] >= 1
        assert fm.uncorrected >= 1              # detected, then repaired
        assert [r["token_ids"] for r in chaos["per_request"]] == clean_ids

    def test_retry_budget_exhaustion_raises(self):
        from repro.cim import faults
        from repro.cim.array import set_resident_ecc

        model, params = _cim_test_model()
        _fresh_cim()
        set_resident_ecc(True)
        try:
            # every verify uncorrectable: the budget cannot save the step
            with faults.faults(faults.FaultConfig(
                    seed=0, uncorrectable_at_verify=tuple(range(200)),
                    raise_on_uncorrectable=True)):
                with pytest.raises(Exception):
                    _serve_cim(model, params, retry_budget=1)
        finally:
            set_resident_ecc(False)
            _fresh_cim()

    def test_mid_run_bank_kill_completes_all_requests(self):
        """One bank killed mid-run: the engine fails over (degraded spec,
        paged KV migrated, weights re-pinned), every admitted request
        completes, and the report shows the failover + zero uncorrected."""
        from repro.cim import faults
        from repro.cim.array import DEFAULT_SPEC, spec_override

        model, params = _cim_test_model()
        _fresh_cim()
        try:
            with faults.faults(faults.FaultConfig(
                    seed=5, kill_bank_at=(2, 1))) as fm:
                rep, requests, engine = _serve_cim(model, params, gen=6)
        finally:
            _fresh_cim()
        assert fm.bank_kills == 1
        assert engine.failovers == 1
        assert engine.spec.disabled_banks == (1,)
        assert engine.spec != DEFAULT_SPEC
        assert spec_override() is None          # _fresh_cim restored it
        for r in requests:
            assert r.done and len(r.tokens) == r.gen
        assert rep["completed"] == len(requests)
        assert rep["shed"] == 0
        assert rep["faults"]["failovers"] == 1
        assert rep["faults"]["uncorrected"] == 0
        assert rep["faults"]["ecc_uncorrected"] == 0
        # KV reservations all live on surviving banks
        assert 1 not in engine.paged.rs.rows_per_bank()

    def test_bank_kill_tokens_match_healthy_run(self):
        """Failover is value-transparent: the degraded-geometry run emits
        the same tokens (remap is bit-exact; host demotion is bit-exact)."""
        from repro.cim import faults

        model, params = _cim_test_model()
        _fresh_cim()
        clean, _, _ = _serve_cim(model, params, gen=6)
        clean_ids = [r["token_ids"] for r in clean["per_request"]]
        _fresh_cim()
        try:
            with faults.faults(faults.FaultConfig(
                    seed=5, kill_bank_at=(2, 0))):
                chaos, _, _ = _serve_cim(model, params, gen=6)
        finally:
            _fresh_cim()
        assert [r["token_ids"] for r in chaos["per_request"]] == clean_ids


class TestAdmissionControl:
    def test_timeout_sheds_stale_requests(self, small_model):
        model, params = small_model
        engine = ServeEngine(model, params, slots=1, max_len=7,
                             warmup_steps=0, timeout_s=0.0)
        # the second request is due immediately but can never be admitted
        # within a 0-second wait while the first owns the only slot
        reqs = [ServeRequest(rid=0, prompt_len=4, gen=3),
                ServeRequest(rid=1, prompt_len=4, gen=3)]
        rep = engine.run(reqs)
        assert rep["shed"] == 1 and engine.shed_count == 1
        assert reqs[1].shed and not reqs[1].tokens
        assert reqs[0].done
        assert rep["completed"] == 1
        shed_reports = [r for r in rep["per_request"] if r["shed"]]
        assert len(shed_reports) == 1 and shed_reports[0]["rid"] == 1

    def test_queue_limit_sheds_excess_from_tail(self, small_model):
        model, params = small_model
        engine = ServeEngine(model, params, slots=1, max_len=7,
                             warmup_steps=0, queue_limit=1)
        reqs = [ServeRequest(rid=i, prompt_len=4, gen=3) for i in range(4)]
        rep = engine.run(reqs)
        # 1 admitted immediately + 1 queued; the rest shed from the tail
        assert rep["shed"] == 2
        assert sum(1 for r in reqs if r.done) == 2
        assert reqs[3].shed                     # tail shed first

    def test_all_shed_report_is_safe(self, small_model):
        """Every request shed: the report builds without crashing, with
        empty-sample percentiles at 0.0 (the _percentile guard end-to-end)
        and decode_tokens pinned at 0, not negative. slots=0 models a
        fully-failed engine draining its queue: nothing can ever be
        admitted, so the 0-second timeout sheds every due request."""
        model, params = small_model
        engine = ServeEngine(model, params, slots=0, max_len=7,
                             warmup_steps=0, queue_limit=0, timeout_s=0.0)
        reqs = [ServeRequest(rid=i, prompt_len=4, gen=3) for i in range(3)]
        rep = engine.run(reqs)
        assert rep["shed"] == 3 and rep["completed"] == 0
        assert rep["total_tokens"] == 0 and rep["decode_tokens"] == 0
        assert rep["p50_ms"] == 0.0 and rep["p99_ms"] == 0.0
        assert rep["tok_s_steady"] == 0.0
        assert all(r["shed"] for r in rep["per_request"])
        assert all(r["queue_ms"] is None for r in rep["per_request"])


# ---------------------------------------------------------------------------
# what the engine shows a profile: token timestamps, reads, phase spans
# ---------------------------------------------------------------------------


def test_engine_stamps_every_token_and_counts_host_reads(small_model):
    model, params = small_model
    engine = ServeEngine(model, params, slots=2, max_len=7, warmup_steps=0)
    requests = [ServeRequest(rid=i, prompt_len=4, gen=3) for i in range(3)]
    rep = engine.run(requests)
    for r in requests:
        assert len(r.token_s) == r.gen
        assert r.token_s == sorted(r.token_s)
        assert r.admit_s >= r.arrival_s
        assert r.token_s[0] == r.first_token_s
        assert r.token_s[-1] <= r.done_s
    assert [p["queue_ms"] for p in rep["per_request"]] \
        == [round((r.admit_s - r.arrival_s) * 1e3, 3) for r in requests]
    # the third request waited for a slot
    assert rep["per_request"][2]["queue_ms"] > 0
    # one blocking read a token: the prefill's first and a slot's per step
    assert engine.host_reads == rep["host_reads"] == rep["total_tokens"] == 9
    gaps = [(b - a) * 1e3 for r in requests
            for a, b in zip(r.token_s, r.token_s[1:])]
    assert rep["p50_ms"] == round(_percentile(gaps, 50), 3)


def test_engine_enters_a_span_per_phase(small_model, monkeypatch):
    seen = []

    class Recording:
        def __init__(self, name, **kwargs):
            seen.append((name, kwargs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
    model, params = small_model
    engine = ServeEngine(model, params, slots=1, max_len=7, warmup_steps=0)
    # the first arrival is not due when the loop starts: it waits first
    requests = [ServeRequest(rid=5, prompt_len=4, gen=3, arrival_s=0.2),
                ServeRequest(rid=6, prompt_len=4, gen=2, arrival_s=0.2)]
    engine.run(requests)
    # the model's own spans mark the jitted steps' tracing alone
    names = [n for n, _ in seen if n.startswith("serve.")]
    assert set(names) == {"serve.admit", "serve.wait", "serve.prefill",
                          "serve.insert", "serve.decode", "serve.sample",
                          "serve.emit"}
    assert names[:2] == ["serve.admit", "serve.wait"]
    assert [kw["rid"] for n, kw in seen if n == "serve.prefill"] == [5, 6]
    decodes = [kw for n, kw in seen if n == "serve.decode"]
    assert [kw["step"] for kw in decodes] == [0, 1, 2]
    # the counter as each step starts: 1 first token, then 1 a step
    assert [kw["host_reads"] for kw in decodes] == [1, 2, 4]
