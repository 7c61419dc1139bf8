"""Continuous-batching serve engine over the CiM-lowered model.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --preset reduced \
      --slots 2 --requests 4 --prompt-len 8 --gen 8 --cim-lower --cim-resident

The engine holds `slots` concurrent sequences in one batched KV cache.
Requests enter a queue with arrival times; each loop iteration admits at
most one due request (a batch-1 prefill, inserted into its slot between
decode steps — prefill and decode interleave, vLLM-style) and then runs ONE
full-batch decode step for every in-flight sequence. Retired sequences free
their slot and their paged KV blocks immediately, so the next queued
request starts without draining the batch.

Timing discipline: every prefill and decode step is bracketed by
`jax.block_until_ready` + perf_counter, so a step's latency is the real
device time, not dispatch time. Steady-state tok/s EXCLUDES prefill and
the first `--warmup-steps` decode steps (compile happens there); prefill
cost is reported separately per request (`prefill_ms`). Each request
records when its prefill began (`admit_s`) and when the engine had each
of its tokens on the host (`token_s`), on the engine's clock; p50/p99 are
over the gaps between a request's consecutive tokens, as its client sees
them (a prefill run between two decode steps lies inside the gap).

Each phase of the loop runs under a profiler span (`serve.admit`,
`serve.prefill` with the request id, `serve.insert`, `serve.decode` with
the step number and the `host_reads` so far, `serve.sample`, `serve.emit`,
`serve.wait`), on the clock of the profile's device plane. `host_reads`
counts the device-to-host reads that block the loop: one per token.

Charge semantics with --cim-lower: prefill and decode steps run UNJITTED
(the grouped-layer scan is unrolled, see ArchConfig.cim_unroll_groups) so
every step's lowered regions charge the ledger per call — `accesses` is
the compute bill, `load_accesses` the streamed-operand row-write bill. A
prefill's charges land on its request; per-request attribution splits
each decode step's ledger delta evenly across the slots active in that
step.

--cim-resident pins the int8 MLP weight planes in the arrays' resident
rows (repro.cim.lower resident mode): warm decode steps charge ZERO loads
for the weight side (prefills stream theirs). The --cim-lower bench mode
runs the SAME request schedule twice — streamed repack, then resident —
and asserts the resident phase's total accesses/token is strictly lower
at identical compute accesses/token; --assert-warm replays the resident
phase and asserts no program-cache misses and no new pins (everything
stayed warm).
"""
from __future__ import annotations

import argparse
import dataclasses
import json as json_lib
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.launch.compile_cache import setup_compile_cache
from repro.launch.paged_kv import PagedKV
from repro.launch.train import preset_config
from repro.models import build
from repro.train import (adra_sample, greedy_sample, make_decode_step,
                         make_prefill_step)


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ServeRequest:
    """One queued generation job and its measured lifecycle."""

    rid: int
    prompt_len: int
    gen: int                       # tokens to produce (incl. the prefill one)
    arrival_s: float = 0.0
    slot: int = -1
    tokens: List[int] = dataclasses.field(default_factory=list)
    prefill_ms: float = 0.0
    first_token_s: float = -1.0
    done_s: float = -1.0
    accesses: float = 0.0          # ledger attribution (see module docstring)
    load_accesses: float = 0.0
    admit_s: float = -1.0          # its prefill began
    token_s: List[float] = dataclasses.field(default_factory=list)
    shed: bool = False             # dropped by admission control, never ran
    repairs: int = 0               # retried decode steps attributed here

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.gen

    def report(self) -> Dict[str, Any]:
        return {
            "rid": self.rid,
            "arrival_s": round(self.arrival_s, 6),
            "first_token_s": round(self.first_token_s, 6),
            "done_s": round(self.done_s, 6),
            "prefill_ms": round(self.prefill_ms, 3),
            # from arrival to the start of its prefill (None: never ran)
            "queue_ms": round((self.admit_s - self.arrival_s) * 1e3, 3)
            if self.admit_s >= 0 else None,
            "tokens": len(self.tokens),
            # the generated ids themselves: what the chaos harness compares
            # bit-exactly against a fault-free run
            "token_ids": list(self.tokens),
            "shed": self.shed,
            "repairs": self.repairs,
            "accesses": round(self.accesses, 3),
            "load_accesses": round(self.load_accesses, 3),
            "total_accesses": round(self.accesses + self.load_accesses, 3),
        }


def _percentile(xs: List[float], q: float) -> float:
    if not xs:
        return 0.0
    ys = sorted(xs)
    i = min(len(ys) - 1, max(0, int(round(q / 100.0 * (len(ys) - 1)))))
    return ys[i]


def _ledger():
    from repro.cim import ledger
    return ledger()


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class ServeEngine:
    """Slot-based continuous batching over one batched cache pytree."""

    def __init__(self, model, params, slots: int, max_len: int,
                 sampler: str = "greedy", cim_lower: bool = False,
                 paged: Optional[PagedKV] = None, warmup_steps: int = 1,
                 seed: int = 0, spec=None, retry_budget: int = 2,
                 queue_limit: Optional[int] = None,
                 timeout_s: Optional[float] = None, scrub_every: int = 0):
        self.model, self.params, self.cfg = model, params, model.cfg
        self.slots, self.max_len = int(slots), int(max_len)
        self.sample = greedy_sample if sampler == "greedy" else adra_sample
        self.cim_lower = cim_lower
        self.paged = paged
        self.warmup_steps = int(warmup_steps)
        self.key = jax.random.PRNGKey(seed)
        # -- self-healing / admission knobs ---------------------------------
        self.spec = spec                      # CiM geometry this engine serves
        self.retry_budget = int(retry_budget)  # decode retries per request
        self.queue_limit = queue_limit        # waiting beyond this are shed
        self.timeout_s = timeout_s            # max unadmitted wait before shed
        self.scrub_every = int(scrub_every)   # decode steps between ECC scrubs
        self.repairs = 0                      # uncorrectable -> re-pin+retry
        self.failovers = 0                    # bank-kill remaps executed
        self.shed_count = 0
        self.host_reads = 0                   # blocking device->host reads
        self.scrub_report = {"scanned": 0, "dropped": 0,
                             "corrected": 0, "uncorrected": 0}
        pre, dec = make_prefill_step(model, max_len), make_decode_step(model)
        # unjitted with --cim-lower: lowered regions then execute (and
        # charge) per call, which is what residency accelerates, and every
        # host eqn runs as its own program, exactly as in a host-policy
        # twin (one program around the regions would fuse, and round, the
        # float ops next to them differently from the twin's program)
        self.prefill_fn = pre if cim_lower else jax.jit(pre)
        self.decode_fn = dec if cim_lower else \
            jax.jit(dec, donate_argnums=(1,))
        self._insert = jax.jit(self._insert_slot)

    # -- fault handling ------------------------------------------------------

    def _check_faults(self, step: int) -> None:
        """Advance the installed FaultModel to `step` and fail over when it
        has killed a bank this engine still serves from."""
        from repro.cim import faults as faults_mod

        fm = faults_mod.active()
        if fm is None:
            return
        fm.on_step(step)
        if self.spec is None or not self.cim_lower:
            return
        dead = [b for b in fm.dead_banks
                if b not in self.spec.disabled_banks
                and b < self.spec.banks]
        if dead:
            self._failover(dead)

    def _failover(self, dead_banks: List[int]) -> None:
        """Remap the serving process off `dead_banks`: degraded spec, paged
        KV migrated (all-or-nothing), stale weight pins dropped so they
        re-pin under the new geometry, and the process-wide spec override
        installed — every spec=None layer re-routes from the next call on.
        Regions whose degraded-geometry cost no longer beats the host are
        demoted by the offload policy when the fresh lowering re-plans."""
        from repro.cim import array as array_mod

        new_spec = self.spec
        for b in dead_banks:
            new_spec = new_spec.disable_bank(b)
        new_rs = array_mod.resident_set(new_spec)
        if self.paged is not None:
            self.paged.migrate(new_spec, new_rs)
        # stale pins (the banked set's, and the unbanked weight pins of the
        # healthy spec=None layers): they re-pin under new_spec
        for key in (self.spec, None):
            old_rs = array_mod._RESIDENT_SETS.get(key)
            if old_rs is not None and old_rs is not new_rs:
                old_rs.clear()
        array_mod.set_current_spec(new_spec)
        self.spec = new_spec
        self.failovers += 1

    def _scrub(self) -> None:
        """One ECC scrub pass over every registry set: the engine's banked
        set (KV blocks) and the set its lowered layers pin weights into."""
        from repro.cim import array as array_mod

        for rs in list(array_mod._RESIDENT_SETS.values()):
            if not rs.ecc:
                continue
            r = rs.scrub()
            for k in self.scrub_report:
                self.scrub_report[k] += r.get(k, 0)

    @staticmethod
    def _insert_slot(batched, single, slot):
        """Land a batch-1 cache pytree in slot `slot` of the batched one.
        The batch axis of each leaf is the first axis where the two shapes
        disagree (leading group axes make it leaf-dependent)."""
        def one(b, s):
            ax = 0
            for i, (db, ds) in enumerate(zip(b.shape, s.shape)):
                if db != ds:
                    ax = i
                    break
            return jax.lax.dynamic_update_slice_in_dim(
                b, s.astype(b.dtype), slot, axis=ax)
        return jax.tree.map(one, batched, single)

    # -- inputs --------------------------------------------------------------

    def _prompt_inputs(self, req: ServeRequest) -> Dict[str, jax.Array]:
        cfg = self.cfg
        k = jax.random.fold_in(self.key, req.rid)
        if cfg.embed_stub:
            return {"embeds": jax.random.normal(
                k, (1, req.prompt_len, cfg.d_model)) * 0.02}
        return {"tokens": jax.random.randint(
            k, (1, req.prompt_len), 0, cfg.vocab_size)}

    def _step_inputs(self, tok, positions, step: int) -> Dict[str, jax.Array]:
        cfg = self.cfg
        pos = jnp.asarray(positions, jnp.int32)
        if cfg.embed_stub:
            return {"embeds": jax.random.normal(
                jax.random.fold_in(self.key, 10_000 + step),
                (self.slots, 1, cfg.d_model)) * 0.02,
                "positions": pos}
        return {"tokens": tok[:, None], "positions": pos}

    # -- run -----------------------------------------------------------------

    def run(self, requests: List[ServeRequest]) -> Dict[str, Any]:
        led = _ledger()
        span = jax.profiler.TraceAnnotation
        pending = deque(sorted(requests, key=lambda r: (r.arrival_s, r.rid)))
        active: Dict[int, ServeRequest] = {}
        free = list(range(self.slots))
        caches = self.model.init_caches(self.slots, self.max_len)
        tok = jnp.zeros((self.slots,), jnp.int32)
        positions = [0] * self.slots
        decode_steps = 0
        steady_tokens = 0
        steady_time = 0.0
        t0 = time.perf_counter()

        def now() -> float:
            return time.perf_counter() - t0

        def _shed(req: ServeRequest) -> None:
            req.shed = True
            req.done_s = now()
            self.shed_count += 1

        while pending or active:
            self._check_faults(decode_steps)

            with span("serve.admit"):
                # admission control: shed the head when it has waited past
                # the per-request timeout, and the tail when more requests
                # are due than the bounded queue admits — a degraded array
                # sheds load instead of stretching every in-flight
                # request's latency
                if self.timeout_s is not None and not free:
                    # only a request actually stuck waiting can time out —
                    # a due head with a free slot is admitted this iteration
                    while pending and pending[0].arrival_s <= now() \
                            and now() - pending[0].arrival_s > self.timeout_s:
                        _shed(pending.popleft())
                if self.queue_limit is not None:
                    # the bounded queue holds what cannot go straight into
                    # a slot: shed the tail past `free slots + queue_limit`
                    while sum(1 for r in pending
                              if r.arrival_s <= now()) - len(free) \
                            > self.queue_limit:
                        _shed(pending.pop())

                # admit at most one due request per iteration: prefill
                # interleaves with decode instead of draining the batch
                req = None
                if pending and free and pending[0].arrival_s <= now():
                    head = pending[0]
                    if self.paged is not None and \
                            not self.paged.alloc(head.rid, head.prompt_len):
                        if not active:
                            raise RuntimeError(
                                f"request {head.rid}: prompt of "
                                f"{head.prompt_len} tokens cannot fit the KV "
                                f"block pool even with every slot idle")
                        # pool pressure: wait for a retirement to free blocks
                    else:
                        req = pending.popleft()
                        req.slot = free.pop(0)

            if req is not None:
                slot = req.slot
                with span("serve.prefill", rid=req.rid):
                    ta = time.perf_counter()
                    req.admit_s = ta - t0
                    l0 = (led.accesses, led.load_accesses)
                    c1, logits1 = self.prefill_fn(self.params,
                                                  self._prompt_inputs(req))
                    jax.block_until_ready(logits1)
                    req.prefill_ms = (time.perf_counter() - ta) * 1e3
                req.accesses += led.accesses - l0[0]
                req.load_accesses += led.load_accesses - l0[1]
                with span("serve.insert"):
                    caches = self._insert(caches, c1, slot)
                    first = self.sample(logits1)[0]
                    tok = tok.at[slot].set(first)
                    req.tokens.append(int(first))
                    self.host_reads += 1
                    req.first_token_s = now()
                    req.token_s.append(req.first_token_s)
                    positions[slot] = req.prompt_len
                    active[slot] = req
                    if req.done:                       # gen == 1
                        self._retire(req, free, active, now())
                continue                               # admit before decode

            if not active:
                if pending:
                    with span("serve.wait"):
                        time.sleep(max(0.0, pending[0].arrival_s - now()))
                continue

            # one full-batch decode step — retried within the per-request
            # budget when an ECC verify finds uncorrectable damage (the
            # failing entry is already invalidated, so the retry re-pins
            # from the host weights: detect -> repair -> redo)
            from repro.cim.faults import UncorrectableFaultError

            with span("serve.decode", step=decode_steps,
                      host_reads=self.host_reads):
                step_in = self._step_inputs(tok, positions, decode_steps)
                ts = time.perf_counter()
                l0 = (led.accesses, led.load_accesses)
                attempts = 0
                while True:
                    try:
                        caches, logits = self.decode_fn(self.params, caches,
                                                        step_in)
                        break
                    except UncorrectableFaultError:
                        attempts += 1
                        self.repairs += 1
                        for req in active.values():
                            req.repairs += 1
                        if attempts > self.retry_budget:
                            raise
                jax.block_until_ready((caches, logits))
                dt = time.perf_counter() - ts
            d_acc = led.accesses - l0[0]
            d_load = led.load_accesses - l0[1]
            with span("serve.sample"):
                tok = self.sample(logits)
            n_active = len(active)
            decode_steps += 1
            if decode_steps > self.warmup_steps:
                steady_tokens += n_active
                steady_time += dt
            with span("serve.emit"):
                for slot, req in list(active.items()):
                    req.tokens.append(int(tok[slot]))
                    self.host_reads += 1
                    req.token_s.append(now())
                    req.accesses += d_acc / n_active
                    req.load_accesses += d_load / n_active
                    positions[slot] += 1
                    if self.paged is not None:
                        self.paged.extend(req.rid)
                    if req.done:
                        self._retire(req, free, active, now())
            if self.scrub_every and decode_steps % self.scrub_every == 0:
                self._scrub()

        total_tokens = sum(len(r.tokens) for r in requests)
        # first token of each SERVED request comes from its prefill (shed
        # requests produced nothing, so an all-shed run reports 0, not -n)
        decode_tokens = sum(max(0, len(r.tokens) - 1) for r in requests)
        gaps_ms = [(b - a) * 1e3 for r in requests
                   for a, b in zip(r.token_s, r.token_s[1:])]
        report: Dict[str, Any] = {
            "slots": self.slots,
            "requests": len(requests),
            "total_tokens": total_tokens,
            "decode_tokens": decode_tokens,
            "decode_steps": decode_steps,
            "warmup_steps": self.warmup_steps,
            "wall_s": round(now(), 4),
            "tok_s_steady": round(steady_tokens / steady_time, 2)
            if steady_time > 0 else 0.0,
            "steady_tokens": steady_tokens,
            "p50_ms": round(_percentile(gaps_ms, 50), 3),
            "p99_ms": round(_percentile(gaps_ms, 99), 3),
            "prefill_ms_mean": round(
                sum(r.prefill_ms for r in requests) / max(1, len(requests)),
                3),
            "shed": self.shed_count,
            "host_reads": self.host_reads,
            "completed": sum(1 for r in requests
                             if not r.shed and r.done),
            "per_request": [r.report() for r in requests],
        }
        from repro.cim import faults as faults_mod

        fm = faults_mod.active()
        if fm is not None or self.repairs or self.failovers:
            fstats = fm.stats() if fm is not None else {}
            report["faults"] = {
                **fstats,
                "repairs": self.repairs,
                "failovers": self.failovers,
                "shed": self.shed_count,
                "scrub": dict(self.scrub_report),
            }
            from repro.cim.array import resident_stats
            rst = resident_stats()
            for k in ("ecc_verifies", "ecc_corrected", "ecc_uncorrected"):
                report["faults"][k] = rst.get(k, 0)
        if self.paged is not None:
            st = self.paged.stats()
            report["kv"] = {
                "n_blocks": st.n_blocks, "block_tokens": st.block_tokens,
                "peak_blocks": st.peak_blocks,
                "failed_allocs": st.failed_allocs,
                "utilization_peak": round(st.peak_blocks
                                          / max(1, st.n_blocks), 4),
            }
        if self.cim_lower:
            led = _ledger()
            per_tok = max(1, decode_tokens)
            report["ledger"] = {
                "accesses": led.accesses,
                "load_accesses": led.load_accesses,
                "total_accesses": led.total_accesses,
                "resident_reuses": led.resident_reuses,
            }
            report["accesses_per_token"] = round(led.accesses / per_tok, 4)
            report["load_accesses_per_token"] = round(
                led.load_accesses / per_tok, 4)
            report["total_accesses_per_token"] = round(
                led.total_accesses / per_tok, 4)
            # cost-model offload decisions cut while lowering this run
            # (deliberately NOT perf-gated keys: verdict counts change
            # whenever the policy or cost calibration does)
            from repro.cim import cost as _cost
            report["offload"] = dict(_cost.PLAN_STATS)
        return report

    def _retire(self, req: ServeRequest, free, active, t: float) -> None:
        req.done_s = t
        if req.slot in active:
            del active[req.slot]
        free.append(req.slot)
        free.sort()
        if self.paged is not None:
            self.paged.free(req.rid)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def make_requests(args) -> List[ServeRequest]:
    return [ServeRequest(rid=i, prompt_len=args.prompt_len, gen=args.gen,
                         arrival_s=i * args.arrival_interval)
            for i in range(args.requests)]


def reset_cim_state() -> None:
    from repro.cim import clear_schedule_cache
    from repro.cim import cost as _cost
    from repro.cim import faults as faults_mod
    from repro.cim.array import clear_resident, set_current_spec
    _ledger().reset()
    clear_resident()
    clear_schedule_cache()
    _cost.reset_plan_stats()
    set_current_spec(None)
    faults_mod.reset_fault_stats()


def build_engine(model, params, args) -> ServeEngine:
    """The engine `main` serves with, sized from the parsed arguments."""
    cfg = model.cfg
    spec = None
    rs = None
    if args.cim_lower:
        from repro.cim.array import DEFAULT_SPEC, resident_set
        spec = DEFAULT_SPEC
        rs = resident_set(spec)
    paged = PagedKV.for_model(cfg, spec=spec, slots=args.slots,
                              max_len=args.prompt_len + args.gen,
                              resident_set=rs)
    return ServeEngine(model, params, slots=args.slots,
                       max_len=args.prompt_len + args.gen,
                       sampler=args.sampler, cim_lower=args.cim_lower,
                       paged=paged, warmup_steps=args.warmup_steps,
                       spec=spec, scrub_every=getattr(args, "scrub_every", 0))


def _serve_once(model, params, args) -> Dict[str, Any]:
    return build_engine(model, params, args).run(make_requests(args))


def check_residency(repack: Dict[str, Any], resident: Dict[str, Any]) -> None:
    """The repack-vs-resident contract of a --cim-lower run: residency
    leaves the compute bill per token unchanged, strictly lowers the total
    bill, and reuses pinned operands. Raises AssertionError otherwise."""
    if resident["accesses_per_token"] != repack["accesses_per_token"]:
        raise AssertionError(
            f"compute accesses/token must not change with residency: "
            f"{resident['accesses_per_token']} != "
            f"{repack['accesses_per_token']}")
    if not resident["total_accesses_per_token"] \
            < repack["total_accesses_per_token"]:
        raise AssertionError(
            f"resident serving must charge strictly fewer total "
            f"accesses/token: {resident['total_accesses_per_token']} !< "
            f"{repack['total_accesses_per_token']}")
    if resident["ledger"]["resident_reuses"] <= 0:
        raise AssertionError("resident serving reused no pinned operand")


def _print_cim_report(tag: str) -> None:
    from repro.cim import cache_stats

    led = _ledger()
    proj = led.projected()
    hist = ", ".join(f"{k}:{v}" for k, v in sorted(led.per_op.items()))
    print(f"cim-lower ledger ({tag}): {led.accesses} compute accesses + "
          f"{led.load_accesses} streamed loads = {led.total_accesses} total, "
          f"{led.resident_reuses} resident reuses, "
          f"{led.words32:.0f} word32-ops")
    print(f"  per-op: {hist}")
    print(f"  projected: {proj['edp_decrease_pct']:.1f}% EDP decrease, "
          f"{proj['energy_saved_fj']:.0f} fJ saved vs near-memory "
          f"(current sensing @1024^2)")
    cs = cache_stats()
    print(f"  schedule cache: {cs['hits']} hits / {cs['misses']} misses, "
          f"{cs['dispatches']} jitted dispatches, {cs['host_eqns']} host "
          f"eqns, {cs['reduce_fold_steps']} folded / "
          f"{cs['reduce_shift_steps']} shifted reduction steps; resident: "
          f"{cs.get('resident_pins', 0)} pins / "
          f"{cs.get('resident_hits', 0)} hits / "
          f"{cs.get('resident_evictions', 0)} evictions, "
          f"{cs.get('resident_rows', 0)} rows held")
    from repro.cim import cost as _cost
    ps = _cost.PLAN_STATS
    print(f"  offload policy: {ps['plans']} plans cut, "
          f"{ps['eqns_lowered']} eqns lowered / {ps['eqns_demoted']} "
          f"demoted ({ps['demoted_accesses']} accesses kept on host), "
          f"{ps['fused_despite_loss']} losing eqns kept fused")


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--preset", default="reduced",
                    choices=("reduced", "100m", "full"))
    ap.add_argument("--slots", "--batch", type=int, default=4,
                    dest="slots", help="concurrent sequences in the batch")
    ap.add_argument("--requests", type=int, default=0,
                    help="queued requests (default: one per slot)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--arrival-interval", type=float, default=0.0,
                    help="seconds between request arrivals (0: all at once)")
    ap.add_argument("--warmup-steps", type=int, default=1,
                    help="decode steps excluded from steady-state metrics")
    ap.add_argument("--sampler", default="greedy", choices=("greedy", "adra"))
    ap.add_argument("--json", default="",
                    help="write the serve report to this JSON file")
    ap.add_argument("--cim-lower", action="store_true",
                    help="run decode MLPs through the jaxpr->CiM lowering "
                         "compiler (unjitted decode, per-call ledger) and "
                         "bench streamed-repack vs resident-weight phases")
    ap.add_argument("--cim-bits", type=int, default=8,
                    help="quantization width for --cim-lower (default 8)")
    ap.add_argument("--cim-resident", action="store_true",
                    help="pin int8 MLP weight planes in array rows "
                         "(with --cim-lower: also run the repack/resident "
                         "comparison)")
    ap.add_argument("--assert-warm", action="store_true",
                    help="replay the resident phase and fail unless every "
                         "program and pin stayed warm")
    ap.add_argument("--cim-faults", action="store_true",
                    help="with --cim-lower: run an extra chaos phase under "
                         "the REPRO_CIM_FAULT_SEED/BER env fault campaign "
                         "with ECC-protected resident operands, asserting "
                         "bit-identical tokens to the fault-free phase")
    ap.add_argument("--scrub-every", type=int, default=0,
                    help="decode steps between ECC scrub passes (0: off)")
    args = ap.parse_args(argv)
    if args.requests <= 0:
        args.requests = args.slots
    return args


def serve_config(args):
    """The served ArchConfig. Weights are held in the activation dtype:
    the per-layer compute cast then returns them unchanged, so they keep
    the identity residency keys on, and they take half the memory of
    float32 master weights at the bfloat16 presets."""
    cfg = preset_config(args.arch, args.preset)
    cfg = dataclasses.replace(cfg, param_dtype=cfg.dtype)
    if args.cim_lower:
        cfg = dataclasses.replace(cfg, cim_mlp_bits=args.cim_bits,
                                  cim_attention_bits=args.cim_bits,
                                  cim_unroll_groups=True)
    if args.cim_resident and not args.cim_lower:
        cfg = dataclasses.replace(cfg, cim_resident=True)
    return cfg


def main(argv: Optional[List[str]] = None):
    args = parse_args(argv)
    print(f"compile cache: {setup_compile_cache()}")
    cfg = serve_config(args)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.cim_lower:
        # lowered serving runs the layers unrolled: hold them unstacked
        params = model.unstack_groups(params)

    out: Dict[str, Any] = {
        "bench": "serve", "arch": args.arch, "preset": args.preset,
        "slots": args.slots, "requests": args.requests,
        "prompt_len": args.prompt_len, "gen": args.gen,
        "sampler": args.sampler,
        "cim": {"lower": bool(args.cim_lower), "bits": args.cim_bits,
                "resident": bool(args.cim_resident)},
    }

    if not args.cim_lower:
        rep = _serve_once(model, params, args)
        out.update(rep)
        print(f"served {rep['requests']} requests / "
              f"{rep['total_tokens']} tokens in {rep['wall_s']:.2f}s: "
              f"{rep['tok_s_steady']:.1f} tok/s steady, "
              f"p50 {rep['p50_ms']:.1f} ms, p99 {rep['p99_ms']:.1f} ms")
    else:
        # one model per phase, built ONCE: the resident model's memoized
        # param slices must keep their identity for the warm replay
        model_resident = build(dataclasses.replace(cfg, cim_resident=True))
        from repro.cim import default_backend_name
        print(f"cim backend: {default_backend_name()}")
        # phase 1: streamed repack — every decode step re-packs the weights
        reset_cim_state()
        repack = _serve_once(model, params, args)
        _print_cim_report("repack")
        # phase 2: resident — weight planes pinned at first touch
        reset_cim_state()
        resident = _serve_once(model_resident, params, args)
        _print_cim_report("resident")
        check_residency(repack, resident)

        if args.assert_warm:
            from repro.cim import cache_stats
            cs0 = cache_stats()
            warm = _serve_once(model_resident, params, args)
            cs1 = cache_stats()
            miss_delta = cs1["misses"] - cs0["misses"]
            pin_delta = cs1.get("resident_pins", 0) \
                - cs0.get("resident_pins", 0)
            assert miss_delta == 0, \
                f"warm replay compiled {miss_delta} new programs"
            assert pin_delta == 0, \
                f"warm replay re-pinned {pin_delta} resident operands"
            assert warm["tok_s_steady"] > 0
            out["warm_replay"] = {
                "tok_s_steady": warm["tok_s_steady"],
                "program_cache_miss_delta": miss_delta,
                "resident_pin_delta": pin_delta,
            }
            print(f"warm replay: {warm['tok_s_steady']:.1f} tok/s, "
                  f"0 new programs, 0 new pins")

        ratio = resident["tok_s_steady"] / max(1e-9, repack["tok_s_steady"])
        out["phases"] = {"repack": repack, "resident": resident}
        out["tok_s_resident_vs_repack_ratio"] = round(ratio, 4)
        # promote the resident phase's per-token bill to the top level:
        # the quantities check_regression gates as never-grow counters
        for k in ("accesses_per_token", "load_accesses_per_token",
                  "total_accesses_per_token", "tok_s_steady", "p50_ms",
                  "p99_ms"):
            out[k] = resident[k]
        print(f"resident vs repack: {resident['tok_s_steady']:.1f} vs "
              f"{repack['tok_s_steady']:.1f} tok/s (x{ratio:.2f}), "
              f"total accesses/token {resident['total_accesses_per_token']} "
              f"vs {repack['total_accesses_per_token']}")

        if args.cim_faults:
            # chaos phase: the resident run again, under the env-configured
            # fault campaign with ECC-protected pins. Stored under
            # phases.chaos (NOT promoted to the gated top-level keys: its
            # tok/s includes verify overhead by design).
            from repro.cim import array as array_mod
            from repro.cim import faults as faults_mod
            reset_cim_state()
            array_mod.set_resident_ecc(True)
            fcfg = faults_mod.FaultConfig.from_env(
                raise_on_uncorrectable=True)
            try:
                with faults_mod.faults(fcfg) as fm:
                    chaos = _serve_once(model_resident, params, args)
            finally:
                array_mod.set_resident_ecc(False)
                array_mod.set_current_spec(None)
            out["phases"]["chaos"] = chaos
            fr = chaos.get("faults", {})
            tokens_match = (
                [r["token_ids"] for r in chaos["per_request"]]
                == [r["token_ids"] for r in resident["per_request"]])
            assert tokens_match, \
                "chaos phase tokens diverged from the fault-free run"
            assert fr.get("uncorrected", 0) == 0, \
                f"chaos phase left {fr.get('uncorrected')} uncorrected bits"
            if fcfg.resident_ber > 0:
                assert fr.get("corrected", 0) > 0, \
                    "resident BER configured but ECC corrected nothing"
            print(f"chaos phase (seed {fcfg.seed}, resident BER "
                  f"{fcfg.resident_ber:g}): bit-identical tokens, "
                  f"{fr.get('injected', 0)} bits injected / "
                  f"{fr.get('corrected', 0)} corrected / 0 uncorrected, "
                  f"{chaos['tok_s_steady']:.1f} tok/s under verify")

    if args.json:
        with open(args.json, "w") as f:
            json_lib.dump(out, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
