"""One run of one cell: set-up, the measured window, the check, the metrics.

The system under test is the serve path as `repro.launch.serve.main` builds
it: `ArchConfig` from the registry, `Model`, `serve.build_engine`,
`ServeEngine.run`. The benchmark makes the weights and the prompts from the
seed, wraps the engine's prefill, decode and sampling calls from outside to
time them and to end the window, and checks what they produced against the
float32 reference of the configuration's model family (`bench/reference/`,
chosen by `model_io.family`), which also makes the weights and counts the
FLOPs.

The window of a closed-loop cell starts at the first step boundary after
`warm_steps` decode steps; that of an open-loop cell when `ServeEngine.run`
starts its clock, after a warm-up run that compiled every shape. Either
closes at the first step boundary at or after `seconds`.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import model_io, record
from bench.record import RequestLog, Run, Step

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"


class StopWindow(Exception):
    """Raised at the first step boundary at or after the window's end."""


class CompileCounter:
    """XLA backend compiles seen, from JAX's own monitoring event."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def _ledger_words32() -> float:
    from repro.cim import ledger
    return ledger().words32


def _ledger_counts() -> Dict[str, float]:
    from repro.cim import ledger
    led = ledger()
    return {"accesses": led.accesses, "load_accesses": led.load_accesses,
            "resident_reuses": led.resident_reuses, "words32": led.words32}


class Recorder:
    """Wraps one engine's steps for one `ServeEngine.run` call."""

    def __init__(self, engine, logs: List[RequestLog], reqs, *, cim: bool,
                 seconds: float, warm_steps: Optional[int],
                 trace_plan: Optional[dict], trace_dir: Optional[Path],
                 compiles: CompileCounter):
        self.logs = logs                      # in the engine's admission order
        self.reqs = {r.rid: r for r in reqs}  # the engine's ServeRequests
        self.cim, self.seconds = cim, seconds
        self.warm_steps = warm_steps          # None: open loop
        self.trace_plan, self.trace_dir = trace_plan, trace_dir
        self.compiles = compiles
        self.steps: List[Step] = []
        self.n_decode = 0
        self.next_admit = 0
        self.live: List[RequestLog] = []
        self.t0_engine: Optional[float] = None
        self.window_t0: Optional[float] = None
        self.window_t1: Optional[float] = None
        self.last_step = -1
        self.trace_on = False
        self.trace_t0 = None
        self.traced = False
        self.at_window = {}
        prefill, decode, sample = engine.prefill_fn, engine.decode_fn, engine.sample
        model = engine.model
        init_caches = model.init_caches

        def init_caches_fn(*a, **k):
            caches = init_caches(*a, **k)
            self.t0_engine = time.perf_counter()
            if self.warm_steps is None:
                self._open_window(self.t0_engine)
            return caches

        def prefill_fn(params, inputs):
            t = self.boundary()
            log = self._admit(t)
            with jax.profiler.TraceAnnotation("bench.prefill"):
                out = jax.block_until_ready(prefill(params, inputs))
            self.steps.append(Step("prefill", -1, t, time.perf_counter(),
                                   self.trace_on))
            self.last_step = -1
            return out

        def decode_fn(params, caches, step_in):
            t = self.boundary()
            w0 = _ledger_words32() if self.cim else 0.0
            with jax.profiler.TraceAnnotation("bench.decode"):
                out = jax.block_until_ready(decode(params, caches, step_in))
            t1 = time.perf_counter()
            w1 = _ledger_words32() if self.cim else 0.0
            self.steps.append(Step("decode", self.n_decode, t, t1,
                                   self.trace_on, w1 - w0))
            self.last_step = self.n_decode
            self.n_decode += 1
            return out

        def sample_fn(logits):
            with jax.profiler.TraceAnnotation("bench.sample"):
                return sample(logits)

        model.init_caches = init_caches_fn
        engine.prefill_fn, engine.decode_fn = prefill_fn, decode_fn
        engine.sample = sample_fn

    # -- window ----------------------------------------------------------------

    def _open_window(self, t: float) -> None:
        self.window_t0 = t
        self.at_window = {"compiles": self.compiles.count,
                          **(_ledger_counts() if self.cim else {})}

    def boundary(self) -> float:
        """A step boundary: stamp the tokens of the last step, open or
        close the window, start or stop the trace."""
        t = time.perf_counter()
        self.flush(t)
        if self.window_t0 is None and self.warm_steps is not None \
                and self.n_decode >= self.warm_steps:
            self._open_window(t)
        if self.window_t0 is not None and t - self.window_t0 >= self.seconds:
            self.close(t)
            raise StopWindow
        if self.trace_plan and self.window_t0 is not None and not self.traced:
            since = t - self.window_t0
            if not self.trace_on and since >= self.trace_plan["start_s"]:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(str(self.trace_dir),
                                         profiler_options=opts)
                self.trace_on, self.trace_t0 = True, time.perf_counter()
            elif self.trace_on and t - self.trace_t0 >= self.trace_plan["seconds"]:
                self.stop_trace()
            t = time.perf_counter()
        return t

    def stop_trace(self) -> None:
        if self.trace_on:
            jax.profiler.stop_trace()
            self.trace_on, self.traced = False, True

    def close(self, t: float) -> None:
        if self.window_t1 is None:
            self.flush(t)
            self.window_t1 = t
            self.at_close = {"compiles": self.compiles.count,
                             **(_ledger_counts() if self.cim else {})}
        self.stop_trace()

    # -- tokens ----------------------------------------------------------------

    def _admit(self, t: float) -> RequestLog:
        while self.logs[self.next_admit].admitted is not None:
            self.next_admit += 1
        log = self.logs[self.next_admit]
        req = self.reqs[log.rid]
        if req.slot < 0:                      # pragma: no cover
            raise RuntimeError("prefill of a request the engine did not admit")
        log.admitted, log.slot = t, req.slot
        self.live.append(log)
        return log

    def flush(self, t: float) -> None:
        """Stamp every token produced since the last boundary. The engine's
        own clock gives a request's first token (`first_token_s`) and its
        last (`done_s`); the others get this boundary's time, which follows
        the step that made them with no wait between."""
        still = []
        for log in self.live:
            req = self.reqs[log.rid]
            for j in range(len(log.token_ids), len(req.tokens)):
                if j == 0:
                    when = self.t0_engine + req.first_token_s
                elif req.done and j == len(req.tokens) - 1:
                    when = self.t0_engine + req.done_s
                else:
                    when = t
                log.token_times.append(when)
                log.token_steps.append(-1 if j == 0 else self.last_step)
                log.token_ids.append(int(req.tokens[j]))
            if not req.done:
                still.append(log)
        self.live = still


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def load_cell(workload: str):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    mix = json.loads((BENCH / "workloads" / f"{cell['traffic']}.json").read_text())
    return bench, cell, model_io.load_config(cell["config"]), mix


def _prompt_inputs(key, reqs, s):
    """Each request's prompt from the seed: token ids, or frame embeddings
    (scaled like the engine's own) for a model fed by embeddings."""
    out = {}
    for r in reqs:
        k = jax.random.fold_in(key, r.rid)
        if s.embed_stub:
            out[r.rid] = {"embeds": (jax.random.normal(
                k, (1, r.prompt_len, s.d_model)) * 0.02).astype(jnp.bfloat16)}
        else:
            out[r.rid] = {"tokens": jax.random.randint(
                k, (1, r.prompt_len), 0, s.vocab)}
    return out


def _engine_args(mix: dict, cim: bool):
    from bench.traffic import serve_mix

    max_prompt, max_gen = serve_mix.max_lengths(mix)
    return argparse.Namespace(
        prompt_len=max_prompt, gen=max_gen, slots=mix["slots"],
        sampler="greedy", cim_lower=cim, warmup_steps=1, scrub_every=0)


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------


def _step_embeds(engine_key, step: int, slots: int, d: int):
    """The engine's decode input for a model fed by embeddings
    (`ServeEngine._step_inputs`): a seeded frame per slot and step."""
    return jax.random.normal(jax.random.fold_in(engine_key, 10_000 + step),
                             (slots, 1, d)) * 0.02


def check_sample(logs: List[RequestLog], closed: bool, seed: int,
                 n_sample: int) -> List[RequestLog]:
    """The requests whose served tokens are compared. A closed-loop window
    finishes few requests, so every request that was served is compared;
    in an open loop, `n_sample` finished requests drawn from the seed, with
    the one that served most tokens among them."""
    served = [g for g in logs if g.token_ids]
    if closed:
        return served
    done = [g for g in served if len(g.token_ids) >= g.gen]
    if not done:
        return []
    longest = max(done, key=lambda g: len(g.token_ids))
    rest = [g for g in done if g is not longest]
    rng = np.random.default_rng(int(seed) + 1)
    pick = rng.choice(len(rest), size=min(n_sample - 1, len(rest)),
                      replace=False) if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def served_gaps(fam, params, s, logs, prompts, engine_key, slots,
                control: Optional[str] = None) -> List[np.ndarray]:
    """For every compared served token: how far the reference's logit of it
    lies below the reference's best logit, by the reference of family
    module `fam`. With a `control` precision, the token compared is the one
    the control puts first."""
    gaps = []
    for g in logs:
        n = len(g.token_ids)
        p = g.prompt_len
        if s.embed_stub:
            frames = [prompts[g.rid]["embeds"][0].astype(jnp.float32)]
            for st in g.token_steps[1:n]:
                frames.append(_step_embeds(engine_key, st, slots,
                                           s.d_model)[g.slot].astype(
                                               jnp.bfloat16).astype(jnp.float32))
            inputs = jnp.concatenate(frames, 0)[:p + n - 1]
        else:
            inputs = np.concatenate([np.asarray(prompts[g.rid]["tokens"][0]),
                                     np.asarray(g.token_ids[:n - 1], np.int32)])
        rows = np.arange(p - 1, p - 1 + n)
        ref = np.asarray(fam.logits_at(params, s, inputs, rows))
        if control is None:
            picked = np.asarray(g.token_ids)
        else:
            ctl = np.asarray(fam.logits_at(params, s, inputs, rows, control))
            picked = ctl.argmax(-1)
        gaps.append(ref.max(-1) - ref[np.arange(n), picked])
    return gaps


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def load_reader(name: str) -> Callable:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, cim_bits: Optional[int] = None,
             control: Optional[str] = None,
             engine_hook: Optional[Callable] = None) -> Dict:
    """One run of `workload`; returns the result line's object.

    `cim_bits` replaces the CiM cells' contraction width and `control`
    names a lower precision for the reference's control reading (see
    `bench/control.py`); `engine_hook(engine)` may replace the engine's
    step functions before they are wrapped (`tests/bench`). A benchmark
    run uses none of them."""
    from repro.launch import serve
    from repro.models import build
    from bench.traffic import serve_mix

    bench, cell, conf, mix = load_cell(workload)
    cim = mix["path"] == "cim"
    closed = mix["loop"] == "closed"
    bits = (cim_bits or 8) if cim else 0
    cfg = model_io.arch_config(conf, cim_bits=bits, cim_resident=cim)
    fam = model_io.family(conf)
    s = fam.sizes_of(cfg)
    device = jax.devices()[0]
    peaks = record.load_peaks(device.device_kind) \
        if device.platform == "tpu" else {}
    compiles = CompileCounter()
    if cim:
        serve.reset_cim_state()

    key = model_io.seed_key(seed)
    params = fam.make_params(jax.random.fold_in(key, 0), s, unstacked=cim)
    jax.block_until_ready(params)
    model = build(cfg)
    reqs_spec = serve_mix.generate(mix, seed, seconds)
    warm_spec = []
    if not closed:
        # one request per slot, cycling through every prompt length: every
        # prefill shape and every slot index is compiled before the window
        lens = mix["prompt_len"]["values"]
        n = max(mix["slots"], len(lens))
        warm_spec = [serve_mix.Request(10 ** 6 + i, lens[i % len(lens)], 2, 0.0)
                     for i in range(n)]
    prompts = _prompt_inputs(jax.random.fold_in(key, 1),
                             reqs_spec + warm_spec, s)
    jax.block_until_ready(prompts)
    engine = serve.build_engine(model, params, _engine_args(mix, cim))
    engine.key = jax.random.fold_in(key, 2)
    engine._prompt_inputs = lambda req: prompts[req.rid]
    if engine_hook is not None:
        engine_hook(engine)

    def requests(specs):
        return [serve.ServeRequest(rid=r.rid, prompt_len=r.prompt_len,
                                   gen=r.gen, arrival_s=r.arrival_s)
                for r in specs]

    base = (engine.prefill_fn, engine.decode_fn, engine.sample,
            model.init_caches)
    if warm_spec:
        warm = requests(warm_spec)
        wlogs = [RequestLog(r.rid, r.prompt_len, r.gen, 0.0) for r in warm]
        Recorder(engine, wlogs, warm, cim=cim, seconds=1e9, warm_steps=None,
                 trace_plan=None, trace_dir=None, compiles=compiles)
        engine.run(warm)
        (engine.prefill_fn, engine.decode_fn, engine.sample,
         model.init_caches) = base
        del warm, wlogs
        gc.collect()

    trace_dir = OUT / "trace" / f"{workload}-{seed}"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    reqs = requests(reqs_spec)
    order = sorted(reqs_spec, key=lambda r: (r.arrival_s, r.rid))
    logs = [RequestLog(r.rid, r.prompt_len, r.gen, r.arrival_s) for r in order]
    rec = Recorder(engine, logs, reqs, cim=cim, seconds=seconds,
                   warm_steps=mix.get("warm_steps") if closed else None,
                   trace_plan=mix["trace"] if trace else None,
                   trace_dir=trace_dir, compiles=compiles)
    try:
        engine.run(reqs)
    except StopWindow:
        pass
    finally:
        rec.close(time.perf_counter())
    for g in logs:
        g.arrival += rec.t0_engine            # scheduled arrival, host clock
    mem = device.memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))

    # free the program's state before the reference runs: its caches went
    # with the run; the pinned planes and compiled regions go here
    engine_key = engine.key
    del engine, model, reqs
    projected = None
    if cim:
        from repro.cim import ledger
        projected = ledger().projected()
        serve.reset_cim_state()
    gc.collect()

    limits = mix["check"]
    compared = check_sample(logs, closed, seed, limits.get("sample", 0))
    per_request = served_gaps(fam, params, s, compared, prompts, engine_key,
                              mix["slots"], control)
    gaps = np.concatenate(per_request) if per_request else np.zeros(0)
    max_gap = float(gaps.max()) if gaps.size else float("inf")
    correct = bool(gaps.size) and max_gap <= limits["max_gap"]

    setup_s = rec.window_t0 - t_start
    run = Run(workload=workload, sizes=s, peaks=peaks, setup_s=setup_s,
              window=(rec.window_t0, rec.window_t1), steps=rec.steps,
              requests=logs, flops=fam.decode_token_flops)
    if trace:
        from bench import trace as trace_mod
        run.trace = trace_mod.summarize(
            trace_mod.Trace.from_xplane(trace_mod.find_xplane(trace_dir)))

    metrics = {}
    for m in bench["per_layer" if trace else "end_to_end"]:
        if workload not in m.get("workloads", [workload]):
            continue
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    window_s = rec.window_t1 - rec.window_t0
    n_tokens = run.tokens_between(rec.window_t0, rec.window_t1)
    modelled = {"window_s": window_s, "tokens": n_tokens,
                "decode_steps": len(run.window_steps("decode")),
                "compiles_in_window": rec.at_close["compiles"]
                - rec.at_window["compiles"],
                "compile_s_total": compiles.seconds,
                "compared_tokens": int(gaps.size)}
    if cim:
        for k in ("accesses", "load_accesses", "resident_reuses", "words32"):
            modelled[f"{k}_per_token"] = \
                (rec.at_close[k] - rec.at_window[k]) / max(1, n_tokens)
        modelled["projected_run"] = projected
    attempted = [g for g in logs if g.admitted is not None
                 or (not closed and g.arrival < rec.window_t1)]
    result = {
        "correct": correct,
        "attempted": len(attempted),
        "failed": sum(1 for x in per_request if x.max() > limits["max_gap"]),
        "metrics": metrics,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": peak_bytes},
        "modelled": modelled,
    }
    if trace:
        summary = run.trace
        result["device"]["busy_s"] = summary.get("busy_s", 0.0)
        result["device"]["window_s"] = summary.get("window_s", 0.0)
        result["breakdown"] = {"device_ops": summary.get("device_ops", []),
                               "idle_gaps": summary.get("idle_gaps", [])}
    result["check"] = {"max_gap": {"value": max_gap,
                                   "limit": limits["max_gap"]}}
    OUT.mkdir(exist_ok=True)
    (OUT / f"{workload}-{seed}-{int(trace)}.json").write_text(
        json.dumps({"result": result, "trace": run.trace,
                    "gaps": gaps.tolist()}, indent=1))
    return result


def emit(result: Dict) -> None:
    """Modelled statistics on an earlier line; the compared numbers last on
    standard error; the result as the last line of standard output."""
    modelled = result.pop("modelled")
    print(json.dumps({"modelled": modelled}), flush=True)
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"over {modelled['compared_tokens']} served tokens",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
