"""Smoke test of the CiM serve path on one TPU, at gemma-2b's published widths.

    python chip_smoke.py

Runs in one process from the root of a checkout and loads nothing from
outside it; the weights are random, made from PRNGKey(0). Three phases go
through the serve entry points (`repro.launch.serve`, `ServeEngine`,
`lower()`, the `pallas-tpu` CiM backend):

  plain  the jitted continuous-batching server. Every request completes,
         and every prefill and decode step's logits are finite.
  cim    `--cim-lower --cim-resident`: a streamed-repack run, then a run
         with the weight planes pinned, held to `serve.check_residency`
         (equal compute accesses per token, strictly fewer total accesses,
         resident reuses > 0).
  twin   the cim requests again with every lowered eqn on the host (offload
         policy "never"). Its greedy tokens and its first decode step's
         logits must be bit-identical to the resident cim run.

Exits non-zero with a message, printing no result, when the repository's
sources are missing, when JAX finds no TPU, or when the CiM backend does not
resolve to `pallas-tpu`. No phase's failure is caught. The last line of
standard output is one JSON object naming the device.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

SRC = Path(__file__).resolve().parent / "src"

ARCH, PRESET = "gemma-2b", "full"
#: the plain server: slots, requests, prompt and new tokens per request
PLAIN = dict(slots=4, requests=8, prompt_len=64, gen=16)
#: the lowered phases, sized by compiling for a described v5e (15.75 GB of
#: HBM to programs): the weights take 5.0 GB, held unstacked
#: (`Model.unstack_groups`); the resident weight planes 1.8 GB per slot (the
#: broadcast [M, K_pad, N] layout); a decode MLP region 1.5 GB of
#: temporaries; and a prefill MLP region 0.74 GB per prompt token (3.0 GB
#: at 4). A prefill admitted while two slots' planes are pinned must fit
#: beside them.
CIM = dict(slots=2, requests=4, prompt_len=4, gen=8)


def require(ok: bool, what: str) -> None:
    """A smoke check that holds under `python -O` too."""
    if not ok:
        raise AssertionError(what)


def serve_argv(sizes, *flags):
    return ["--arch", ARCH, "--preset", PRESET,
            "--slots", str(sizes["slots"]),
            "--requests", str(sizes["requests"]),
            "--prompt-len", str(sizes["prompt_len"]),
            "--gen", str(sizes["gen"]), *flags]


class CompileClock:
    """Seconds spent in XLA backend compiles, from JAX's own event."""

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration


class StepRecorder:
    """Wraps an engine's prefill and decode steps: whether every step's
    logits were finite, and the first decode step's logits."""

    def __init__(self, engine):
        self.finite = True
        self.first_decode = None
        prefill, decode = engine.prefill_fn, engine.decode_fn

        def prefill_fn(params, inputs):
            caches, logits = prefill(params, inputs)
            self._see(logits)
            return caches, logits

        def decode_fn(params, caches, step_in):
            caches, logits = decode(params, caches, step_in)
            if self.first_decode is None:
                self.first_decode = np.asarray(logits)
            self._see(logits)
            return caches, logits

        engine.prefill_fn, engine.decode_fn = prefill_fn, decode_fn

    def _see(self, logits):
        self.finite = self.finite and bool(jnp.isfinite(logits).all())


def run_phase(name, model, params, args, clock, device):
    """Serve `args`' requests once on `model`; report and check completion."""
    from repro.launch import serve

    gc.collect()          # the last phase's engine, caches and pins
    c0, t0 = clock.total, time.perf_counter()
    engine = serve.build_engine(model, params, args)
    rec = StepRecorder(engine)
    rep = engine.run(serve.make_requests(args))
    wall = time.perf_counter() - t0
    tokens = [len(r["token_ids"]) for r in rep["per_request"]]
    require(rep["completed"] == args.requests and rep["shed"] == 0,
            f"{name}: {rep['completed']}/{args.requests} requests completed")
    require(all(n == args.gen for n in tokens), f"{name}: tokens {tokens}")
    require(rec.finite, f"{name}: non-finite logits")
    mem = device.memory_stats() or {}
    line = (f"{name}: {args.requests} requests, tokens per request {tokens}, "
            f"{rep['decode_steps']} decode steps, wall {wall:.1f} s, "
            f"compile {clock.total - c0:.1f} s, bytes_in_use "
            f"{mem.get('bytes_in_use', 'n/a')}, peak_bytes_in_use "
            f"{mem.get('peak_bytes_in_use', 'n/a')}")
    if "accesses_per_token" in rep:
        line += (f", accesses/token {rep['accesses_per_token']} compute + "
                 f"{rep['load_accesses_per_token']} load = "
                 f"{rep['total_accesses_per_token']}, resident reuses "
                 f"{rep['ledger']['resident_reuses']}")
    print(line, flush=True)
    return rep, rec


def main() -> int:
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository sources at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {device.platform!r})",
              file=sys.stderr)
        return 1
    from repro.cim import default_backend_name

    backend = default_backend_name()
    if backend != "pallas-tpu":
        print(f"chip_smoke: CiM backend resolved to {backend!r}, "
              f"not 'pallas-tpu'", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import setup_compile_cache

    print(f"device: {device.device_kind} x{len(jax.devices())}, "
          f"cim backend {backend}, compile cache {setup_compile_cache()}",
          flush=True)
    smoke(device)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


def smoke(device) -> None:
    """The three phases; raises on the first failed check."""
    from repro.launch import serve
    from repro.models import build

    clock = CompileClock()

    plain_args = serve.parse_args(serve_argv(PLAIN))
    cfg = serve.serve_config(plain_args)
    print(f"model: {ARCH} ({PRESET}) layers {cfg.n_layers}, d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}x"
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"params {cfg.param_dtype}; plain {PLAIN}; cim {CIM}", flush=True)
    t0 = time.perf_counter()
    params = build(cfg).init(jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    print(f"init: {time.perf_counter() - t0:.1f} s", flush=True)

    run_phase("plain", build(cfg), params, plain_args, clock, device)

    # the lowered phases run the layers unrolled: hold them unstacked, once
    params = build(cfg).unstack_groups(params)

    cim_args = serve.parse_args(
        serve_argv(CIM, "--cim-lower", "--cim-resident"))
    cim_cfg = serve.serve_config(cim_args)
    serve.reset_cim_state()
    repack, _ = run_phase("cim repack", build(cim_cfg), params, cim_args,
                          clock, device)
    serve.reset_cim_state()
    resident, rec_cim = run_phase(
        "cim resident", build(dataclasses.replace(cim_cfg, cim_resident=True)),
        params, cim_args, clock, device)
    serve.check_residency(repack, resident)

    serve.reset_cim_state()
    twin, rec_twin = run_phase(
        "twin", build(dataclasses.replace(cim_cfg, cim_policy="never")),
        params, cim_args, clock, device)
    cim_tokens = [r["token_ids"] for r in resident["per_request"]]
    twin_tokens = [r["token_ids"] for r in twin["per_request"]]
    require(twin_tokens == cim_tokens,
            f"greedy tokens differ: cim {cim_tokens} twin {twin_tokens}")
    a, b = rec_cim.first_decode, rec_twin.first_decode
    require(a.shape == b.shape and a.dtype == b.dtype
            and a.tobytes() == b.tobytes(), "first decode step logits differ")
    print(f"twin: tokens and first-step logits {a.shape} bit-identical "
          f"to cim", flush=True)


if __name__ == "__main__":
    sys.exit(main())
