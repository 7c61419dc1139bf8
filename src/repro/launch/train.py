"""End-to-end training driver with fault tolerance.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
      --steps 200 --preset reduced --batch 8 --seq 128

Presets: reduced (CPU-friendly smoke), 100m (~100M-param variant for the
end-to-end example), full (the published config — production meshes only).
The loop runs under the Supervisor: async checkpoints, NaN sentinel,
restore-on-failure.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import DataConfig, embed_stub_batch, synthetic_batch
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_smoke_mesh
from repro.models import build
from repro.optim import AdamWConfig, cosine_schedule
from repro.runtime import Supervisor, SupervisorConfig
from repro.sharding import batch_specs, state_specs, to_named
from repro.train import init_state, make_train_step


def preset_config(name: str, preset: str):
    cfg = get_config(name)
    if preset == "reduced":
        return cfg.reduced()
    if preset == "100m":
        # ~100M-param same-family variant (for the end-to-end example)
        return dataclasses.replace(
            cfg.reduced(), name=cfg.name + "-100m",
            n_layers=8, d_model=768, n_heads=12, n_kv_heads=min(cfg.n_kv_heads, 4),
            head_dim=64, d_ff=3072 if cfg.d_ff else 0, vocab_size=32768,
        )
    return cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--preset", default="reduced", choices=("reduced", "100m", "full"))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    setup_compile_cache()

    cfg = preset_config(args.arch, args.preset)
    model = build(cfg)
    opt_cfg = AdamWConfig(lr=args.lr, state_dtype=cfg.opt_state_dtype)
    sched = cosine_schedule(args.lr, warmup=max(args.steps // 20, 5), total=args.steps)

    mesh = make_smoke_mesh()
    key = jax.random.PRNGKey(0)
    state = init_state(model, key, opt_cfg, compress_grads=args.compress_grads)
    st_specs = to_named(mesh, state_specs(cfg, state, mesh))
    state = jax.device_put(state, st_specs)

    dcfg = DataConfig(vocab_size=cfg.vocab_size, batch=args.batch, seq_len=args.seq)

    def make_batch(step: int):
        if cfg.embed_stub:
            return {k: jnp.asarray(v) for k, v in
                    embed_stub_batch(step, cfg, args.batch, args.seq).items()}
        return {k: jnp.asarray(v) for k, v in synthetic_batch(step, dcfg).items()}

    example_batch = make_batch(0)  # host-side numpy: shapes only
    step_fn = jax.jit(
        make_train_step(model, opt_cfg, lr_schedule=sched,
                        compress_grads=args.compress_grads),
        in_shardings=(st_specs, to_named(mesh, batch_specs(cfg, example_batch, mesh))),
        out_shardings=(st_specs, None),
        donate_argnums=(0,),
    )

    ckpt = CheckpointManager(args.ckpt_dir)
    sup = Supervisor(step_fn, make_batch, ckpt,
                     SupervisorConfig(ckpt_every=args.ckpt_every))

    t0 = time.monotonic()
    n_done = 0

    def logging_step(state, batch):
        nonlocal n_done
        out = step_fn(state, batch)
        n_done += 1
        if n_done % args.log_every == 0:
            m = {k: float(jax.device_get(v)) for k, v in out[1].items()
                 if hasattr(v, "shape") or isinstance(v, (int, float))}
            rate = n_done / (time.monotonic() - t0)
            print(f"step {n_done:5d}  loss {m['loss']:.4f}  ce {m['ce']:.4f} "
                  f" gnorm {m['grad_norm']:.3f}  {rate:.2f} it/s", flush=True)
        return out

    sup.train_step = logging_step
    with mesh:
        state, metrics = sup.run(state, args.steps)
    print(f"done: {args.steps} steps in {time.monotonic()-t0:.1f}s; "
          f"final loss {float(jax.device_get(metrics['loss'])):.4f}")


if __name__ == "__main__":
    main()
