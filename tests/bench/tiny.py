"""A tiny cell for the CPU tests: the harness's own `load_cell`, with a
configuration and mixes of a few dozen widths."""
from __future__ import annotations

import copy
import json

from bench import cell, model_io

TINY = {
    "name": "tiny",
    "program": {"arch": "granite-3-8b", "n_layers": 2, "d_model": 64,
                "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                "vocab_size": 256, "norm_eps": 1e-05, "dtype": "bfloat16"},
}
TINY_STUB = copy.deepcopy(TINY)
TINY_STUB["program"].update(arch="musicgen-large", n_kv_heads=4)

CIM = {"generator": "serve_mix", "path": "cim", "slots": 2, "loop": "closed",
       "requests": 6, "warm_steps": 2, "prompt_len": {"values": [4]},
       "output_len": {"values": [6, 9]},
       "trace": {"start_s": 0, "seconds": 0.5}, "check": {"max_gap": 0.05}}
CHAT = {"generator": "serve_mix", "path": "plain", "slots": 4, "loop": "open",
        "rate_per_s": 20.0,
        "prompt_len": {"values": [8, 16, 32],
                       "lognormal": {"median": 16, "sigma": 0.8}},
        "output_len": {"lognormal": {"median": 6, "sigma": 0.5},
                       "min": 2, "max": 12},
        "trace": {"start_s": 0.5, "seconds": 0.5},
        "check": {"max_gap": 0.05, "sample": 4}}

BENCH = json.loads((cell.ROOT / "BENCHMARK.json").read_text())


def patch(monkeypatch, conf: dict, mix: dict, name: str = "tiny-cell"):
    """Make `name` a cell of `conf` under `mix` for `cell.run_cell`."""
    metrics = copy.deepcopy(BENCH)
    kind = "cim" if mix["path"] == "cim" else "chat"
    like = {"cim": "granite8b-cim-batch", "chat": "granite8b-chat-over"}[kind]
    for group in ("end_to_end", "per_layer"):
        for m in metrics[group]:
            if like in m.get("workloads", [like]):
                m["workloads"] = [name]
    c = {"name": name, "config": "tiny", "traffic": name, "chips": 1}
    metrics["workloads"] = [c]

    def load_cell(workload):
        assert workload == name
        return metrics, c, conf, mix

    monkeypatch.setattr(cell, "load_cell", load_cell)
    monkeypatch.setattr(model_io, "load_config", lambda n: conf)
