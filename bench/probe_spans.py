"""Record a small profile of the program's spans for the CPU tests.

    python3 bench/probe_spans.py [--out tests/bench/data/serve_cim_spans.json.gz]

A few CiM decode steps of a 2-layer granite-shaped model at tiny widths
(64 wide, 4 query and 2 KV heads of 16), 2 slots, resident weights,
through `ServeEngine` as the harness runs a cell (`bench.cell.run_cell`,
with the profiler on), reduced by `bench.spans.SpanTrace` to the device's
programs and ops, the benchmark's `bench.*` spans and the program's
`serve.*`, `model.*` and `cim.*` spans, cut after `--steps` traced decode
steps.
Run it from the root of a checkout on one TPU; it prints the run's
result line, then the reduced profile's summary.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
NAME = "probe-spans"
CONF = {"name": "probe",
        "program": {"arch": "granite-3-8b", "n_layers": 2, "d_model": 64,
                    "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
                    "d_ff": 128, "vocab_size": 256, "norm_eps": 1e-05,
                    "dtype": "bfloat16"}}
MIX = {"generator": "serve_mix", "path": "cim", "slots": 2, "loop": "closed",
       "requests": 2, "warm_steps": 2, "prompt_len": {"values": [4]},
       "output_len": {"values": [64]},
       "trace": {"start_s": 0, "seconds": 0.2}, "check": {"max_gap": 0.05}}


def cut(st, steps: int):
    """Keep what starts before the end of the `steps`-th traced
    `bench.decode` span, and the spans that end by then."""
    decodes = sorted(h[2] for h in st.trace.host if h[0] == "bench.decode")
    if len(decodes) <= steps:
        return st
    end = decodes[steps - 1]
    t = st.trace
    t.modules = [m for m in t.modules if m[1] < end]
    t.ops = [o for o in t.ops if o[1] < end]
    t.host = [h for h in t.host if h[2] <= end]
    st.spans = [s for s in st.spans if s[2] <= end]
    return st


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(ROOT / "tests" / "bench" / "data"
                                         / "serve_cim_spans.json.gz"))
    ap.add_argument("--seed", type=int, default=2 ** 31 + 13)
    ap.add_argument("--steps", type=int, default=2)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import cell, model_io, spans

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    like = "granite8b-cim-batch"
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if like in m.get("workloads", [like]):
                m["workloads"] = [NAME]
    entry = {"name": NAME, "config": "probe", "traffic": NAME, "chips": 1}
    bench["workloads"] = [entry]
    cell.load_cell = lambda w: (bench, entry, CONF, copy.deepcopy(MIX))
    model_io.load_config = lambda n: CONF

    result = cell.run_cell(NAME, args.seed, 1.0, True, time.perf_counter())
    print(json.dumps(result), flush=True)
    st = cut(spans.SpanTrace.from_xplane(spans.run_xplane(NAME, args.seed)),
             args.steps)
    st.to_json(args.out)
    summary = spans.summarize(st)
    summary["events"] = {"modules": len(st.trace.modules),
                         "ops": len(st.trace.ops),
                         "host": len(st.trace.host), "spans": len(st.spans)}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
