"""Find the knee of an open-loop cell: the highest rate with no growing
backlog, by one sweep on the chip.

    python3 bench/sweep.py --workload granite8b-chat-over --rates 2 4 6 8 \
        --seconds 30 [--out sweep.jsonl]

Runs the cell once per rate, in this one process, with the mix's
`rate_per_s` replaced. For each rate it prints the requests due and the
ones still queued at the window's end, the median wait from arrival to
prefill in the window's first and second halves (a backlog that grows
shows as a second half that waits longer), the tail of the wait, and
the tokens completed per second. The cell's rate is written into its mix
file by hand: about four fifths of the knee for a cell judged on its
tails, or past the rate where the tokens completed stop rising for one
judged on them.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU found", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    from bench import cell
    from bench.record import p95, window_tok_s

    load_cell = cell.load_cell
    for rate in args.rates:
        def with_rate(name, rate=rate):
            bench, c, conf, mix = load_cell(name)
            return bench, c, conf, dict(mix, rate_per_s=rate)

        cell.load_cell = with_rate
        seen = {}
        reader = cell.load_reader

        def spy(name):
            read = reader(name)

            def wrapped(run):
                seen["run"] = run
                return read(run)
            return wrapped

        cell.load_reader = spy
        r = cell.run_cell(args.workload, args.seed, args.seconds, False,
                          time.perf_counter())
        cell.load_reader = reader
        run = seen["run"]
        t0, t1 = run.window
        mid = (t0 + t1) / 2
        due = [g for g in run.requests if g.arrival < t1]
        waits = {h: [((g.admitted or t1) - g.arrival) * 1e3 for g in due
                     if (g.arrival < mid) == (h == "first")]
                 for h in ("first", "second")}
        line = {
            "rate_per_s": rate, "due": len(due),
            "queued_at_end": sum(1 for g in due if g.admitted is None),
            "wait_median_ms": {h: float(np.median(w)) if w else None
                               for h, w in waits.items()},
            "wait_p95_ms": p95([w for ws in waits.values() for w in ws]),
            "metrics": {k: v["value"] for k, v in r["metrics"].items()},
            "tokens_per_s": window_tok_s(run),
            "correct": r["correct"], "max_gap": r["check"]["max_gap"]["value"],
        }
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
