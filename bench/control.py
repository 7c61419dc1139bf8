"""Readings that set a cell's limits: the program's and its control's.

    python3 bench/control.py --workload <name> --seeds 1 2 3 --seconds 30 \
        [--cim-bits 4 | --control fp8] [--out readings.jsonl]

Runs the cell once per seed in this one process, on the chip, and prints
the compared number of each run: with no option the program's own reading
(the lower end of a limit), with one the control's (the upper end). The
control of a CiM cell is the program's own narrower path (`--cim-bits 4`:
every lowered contraction at 4 bits in place of 8); that of a plain cell is
the reference computed in a lower precision (`--control fp8`), read at the
positions of the program's own served tokens. The benchmark's runs never
run this.
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cim-bits", type=int)
    ap.add_argument("--control")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU found", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    from bench import cell

    for seed in args.seeds:
        t0 = time.perf_counter()
        r = cell.run_cell(args.workload, seed, args.seconds, False, t0,
                          cim_bits=args.cim_bits, control=args.control)
        line = {"workload": args.workload, "seed": seed,
                "cim_bits": args.cim_bits, "control": args.control,
                "max_gap": r["check"]["max_gap"]["value"],
                "compared_tokens": r["modelled"]["compared_tokens"],
                "metrics": {k: v["value"] for k, v in r["metrics"].items()},
                "memory_peak_bytes": r["device"]["memory_peak_bytes"],
                "seconds": time.perf_counter() - t0}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
