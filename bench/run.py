"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cells, metrics and bounds are in
`BENCHMARK.json`; each cell's model is `bench/configs/<config>.json`, its
traffic `bench/workloads/<traffic>.json`, each metric's reader
`bench/metrics/<metric>.py`. The last line of standard output is one JSON
object (`correct`, `attempted`, `failed`, `metrics`, `device`, with
`--trace 1` also `breakdown`, and last `check`: each compared number with its
limit). Exits non-zero, printing no result, where JAX finds no TPU, fewer
chips than the cell asks for, a CiM backend other than `pallas-tpu`, or no
sources of the system under test.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        return fail(f"no sources of the system under test at {src}")
    sys.path[:0] = [str(ROOT), str(src)]

    import jax

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        return fail(f"no workload {args.workload!r} in BENCHMARK.json")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU found (JAX platform {devices[0].platform!r})")
    if len(devices) < cells[args.workload]["chips"]:
        return fail(f"{len(devices)} chips, the cell asks for "
                    f"{cells[args.workload]['chips']}")
    from repro.cim import default_backend_name

    if default_backend_name() != "pallas-tpu":
        return fail(f"CiM backend resolved to {default_backend_name()!r}, "
                    f"not 'pallas-tpu'")
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    from bench import cell

    result = cell.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), T_START)
    cell.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
