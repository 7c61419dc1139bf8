"""What one run recorded, as the metric readers see it.

Times are `time.perf_counter()` seconds on the host. A metric reader is a
file `bench/metrics/<metric name>.py` with `read(run: Run) -> float | None`;
it returns None where the run gives it nothing to read.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Step:
    kind: str                 # "prefill" or "decode"
    index: int                # decode: the engine's step number; prefill: -1
    t0: float
    t1: float
    traced: bool
    words32: float = 0.0      # ledger compute word-ops charged by the step

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class RequestLog:
    rid: int
    prompt_len: int
    gen: int
    arrival: float            # scheduled arrival, host clock
    admitted: Optional[float] = None      # its prefill started
    token_times: List[float] = dataclasses.field(default_factory=list)
    token_steps: List[int] = dataclasses.field(default_factory=list)
    token_ids: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1


@dataclasses.dataclass
class Run:
    workload: str
    sizes: object             # the model family's `Sizes`
    peaks: Dict[str, float]
    setup_s: float
    window: tuple             # (t0, t1) host clock
    steps: List[Step]
    requests: List[RequestLog]
    trace: Optional[Dict] = None          # bench.trace.summarize(...)
    # the model family's `decode_token_flops(sizes, context)`
    flops: Optional[Callable[[object, int], float]] = None

    def window_steps(self, kind: str, traced: Optional[bool] = None):
        t0, t1 = self.window
        return [s for s in self.steps if s.kind == kind and t0 <= s.t0 < t1
                and (traced is None or s.traced == traced)]

    def traced_decode_steps(self) -> Optional[List[Step]]:
        """The decode steps run under the profiler, where they are all the
        traced steps: the trace's program time is theirs. None where a
        prefill was traced too, or nothing was."""
        traced = [s for s in self.steps if s.traced]
        if not traced or any(s.kind != "decode" for s in traced):
            return None
        return traced

    def tokens_between(self, t0: float, t1: float) -> int:
        return sum(1 for r in self.requests for t in r.token_times
                   if t0 < t <= t1)


def p95(values) -> Optional[float]:
    return float(np.percentile(values, 95)) if len(values) else None


def token_flops(run: Run, steps) -> float:
    """Model FLOPs of the tokens that the decode `steps` produced, by the
    run's own FLOP count (`Run.flops`)."""
    want = {s.index for s in steps}
    total = 0.0
    for r in run.requests:
        for j, st in enumerate(r.token_steps):
            if st in want:
                total += run.flops(run.sizes, r.prompt_len + j)
    return total


def load_peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][device_kind]


def mean_step_ms(run: Run, kind: str) -> Optional[float]:
    """Mean host-clock time of the window's `kind` steps, each timed around
    `block_until_ready`; steps run under the profiler are left out."""
    steps = run.window_steps(kind, traced=False)
    return 1e3 * sum(s.seconds for s in steps) / len(steps) if steps else None


def idle_pct(run: Run) -> Optional[float]:
    """Share of the traced window in which no operation ran on the device."""
    t = run.trace or {}
    if not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def window_tok_s(run: Run) -> float:
    """Every token produced in the window, by decode steps and by prefills,
    over the window's length. The window closes at a step boundary, so it
    holds whole steps."""
    t0, t1 = run.window
    return run.tokens_between(t0, t1) / (t1 - t0)


def window_mfu(run: Run) -> Optional[float]:
    """Model FLOPs of the tokens the window's decode steps produced over
    the window's length times the chip's peak bf16 rate, in percent."""
    if not run.peaks:
        return None
    t0, t1 = run.window
    flops = token_flops(run, run.window_steps("decode"))
    return 100.0 * flops / ((t1 - t0) * run.peaks["bf16_flops_per_s"])
