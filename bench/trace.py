"""Reduction of a profiler trace to the numbers the metric readers use.

A trace is kept as three lists of (name, start_ns, end_ns), all on the
profiler's one clock:

  modules  programs run on the device (the device plane's "XLA Modules" line)
  ops      operations run on the device ("XLA Ops")
  host     the benchmark's own host spans (`TraceAnnotation`s named "bench.*")

A region program is a module inside which a fused-kernel call runs: an op
whose name holds `KERNEL`. Every other program is host-island work of the
lowering (or the whole step, off the CiM path). The split is by module
identity over the whole trace, not by which host span a module fell in,
so it does not depend on the device and host clocks lining up.
"""
from __future__ import annotations

import bisect
import dataclasses
import gzip
import json
import re
from pathlib import Path
from typing import Dict, List, Tuple

KERNEL = "fused_planes_op"
Event = Tuple[str, float, float]


@dataclasses.dataclass
class Trace:
    modules: List[Event]
    ops: List[Event]
    host: List[Event]

    @classmethod
    def from_xplane(cls, path) -> "Trace":
        import jax

        pd = jax.profiler.ProfileData.from_file(str(path))
        modules, ops, host = [], [], []
        device_seen = False
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:") and not device_seen:
                device_seen = True        # the one chip a cell runs on
                for line in plane.lines:
                    dest = {"XLA Modules": modules, "XLA Ops": ops}.get(line.name)
                    if dest is not None:
                        dest.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                                    for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events if e.name.startswith("bench."))
        return cls(sorted(modules, key=lambda e: e[1]),
                   sorted(ops, key=lambda e: e[1]),
                   sorted(host, key=lambda e: e[1]))

    def to_json(self, path) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(dataclasses.asdict(self), f)

    @classmethod
    def from_json(cls, path) -> "Trace":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls(*[[tuple(e) for e in d[k]]
                     for k in ("modules", "ops", "host")])


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, non-overlapping cover of `intervals`."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def base_name(name: str) -> str:
    """'%fusion.18 = (...) fusion(...)' -> 'fusion'; 'jit_fn(123)' -> 'jit_fn'."""
    head = name.split(" = ", 1)[0].split("(", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def region_modules(t: Trace) -> List[bool]:
    """For each module, whether a fused-kernel op starts inside it."""
    starts = [o[1] for o in t.ops if KERNEL in o[0]]
    out = []
    for _, s, e in t.modules:
        i = bisect.bisect_left(starts, s)
        out.append(i < len(starts) and starts[i] <= e)
    return out


def summarize(t: Trace) -> Dict:
    """Busy and idle time over the traced window, the device time of region
    programs and of all other programs in the trace, the device ops that
    took most time, and idle time by what the host was doing."""
    if not t.host or not t.ops:
        return {}
    lo = min(h[1] for h in t.host)
    hi = max(h[2] for h in t.host)
    busy = union(_clip([(s, e) for _, s, e in t.ops], lo, hi))
    busy_ns = sum(e - s for s, e in busy)

    region_ns = other_ns = 0.0
    for (_, s, e), reg in zip(t.modules, region_modules(t)):
        if reg:
            region_ns += e - s
        else:
            other_ns += e - s
    host_sorted = sorted(t.host, key=lambda h: h[1])
    host_starts = [h[1] for h in host_sorted]

    mod_starts = [m[1] for m in t.modules]
    per_op: Dict[str, float] = {}
    for name, s, e in t.ops:
        i = bisect.bisect_right(mod_starts, s) - 1
        mod = base_name(t.modules[i][0]) if i >= 0 and t.modules[i][2] >= s else "?"
        key = f"{mod}:{base_name(name)}"
        per_op[key] = per_op.get(key, 0.0) + (e - s)

    # the host's state over the window, piece by piece: the innermost
    # bench span, or none
    points = sorted({lo, hi, *(x for h in t.host for x in h[1:])})
    pieces = [(a, b, _innermost(host_sorted, host_starts, (a + b) / 2)
               or "outside bench spans")
              for a, b in zip(points, points[1:]) if lo <= a < hi]
    gaps: Dict[str, float] = {}
    idle = []
    prev = lo
    for s_, e_ in busy + [(hi, hi)]:
        if s_ > prev:
            idle.append((prev, s_))
        prev = max(prev, e_)
    j = 0
    for a, b in idle:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            pa, pb, name = pieces[k]
            gaps[name] = gaps.get(name, 0.0) + min(b, pb) - max(a, pa)
            k += 1
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    by_host = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "region_s": region_ns / 1e9,
        "other_s": other_ns / 1e9,
        "device_ops": [[k, v / 1e9] for k, v in top],
        "idle_gaps": [[k, v / 1e9] for k, v in by_host],
    }


def _innermost(host_sorted, host_starts, t: float):
    """Name of the shortest bench span that covers time `t`, if any. The
    benchmark's spans nest at most a few deep, so the few latest spans that
    start before `t` are the only candidates."""
    best = None
    i = bisect.bisect_right(host_starts, t)
    for name, s, e in host_sorted[max(0, i - 4):i]:
        if e >= t and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else None


def find_xplane(log_dir) -> Path:
    files = sorted(Path(log_dir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]
