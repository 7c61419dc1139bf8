"""The program's own spans in a profile, read beside the benchmark's.

`bench/trace.py` keeps the benchmark's `bench.*` host spans. The program
enters spans of its own, on the same clock: `serve.*` around each phase of
the engine's loop (`repro.launch.serve`), `model.*` around the phases of
the model's step (`repro.models.model`) and `cim.*` inside the lowering
executor (`repro.cim.lower`). This module reads them from the same profile
and sums up what spans from outside the program cannot tell:

  idle_by_span   device idle time in the traced window by the innermost
                 span of any kind covering it (`bench.*`, `serve.*`,
                 `model.*` or `cim.*`), by a sweep that holds at any
                 nesting depth
  loop_idle_s    the same over the `serve.*` spans alone
  clock_check    the share of device busy time that lies inside some
                 `serve.*` span, between the first and the last of them:
                 below 99% the host and device clocks disagree
  host_eqns      the eqns the `cim.host` islands bound: a host count, the
                 `eqns` each island span carries
  host_reads     per decode step: a host count, the engine's `host_reads`
                 counter as each `serve.decode` span carries it
  scopes         device time by program, innermost `cim.*` named scope and
                 op, from the profile's trace-viewer file
  span_args      by span name, the program's spans in the window: their
                 `count` and, under `sum`, the total of each numeric
                 argument they carry (a string such as `cim.call`'s `fn`
                 is not summed)
  region_s_by_fn the device time of the region programs, `bench/trace.py`'s
                 `region_s` over the same modules and the whole trace, by
                 the lowered function `<fn>` of each program's name
                 `jit_cim_<fn>_r<k>` (`?` for a name of another form); a
                 program shared by identical regions keeps its first name,
                 so its time goes to that name's function

The window is `bench/trace.py`'s: from the first to the last `bench.*`
span. A span open when the profiler started or stopped is not in the
profile: the first traced decode step has no `serve.decode` span.

A new mechanism's spans and counters need no edit here: its per-layer
metric is a new reader `bench/metrics/<name>.py` over `span_args` and
`region_s_by_fn`.

The readers in `bench/metrics/` get a run's summary from `of_run`: the
profile in the run's own trace directory, `.bench_out/trace/<workload>-
<seed>`, with the seed `bench/run.py` was given (the readers run in its
process). A profile without the program's spans gives them nothing to
read.
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import gzip
import heapq
import json
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench import trace as trace_mod

OUT = Path(__file__).resolve().parent.parent / ".bench_out"
PROGRAM = ("serve.", "model.", "cim.")
OUTSIDE = "outside spans"
#: the engine's loop outside the model calls
LOOP = ("serve.admit", "serve.insert", "serve.emit", "serve.wait")
#: a region program's name (`repro.cim.lower`): the lowered function, the region
REGION = re.compile(r"jit_cim_(.+)_r\d+")

Span = Tuple[str, float, float, Dict]


def strip_name(name: str) -> str:
    """'serve.decode#step=3,host_reads=7#' -> 'serve.decode': the metadata a
    `TraceAnnotation`'s keyword arguments encode in the name."""
    return name.split("#", 1)[0]


@dataclasses.dataclass
class SpanTrace:
    trace: trace_mod.Trace     # modules, ops and bench.* spans
    spans: List[Span]          # the program's spans, by start
    scopes: List[list] = dataclasses.field(default_factory=list)

    @classmethod
    def from_xplane(cls, path) -> "SpanTrace":
        import jax

        t = trace_mod.Trace.from_xplane(path)
        pd = jax.profiler.ProfileData.from_file(str(path))
        spans = []
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    name = strip_name(e.name)
                    if name.startswith(PROGRAM):
                        spans.append((name, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      dict(e.stats)))
        spans.sort(key=lambda s: s[1])
        viewer = sorted(Path(path).parent.glob("*.trace.json.gz"))
        scopes = scope_split(viewer[-1]) if viewer else []
        return cls(t, spans, scopes)

    def to_json(self, path) -> None:
        d = dataclasses.asdict(self.trace)
        d.update(spans=[list(s) for s in self.spans], scopes=self.scopes)
        with gzip.open(path, "wt") as f:
            json.dump(d, f)

    @classmethod
    def from_json(cls, path) -> "SpanTrace":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        t = trace_mod.Trace(*[[tuple(e) for e in d[k]]
                              for k in ("modules", "ops", "host")])
        spans = [(strip_name(n), s, e, st) for n, s, e, st in d.get("spans", [])]
        return cls(t, spans, d.get("scopes", []))


def idle_intervals(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The gaps of `busy` (merged, sorted) inside [lo, hi]."""
    out, prev = [], lo
    for s, e in busy:
        if s > prev:
            out.append((prev, min(s, hi)))
        prev = max(prev, e)
        if prev >= hi:
            break
    if prev < hi:
        out.append((prev, hi))
    return [(a, b) for a, b in out if b > a]


def innermost_pieces(spans, lo: float, hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut where any span starts or ends, each piece named after
    the innermost span covering it (the latest started: spans of one
    thread nest), or OUTSIDE. A heap of the open spans, by start, with
    ended ones dropped as they reach its top: O(n log n) at any depth."""
    events = sorted((s, e, name) for name, s, e, *_ in spans if e > lo and s < hi)
    cuts = sorted({lo, hi, *(x for s, e, _ in events for x in (s, e)
                             if lo < x < hi)})
    pieces, heap, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(events) and events[i][0] <= a:
            s, e, name = events[i]
            heapq.heappush(heap, (-s, e - s, e, name))
            i += 1
        while heap and heap[0][2] <= a:
            heapq.heappop(heap)
        pieces.append((a, b, heap[0][3] if heap else OUTSIDE))
    return pieces


def attribute(idle, pieces) -> Dict[str, float]:
    """Idle time (ns) by the name of the piece it falls in."""
    out: Dict[str, float] = {}
    j = 0
    for a, b in idle:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            pa, pb, name = pieces[k]
            out[name] = out.get(name, 0.0) + min(b, pb) - max(a, pa)
            k += 1
    return out


def inside_share(busy, spans) -> Optional[float]:
    """Percent of `busy` between the first and last of `spans` that lies
    inside one of them."""
    if not spans or not busy:
        return None
    lo, hi = spans[0][1], max(s[2] for s in spans)
    busy = [(max(s, lo), min(e, hi)) for s, e in busy if e > lo and s < hi]
    total = sum(e - s for s, e in busy)
    if total <= 0:
        return None
    cover = trace_mod.union([(s, e) for _, s, e, *_ in spans])
    inside, j = 0.0, 0
    for s, e in busy:
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            inside += min(e, cover[k][1]) - max(s, cover[k][0])
            k += 1
    return 100.0 * inside / total


def summarize(st: SpanTrace) -> Dict:
    """The summary the readers use (see the module's docstring); {} where
    the profile holds no `bench.*` span or no device op."""
    t = st.trace
    if not t.host or not t.ops:
        return {}
    lo = min(h[1] for h in t.host)
    hi = max(h[2] for h in t.host)
    busy = trace_mod.union([(s, e) for _, s, e in t.ops])
    idle = idle_intervals(busy, lo, hi)
    inwin = [s for s in st.spans if s[2] > lo and s[1] < hi]
    serve = [s for s in inwin if s[0].startswith("serve.")]
    every = [(n, s, e) for n, s, e in t.host] + [s[:3] for s in inwin]
    by_span = attribute(idle, innermost_pieces(every, lo, hi))
    by_loop = attribute(idle, innermost_pieces(serve, lo, hi))
    decodes = [s for s in st.spans if s[0] == "serve.decode"]
    reads = [s[3]["host_reads"] for s in decodes if "host_reads" in s[3]]
    return {
        "window_s": (hi - lo) / 1e9,
        "program_spans": len(inwin),
        "idle_by_span": [[k, v / 1e9] for k, v in
                         sorted(by_span.items(), key=lambda kv: -kv[1])],
        "loop_idle_s": {k: by_loop.get(k, 0.0) / 1e9 for k in LOOP}
        if serve else {},
        "clock_check": inside_share(busy, [s for s in st.spans
                                           if s[0].startswith("serve.")]),
        "cim_spans": sum(1 for s in inwin if s[0].startswith("cim.")),
        "host_eqns": sum(s[3].get("eqns", 0) for s in inwin
                         if s[0] == "cim.host"),
        "host_reads_per_step": (reads[-1] - reads[0]) / (len(reads) - 1)
        if len(reads) > 1 else None,
        "serve_decode_ms": 1e3 * sum(e - s for _, s, e, _ in decodes)
        / 1e9 / len(decodes) if decodes else None,
        "scopes": st.scopes,
        "span_args": span_args(inwin),
        "region_s_by_fn": region_split(t),
    }


def span_args(spans) -> Dict[str, Dict]:
    """Each span name's count and the sums of its numeric arguments."""
    out: Dict[str, Dict] = {}
    for name, _, _, args in spans:
        entry = out.setdefault(name, {"count": 0, "sum": {}})
        entry["count"] += 1
        for k, v in args.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                entry["sum"][k] = entry["sum"].get(k, 0) + v
    return out


def region_split(t: trace_mod.Trace) -> Dict[str, float]:
    """Region programs' device time (s) by lowered function, most first."""
    out: Dict[str, float] = {}
    for (name, s, e), reg in zip(t.modules, trace_mod.region_modules(t)):
        if reg:
            m = REGION.fullmatch(trace_mod.base_name(name))
            fn = m.group(1) if m else "?"
            out[fn] = out.get(fn, 0.0) + e - s
    return {k: v / 1e9 for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def scope_split(viewer_json, top: int = 16) -> List[list]:
    """Device op time (s) by `<program>:<innermost cim.* scope>/<op>`, or
    `<program>:<op>` outside every `cim.*` scope, from the trace-viewer
    file the profiler writes beside the .xplane.pb: its ops carry their
    scope path as `tf_op`."""
    with gzip.open(viewer_json, "rt") as f:
        events = json.load(f).get("traceEvents", [])
    device = {e["pid"] for e in events if e.get("ph") == "M"
              and e.get("name") == "process_name"
              and str(e["args"].get("name", "")).startswith("/device:TPU:")}
    if not device:
        return []
    pid = min(device)
    lines = {e["tid"]: e["args"]["name"] for e in events if e.get("ph") == "M"
             and e.get("name") == "thread_name" and e.get("pid") == pid}
    mods, ops = [], []
    for e in events:
        if e.get("ph") != "X" or e.get("pid") != pid:
            continue
        line = lines.get(e.get("tid"))
        if line == "XLA Modules":
            mods.append((e["ts"], e["ts"] + e["dur"], e["name"]))
        elif line == "XLA Ops":
            ops.append(e)
    mods.sort()
    starts = [m[0] for m in mods]
    out: Dict[str, float] = {}
    for e in ops:
        i = bisect.bisect_right(starts, e["ts"]) - 1
        mod = trace_mod.base_name(mods[i][2]) \
            if i >= 0 and mods[i][1] >= e["ts"] else "?"
        scope = [p for p in str(e.get("args", {}).get("tf_op", "")).split("/")
                 if p.startswith("cim.")]
        op = trace_mod.base_name(e["name"])
        key = f"{mod}:{scope[-1]}/{op}" if scope else f"{mod}:{op}"
        out[key] = out.get(key, 0.0) + e["dur"] / 1e6
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:top]]


def run_xplane(workload: str, seed: int) -> Optional[Path]:
    """The profile of the run of `workload` at `seed`: `bench.cell.run_cell`
    traces into `.bench_out/trace/<workload>-<seed>/`, emptied first."""
    files = sorted((OUT / "trace" / f"{workload}-{seed}").glob(
        "**/*.xplane.pb"))
    return files[-1] if files else None


def command_seed() -> Optional[int]:
    """The `--seed` on this process's command line (`bench/run.py`'s)."""
    ap = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    ap.add_argument("--seed", type=int)
    return ap.parse_known_args(sys.argv[1:])[0].seed


_SUMMARIES: Dict[tuple, Dict] = {}


def of_run(run) -> Optional[Dict]:
    """The summary of the run's profile (read once, then kept), written to
    `.bench_out/<workload>-<seed>-spans.json` and, in short, to standard
    error; None where the command line names no seed, the run left no
    profile or it holds nothing."""
    seed = command_seed()
    path = None if seed is None else run_xplane(run.workload, seed)
    if path is None:
        return None
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _SUMMARIES:
        _SUMMARIES.clear()
        summary = summarize(SpanTrace.from_xplane(path))
        _SUMMARIES[key] = summary
        (OUT / f"{run.workload}-{seed}-spans.json").write_text(
            json.dumps(summary, indent=1))
        if summary:
            brief = {k: summary[k] for k in ("clock_check", "host_eqns",
                                             "host_reads_per_step")}
            brief["idle_by_span"] = summary["idle_by_span"][:8]
            print(f"spans {json.dumps(brief)}", file=sys.stderr, flush=True)
    return _SUMMARIES[key] or None
