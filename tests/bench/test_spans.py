"""The program's spans beside the benchmark's: idle time put down to the
innermost span at any depth, the clock check, and the readers built on
them."""
import gzip
import json
import random
from pathlib import Path

import pytest

from bench import cell, spans, trace
from bench.record import Run, Step

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6


def brute_force(all_spans, idle):
    """Idle time by innermost span, by scanning every span at each piece."""
    cuts = sorted({x for a, b in idle for x in (a, b)}
                  | {x for _, s, e in all_spans for x in (s, e)})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        if not any(s <= mid < e for s, e in idle):
            continue
        cover = [(s, -(e - s), n) for n, s, e in all_spans if s <= mid < e]
        name = max(cover)[2] if cover else spans.OUTSIDE
        out[name] = out.get(name, 0.0) + b - a
    return out


def test_names_lose_their_metadata():
    assert spans.strip_name("serve.decode#step=3,host_reads=7#") \
        == "serve.decode"
    assert spans.strip_name("cim.host") == "cim.host"


def test_deep_nesting_and_hundreds_of_islands():
    """A decode span holding 8 nested spans, the innermost holding 300
    islands; after the last island, the spans around it still own the
    idle time (four spans of look-back would have found none)."""
    nest = [(f"cim.level{d}", (d + 1) * MS, (999 - d) * MS)
            for d in range(8)]
    islands = [("cim.host", (10 + 3 * i) * MS, (11 + 3 * i) * MS)
               for i in range(300)]
    host = [("bench.decode", 0.0, 1000 * MS)]
    idle = spans.idle_intervals([(500 * MS, 600 * MS)], 0.0, 1000 * MS)
    got = spans.attribute(idle, spans.innermost_pieces(
        host + nest + islands, 0.0, 1000 * MS))
    # 33 islands lie in the busy 100 ms (502 to 599)
    assert got["cim.host"] == pytest.approx(267 * MS)
    assert got["cim.level7"] == pytest.approx((984 - 300 - 67) * MS)
    for d in range(7):
        assert got[f"cim.level{d}"] == pytest.approx(2 * MS)
    assert got["bench.decode"] == pytest.approx(2 * MS)
    assert sum(got.values()) == pytest.approx(900 * MS)


@pytest.mark.parametrize("seed", range(3))
def test_sweep_matches_brute_force(seed):
    rng = random.Random(seed)
    nested = []

    def grow(lo, hi, depth):
        t = lo
        while t < hi and depth < 7:
            s = t + rng.uniform(0, (hi - lo) / 4)
            e = min(hi, s + rng.uniform(0, (hi - lo) / 2))
            if e <= s:
                break
            nested.append((f"s{depth}", s, e))
            grow(s, e, depth + 1)
            t = e
    grow(0.0, 1e6, 0)
    busy = trace.union([(x, x + rng.uniform(0, 2e3))
                        for x in (rng.uniform(0, 1e6) for _ in range(400))])
    idle = spans.idle_intervals(busy, 0.0, 1e6)
    got = spans.attribute(idle, spans.innermost_pieces(nested, 0.0, 1e6))
    want = brute_force(nested, idle)
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k])


def test_clock_check():
    serve = [("serve.decode", 0.0, 10.0, {}), ("serve.emit", 12.0, 20.0, {})]
    # 10 busy between the first and last span, 2 of it between them
    busy = [(-5.0, 1.0), (9.0, 13.0), (15.0, 19.0), (30.0, 40.0)]
    assert spans.inside_share(busy, serve) == pytest.approx(100 * 7 / 9)
    assert spans.inside_share(busy, []) is None


def synthetic():
    """Two decode steps, the first traced without its `serve.decode` span
    (it was open when the profiler started)."""
    modules = [("jit_cim_mlp_r0(1)", 2 * MS, 4 * MS),
               ("jit_cim_mlp_r0(1)", 12 * MS, 14 * MS)]
    ops = [("%fused_planes_op.1 = custom-call()", 2 * MS, 4 * MS),
           ("%fused_planes_op.2 = custom-call()", 12 * MS, 14 * MS),
           ("%fusion.3 = fusion()", 17 * MS, 17.5 * MS)]
    host = [("bench.decode", 1 * MS, 6 * MS),
            ("bench.sample", 6 * MS, 7 * MS),
            ("bench.decode", 11 * MS, 16 * MS),
            ("bench.sample", 16 * MS, 17 * MS)]
    program = [("cim.call", 1 * MS, 5 * MS, {"fn": "mlp"}),
               ("cim.host", 1 * MS, 2 * MS, {"eqns": 7}),
               ("cim.region", 2 * MS, 4 * MS, {"region": 0}),
               ("serve.sample", 6 * MS, 7 * MS, {}),
               ("serve.emit", 7 * MS, 9 * MS, {}),
               ("serve.admit", 9 * MS, 10 * MS, {}),
               ("serve.decode", 10 * MS, 16 * MS,
                {"step": 1, "host_reads": 2}),
               ("cim.call", 11 * MS, 15 * MS, {"fn": "mlp"}),
               ("cim.host", 11 * MS, 12 * MS, {"eqns": 7}),
               ("cim.region", 12 * MS, 14 * MS, {"region": 0}),
               ("serve.sample", 16 * MS, 17 * MS, {}),
               ("serve.emit", 17 * MS, 18 * MS, {}),
               ("serve.decode", 18 * MS, 19 * MS,
                {"step": 2, "host_reads": 4})]
    return spans.SpanTrace(trace.Trace(modules, ops, host), program)


def test_summary_of_a_synthetic_trace(tmp_path):
    st = synthetic()
    st.to_json(tmp_path / "s.json.gz")
    assert spans.SpanTrace.from_json(tmp_path / "s.json.gz") == st
    s = spans.summarize(st)
    # the window is bench/trace.py's, so its idle time is too
    assert s["window_s"] == trace.summarize(st.trace)["window_s"] == 16e-3
    idle = dict(s["idle_by_span"])
    assert idle == pytest.approx({
        "cim.host": 2e-3, "cim.call": 2e-3, "bench.decode": 2e-3,
        "bench.sample": 2e-3, "serve.emit": 2e-3, "serve.admit": 1e-3,
        "serve.decode": 1e-3})
    assert sum(idle.values()) + 4e-3 == pytest.approx(s["window_s"])
    assert s["loop_idle_s"] == pytest.approx({
        "serve.admit": 1e-3, "serve.insert": 0.0, "serve.emit": 2e-3,
        "serve.wait": 0.0})
    assert s["host_eqns"] == 14 and s["cim_spans"] == 6
    assert s["host_reads_per_step"] == 2.0
    # the window holds the first serve.decode, not the second; `fn` is a
    # string and is not summed
    assert s["span_args"]["serve.decode"] == {
        "count": 1, "sum": {"step": 1, "host_reads": 2}}
    assert s["span_args"]["cim.call"] == {"count": 2, "sum": {}}
    assert s["span_args"]["cim.host"] == {"count": 2, "sum": {"eqns": 14}}
    assert s["region_s_by_fn"] == pytest.approx({"mlp": 4e-3})
    # from 6 ms: 2 ms of [12, 14] in serve.decode and 0.5 ms of [17, 17.5]
    # in serve.emit, of 2.5 ms busy
    assert s["clock_check"] == pytest.approx(100.0)


def test_region_split_by_lowered_function():
    """A region program goes under the `<fn>` of `jit_cim_<fn>_r<k>`, or
    `?`; a program without a fused-kernel call is no region."""
    modules = [("jit_cim_attn_bmm_r3(5)", 0.0, 2 * MS),
               ("jit_cim_mlp_r0(1)", 3 * MS, 4 * MS),
               ("jit_add(2)", 5 * MS, 6 * MS),
               ("jit_step(3)", 7 * MS, 10 * MS),
               ("jit_cim_mlp_r2(1)", 11 * MS, 12.5 * MS)]
    ops = [("%fused_planes_op.1 = custom-call()", s + 0.1 * MS, e)
           for name, s, e in modules if name != "jit_add(2)"]
    t = trace.Trace(modules, sorted(ops + [("%add.1 = add()", 5 * MS,
                                            6 * MS)], key=lambda o: o[1]),
                    [])
    got = spans.region_split(t)
    assert got == pytest.approx({"attn_bmm": 2e-3, "mlp": 2.5e-3,
                                 "?": 3e-3})
    assert list(got) == ["?", "mlp", "attn_bmm"]


def test_readers(monkeypatch):
    steps = [Step("decode", 0, 1e-3, 6e-3, True, 1.0),
             Step("decode", 1, 11e-3, 16e-3, True, 1.0)]
    run = Run("w", None, {}, 1.0, (0.0, 1.0), steps, [])
    summary = spans.summarize(synthetic())
    monkeypatch.setattr(spans, "of_run", lambda r: summary)

    def value(name):
        return cell.load_reader(name)(run)
    assert value("host_eqns_per_step.cim") == 7.0
    assert value("host_idle_ms_per_step.cim") == pytest.approx(1.0)
    assert value("host_reads_per_step.chat") == 2.0
    assert value("loop_idle_ms_per_step.chat") == pytest.approx(1.5)


def test_readers_find_nothing_without_program_spans(monkeypatch):
    """A program without spans, like the recorded probe: every new reader
    reads nothing, and the summary's window is bench/trace.py's."""
    st = spans.SpanTrace.from_json(DATA / "probe_mlp_cim.json.gz")
    assert st.spans == []
    summary = spans.summarize(st)
    assert summary["window_s"] == pytest.approx(0.06328881)
    assert summary["clock_check"] is None
    steps = [Step("decode", 0, 0.0, 1.0, True), Step("decode", 1, 1.0, 2.0,
                                                     True)]
    run = Run("w", None, {}, 1.0, (0.0, 2.0), steps, [])
    for found in (summary, None):
        monkeypatch.setattr(spans, "of_run", lambda r: found)
        for name in ("host_eqns_per_step.cim", "host_idle_ms_per_step.cim",
                     "host_reads_per_step.chat",
                     "loop_idle_ms_per_step.chat"):
            assert cell.load_reader(name)(run) is None


def test_of_run_reads_the_runs_own_profile(tmp_path, monkeypatch):
    """The profile under `<workload>-<seed>` for the seed on the command
    line, though another seed's is newer; none without a seed."""
    import os
    import types

    monkeypatch.setattr(spans, "OUT", tmp_path)
    monkeypatch.setattr(spans, "_SUMMARIES", {})
    for seed in (7, 8):
        d = tmp_path / "trace" / f"w-{seed}" / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_text(str(seed))
        os.utime(d / "host.xplane.pb", ns=(seed * 10 ** 9, seed * 10 ** 9))
    monkeypatch.setattr(spans.SpanTrace, "from_xplane",
                        staticmethod(lambda path: Path(path).read_text()))
    monkeypatch.setattr(spans, "summarize", lambda st: {
        "seed": st, "clock_check": None, "host_eqns": 0,
        "host_reads_per_step": None, "idle_by_span": []})
    run = types.SimpleNamespace(workload="w")
    monkeypatch.setattr("sys.argv", ["bench/run.py", "--workload", "w",
                                     "--seed", "7", "--trace", "1"])
    assert spans.command_seed() == 7
    assert spans.of_run(run)["seed"] == "7"
    assert json.loads((tmp_path / "w-7-spans.json").read_text())["seed"] \
        == "7"
    monkeypatch.setattr("sys.argv", ["bench/run.py", "--workload", "w"])
    assert spans.command_seed() is None and spans.of_run(run) is None


def test_recorded_serve_trace():
    """A profile recorded on one TPU v5e by `bench/probe_spans.py`: one
    resident CiM decode step of a 2-layer model at tiny widths, 2 slots,
    through `ServeEngine` under the harness's spans. What `bench/trace.py`
    puts down to `bench.decode` the program's spans split into the
    lowered calls' host islands, region dispatch and call glue, and the
    model's phases between the lowered calls."""
    st = spans.SpanTrace.from_json(DATA / "serve_cim_spans.json.gz")
    t = st.trace
    assert (len(t.modules), len(t.ops), len(t.host), len(st.spans)) \
        == (653, 1992, 2, 55)
    assert not any("#" in s[0] for s in st.spans)
    outside = trace.summarize(t)
    s = spans.summarize(st)
    assert s["window_s"] == outside["window_s"] == pytest.approx(0.23160984)
    idle = dict(s["idle_by_span"])
    assert sum(idle.values()) + outside["busy_s"] \
        == pytest.approx(s["window_s"])
    assert idle["cim.host"] == pytest.approx(0.110136661)
    assert idle["model.qkv"] == pytest.approx(0.052317465)
    assert idle["bench.decode"] == pytest.approx(0.001232784)
    decode_idle = dict(outside["idle_gaps"])["bench.decode"]
    assert sum(v for k, v in idle.items()
               if k == "bench.decode" or k.startswith(("cim.", "model."))) \
        == pytest.approx(decode_idle)
    # all but 0.5% of it lies under a span the program named
    assert idle["bench.decode"] < 0.006 * decode_idle
    assert idle["serve.sample"] > 0
    assert s["clock_check"] == pytest.approx(100.0)
    # 2 layers of sdpa (27 + 43 + 6) and mlp (18 + 22 + 23 + 5) eqns, warm
    assert s["host_eqns"] == 2 * (27 + 43 + 6 + 18 + 22 + 23 + 5) == 288
    # region programs carry their names; mlp's second region runs the
    # program its first compiled; trace.py still finds the kernel in them
    regions = trace.region_modules(t)
    assert {trace.base_name(m[0]) for m, r in zip(t.modules, regions) if r} \
        == {"jit_cim_mlp_r0", "jit_cim_mlp_r2", "jit_cim_sdpa_r0",
            "jit_cim_sdpa_r1"}
    assert sum(regions) == 10
    assert "jit_cim_mlp_r0:cim.kernel/fused_planes_op" in dict(st.scopes)


#: what `summarize` gave for the stored profile before it had `span_args`
#: and `region_s_by_fn`: those keys keep these values
STORED_SUMMARY = {
    "window_s": 0.23160984,
    "program_spans": 53,
    "idle_by_span": [["cim.host", 0.110136661],
                     ["model.qkv", 0.052317465],
                     ["model.kv_write", 0.028128054],
                     ["model.norm", 0.013308962],
                     ["model.cache_slice", 0.007260162],
                     ["model.attn", 0.004482041],
                     ["cim.region", 0.004375487],
                     ["model.head", 0.001967946],
                     ["model.cache_stack", 0.001946648],
                     ["cim.call", 0.001903801],
                     ["model.out_proj", 0.001819715],
                     ["bench.decode", 0.001232784],
                     ["model.cast", 0.001060042],
                     ["model.mlp", 0.000564369],
                     ["model.embed", 0.00033827],
                     ["bench.sample", 0.00019227],
                     ["model.layer", 9.469e-05],
                     ["outside spans", 6.579e-05],
                     ["serve.sample", 3.16e-06]],
    "loop_idle_s": {"serve.admit": 0.0, "serve.insert": 0.0,
                    "serve.emit": 0.0, "serve.wait": 0.0},
    "clock_check": 100.0,
    "cim_spans": 28,
    "host_eqns": 288,
    "host_reads_per_step": None,
    "serve_decode_ms": None,
    "scopes": [
        ["jit_convert_element_type:convert_element_type",
         4.5784921999999996e-05],
        ["jit_broadcast_in_dim:copy", 3.0734688000000016e-05],
        ["jit_multiply:broadcast_multiply_fusion", 1.9512344e-05],
        ["jit_cim_mlp_r0:cim.kernel/fused_planes_op", 1.2753827999999996e-05],
        ["jit_abs:abs", 1.2720156000000002e-05],
        ["jit_squeeze:copy", 1.0808515999999999e-05],
        ["jit_cim_mlp_r0:cim.multiply/slice-done", 9.80125e-06],
        ["jit_cim_mlp_r0:copy-done", 9.737187999999998e-06],
        ["jit_reshape:copy", 9.64586e-06],
        ["jit_round:round", 8.335782e-06],
        ["jit_div:broadcast_divide_fusion", 7.793594e-06],
        ["jit_max:broadcast_maximum_fusion", 7.630078e-06],
        ["jit_reduce_max:copy-done", 7.497185999999999e-06],
        ["jit_cim_sdpa_r1:cim.kernel/fused_planes_op", 6.8539839999999995e-06],
        ["jit_cim_mlp_r2:cim.kernel/fused_planes_op", 6.852968e-06],
        ["jit_dynamic_slice:copy", 6.8278900000000005e-06]],
}


def test_recorded_serve_trace_counters_and_region_split():
    """The stored profile's spans' counters and its region time by lowered
    function, beside every key the summary had, value for value."""
    st = spans.SpanTrace.from_json(DATA / "serve_cim_spans.json.gz")
    s = spans.summarize(st)
    assert set(s) == set(STORED_SUMMARY) | {"span_args", "region_s_by_fn"}
    for key, value in STORED_SUMMARY.items():
        assert s[key] == value, key
    by_fn = s["region_s_by_fn"]
    assert sum(by_fn.values()) == pytest.approx(
        trace.summarize(st.trace)["region_s"], rel=1e-9, abs=0)
    assert set(by_fn) == {"mlp", "sdpa"}
    args = s["span_args"]
    assert args["cim.host"]["sum"]["eqns"] == s["host_eqns"] == 288
    lo = min(h[1] for h in st.trace.host)
    hi = max(h[2] for h in st.trace.host)
    assert args["cim.region"]["count"] == sum(
        1 for n, a, b, _ in st.spans
        if n == "cim.region" and b > lo and a < hi) == 10
    assert "serve.decode" not in args
    assert sum(a["count"] for a in args.values()) == s["program_spans"]
    assert sum(a["count"] for n, a in args.items()
               if n.startswith("cim.")) == s["cim_spans"]


HOST_EQNS_READER = '''"""Eqns bound by the lowering's host islands in the traced window, from
the `cim.host` spans' summed `eqns`."""
from bench import spans


def read(run):
    s = spans.of_run(run)
    host = (s or {}).get("span_args", {}).get("cim.host")
    return host["sum"].get("eqns") if host else None
'''


def test_reader_added_as_a_file_reads_span_args(monkeypatch, tmp_path):
    """A per-layer metric of the program's counters is a new reader file
    over `span_args`: no edit to `bench/spans.py`."""
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "island_eqns.py").write_text(HOST_EQNS_READER)
    monkeypatch.setattr(cell, "BENCH", tmp_path)
    summary = spans.summarize(
        spans.SpanTrace.from_json(DATA / "serve_cim_spans.json.gz"))
    run = Run("w", None, {}, 1.0, (0.0, 1.0), [], [])
    read = cell.load_reader("island_eqns")
    monkeypatch.setattr(spans, "of_run", lambda r: summary)
    assert read(run) == summary["host_eqns"] == 288
    monkeypatch.setattr(spans, "of_run", lambda r: None)
    assert read(run) is None


def test_scope_split(tmp_path):
    """Device op time by program and innermost `cim.*` scope, from the
    trace-viewer file: its ops carry their scope path as `tf_op`."""
    meta = [{"ph": "M", "pid": 3, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
             "args": {"name": "XLA Modules"}},
            {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
             "args": {"name": "XLA Ops"}}]

    def op(name, ts, dur, tf_op):
        return {"ph": "X", "pid": 3, "tid": 3, "ts": ts, "dur": dur,
                "name": name, "args": {"tf_op": tf_op}}
    events = meta + [
        {"ph": "X", "pid": 3, "tid": 2, "ts": 0.0, "dur": 100.0,
         "name": "jit_cim_mlp_r0(7)"},
        op("pad.3", 0.0, 10.0, "jit(cim_mlp_r0)/cim.reduce/concatenate"),
        op("pad.4", 10.0, 20.0, "jit(cim_mlp_r0)/cim.reduce/concatenate"),
        op("fused_planes_op.2", 30.0, 40.0, "jit(cim_mlp_r0)/cim.reduce/"
           "cim.kernel/jit(fused_planes_op)/pallas_call"),
        op("copy.1", 70.0, 5.0, "jit(cim_mlp_r0)/reshape")]
    with gzip.open(tmp_path / "t.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    got = dict(spans.scope_split(tmp_path / "t.trace.json.gz"))
    assert got == pytest.approx({
        "jit_cim_mlp_r0:cim.reduce/pad": 30e-6,
        "jit_cim_mlp_r0:cim.kernel/fused_planes_op": 40e-6,
        "jit_cim_mlp_r0:copy": 5e-6})
