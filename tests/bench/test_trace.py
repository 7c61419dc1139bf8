"""The trace reduction: busy and idle time as a union of device intervals,
region programs told from the others by the fused kernel inside them."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


def synthetic():
    ms = 1e6
    modules = [("jit_fn(1)", 1 * ms, 4 * ms),          # region: kernel inside
               ("jit_mul(2)", 5 * ms, 6 * ms),
               ("jit_fn(3)", 12 * ms, 13 * ms)]        # in the prefill span
    ops = [("%fused_planes_op.3 = u32[8,128] custom-call(...)", 1 * ms, 3 * ms),
           ("%fusion.7 = f32[2] fusion(...)", 2.5 * ms, 4 * ms),   # overlaps
           ("%multiply.1 = f32[2] multiply(...)", 5 * ms, 6 * ms),
           ("%fused_planes_op.9 = u32[8,128] custom-call(...)", 12 * ms, 13 * ms)]
    host = [("bench.decode", 0.5 * ms, 7 * ms),
            ("bench.sample", 7 * ms, 8 * ms),
            ("bench.prefill", 11 * ms, 14 * ms)]
    return trace.Trace(modules, ops, host)


def test_summary_of_a_synthetic_trace():
    s = trace.summarize(synthetic())
    assert s["window_s"] == pytest.approx(13.5e-3)
    # union: [1,4] + [5,6] + [12,13] = 5 ms
    assert s["busy_s"] == pytest.approx(5e-3)
    # by module identity, whichever span each fell in: jit_fn(1) and
    # jit_fn(3) are regions, jit_mul(2) is not
    assert s["region_s"] == pytest.approx(4e-3)
    assert s["other_s"] == pytest.approx(1e-3)
    ops = dict(s["device_ops"])
    assert ops["jit_fn:fused_planes_op"] == pytest.approx(3e-3)
    assert ops["jit_fn:fusion"] == pytest.approx(1.5e-3)
    gaps = dict(s["idle_gaps"])
    # idle: [0.5,1] + [4,5] + [6,7] in decode, [7,8] sample,
    # [8,11] outside any span, [11,12] + [13,14] prefill
    assert gaps["bench.decode"] == pytest.approx(2.5e-3)
    assert gaps["bench.sample"] == pytest.approx(1e-3)
    assert gaps["outside bench spans"] == pytest.approx(3e-3)
    assert gaps["bench.prefill"] == pytest.approx(2e-3)
    assert s["busy_s"] + sum(gaps.values()) == pytest.approx(s["window_s"])


def test_base_names():
    assert trace.base_name("%fusion.18 = (u32[1]) fusion(u32[8] %x)") == "fusion"
    assert trace.base_name("jit_convert_element_type(1538)") \
        == "jit_convert_element_type"
    assert trace.base_name("%fused_planes_op.26 = u32[17,32768] custom-call()") \
        == "fused_planes_op"


def test_json_round_trip(tmp_path):
    t = synthetic()
    t.to_json(tmp_path / "t.json.gz")
    assert trace.Trace.from_json(tmp_path / "t.json.gz") == t


def test_recorded_trace():
    """A trace recorded on one TPU v5e: two calls of a lowered, resident
    SwiGLU MLP (2 rows, 512 x 1024, int8) under `bench.decode`, and two of
    the plain jitted MLP under `bench.plain`."""
    t = trace.Trace.from_json(DATA / "probe_mlp_cim.json.gz")
    assert (len(t.modules), len(t.ops), len(t.host)) == (180, 898, 4)
    regions = trace.region_modules(t)
    assert sum(regions) == 6              # 3 regions a call, 2 calls
    assert {trace.base_name(m[0]) for m, r in zip(t.modules, regions) if r} \
        == {"jit_fn"}
    s = trace.summarize(t)
    assert s["window_s"] == pytest.approx(0.06328881)
    assert s["busy_s"] == pytest.approx(0.003036619)
    assert s["region_s"] == pytest.approx(0.002882458)
    # the lowered calls' other programs and the plain MLP's
    assert s["other_s"] == pytest.approx(0.000224021)
    assert s["device_ops"][0][0] == "jit_fn:fused_planes_op"
    assert s["busy_s"] + sum(v for _, v in s["idle_gaps"]) \
        == pytest.approx(s["window_s"])
