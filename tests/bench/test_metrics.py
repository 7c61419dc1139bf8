"""The metric readers on a small hand-made run."""
import pytest

from bench import cell
from bench.reference import model as dense
from bench.record import RequestLog, Run, Step

S = dense.Sizes(1, 4, 1, 1, 4, 8, 10, 256, False, True, 1e-5, 1e4)
PEAKS = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e3}


def run(trace=None):
    # window [10, 20]: request 0 arrives at 9, admitted at 10, tokens at
    # 11, 12, 14; request 1 arrives at 15 and is still queued at 20
    steps = [Step("prefill", -1, 10.0, 11.0, False),
             Step("decode", 0, 11.5, 12.0, False, words32=5.0),
             Step("decode", 1, 13.0, 14.0, True, words32=7.0)]
    reqs = [RequestLog(0, 3, 3, 9.0, admitted=10.0,
                       token_times=[11.0, 12.0, 14.0],
                       token_steps=[-1, 0, 1], token_ids=[1, 2, 3]),
            RequestLog(1, 3, 3, 15.0)]
    return Run("w", S, PEAKS, 42.0, (10.0, 20.0), steps, reqs, trace,
               flops=dense.decode_token_flops)


def value(name, r):
    return cell.load_reader(name)(r)


def test_end_to_end_readers():
    r = run()
    assert value("tok_s", r) == pytest.approx(3 / 10)
    assert value("tok_s.chat", r) == pytest.approx(3 / 10)
    assert value("setup_s", r) == 42.0
    # gaps 1000 ms and 2000 ms: p95 by linear interpolation
    assert value("itl_p95_ms.chat", r) == pytest.approx(1000 + 0.95 * 1000)


def test_step_readers_leave_traced_steps_out():
    r = run()
    assert value("decode_step_ms.cim", r) == pytest.approx(500.0)
    assert value("prefill_ms_per_ktok.chat", r) == pytest.approx(1000 / 0.003)
    assert value("mfu.cim", r) == pytest.approx(
        100 * (dense.decode_token_flops(S, 4) + dense.decode_token_flops(S, 5))
        / (10 * 1e6))


def test_trace_readers():
    assert value("region_ms_per_step.cim", run()) is None
    t = {"window_s": 4.0, "busy_s": 1.0, "region_s": 0.5, "other_s": 0.25}
    r = run(t)
    assert value("device_idle_pct.cim", r) == pytest.approx(75.0)
    assert value("region_ms_per_step.cim", r) == pytest.approx(500.0)
    assert value("host_island_ms_per_step.cim", r) == pytest.approx(250.0)
    # 7 word-ops of the traced step at 12 bytes over 1000 bytes/s: 84 ms
    assert value("region_roofline", r) == pytest.approx(100 * 0.084 / 0.5)
    assert value("mfu.chat", r) == value("mfu.cim", r)


def test_split_readers_need_decode_steps_alone_in_the_trace():
    """Region and host-island time are the whole trace's, per traced decode
    step: a traced prefill would be charged to the steps, so they read
    nothing then."""
    r = run({"window_s": 4.0, "busy_s": 1.0, "region_s": 0.5, "other_s": 0.25})
    r.steps[0].traced = True
    for name in ("region_ms_per_step.cim", "host_island_ms_per_step.cim",
                 "region_roofline"):
        assert value(name, r) is None
