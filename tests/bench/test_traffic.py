"""The traffic generator: deterministic per seed, the same work for every
seed, and the length distributions its mix files ask for."""
import json

import numpy as np
import pytest

from bench import cell
from bench.traffic import serve_mix

MIXES = sorted(p.stem for p in (cell.BENCH / "workloads").glob("*.json"))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests_and_every_seed_same_work(name):
    mix = json.loads((cell.BENCH / "workloads" / f"{name}.json").read_text())
    a = serve_mix.generate(mix, 2 ** 31 + 12345, 30.0)
    b = serve_mix.generate(mix, 2 ** 31 + 12345, 30.0)
    c = serve_mix.generate(mix, 7, 30.0)
    assert a == b
    assert a != c or mix["loop"] == "closed" and len(set(
        (r.prompt_len, r.gen) for r in a)) == 1
    assert sorted(r.prompt_len for r in a) == sorted(r.prompt_len for r in c)
    assert sorted(r.gen for r in a) == sorted(r.gen for r in c)
    if mix["loop"] == "open":
        ga = np.diff([0.0] + [r.arrival_s for r in a])
        gc = np.diff([0.0] + [r.arrival_s for r in c])
        assert np.allclose(sorted(ga), sorted(gc))
        assert 0 < a[-1].arrival_s < 30.0
        assert len(a) == int(mix["rate_per_s"] * 30.0)
    else:
        assert all(r.arrival_s == 0.0 for r in a)


def test_lognormal_lengths_have_the_asked_median_and_clip():
    spec = {"lognormal": {"median": 128, "sigma": 0.8}, "min": 8, "max": 512}
    x = serve_mix.lengths(spec, 1001)
    assert np.median(x) == 128
    assert x.min() >= 8 and x.max() == 512
    assert list(x) == sorted(x)


def test_weighted_values_follow_the_lognormal_density():
    spec = {"values": [128, 256, 384, 512, 768, 1024, 1536, 2048],
            "lognormal": {"median": 512, "sigma": 0.8}}
    x = serve_mix.lengths(spec, 1000)
    assert set(x) <= set(spec["values"])
    assert np.median(x) == 512
    counts = {v: int((x == v).sum()) for v in spec["values"]}
    assert counts[512] > counts[128] and counts[512] > counts[2048] > 0


def test_plain_values_are_equally_weighted():
    x = serve_mix.lengths({"values": [250, 500, 750, 1500]}, 64)
    assert [int((x == v).sum()) for v in (250, 500, 750, 1500)] == [16] * 4
    assert serve_mix.max_lengths({"prompt_len": {"values": [4]},
                                  "output_len": {"values": [250, 1500]}}) \
        == (4, 1500)
