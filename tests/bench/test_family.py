"""A configuration's model family: found by its `"reference"` key, and one
added as a new file alone is the one a run uses for its weights, its
reference and its FLOP count; nested blocks of the program's `ArchConfig`."""
import dataclasses
import json
import time
from pathlib import Path

import pytest

from bench import cell, model_io, record
from bench.reference import model as dense
import tiny

SEED = 2 ** 31 + 91

DOUBLED = '''"""The dense family, with its FLOP count doubled and its reference's
calls counted."""
from bench.reference import model as dense
from bench.reference.model import Sizes, make_params, sizes_of  # noqa: F401

CALLS = []


def logits_at(params, s, inputs, rows, control=None):
    CALLS.append(control)
    return dense.logits_at(params, s, inputs, rows, control)


def decode_token_flops(s, context):
    return 2 * dense.decode_token_flops(s, context)
'''


EXPORTS = ("Sizes", "sizes_of", "make_params", "logits_at",
           "decode_token_flops")


def check_named_family(conf):
    """`model_io.family(conf)` is the module loaded from
    `bench/reference/<conf["reference"]>.py` (`model.py` where the key is
    absent), and exports every name the harness reads."""
    path = model_io.BENCH / "reference" / f"{conf.get('reference', 'model')}.py"
    fam = model_io.family(conf)
    assert Path(fam.__file__).resolve() == path.resolve()
    for name in EXPORTS:
        assert callable(getattr(fam, name)), name
    return fam


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (model_io.BENCH / "configs").glob("*.json")) + ["tiny"])
def test_each_configuration_gets_the_family_it_names(name):
    conf = tiny.TINY if name == "tiny" else model_io.load_config(name)
    fam = check_named_family(conf)
    if "reference" not in conf:
        assert fam is dense


def test_configuration_naming_a_new_family_as_new_files(monkeypatch,
                                                        tmp_path):
    """A configuration file that carries `"reference"`, beside the family
    it names, both new files: `load_config` finds the one and `family`
    loads the other."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "reference").mkdir()
    (tmp_path / "reference" / "doubled.py").write_text(DOUBLED)
    conf = dict(model_io.load_config("granite-3-8b-s8"), reference="doubled")
    (tmp_path / "configs" / "probe.json").write_text(json.dumps(conf))
    monkeypatch.setattr(model_io, "BENCH", tmp_path)
    monkeypatch.setattr(model_io, "FAMILIES", tmp_path / "reference")
    loaded = model_io.load_config("probe")
    assert loaded == conf
    fam = check_named_family(loaded)
    assert fam is not dense


def test_unknown_family_is_an_error():
    with pytest.raises(FileNotFoundError):
        model_io.family(dict(tiny.TINY, reference="no_such_family"))


@pytest.mark.parametrize("which", ["cim", "chat"])
def test_family_added_as_a_file_is_the_one_a_run_uses(monkeypatch, tmp_path,
                                                      which):
    (tmp_path / "doubled.py").write_text(DOUBLED)
    monkeypatch.setattr(model_io, "FAMILIES", tmp_path)
    conf = dict(tiny.TINY, reference="doubled")
    mix = tiny.CIM if which == "cim" else tiny.CHAT
    tiny.patch(monkeypatch, conf, mix)
    runs = []

    def keep_run(**kw):
        runs.append(record.Run(**kw))
        return runs[-1]

    monkeypatch.setattr(cell, "Run", keep_run)
    r = cell.run_cell("tiny-cell", SEED, 2.0, False, time.perf_counter())
    fam = model_io.family(conf)
    assert fam.__file__ == str(tmp_path / "doubled.py")
    assert r["correct"], r["check"]
    assert len(fam.CALLS) >= 1
    (run,) = runs
    steps = run.window_steps("decode")
    base = record.token_flops(
        dataclasses.replace(run, flops=dense.decode_token_flops), steps)
    assert base > 0
    assert record.token_flops(run, steps) == 2 * base


DSV2 = {"arch": "deepseek-v2-lite-16b", "n_layers": 5, "moe": {"n_experts": 8}}


def test_nested_block_replaces_fields_of_the_architectures_own():
    from repro.configs import get_config
    from repro.configs.base import MoEConfig

    published = get_config("deepseek-v2-lite-16b")
    cfg = model_io.arch_config({"program": DSV2})
    assert cfg.n_layers == 5
    assert cfg.moe == MoEConfig(n_experts=8, top_k=6, d_ff_expert=1408,
                                n_shared=2,
                                capacity_factor=published.moe.capacity_factor,
                                router_renorm=published.moe.router_renorm)
    assert cfg.mla == published.mla


@pytest.mark.parametrize("program", [
    dict(DSV2, moe={"n_experts": 8, "n_expert": 8}),
    {"arch": "granite-3-8b", "n_layers": 2, "moe": {"n_experts": 8}},
], ids=["unknown-key", "arch-without-moe"])
def test_nested_block_errors(program):
    with pytest.raises(ValueError):
        model_io.arch_config({"program": program})
