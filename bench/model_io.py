"""Configurations of the benchmark's models, and the family each belongs to.

A configuration is a JSON file under `bench/configs/`, found by its name. Its
`program` block says how the system under test is set up for it: a registry
architecture and the fields replaced on it. Its optional `"reference"` key
names its model family, a module `bench/reference/<name>.py` (`model`, the
dense decoder, where the key is absent). A family module exports:

- `Sizes`: a frozen dataclass of the shapes the benchmark needs, with at
  least `d_model`, `vocab`, `vocab_padded` and `embed_stub`;
- `sizes_of(cfg)`: those shapes for the program's `ArchConfig`;
- `make_params(key, s, unstacked, dtype=jnp.bfloat16)`: the weights in the
  program's parameter layout, made on the device in one jitted call;
- `logits_at(params, s, inputs, rows, control=None)`: the float32 reference
  and its lower-precision controls;
- `decode_token_flops(s, context)`: model FLOPs of one decoded token.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType

import jax

BENCH = Path(__file__).resolve().parent
FAMILIES = BENCH / "reference"


def load_config(name: str) -> dict:
    path = BENCH / "configs" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration file {path}")
    return json.loads(path.read_text())


def family(conf: dict) -> ModuleType:
    """The family module that a configuration names under `"reference"`,
    loaded from its file under `FAMILIES`. A module already loaded from
    that file is returned as it is, so one family has one copy in a
    process."""
    name = conf.get("reference", "model")
    path = (FAMILIES / f"{name}.py").resolve()
    if not path.is_file():
        raise FileNotFoundError(f"no model family file {path}")
    mod_name = f"bench.reference.{name}"
    mod = sys.modules.get(mod_name)
    if mod is not None and Path(mod.__file__).resolve() == path:
        return mod
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod    # a dataclass looks up its own module
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[mod_name]
        raise
    return mod


def arch_config(conf: dict, cim_bits: int = 0, cim_resident: bool = False):
    """The program's `ArchConfig` for a configuration file. A dict given
    for a dataclass-valued field (`moe`, `mla`) replaces fields of the
    architecture's own value."""
    from repro.configs import get_config

    prog = dict(conf["program"])
    arch = prog.pop("arch")
    cfg = get_config(arch)
    for k, v in prog.items():
        if not isinstance(v, dict):
            continue
        cur = getattr(cfg, k, None)
        if not dataclasses.is_dataclass(cur):
            raise ValueError(f"{arch} has no {k} block to replace fields of "
                             f"(it holds {cur!r})")
        known = {f.name for f in dataclasses.fields(cur)}
        unknown = sorted(set(v) - known)
        if unknown:
            raise ValueError(f"{k} of {arch} has no field {unknown}")
        prog[k] = dataclasses.replace(cur, **v)
    cfg = dataclasses.replace(cfg, **prog)
    cfg = dataclasses.replace(cfg, param_dtype=cfg.dtype)
    if cim_bits:
        cfg = dataclasses.replace(cfg, cim_mlp_bits=cim_bits,
                                  cim_attention_bits=cim_bits,
                                  cim_unroll_groups=True,
                                  cim_resident=cim_resident)
    return cfg


def seed_key(seed: int):
    """A PRNG key for any whole-number seed, including ones past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
