"""Architectures by name. A configuration file's `"arch"` key names one
module, `bench/arch/<arch>.py`, which holds all that the benchmark knows
of that architecture:

  param_shapes(cfg)      leaf shapes, in the program's parameter layout
  fan_in(name, shape)    fan-in of a projection leaf, for its draw
  hidden(params, cfg, tokens, precision)
                         float32 forward: the final normed hidden states
  output_table(params)   the output head's table, rows by token id
  decode_step(cfg, lengths)
                         FLOPs and HBM bytes of one decode step
  program_config(cfg)    the program's ModelConfig

The harness reads the configuration from `<root>/bench/configs/` and
records in it (`"arch_dir"`) the `bench/arch/` directory of the same
root, so a new architecture lands as a new file beside its
configuration. A configuration without `"arch_dir"` takes this
directory.
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib
from typing import List

HERE = pathlib.Path(__file__).resolve().parent


def known(directory: pathlib.Path = HERE) -> List[str]:
    return sorted(p.stem for p in pathlib.Path(directory).glob("*.py")
                  if not p.stem.startswith("_"))


@functools.lru_cache(maxsize=None)
def _load(path: str):
    # one module object per file, so its jitted functions compile once
    spec = importlib.util.spec_from_file_location(
        "bench_arch_" + pathlib.Path(path).stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def of(cfg: dict):
    """The module of `cfg`'s architecture. A missing or unknown `"arch"`
    is an error that lists the known ones, never a default."""
    directory = pathlib.Path(cfg.get("arch_dir", HERE))
    name, names = cfg.get("arch"), known(directory)
    if name not in names:
        raise KeyError(f"configuration {cfg.get('name')!r}: no "
                       f"architecture {name!r}; known: {names}")
    return _load(str(directory / f"{name}.py"))
