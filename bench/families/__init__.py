"""Everything the benchmark knows about one kind of model, found by name.

A configuration file names its family under ``"family"``; without the key
it is ``"dense"``.  The family is the module ``bench/families/<family>.py``
of the checkout the cell was loaded from, and provides:

* ``lm_config(config) -> LMConfig``: the program's config for the file,
  refusing any key it cannot honour;
* ``reference_spec(config)`` and ``logits_at(spec, seed, tokens, pos,
  mode="f32")``: the plain reference of ``bench/reference/`` that decides
  ``correct`` (``mode="fp8"`` is its control).  A reference module imports
  nothing of the program, so the program-facing mapping lives here;
* ``decode_calls(config, n_slots)``: the ``ft_matmul`` kernel calls of one
  decode step (``bench.work.Call``), and ``step_model_flops(config, active,
  attended)``: the model FLOPs of one step.

A new family is new files: its module here, its reference, the limits of
its cells and any metric readers it needs.
"""
from __future__ import annotations

import hashlib
import importlib.util
import pathlib
import re
import sys
import types

DIR = pathlib.Path(__file__).resolve().parent
DEFAULT = "dense"


def name(config: dict) -> str:
    return config.get("family", DEFAULT)


def load(config: dict, families_dir: pathlib.Path = DIR) -> types.ModuleType:
    """The family module of ``config`` in ``families_dir``."""
    path = pathlib.Path(families_dir) / f"{name(config)}.py"
    if not path.is_file():
        raise KeyError(f"no family module {path} for family {name(config)!r}")
    return load_file(path)


def load_file(path) -> types.ModuleType:
    """The Python file at ``path`` as a module, run once per process."""
    path = pathlib.Path(path).resolve()
    stem, digest = re.sub(r"\W", "_", path.stem), hashlib.sha1(str(path).encode()).hexdigest()[:12]
    mod_name = f"bench_file_{stem}_{digest}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod          # dataclasses look their module up here
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[mod_name]
        raise
    return mod
