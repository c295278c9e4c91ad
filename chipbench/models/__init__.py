"""The model of a configuration: a module found by the name its file gives.

A configuration file names its model (``"model": "dense"``), and the model is
the module ``models/<model>.py`` beside this file. ``harness.load_cell``
resolves the name under the checkout it loads from and keeps the path in the
configuration (``FILE_KEY``); a configuration read straight from its file
finds the module in this directory. Adding a model adds one file here and
edits none. The module gives:

- ``leaf_specs(cfg)``: {path: (shape, init)} or {path: (shape, init, dtype)}
  of every parameter, in the repo's parameter layout (``chipbench.weights``
  draws them): init is a std of a normal draw, ``weights.ONES``,
  ``weights.ZEROS`` or a function of (key, shape) that returns float32, and
  a leaf without a dtype is held in the configuration's ``torch_dtype``;
- ``nll_sum(params, tokens, cfg, precision, targets)``: the plain float32
  reference's forward pass and summed next-token loss, every matmul through
  ``chipbench.reference._mm`` so that ``precision="fp8"`` is the control;
- ``model_flops_per_token(cfg, seq_len)``: training FLOPs per token, counted
  from the file's shapes alone;
- ``program_config(cfg)``: the program's ``ModelConfig`` for the file;
- ``SMALL`` and ``WIDE``: keys that shrink the file to sizes a CPU holds, a
  tiny model for the planted faults and the published widths at little depth
  for the fp8 control;
- optionally ``LAYERS``: {metric: (scope, ...)}, the program's scopes of the
  model's own layers and the per-layer metric each counts under, beside the
  scopes every model shares (``chipbench.scopes``).
"""
from __future__ import annotations

import functools
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))
FILE_KEY = "model_file"


def path(cfg: dict, models_dir: str = HERE) -> str:
    """The file of the model that ``cfg`` names, in ``models_dir``."""
    if "model" not in cfg:
        raise KeyError(f"configuration {cfg.get('name')!r} names no model")
    return os.path.join(models_dir, f"{cfg['model']}.py")


def of(cfg: dict):
    """The module of the model that ``cfg`` names."""
    return _load(cfg.get(FILE_KEY) or path(cfg))


@functools.lru_cache(maxsize=None)
def _load(file: str):
    name = os.path.splitext(os.path.basename(file))[0]
    spec = importlib.util.spec_from_file_location(f"chipbench.models.{name}", file)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
