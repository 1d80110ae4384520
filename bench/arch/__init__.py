"""The seam between the harness and the architecture it measures.

A configuration file names its architecture under ``"arch"``; a file
without the key is ``"dense"``.  ``load`` gives the module
``bench/arch/<arch>.py``: a plain module of these functions, which the
harness, the check and the per-layer readers call and nothing else of
the architecture.

- ``sizes(config)``: the configuration's sizes, an object with at least
  ``vocab``.
- ``program_config(config)``: the program's model config, once the
  module has checked that the program serves what the file states.
- ``recipe(config)``: the ``QuantRecipe`` the artifact nests with.
- ``program_params(seed, sizes, wcfg)``: the program's parameter tree,
  made on the device from the weight seed.
- ``score(rows, sizes, wcfg, seed, quant)``: the plain float32
  reference, as ``reference.score`` documents it.
- ``quantized_leaves(sizes)``: ``(K, N, count)`` of every weight the
  ladder quantizes, from which ``shapes.delta_bytes`` counts a switch.
- ``decode_step_least_s(sizes, bits, rung, M, peaks)``: the least time
  of the packed matmuls of one decode step of M rows.
- ``decode_flops(sizes, rows, steps)``: the model FLOPs of ``steps``
  decode steps over real rows of (prompt tokens, answer tokens).

A new architecture is a new module here and configuration files that
name it; no file the benchmark already has changes.
"""
from __future__ import annotations

import importlib
import re
from pathlib import Path
from types import ModuleType
from typing import Dict, List

HERE = Path(__file__).resolve().parent
DEFAULT = "dense"
CONTRACT = ("sizes", "program_config", "recipe", "program_params", "score",
            "quantized_leaves", "decode_step_least_s", "decode_flops")


def known() -> List[str]:
    """The architecture modules under ``bench/arch/``."""
    return sorted(p.stem for p in HERE.glob("*.py")
                  if not p.stem.startswith("_"))


def load(config: Dict) -> ModuleType:
    """The architecture module of a configuration file."""
    name = config.get("arch", DEFAULT)
    mod = None
    if isinstance(name, str) and re.fullmatch(r"[A-Za-z]\w*", name):
        try:
            mod = importlib.import_module(f"{__name__}.{name}")
        except ModuleNotFoundError as e:
            if e.name != f"{__name__}.{name}":
                raise
    if mod is None:
        raise SystemExit(f"configuration {config.get('name')!r} names the "
                         f"architecture {name!r}; bench/arch/ has "
                         f"{', '.join(known())}")
    missing = [f for f in CONTRACT if not callable(getattr(mod, f, None))]
    if missing:
        raise SystemExit(f"bench/arch/{name}.py lacks {', '.join(missing)}")
    return mod
