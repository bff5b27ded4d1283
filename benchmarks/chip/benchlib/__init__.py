"""Shared harness code of the chip benchmark (``benchmarks/chip``).

Everything that belongs to one configuration, traffic mix, per-layer
metric or kernel lives in a file of its own and is found by name:

* ``configs/<config>.json``   sizes of one model configuration;
* ``traffic/<traffic>.json``  parameters of one traffic mix, naming the
  runner that generates and serves it;
* ``runners/<runner>.py``     ``run(ctx) -> Observations``;
* ``metrics/<metric>.py``     ``read(obs) -> float | None``;
* ``work/<kernel>.py``        required operations and bytes of a call.

:func:`load` imports such a file by path, so file names may carry the
dots of the names in ``BENCHMARK.json``.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parents[1]
ROOT = CHIP_DIR.parents[1]


def load(kind: str, name: str):
    """Import ``<CHIP_DIR>/<kind>/<name>.py`` once and return it."""
    mod_name = f"chipbench.{kind}.{name}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    path = CHIP_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod
