"""The chip: refusal without one, the device record, the peaks table
and JAX's persistent compilation cache."""

from __future__ import annotations

import json
import os
from typing import Dict, List

from benchlib import CHIP_DIR, ROOT

CACHE_DIR = ROOT / ".jax_cache"


class NoChipError(SystemExit):
    """Raised (exit code 1) when JAX finds no TPU or too few chips."""


def require_chips(n: int) -> List:
    """The first ``n`` TPU devices, or exit non-zero: a run is
    meaningful only on the chip and never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChipError(f"chip benchmark: needs a TPU, JAX found "
                          f"{devices[0].platform!r}")
    if len(devices) < n:
        raise NoChipError(f"chip benchmark: the cell needs {n} chips, "
                          f"JAX found {len(devices)}")
    return devices[:n]


def enable_compile_cache() -> str:
    """Keep compiled programs in ``<checkout>/.jax_cache``, a fixed path
    (the path is part of the cache key), for every process of every
    run; the program sees the same directory through
    ``JAX_COMPILATION_CACHE_DIR``."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def load_peaks() -> Dict[str, dict]:
    with open(CHIP_DIR / "peaks.json") as f:
        return json.load(f)


def peaks_for(kind: str) -> dict:
    """Peaks of one ``device_kind``; a kind not in the table is an
    error, never a default."""
    table = load_peaks()
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no entry in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


def record(devices) -> dict:
    import jax
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices())}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip of the cell."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0
