"""Shared set-up of the chip benchmark's own tests: the harness on the
CPU at tiny sizes, with the look for a chip skipped."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
for p in (str(ROOT / "src"), str(CHIP)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

from benchlib import device, load, manifest  # noqa: E402
from benchlib.context import Context  # noqa: E402
from benchlib.manifest import Cell  # noqa: E402

PEAKS = device.load_peaks()["TPU v5 lite"]
SEED = 2**33 + 12345          # wider than 32 bits, as a run's seed may be


def _load_run():
    spec = importlib.util.spec_from_file_location("chipbench_run",
                                                  CHIP / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


RUN = _load_run()

TINY_LM = {"hidden_size": 64, "intermediate_size": 128,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "num_hidden_layers": 2, "vocab_size": 512}
TINY_PROGRAM = {"d_model": 64, "d_ff": 128, "n_heads": 4, "n_kv_heads": 2,
                "n_layers": 2, "vocab": 512}


def config(name: str, **overrides) -> dict:
    with open(CHIP / "configs" / f"{name}.json") as f:
        conf = json.load(f)
    conf.update(overrides)
    return conf


def tiny_lm(name: str, *, d_model: int = 64, d_ff: int = 128,
            n_layers: int = 2, vocab: int = 512) -> dict:
    """A configuration file's model at tiny widths, lora_d recomputed."""
    from repro.common.tree import TaskVectorSpace
    from repro.configs.base import load_arch
    sizes = {"hidden_size": d_model, "intermediate_size": d_ff,
             "num_hidden_layers": n_layers, "vocab_size": vocab}
    conf = config(name, **dict(TINY_LM, **sizes))
    prog = dict(conf["program"])
    prog["overrides"] = dict(prog["overrides"], **dict(
        TINY_PROGRAM, d_model=d_model, d_ff=d_ff, n_layers=n_layers,
        vocab=vocab))
    conf["program"] = prog
    cfg = dataclasses.replace(load_arch(prog["arch"]), **prog["overrides"])
    lshape = jax.eval_shape(cfg.build().lora_init, jax.random.PRNGKey(0))
    conf["lora_d"] = TaskVectorSpace.from_tree(lshape).d
    return conf


def traffic(name: str, **overrides) -> dict:
    with open(CHIP / "traffic" / f"{name}.json") as f:
        tf = json.load(f)
    tf.update(overrides)
    return tf


def cell(workload: str, conf: dict, tf: dict) -> Cell:
    """A cell of BENCHMARK.json with its configuration and traffic
    replaced by tiny ones."""
    c = manifest.cell(manifest.load(), workload)
    return dataclasses.replace(c, config=conf, traffic=tf)


def run_cell(c: Cell, *, seed: int = SEED, seconds: float = 1.0,
             trace: bool = False, trace_dir: str = "") -> dict:
    ctx = Context(c, seed, seconds, trace, jax.devices(), PEAKS,
                  time.perf_counter(), trace_dir)
    return RUN.measure(c, ctx)


def control(c: Cell, *, seed: int = SEED) -> dict:
    ctx = Context(c, seed, 1.0, False, jax.devices(), PEAKS,
                  time.perf_counter(), "")
    return load("runners", c.traffic["runner"]).control(ctx)
