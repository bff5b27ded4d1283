"""Shared harness for the paper-table benchmarks.

Each bench_* module exposes ``run(quick: bool) -> dict`` returning
{"rows": [(name, us_per_call, derived)], "detail": {...}}; run.py
aggregates the CSV and persists detail JSON under results/bench/.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict

import numpy as np

RESULTS_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "results", "bench")


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, (time.perf_counter() - t0) * 1e6


def save_detail(name: str, detail: Dict) -> None:
    """Persist a bench's detail dict, merging into any existing
    ``results/bench/<name>.json`` instead of clobbering it — a re-run
    of one leg must not drop the rows another leg wrote earlier.  The
    merge is one level deep: legs share top-level grid keys but each
    writes its own sub-keys, so dict values merge per sub-key (new leg
    wins on conflicts) while scalar values replace."""
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    merged: Dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                merged = json.load(f)
        except (json.JSONDecodeError, OSError):
            merged = {}    # unreadable stale file: start fresh
    for k, v in detail.items():
        if isinstance(v, dict) and isinstance(merged.get(k), dict):
            merged[k].update(v)
        else:
            merged[k] = v
    with open(path, "w") as f:
        json.dump(merged, f, indent=2, default=lambda o: float(o)
                  if isinstance(o, (np.floating,)) else str(o))


def standard_setting(n_tasks=8, n_clients=16, zeta_t=0.0, tasks_per_client=None,
                     conflict_pairs=((0, 1),), seed=0):
    from repro.data.dirichlet import dirichlet_split
    from repro.data.synthetic import make_constellation
    from repro.fed.testbed import MLPBackbone

    con = make_constellation(n_tasks=n_tasks, n_groups=3, feat_dim=32,
                             n_classes=8, conflict_pairs=list(conflict_pairs),
                             seed=seed)
    split = dirichlet_split(n_clients=n_clients, n_tasks=n_tasks, n_classes=8,
                            zeta_t=zeta_t, tasks_per_client=tasks_per_client,
                            zeta_c=0.1, seed=seed)
    bb = MLPBackbone(32, hidden=64, lora_rank=8)
    return con, split, bb


def run_strategy(name, con, split, bb, cfg, **strategy_kw):
    from repro.fed.simulator import FedConfig, FedSimulator
    from repro.fed.strategies import STRATEGIES

    cls = STRATEGIES[name]
    if name == "fedper":
        strategy_kw.setdefault("split_point", bb.split_point)
    strat = cls(con.n_tasks, bb.d, **strategy_kw)
    sim = FedSimulator(cfg, con, split, bb, strat)
    hist = sim.run()
    return hist, strat
