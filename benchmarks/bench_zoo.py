"""Cross-architecture zoo round: wall-clock and measured wire bits for
a mixed round over the reduced model zoo (one ArchBackbone per family),
every client training through its family's REAL forward and flattening
through its own TaskVectorSpace manifest into the shared slot layout.

Rows land next to the engine rows in results/bench/round_engine.json
(``zoo`` key, per-family d + wire bits + the round wall-clock), so one
file holds the whole server-round story.
"""

from __future__ import annotations

import time

from benchmarks.common import save_detail


def run(quick: bool = False) -> dict:
    from repro.data.dirichlet import FedSplit
    from repro.data.synthetic import make_constellation
    from repro.fed.simulator import FedConfig, FedSimulator
    from repro.fed.strategies import MaTUStrategy
    from repro.fed.testbed import make_zoo_backbones, round_up_d

    families = ["lm", "vit", "ssm", "moe"] if quick else \
        ["lm", "encdec", "vit", "ssm", "moe"]
    n_tasks = 8 if quick else 30
    feat_dim = 32  # == reduced vit patch_dim
    zoo = make_zoo_backbones(feat_dim, families=families)

    con = make_constellation(n_tasks=n_tasks, n_groups=4, feat_dim=feat_dim,
                             n_classes=4, seed=0)
    tasks = [[t] for t in range(n_tasks)]
    split = FedSplit(tasks, {(c, c): None for c in range(n_tasks)},
                     {(c, c): 64 for c in range(n_tasks)})
    bbs = {c: zoo[families[c % len(families)]] for c in range(n_tasks)}
    d = round_up_d(max(b.d for b in bbs.values()))

    cfg = FedConfig(rounds=2, local_steps=2 if quick else 4,
                    batch_size=8, local_data=32, eval_every=2, seed=0)
    strat = MaTUStrategy(n_tasks, d)
    sim = FedSimulator(cfg, con, split, bbs, strat)

    t0 = time.perf_counter()
    hist = sim.run()
    us_round = (time.perf_counter() - t0) * 1e6 / cfg.rounds

    uplink = int(hist.uplink_bits_per_round[-1])
    downlink = int(hist.downlink_bits_per_round[-1])
    detail = {"zoo": {
        "families": families,
        "n_tasks": n_tasks,
        "common_d": d,
        "family_d": {f: int(zoo[f].d) for f in families},
        "fingerprints": {f: zoo[f].fingerprint for f in families},
        "us_per_round": us_round,
        "uplink_bits_per_round": uplink,
        "downlink_bits_per_round": downlink,
        "mean_acc": hist.final_mean_acc,
    }}
    save_detail("round_engine", detail)
    return {"rows": [
        ("zoo_round", us_round,
         f"families={len(families)} T={n_tasks} d={d} "
         f"uplink_bits={uplink}"),
    ], "detail": detail}


if __name__ == "__main__":
    out = run(quick=True)
    for r in out["rows"]:
        print(f"{r[0]},{r[1]:.1f},{r[2]}")
