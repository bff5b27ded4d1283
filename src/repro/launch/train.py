"""Training launcher.

Two modes:

* ``fed`` (default) — the paper's pipeline: many-task federated LoRA
  fine-tuning with a selectable aggregation strategy on the synthetic
  constellation, with checkpointing and the communication ledger.

    PYTHONPATH=src python -m repro.launch.train fed --strategy matu \
        --tasks 8 --clients 16 --rounds 40

* ``lm`` — supervised LoRA fine-tuning steps of one assigned
  architecture: the reduced variant by default, the published config
  (full widths, bf16) with ``--no-reduced``.

    PYTHONPATH=src python -m repro.launch.train lm --arch qwen2-0.5b --steps 50
    PYTHONPATH=src python -m repro.launch.train lm --arch qwen2-0.5b --no-reduced
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache


def run_fed(args) -> None:
    from repro.ckpt.checkpoint import save
    from repro.data.dirichlet import dirichlet_split
    from repro.data.synthetic import make_constellation
    from repro.fed.simulator import FedConfig, FedSimulator, individual_baseline
    from repro.fed.strategies import STRATEGIES
    from repro.fed.testbed import MLPBackbone, ViTBackbone

    con = make_constellation(n_tasks=args.tasks, n_groups=3, feat_dim=32,
                             n_classes=8, conflict_pairs=[(0, 1)],
                             seed=args.seed)
    split = dirichlet_split(n_clients=args.clients, n_tasks=args.tasks,
                            n_classes=8, zeta_t=args.zeta_t,
                            tasks_per_client=args.tasks_per_client or None,
                            seed=args.seed)
    bb = (ViTBackbone(seed=args.seed) if args.backbone == "vit"
          else MLPBackbone(32, hidden=64, lora_rank=8, seed=args.seed))
    cfg = FedConfig(rounds=args.rounds, local_steps=args.local_steps,
                    lr=args.lr, participation=args.participation,
                    eval_every=max(args.rounds // 4, 1), seed=args.seed)

    cls = STRATEGIES[args.strategy]
    kw = {"split_point": bb.split_point} if args.strategy == "fedper" else {}
    strat = cls(args.tasks, bb.d, **kw)
    sim = FedSimulator(cfg, con, split, bb, strat)
    hist = sim.run(verbose=True)

    print(f"\nfinal mean acc: {hist.final_mean_acc:.3f}  "
          f"uplink/round: {hist.mean_uplink_bits/8/2**20:.2f} MiB")
    if args.compare_individual:
        ind = individual_baseline(cfg, con, bb)
        print(f"individual upper bound: {np.mean(list(ind.values())):.3f}")
    if args.ckpt and strat.name == "matu":
        save(args.ckpt, {"task_vectors": strat.server.last_task_vectors},
             {"rounds": args.rounds, "strategy": strat.name})
        print(f"saved server task vectors -> {args.ckpt}.npz")


def run_lm(args) -> None:
    from repro.configs.base import SHAPES, input_specs, load_arch
    from repro.optim import adamw, linear_warmup_cosine
    from repro.train.trainer import make_train_step

    cfg = load_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = cfg.build(SHAPES["train_4k"])
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    lora = model.lora_init(jax.random.PRNGKey(args.seed + 1))
    step, opt = make_train_step(
        model, adamw(linear_warmup_cosine(args.lr, 10, args.steps)))
    state = opt.init(lora)
    step = jax.jit(step)

    rng = jax.random.PRNGKey(7)
    for i in range(args.steps):
        rng, k = jax.random.split(rng)
        batch = input_specs(cfg, SHAPES["train_4k"], concrete=True,
                            batch_override=args.batch, seq_override=args.seq)
        batch["tokens"] = jax.random.randint(k, batch["tokens"].shape, 0, cfg.vocab)
        batch["labels"] = jnp.roll(batch["tokens"], -1, axis=1)
        t0 = time.perf_counter()
        lora, state, m = step(params, lora, state, batch)
        if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
            jax.block_until_ready(m["loss"])
            print(f"step {i:4d}  loss {float(m['loss']):.4f}  "
                  f"{(time.perf_counter()-t0)*1e3:.0f} ms")


def main() -> None:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode")

    f = sub.add_parser("fed")
    f.add_argument("--strategy", default="matu")
    f.add_argument("--tasks", type=int, default=8)
    f.add_argument("--clients", type=int, default=16)
    f.add_argument("--rounds", type=int, default=40)
    f.add_argument("--local-steps", type=int, default=30)
    f.add_argument("--lr", type=float, default=1e-2)
    f.add_argument("--zeta-t", type=float, default=0.0)
    f.add_argument("--tasks-per-client", type=int, default=0)
    f.add_argument("--participation", type=float, default=1.0)
    f.add_argument("--backbone", choices=["mlp", "vit"], default="mlp")
    f.add_argument("--compare-individual", action="store_true")
    f.add_argument("--ckpt", default="")
    f.add_argument("--seed", type=int, default=0)

    l = sub.add_parser("lm")
    l.add_argument("--arch", default="qwen2-0.5b")
    l.add_argument("--steps", type=int, default=50)
    l.add_argument("--batch", type=int, default=4)
    l.add_argument("--seq", type=int, default=64)
    l.add_argument("--lr", type=float, default=5e-3)
    l.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="tiny-width smoke variant (--no-reduced builds the "
                        "published config)")
    l.add_argument("--seed", type=int, default=0)

    args = ap.parse_args()
    enable_compile_cache()
    if args.mode == "lm":
        run_lm(args)
    else:
        run_fed(args)


if __name__ == "__main__":
    main()
