"""End-to-end driver: many-task federated LoRA fine-tuning of a REAL
language model from the assigned zoo (reduced qwen2 family), with MaTU
aggregation over the flat LoRA space — the paper's pipeline applied to
an actual transformer.

Three synthetic "tasks" = three next-token languages (distinct Markov
transition structures over the token space).  Each of 4 clients holds
1-2 tasks; per round every client fine-tunes LoRA per task, unifies,
uploads; the stateless server runs Eq. 3-6 and downlinks modulators.

    PYTHONPATH=src python examples/fed_finetune_lm.py [--rounds 5]
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.checkpoint import save
from repro.common.tree import TaskVectorSpace
from repro.configs.base import SHAPES, load_arch
from repro.core.client import ClientUpload
from repro.core.server import MaTUServer, MaTUServerConfig
from repro.core.unify import modulate, unify_with_modulators
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import adamw
from repro.train.trainer import make_train_step


def make_task_sampler(task_id: int, vocab: int, seed: int = 0):
    """Markov-chain 'language' over the token space, one per task."""
    rng = np.random.default_rng(seed + 101 * task_id)
    base = rng.dirichlet([0.05] * 64, size=64)  # sparse 64-state chain

    def sample(key, batch, seq):
        k1, k2 = jax.random.split(key)
        toks = np.zeros((batch, seq), np.int32)
        states = rng.integers(0, 64, batch)
        for s in range(seq):
            probs = base[states]
            states = np.array([rng.choice(64, p=p) for p in probs])
            toks[:, s] = states + task_id * 64  # distinct token regions
        t = jnp.asarray(toks % vocab)
        return {"tokens": t, "labels": jnp.concatenate(
            [t[:, 1:], jnp.full((batch, 1), -100, jnp.int32)], axis=1)}

    return sample


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=48)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = load_arch("qwen2-0.5b").reduced()
    model = cfg.build(SHAPES["train_4k"])
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    lora0 = model.lora_init(jax.random.PRNGKey(1))
    # the flat d-axis is DEFINED by the layout manifest; its fingerprint
    # is what client and server compare before a round
    space = TaskVectorSpace.from_tree(lora0)
    d = space.d
    print(f"model: reduced qwen2 family, LoRA d = {d}, "
          f"layout {space.fingerprint}")

    n_tasks = 3
    client_tasks = [[0], [1], [2], [0, 2]]
    samplers = {t: make_task_sampler(t, cfg.vocab) for t in range(n_tasks)}

    train_step, opt = make_train_step(model, adamw(5e-3))
    server = MaTUServer(MaTUServerConfig(n_tasks=n_tasks))
    downlinks = {}

    def local_finetune(tv_flat, task, rng):
        """θ_p ⊕ τ -> E local steps -> new τ (flat).  The flat vector
        crosses the wire edge through the layout manifest: unflatten
        once on entry, flatten once on return."""
        lora = jax.tree_util.tree_map(
            jnp.add, lora0, space.unflatten(tv_flat))
        state = opt.init(lora)
        loss = None
        for s in range(args.local_steps):
            rng, k = jax.random.split(rng)
            batch = samplers[task](k, args.batch, args.seq)
            lora, state, m = train_step(params, lora, state, batch)
            loss = float(m["loss"])
        delta = jax.tree_util.tree_map(jnp.subtract, lora, lora0)
        return space.flatten(delta), loss

    rng = jax.random.PRNGKey(42)
    for r in range(args.rounds):
        uploads, losses = [], []
        for cid, tasks in enumerate(client_tasks):
            tvs = []
            for i, t in enumerate(tasks):
                rng, k = jax.random.split(rng)
                if cid in downlinks:
                    dl = downlinks[cid]
                    tv0 = modulate(dl.unified, dl.masks[i], dl.lams[i])
                else:
                    tv0 = jnp.zeros((d,), jnp.float32)
                tv, loss = local_finetune(tv0, t, k)
                tvs.append(tv)
                losses.append(loss)
            unified, masks, lams = unify_with_modulators(jnp.stack(tvs))
            uploads.append(ClientUpload(
                cid, tasks, unified, masks, lams,
                [args.batch * args.seq] * len(tasks),
                fingerprint=space.fingerprint))
        downlinks.update(server.round(uploads))
        bits = sum(u.uplink_bits() for u in uploads)
        print(f"round {r+1}: mean local loss {np.mean(losses):.4f}  "
              f"uplink {bits/8/2**20:.2f} MiB  "
              f"S(0,2)={float(server.last_similarity[0,2]):.2f}")

    # results/ckpt/ is git-ignored: run artifacts never land in the tree
    save("results/ckpt/fed_lm", {"task_vectors": server.last_task_vectors},
         {"rounds": args.rounds})
    print("saved server task vectors -> results/ckpt/fed_lm.npz")


if __name__ == "__main__":
    main()
