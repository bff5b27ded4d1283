"""Multi-tenant serving demo: ONE backbone + ONE unified task vector +
T cheap modulators, decoding a mixed-task batch through one compiled
program.

An actual federated round feeds serving: per-task clients fine-tune
LoRA on distinct Markov "languages" (same rig as fed_finetune_lm),
the MaTU server aggregates, and ``serving_downlink`` hands the round's
unified vector + packed modulators straight to a ``ModulatorStore``.
Requests then carry task ids as DATA: the routed decode program
compiles once and serves every task mix — dense-routed adapters from
the store's LRU, or the fused path where packed mask bits are
modulated inside the LoRA matmul kernel.

    PYTHONPATH=src python examples/serve_decode.py [--quick]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.tree import TaskVectorSpace
from repro.configs.base import SHAPES, load_arch
from repro.core.client import ClientUpload
from repro.core.server import MaTUServer, MaTUServerConfig
from repro.core.unify import unify_with_modulators
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import adamw
from repro.serve import GenerationConfig, ModulatorStore, MultiTenantDecoder
from repro.train.trainer import make_train_step

from fed_finetune_lm import make_task_sampler


def federated_round(model, params, lora0, space, samplers, *,
                    local_steps, batch, seq, vocab):
    """One synchronous round, one single-task client per task, through
    the real local-trainer + MaTU server pipeline."""
    train_step, opt = make_train_step(model, adamw(5e-3))
    uploads = []
    rng = jax.random.PRNGKey(42)
    for t in sorted(samplers):
        lora = lora0
        state = opt.init(lora)
        for _ in range(local_steps):
            rng, k = jax.random.split(rng)
            lora, state, m = train_step(params, lora, state,
                                        samplers[t](k, batch, seq))
        delta = jax.tree_util.tree_map(jnp.subtract, lora, lora0)
        unified, masks, lams = unify_with_modulators(
            space.flatten(delta)[None])
        uploads.append(ClientUpload(
            t, [t], unified, masks, lams, [batch * seq],
            fingerprint=space.fingerprint))
    server = MaTUServer(MaTUServerConfig(n_tasks=len(samplers)))
    server.round(uploads)
    return server


def timed_batches(decoder, prompts, task_ids, *, reps):
    decoder.generate(prompts, task_ids)                 # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = decoder.generate(prompts, task_ids)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    return out, reps * len(task_ids) / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-smoke sizes (fewer local steps / reps)")
    ap.add_argument("--tasks", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()
    local_steps = args.local_steps or (2 if args.quick else 6)
    reps = 2 if args.quick else 8

    cfg = load_arch("qwen2-0.5b").reduced()
    model = cfg.build(SHAPES["decode_32k"])
    params = model.init(jax.random.PRNGKey(0))
    lora0 = model.lora_init(jax.random.PRNGKey(1))
    space = TaskVectorSpace.from_tree(lora0)
    print(f"backbone: reduced qwen2, LoRA d = {space.d}, "
          f"layout {space.fingerprint}")

    samplers = {t: make_task_sampler(t, cfg.vocab)
                for t in range(args.tasks)}
    server = federated_round(model, params, lora0, space, samplers,
                             local_steps=local_steps, batch=4, seq=32,
                             vocab=cfg.vocab)

    # -- the serving handoff: one downlink makes the round resident ----
    store = ModulatorStore(space, lora0, capacity=args.tasks)
    store.ingest(server.serving_downlink(fingerprint=space.fingerprint))
    rep = store.storage_report()
    print(f"store: {rep['tasks']} tasks resident in "
          f"{rep['resident_bytes']/2**20:.2f} MiB vs "
          f"{rep['checkpoint_bytes']/2**20:.2f} MiB of per-task "
          f"checkpoints ({rep['ratio']:.1f}x smaller)")

    # -- mixed-task traffic: task ids are data, one program serves all --
    gen_cfg = GenerationConfig(max_new_tokens=8, temperature=0.0)
    b = args.tasks
    prompts = jax.random.randint(jax.random.PRNGKey(3), (b, 16),
                                 1, cfg.vocab)
    mixes = [list(range(args.tasks)),
             list(range(args.tasks))[::-1],
             [0] * b]
    dense = MultiTenantDecoder(model, params, store, cfg=gen_cfg)
    fused = MultiTenantDecoder(model, params, store, fused=True,
                               cfg=gen_cfg)

    for mix in mixes:
        out = dense.generate(prompts, mix)
        print(f"  mix {mix}: first tokens "
              f"{[int(x) for x in out[:, prompts.shape[1]]]}")
    assert dense.compile_count() == 1, "decode recompiled across mixes"

    mix = mixes[0]
    out_d, rps_d = timed_batches(dense, prompts, mix, reps=reps)
    out_f, rps_f = timed_batches(fused, prompts, mix, reps=reps)
    same = bool(jnp.array_equal(out_d, out_f))
    print(f"dense-routed: {rps_d:.1f} req/s   fused: {rps_f:.1f} req/s   "
          f"tokens identical: {same}")
    print(f"compiled decode programs: dense={dense.compile_count()} "
          f"fused={fused.compile_count()}  "
          f"LRU hits/misses: {store.hits}/{store.misses}")
    assert same, "fused decode diverged from dense-routed"


if __name__ == "__main__":
    main()
