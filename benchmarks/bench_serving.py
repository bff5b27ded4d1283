"""Multi-tenant serving bench: requests/sec of the task-routed decode
subsystem vs per-task-checkpoint swapping, plus the resident-bytes win
that is MaTU's serving headline.

Three timed legs over one mixed-task decode batch on the reduced
qwen2 backbone:

* ``serve_dense``  — ModulatorStore + dense-routed adapters (LRU),
  one compiled program for every task mix;
* ``serve_fused``  — ModulatorStore + the fused ``modulated_matmul``
  path (packed mask bits modulated inside the LoRA matmul);
* ``serve_ckpt_swap`` — the baseline a per-task-checkpoint server
  runs: each request decoded B=1 with its task's own adapter.

Storage: ``resident_bytes`` (backbone adapter + unified vector + T
packed modulators) vs T full per-task checkpoints, at T=30 — the
acceptance bar is a >=5x win.  Detail lands in
results/bench/serving.json.
"""

from __future__ import annotations

import time

from benchmarks.common import save_detail


def _timed_reqs(fn, n_requests, *, reps):
    fn()                                    # compile + warm
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn()
    import jax
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    return dt * 1e6 / reps, reps * n_requests / dt


def run(quick: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.common.tree import TaskVectorSpace, tree_add
    from repro.configs.base import SHAPES, load_arch
    from repro.core.client import ClientUpload
    from repro.core.server import MaTUServer, MaTUServerConfig
    from repro.serve import (GenerationConfig, ModulatorStore,
                             MultiTenantDecoder, generate)

    n_tasks = 30
    batch = 4 if quick else 8
    gen = 4 if quick else 16
    reps = 2 if quick else 5

    cfg = load_arch("qwen2-0.5b").reduced()
    model = cfg.build(SHAPES["decode_32k"])
    params = model.init(jax.random.PRNGKey(0))
    lora0 = model.lora_init(jax.random.PRNGKey(1))
    space = TaskVectorSpace.from_tree(lora0)

    # a real T=30 round over synthetic task vectors (serving is what is
    # being measured, not local training)
    rng = np.random.default_rng(0)
    uploads = [ClientUpload(
        t, [t],
        jnp.asarray(0.05 * rng.standard_normal(space.d), jnp.float32),
        jnp.ones((1, space.d), bool), jnp.ones((1,), jnp.float32), [64],
        fingerprint=space.fingerprint) for t in range(n_tasks)]
    server = MaTUServer(MaTUServerConfig(n_tasks=n_tasks))
    server.round(uploads)

    store = ModulatorStore(space, lora0, capacity=batch)
    store.ingest(server.serving_downlink(fingerprint=space.fingerprint))
    rep = store.storage_report()

    gen_cfg = GenerationConfig(max_new_tokens=gen, temperature=0.0)
    prompts = jax.random.randint(jax.random.PRNGKey(3), (batch, 16),
                                 1, cfg.vocab)
    mix = [t % n_tasks for t in range(batch)]
    max_len = int(prompts.shape[1]) + gen + 8

    dense = MultiTenantDecoder(model, params, store, cfg=gen_cfg)
    fused = MultiTenantDecoder(model, params, store, fused=True,
                               cfg=gen_cfg)
    us_dense, rps_dense = _timed_reqs(
        lambda: dense.generate(prompts, mix), batch, reps=reps)
    us_fused, rps_fused = _timed_reqs(
        lambda: fused.generate(prompts, mix), batch, reps=reps)

    # checkpoint-swap baseline: every request decoded alone with its
    # task's materialised adapter (what T independent checkpoints cost)
    adapters = {t: store.adapter(t) for t in set(mix)}
    gen_one = jax.jit(lambda lora, p: generate(
        model, params, lora, p, gen_cfg, max_len=max_len))

    def ckpt_swap():
        out = None
        for r, t in enumerate(mix):
            out = gen_one(adapters[t], prompts[r:r + 1])
        return out

    us_swap, rps_swap = _timed_reqs(ckpt_swap, batch, reps=reps)

    detail = {"serving": {
        "arch": "qwen2-0.5b-reduced", "d": int(space.d),
        "n_tasks": n_tasks, "batch": batch,
        "max_new_tokens": gen,
        "req_per_s_dense": rps_dense,
        "req_per_s_fused": rps_fused,
        "req_per_s_ckpt_swap": rps_swap,
        "compiled_programs_dense": dense.compile_count(),
        "compiled_programs_fused": fused.compile_count(),
        "resident_bytes": int(rep["resident_bytes"]),
        "checkpoint_bytes": int(rep["checkpoint_bytes"]),
        "resident_ratio_T30": rep["ratio"],
    }}
    save_detail("serving", detail)
    assert rep["ratio"] >= 5.0, \
        f"resident-bytes win {rep['ratio']:.2f}x < 5x at T={n_tasks}"
    return {"rows": [
        ("serve_dense", us_dense,
         f"req_s={rps_dense:.1f} B={batch} T={n_tasks}"),
        ("serve_fused", us_fused, f"req_s={rps_fused:.1f}"),
        ("serve_ckpt_swap", us_swap, f"req_s={rps_swap:.1f}"),
        ("serve_storage", 0.0,
         f"T={n_tasks} resident={rep['resident_bytes']} "
         f"ratio={rep['ratio']:.1f}x"),
    ], "detail": detail}


if __name__ == "__main__":
    out = run(quick=True)
    for r in out["rows"]:
        print(f"{r[0]},{r[1]:.1f},{r[2]}")
