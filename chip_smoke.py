"""Smoke run of the MaTU main path on a TPU, at the published widths of
qwen2-0.5b (the model zoo's ``lm`` entry: 24 layers, d_model 896,
d_ff 4864, vocab 151,936, bf16, LoRA rank 16 on mixer/wq, mixer/wo and
ffn/down — a task vector of d = 3,588,168 coordinates).  Weights are
random, made from ``--seed``; so is every other input.

    python chip_smoke.py [--seed N]              # one chip
    python chip_smoke.py --chips 4 [--seed N]    # four chips

One chip runs four phases through the entry points a user calls, each
printing its facts on one line:

* device  — the first device must be a TPU, or the script exits
  non-zero before doing anything else;
* clients — 4 clients (one holding 2 of the 3 tasks) each take 2 local
  LoRA steps per task (``make_train_step``, batch 4, sequence 256) on
  seeded synthetic token streams, unify their task vectors
  (``batched_client_unify``) and build fingerprinted ``ClientUpload``s;
* server  — ``MaTUServer.round`` (the Pallas kernels on the chip),
  checked against the pure-jnp ``mode="ref"`` round on the same
  uploads, and ``round_chunked`` checked against the monolithic round;
* serving — the round's ``serving_downlink`` feeds a ``ModulatorStore``;
  one mixed-task batch of 8 requests decodes greedily, dense-routed and
  fused, under two task mixes: tokens must agree and each decoder must
  compile exactly one program.

``--chips 4`` runs only the taskvec-sharded round: synthetic uploads at
the same d (N = 32 clients, T = 30 tasks, 2 tasks each) go through
``MaTUServer(mesh=make_round_mesh(4))`` and through a single-device
server, and the script checks the results agree and that the compiled
sharded program splits d four ways.

Every check raises on failure; no phase catches it.  The last line of
standard output is ``{"ok": true, "device": {...}}``, printed only
when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen2-0.5b"
LORA_D = 3_588_168          # jax.eval_shape of lora_init at the published widths
CLIENT_TASKS = [[0], [1], [2], [0, 2]]
N_TASKS = 3
LOCAL_STEPS, BATCH, SEQ = 2, 4, 256
N_REQUESTS, PROMPT_LEN, NEW_TOKENS = 8, 32, 16
TASK_MIXES = ([0, 1, 2, 0, 1, 2, 0, 1], [2, 2, 1, 0, 0, 1, 2, 0])
TOKEN_REGION = 4096         # task t draws tokens from [t·R, (t+1)·R)
SHARDED_CLIENTS, SHARDED_TASKS, SHARDED_K = 32, 30, 2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def equal(a, b) -> bool:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and np.array_equal(a, b)


def close(a, b, rtol: float, atol: float) -> bool:
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return a.shape == b.shape and np.allclose(a, b, rtol=rtol, atol=atol)


def require_tpu(n_chips: int):
    """Phase ``device``: the run is meaningful only on the chip."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} chips, found {len(devices)}")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def task_batch(key, task: int, batch: int, seq: int, region: int):
    """Seeded synthetic token stream of one task: uniform draws from the
    task's own token region, labels are the next token."""
    import jax
    x = jax.random.randint(key, (batch, seq + 1), 0, region) + task * region
    return {"tokens": x[:, :-1], "labels": x[:, 1:]}


def phase_clients(cfg, *, seed: int, client_tasks=CLIENT_TASKS,
                  steps: int = LOCAL_STEPS, batch: int = BATCH,
                  seq: int = SEQ):
    """Local LoRA fine-tuning per (client, task), client-side unify and
    the fingerprinted uploads.  Returns (model, params, lora0, space,
    uploads)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.common.tree import TaskVectorSpace
    from repro.configs.base import SHAPES
    from repro.core.client import ClientUpload
    from repro.core.engine import batched_client_unify
    from repro.optim import adamw
    from repro.train.trainer import make_train_step

    t0 = time.perf_counter()
    model = cfg.build(SHAPES["train_4k"])
    params = model.init(jax.random.PRNGKey(seed))
    lora0 = model.lora_init(jax.random.PRNGKey(seed + 1))
    space = TaskVectorSpace.from_tree(lora0)
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    step, opt = make_train_step(model, adamw(5e-3))
    step = jax.jit(step)

    k_max = max(len(t) for t in client_tasks)
    n_tasks = 1 + max(max(t) for t in client_tasks)
    region = min(TOKEN_REGION, cfg.vocab // n_tasks)
    tvs = np.zeros((len(client_tasks), k_max, space.d), np.float32)
    valid = np.zeros((len(client_tasks), k_max), bool)
    losses = []
    key = jax.random.PRNGKey(seed + 2)
    for cid, tasks in enumerate(client_tasks):
        for slot, task in enumerate(tasks):
            lora, state = lora0, opt.init(lora0)
            for _ in range(steps):
                key, sub = jax.random.split(key)
                lora, state, m = step(params, lora, state,
                                      task_batch(sub, task, batch, seq, region))
                losses.append(float(m["loss"]))
            delta = jax.tree_util.tree_map(jnp.subtract, lora, lora0)
            tvs[cid, slot] = np.asarray(space.flatten(delta))
            valid[cid, slot] = True
    check(bool(np.all(np.isfinite(losses))), f"non-finite loss: {losses}")

    unified, words, lams = batched_client_unify(jnp.asarray(tvs),
                                                jnp.asarray(valid))
    uploads = [ClientUpload(cid, tasks, unified[cid], words[cid, :len(tasks)],
                            lams[cid, :len(tasks)], [batch * seq] * len(tasks),
                            fingerprint=space.fingerprint)
               for cid, tasks in enumerate(client_tasks)]
    check(bool(np.all(np.isfinite(np.asarray(lams)))), "non-finite λ")
    print(f"clients: arch={cfg.name} params={n_params} lora_d={space.d} "
          f"layout={space.fingerprint} clients={len(client_tasks)} "
          f"local_steps={steps} batch={batch} seq={seq} "
          f"losses={[round(x, 4) for x in losses]} finite=True "
          f"t={time.perf_counter() - t0:.1f}s", flush=True)
    return model, params, lora0, space, uploads


def compare_rounds(out_a, out_b, *, rtol: float, atol: float):
    """Field-by-field agreement of two packed-round EngineOutputs:
    integer and bit fields exactly, fp32 fields to (rtol, atol), the
    bf16 wire vectors to one bf16 rounding.  Returns the failures."""
    exact = ("alpha_num", "n_held", "similarity", "down_masks")
    approx = ("task_vectors", "tau_hats", "down_lams")
    bad = [f for f in exact if not equal(getattr(out_a, f), getattr(out_b, f))]
    bad += [f for f in approx
            if not close(getattr(out_a, f), getattr(out_b, f), rtol, atol)]
    if not close(out_a.down_unified, out_b.down_unified, 1e-2, 1e-5):
        bad.append("down_unified")
    return bad


def phase_server(uploads, n_tasks: int = N_TASKS):
    """The MaTU round on the chip's kernels, against the jnp reference
    and the chunked round.  Returns the server (holding the round)."""
    from repro.core.server import MaTUServer, MaTUServerConfig
    from repro.kernels import ops

    t0 = time.perf_counter()
    mode = ops.resolve_mode()
    server = MaTUServer(MaTUServerConfig(n_tasks=n_tasks))
    downs = server.round(uploads)
    downs_k, out_k = server.engine.round(uploads)
    downs_r, out_r = server.engine.round(uploads, mode="ref")
    check(equal(server.last_task_vectors, out_k.task_vectors),
          "MaTUServer.round and RoundEngine.round disagree")
    # tolerances of tests/test_round_engine.py::test_dispatch_modes_agree
    bad = compare_rounds(out_k, out_r, rtol=1e-5, atol=1e-5)
    check(not bad, f"{mode} round != ref round on {bad}")
    for cid, dl in downs.items():
        check(equal(dl.masks, downs_r[cid].masks),
              f"client {cid} downlink masks differ from ref")

    downs_c, out_c, stats = server.engine.round_chunked(uploads,
                                                        chunk_clients=2)
    # the chunked round's contract: bit-identical to the monolithic
    # round computed by the same (streaming jnp) math
    bad = [f for f in ("task_vectors", "tau_hats", "similarity", "m_hats")
           if not equal(getattr(out_c, f), getattr(out_r, f))]
    bad += [f"client {cid} {f}" for cid, dl in downs_c.items()
            for f in ("unified", "masks", "lams")
            if not equal(getattr(dl, f), getattr(downs_r[cid], f))]
    check(not bad, f"chunked round != monolithic round on {bad}")
    print(f"server: mode={mode} clients={len(uploads)} tasks={n_tasks} "
          f"pallas_vs_ref=match chunked(C=2, chunks={stats['n_chunks']})"
          f"_vs_monolithic=bit-identical "
          f"max|tv-tv_ref|={float(abs(out_k.task_vectors - out_r.task_vectors).max()):.3g} "
          f"t={time.perf_counter() - t0:.1f}s", flush=True)
    return server


def phase_serving(cfg, model, params, lora0, space, server, *, seed: int,
                  n_requests: int = N_REQUESTS,
                  prompt_len: int = PROMPT_LEN,
                  new_tokens: int = NEW_TOKENS, mixes=TASK_MIXES):
    """Multi-tenant greedy decode, dense-routed and fused, over the
    round's serving handoff."""
    import jax
    import numpy as np
    from repro.serve import (GenerationConfig, ModulatorStore,
                             MultiTenantDecoder)

    t0 = time.perf_counter()
    store = ModulatorStore(space, lora0, capacity=N_TASKS)
    store.ingest(server.serving_downlink(fingerprint=space.fingerprint))
    gen = GenerationConfig(max_new_tokens=new_tokens, temperature=0.0)
    prompts = jax.random.randint(jax.random.PRNGKey(seed + 3),
                                 (n_requests, prompt_len), 1, cfg.vocab)
    dense = MultiTenantDecoder(model, params, store, cfg=gen)
    fused = MultiTenantDecoder(model, params, store, fused=True, cfg=gen)
    for mix in mixes:
        out_d = np.asarray(dense.generate(prompts, mix))
        out_f = np.asarray(fused.generate(prompts, mix))
        check(out_d.shape == (n_requests, prompt_len + new_tokens),
              f"decoded shape {out_d.shape}")
        check(bool(((out_d >= 0) & (out_d < cfg.vocab)).all()),
              "token id out of range")
        n_diff = int((out_d != out_f).sum())
        check(n_diff == 0, f"mix {mix}: fused and dense decode differ in "
                           f"{n_diff} tokens")
    counts = (dense.compile_count(), fused.compile_count())
    check(counts == (1, 1), f"decode programs compiled {counts}, want (1, 1)")
    print(f"serving: requests={n_requests} prompt={prompt_len} "
          f"new_tokens={new_tokens} mixes={len(mixes)} "
          f"fused_vs_dense=tokens-identical compile_count=dense:{counts[0]}"
          f",fused:{counts[1]} t={time.perf_counter() - t0:.1f}s",
          flush=True)


def sharded_uploads(*, seed: int, d: int, n_clients: int, n_tasks: int,
                    k: int):
    """Seeded synthetic wire uploads: every client holds ``k`` distinct
    tasks with random task vectors, unified on the device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.client import ClientUpload
    from repro.core.engine import batched_client_unify

    rng = np.random.default_rng(seed)
    tvs = jax.random.normal(jax.random.PRNGKey(seed), (n_clients, k, d),
                            jnp.float32)
    unified, words, lams = batched_client_unify(
        tvs, jnp.ones((n_clients, k), bool))
    return [ClientUpload(cid,
                         sorted(rng.choice(n_tasks, k, replace=False).tolist()),
                         unified[cid], words[cid], lams[cid],
                         rng.integers(10, 200, size=k).tolist())
            for cid in range(n_clients)]


def phase_sharded(*, seed: int, n_devices: int, d: int = LORA_D,
                  n_clients: int = SHARDED_CLIENTS,
                  n_tasks: int = SHARDED_TASKS, k: int = SHARDED_K):
    """The taskvec-sharded round on ``n_devices`` chips against the
    single-device round, plus the layout of the compiled program."""
    import re
    from repro.core.engine import pack_uploads, pad_d_for_shards
    from repro.core.server import MaTUServer, MaTUServerConfig
    from repro.kernels import ops
    from repro.launch.mesh import make_round_mesh

    t0 = time.perf_counter()
    mode = ops.resolve_mode()
    uploads = sharded_uploads(seed=seed, d=d, n_clients=n_clients,
                              n_tasks=n_tasks, k=k)
    mesh = make_round_mesh(n_devices)
    single = MaTUServer(MaTUServerConfig(n_tasks=n_tasks))
    sharded = MaTUServer(MaTUServerConfig(n_tasks=n_tasks), mesh=mesh)
    downs_1, out_1 = single.engine.round(uploads)
    downs_n, out_n = sharded.engine.round(uploads)
    # tolerances of tests/test_sharded_engine.py (the λ partial sums
    # cross shards through a psum whose grouping differs)
    bad = compare_rounds(out_1, out_n, rtol=1e-4, atol=1e-5)
    bad += [f"client {cid} masks" for cid, dl in downs_1.items()
            if not equal(dl.masks, downs_n[cid].masks)]
    check(not bad, f"{n_devices}-way sharded round != single-device on {bad}")

    # the compiled program must hold a 1/n_devices d-slice per device
    batch = pack_uploads(uploads, n_tasks, mesh=mesh)
    d_pad = pad_d_for_shards(d, n_devices)
    args = (batch.unified, batch.slot_masks, batch.slot_lams,
            batch.slot_sizes, batch.slot_valid, batch.slot_tasks)
    compiled = sharded.engine._impl(mode, batch.d).lower(*args).compile()
    in_sh = compiled.input_shardings[0]
    shard_shape = in_sh[0].shard_shape(batch.unified.shape)
    n_dev = len(in_sh[0].device_set)
    check(shard_shape == (batch.unified.shape[0], d_pad // n_devices)
          and n_dev == n_devices,
          f"unified arrives as {shard_shape} on {n_dev} devices")
    hlo = compiled.as_text()
    local = f"bf16[{batch.unified.shape[0]},{d_pad // n_devices}]"
    check(local in hlo and f",{d_pad}]" not in hlo,
          f"compiled program does not hold {local} per device")
    n_allreduce = len(re.findall(r"= \S+ %?all-reduce\(", hlo))
    print(f"sharded: mode={mode} devices={n_devices} clients={n_clients} "
          f"tasks={n_tasks} k={k} d={d} d_pad={d_pad} "
          f"per_device={local} all_reduces={n_allreduce} "
          f"sharded_vs_single=match t={time.perf_counter() - t0:.1f}s",
          flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the taskvec-sharded round")
    args = ap.parse_args()

    device = require_tpu(args.chips)
    from repro.configs.base import load_arch
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.chips == 4:
        phase_sharded(seed=args.seed, n_devices=4)
    else:
        cfg = load_arch(ARCH)
        model, params, lora0, space, uploads = phase_clients(cfg,
                                                             seed=args.seed)
        check(space.d == LORA_D, f"LoRA d {space.d} != {LORA_D}")
        server = phase_server(uploads)
        phase_serving(cfg, model, params, lora0, space, server,
                      seed=args.seed)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
