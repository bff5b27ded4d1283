"""Runner ``serve``: multi-tenant greedy decode through
``MultiTenantDecoder.generate`` over a ``ModulatorStore``.

Traffic parameters:

* ``batch``, ``prompt_len``, ``new_tokens``: the decode program's
  shape; every batch holds ``batch`` distinct requests;
* ``tasks``, ``zipf_s``: each request's task, Zipf(s) over the tasks;
* ``pool_batches``: the requests are a pool of this many batches, the
  window cycles over it; the sequence of task ranks is fixed, and the
  seed maps ranks to tasks and draws the prompts;
* ``lru_capacity``: the store's LRU of materialised adapters;
* ``modulator_density``, ``task_vector_scale``, ``lambda_range``: the
  all-tasks downlink the store ingests in set-up (bf16 unified vector,
  packed mask rows, lambdas), as a server's ``serving_downlink`` ships;
* ``check_requests``: completed requests, drawn from the seed, whose
  served tokens the reference scores;
* ``limits``: the limit of each compared number.

Closed loop: the decoder takes the next ``batch`` requests of the pool
as soon as it is free, on its default (dense-routed) path; a request
completes when its tokens are in host memory.
"""

from __future__ import annotations

import time

import numpy as np

from benchlib import bits, seeds
from benchlib.lm import build_model, make_state
from benchlib.context import Check, Observations
from benchlib.trace import reduce, span


def leaf_layout(lora):
    """(offset, shape) of each LoRA leaf on the flat task-vector axis:
    canonical tree order, each leaf raveled C-order."""
    import jax
    out, off = [], 0
    for leaf in jax.tree_util.tree_leaves(lora):
        out.append((off, tuple(leaf.shape)))
        off += int(np.prod(leaf.shape))
    return out


def make_downlink(seed: int, tf: dict, d: int):
    """All-tasks serving downlink: bf16 unified vector (d,), packed mask
    rows (T, W), lambdas (T,)."""
    import jax
    import jax.numpy as jnp
    t = tf["tasks"]
    k1, k2 = seeds.key(seed, 20), seeds.key(seed, 21)
    unified = (jax.random.normal(k1, (d,), jnp.float32)
               * tf["task_vector_scale"]).astype(jnp.bfloat16)
    masks = jax.random.uniform(k2, (t, d)) < tf["modulator_density"]
    lo, hi = tf["lambda_range"]
    lams = seeds.rng(seed, 22).uniform(lo, hi, size=t).astype(np.float32)
    return unified, bits.pack(masks), jnp.asarray(lams)


def adapter(lora0, unified, words, lam, d: int):
    """Reference adapter of one task: lora0 + unflatten(lam * m * tau),
    in float32."""
    import jax
    import jax.numpy as jnp
    delta = lam * jnp.where(bits.unpack(words, d),
                            unified.astype(jnp.float32), 0.0)
    leaves, treedef = jax.tree_util.tree_flatten(lora0)
    out = [leaf.astype(jnp.float32)
           + delta[off:off + int(np.prod(shape))].reshape(shape)
           for leaf, (off, shape) in zip(leaves, leaf_layout(lora0))]
    return jax.tree_util.tree_unflatten(treedef, out)


def task_ids(seed: int, tf: dict, n: int) -> np.ndarray:
    """Zipf(s) task ranks in a fixed sequence, the same for every seed;
    the seed only chooses which task holds each rank, so every seed's
    batches make the store do the same work."""
    fixed = np.random.default_rng(8)
    z = np.zeros(0, np.int64)
    while len(z) < n:
        draw = fixed.zipf(tf["zipf_s"], size=2 * n + 64)
        z = np.concatenate([z, draw[draw <= tf["tasks"]]])
    return seeds.rng(seed, 31).permutation(tf["tasks"])[z[:n] - 1].astype(np.int64)


def serve_gaps(params, store_state, cfg_json, samples, quant=None):
    """Widest gap, over the sampled requests' served tokens, between
    the reference's best logit and the served token's."""
    import jax
    import jax.numpy as jnp
    from chipref import qwen2
    lora0, unified, words, lams, d = store_state
    fwd = jax.jit(lambda p, lo, tok: qwen2.logits(p, lo, tok, cfg_json, quant))
    worst = 0.0
    for task, prompt, served in samples:
        lo = adapter(lora0, unified, words[task], lams[task], d)
        seq = np.concatenate([prompt, served])[None, :-1]
        lg = fwd(params, lo, jnp.asarray(seq, jnp.int32))[0]   # (S-1, V)
        lg = lg[len(prompt) - 1:]
        picked = jnp.take_along_axis(lg, jnp.asarray(served)[:, None], 1)[:, 0]
        worst = max(worst, float(jnp.max(jnp.max(lg, axis=-1) - picked)))
    return worst


def control_gaps(params, store_state, cfg_json, samples):
    """The control: the reference at fp8 in the program's place; at each
    position of the same sequences, the gap of the token it puts first."""
    import jax
    import jax.numpy as jnp
    from chipref import qwen2
    lora0, unified, words, lams, d = store_state
    fwd = jax.jit(lambda p, lo, tok, q: qwen2.logits(p, lo, tok, cfg_json, q),
                  static_argnums=3)
    worst = 0.0
    for task, prompt, served in samples:
        lo = adapter(lora0, unified, words[task], lams[task], d)
        seq = jnp.asarray(np.concatenate([prompt, served])[None, :-1], jnp.int32)
        ref = fwd(params, lo, seq, None)[0][len(prompt) - 1:]
        low = fwd(params, lo, seq, "fp8")[0][len(prompt) - 1:]
        first = jnp.argmax(low, axis=-1)
        picked = jnp.take_along_axis(ref, first[:, None], 1)[:, 0]
        worst = max(worst, float(jnp.max(jnp.max(ref, axis=-1) - picked)))
    return worst


class Setup:
    """Model, store and decoder as one seed makes them."""

    def __init__(self, ctx):
        from repro.common.tree import TaskVectorSpace
        from repro.core.client import ClientDownlink
        from repro.serve import (GenerationConfig, ModulatorStore,
                                 MultiTenantDecoder)
        tf, conf = ctx.traffic, ctx.config
        self.cfg, self.model = build_model(conf)
        self.params, self.lora0 = make_state(conf, self.model, ctx.seed,
                                             lora_b_scale=0.0)
        space = TaskVectorSpace.from_tree(self.lora0)
        self.d = space.d
        if self.d != conf["lora_d"]:
            raise ValueError(f"LoRA d {self.d} != {conf['lora_d']}")
        unified, words, lams = make_downlink(ctx.seed, tf, self.d)
        self.store = ModulatorStore(space, self.lora0,
                                    capacity=tf["lru_capacity"])
        self.store.ingest(ClientDownlink(unified, words, lams,
                                         fingerprint=space.fingerprint))
        self.store_state = (self.lora0, unified, words, lams, self.d)
        gen = GenerationConfig(max_new_tokens=tf["new_tokens"],
                               temperature=0.0)
        self.decoder = MultiTenantDecoder(self.model, self.params, self.store,
                                          cfg=gen)

    def generate(self, prompts: np.ndarray, tasks) -> np.ndarray:
        import jax.numpy as jnp
        with span("prepare"):
            p = jnp.asarray(prompts, jnp.int32)
        with span("generate"):
            out = self.decoder.generate(p, [int(t) for t in tasks])
        with span("to_host"):
            return np.asarray(out)


def requests(seed: int, tf: dict, vocab: int):
    """The pool: task ids (P,) and prompts (P, prompt_len)."""
    n = tf["batch"] * tf["pool_batches"]
    tasks = task_ids(seed, tf, n)
    prompts = seeds.rng(seed, 32).integers(1, vocab, size=(n, tf["prompt_len"]),
                                           dtype=np.int32)
    return tasks, prompts


def run(ctx) -> Observations:
    tf = ctx.traffic
    b, plen, new = tf["batch"], tf["prompt_len"], tf["new_tokens"]
    s = Setup(ctx)
    tasks, prompts = requests(ctx.seed, tf, ctx.config["vocab_size"])
    pool = len(tasks)
    # warm-up: the one decode program, and the store's rebuild ops
    warm = seeds.rng(ctx.seed, 33)
    for _ in range(2):
        s.generate(prompts[:b], warm.integers(0, tf["tasks"], size=b))
    obs = Observations(peaks=ctx.peaks)
    obs.end_to_end["setup_s"] = ctx.setup_done()
    compiles0 = s.decoder.compile_count()
    hits0, misses0 = s.store.hits, s.store.misses

    pick = seeds.rng(ctx.seed, 34)
    keep = int(tf["check_requests"])
    sample = []                      # (request index, served tokens)
    seconds = ctx.window_seconds()
    gen_s, batches, served = 0.0, 0, 0
    with ctx.window():
        t0 = time.perf_counter()
        while True:
            idx = (batches * b + np.arange(b)) % pool
            g0 = time.perf_counter()
            out = s.generate(prompts[idx], tasks[idx])
            gen_s += time.perf_counter() - g0
            for row, i in enumerate(idx):          # reservoir over requests
                if len(sample) < keep:
                    sample.append((int(i), out[row, plen:].copy()))
                else:
                    j = int(pick.integers(0, served + row + 1))
                    if j < keep:
                        sample[j] = (int(i), out[row, plen:].copy())
            served += b
            batches += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
    obs.attempted = served
    obs.failed = 0
    obs.end_to_end["serve_tokens_per_s"] = served * new / elapsed
    obs.memory_peak_bytes = ctx.memory_peak()
    obs.counters = {"hits": s.store.hits - hits0,
                    "misses": s.store.misses - misses0,
                    "window_compiles": s.decoder.compile_count() - compiles0}
    obs.work = {"batch_requests": [b] * batches, "generate_s": gen_s,
                "elapsed_s": elapsed, "prompt_len": plen, "new_tokens": new,
                "config": ctx.config}
    if ctx.trace:
        obs.trace = reduce(ctx.trace_dir)
    samples = [(int(tasks[i]), prompts[i], toks) for i, toks in sample]
    params, store_state = s.params, s.store_state
    del s
    gap = serve_gaps(params, store_state, ctx.config, samples)
    obs.checks = [Check("served_logit_gap", gap,
                        float(tf["limits"]["served_logit_gap"])),
                  Check("window_compiles",
                        float(obs.counters["window_compiles"]), 0.0)]
    return obs


def control(ctx) -> dict:
    """Readings of the fp8 control, and of the program, on requests of
    the window's first batch."""
    tf = ctx.traffic
    b, plen, n = tf["batch"], tf["prompt_len"], int(tf["check_requests"])
    s = Setup(ctx)
    tasks, prompts = requests(ctx.seed, tf, ctx.config["vocab_size"])
    out = s.generate(prompts[:b], tasks[:b])
    samples = [(int(tasks[i]), prompts[i], out[i, plen:]) for i in range(n)]
    params, store_state = s.params, s.store_state
    del s
    return {"served_logit_gap": control_gaps(params, store_state, ctx.config,
                                             samples),
            "program_served_logit_gap": serve_gaps(params, store_state,
                                                   ctx.config, samples)}
