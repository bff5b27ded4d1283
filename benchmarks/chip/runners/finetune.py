"""Runner ``finetune``: one client's local LoRA fine-tune through the
jitted ``make_train_step`` with ``adamw``.

Traffic parameters:

* ``batch``, ``seq``: every step trains on batch x seq tokens;
* ``task_region``: token ids of the client's task are uniform over
  ``[t * region, (t + 1) * region)`` (a seeded task ``t``); every step
  draws fresh rows from the seed and the step number;
* ``lr``, ``grad_clip``: the optimizer (AdamW, b1 0.9, b2 0.999, eps
  1e-8, no weight decay) and the global-norm clip;
* ``lora_b_scale``: the client starts from an adapter with nonzero B
  (as after earlier rounds), so every LoRA leaf has a gradient;
* ``check_steps``: set-up drives the compiled step through its first
  steps; the reference follows them;
* ``limits``: the limit of each compared number.

Set-up builds one object, the compiled step with its state, drives it
through the first ``check_steps`` steps on rows that all differ, and
hands that same object to the window, which steps it back to back.
"""

from __future__ import annotations

import time

import numpy as np

from benchlib import seeds
from benchlib.context import Check, Observations
from benchlib.lm import build_model, make_state
from benchlib.trace import reduce, span

B1 = 0.9


def make_batch_fn(tf: dict):
    import jax
    import jax.numpy as jnp
    b, s, region = tf["batch"], tf["seq"], tf["task_region"]

    @jax.jit
    def make(key, task):
        x = jax.random.randint(key, (b, s + 1), 0, region) + task * region
        return {"tokens": x[:, :-1].astype(jnp.int32),
                "labels": x[:, 1:].astype(jnp.int32)}
    return make


def leaf_norms(tree) -> dict:
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
             for _, x in flat]
    keys = ["/".join(str(getattr(k, "key", k)) for k in kp) for kp, _ in flat]
    return dict(zip(keys, (float(v) for v in jax.device_get(norms))))


def delta_norms(new, old) -> dict:
    import jax
    import jax.numpy as jnp
    diff = jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), new, old)
    return leaf_norms(diff)


def norm_gap(got: dict, want: dict, keep=None) -> float:
    """Worst leaf: |norm_got - norm_want| over the larger of the leaf's
    reference norm and the median leaf's."""
    keys = [k for k in want if keep is None or k in keep]
    if not keys:
        return float("inf")
    med = float(np.median([want[k] for k in keys]))
    worst = 0.0
    for k in keys:
        den = max(want[k], med)
        if den > 0:
            worst = max(worst, abs(got.get(k, float("inf")) - want[k]) / den)
    return worst


class Trainer:
    """The compiled step, its state and its feed."""

    def __init__(self, ctx, params, lora):
        import jax
        from repro.optim import adamw
        from repro.train.trainer import make_train_step
        tf = ctx.traffic
        _, model = build_model(ctx.config)
        step, opt = make_train_step(model, adamw(tf["lr"]),
                                    grad_clip=tf["grad_clip"])
        self.step = jax.jit(step)
        self.params = params
        self.lora = lora
        self.state = opt.init(lora)
        self.make = make_batch_fn(tf)
        self.seed = ctx.seed
        self.task = int(seeds.rng(ctx.seed, 40).integers(
            0, ctx.config["vocab_size"] // tf["task_region"]))
        self.n = 0

    def batch(self, i: int):
        return self.make(seeds.key(self.seed, 41, i), self.task)

    def __call__(self):
        with span("feed"):
            b = self.batch(self.n)
        with span("step"):
            self.lora, self.state, m = self.step(self.params, self.lora,
                                                 self.state, b)
        self.n += 1
        return m["loss"]


def reference_steps(params, lora0, batches, cfg_json, tf, quant=None):
    """The reference's first steps: losses, the clipped first gradient's
    leaf norms, and the adapter's change after the last step."""
    import jax
    import jax.numpy as jnp
    from chipref import qwen2
    lr, clip = tf["lr"], tf["grad_clip"]
    b2, eps = 0.999, 1e-8
    dtypes = jax.tree_util.tree_map(lambda x: x.dtype, lora0)

    @jax.jit
    def grad(params, lora, tokens, labels):
        return jax.value_and_grad(
            lambda lo: qwen2.loss(params, lo, tokens, labels, cfg_json,
                                  quant=quant))(lora)

    lora = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), lora0)
    mu = jax.tree_util.tree_map(jnp.zeros_like, lora)
    nu = jax.tree_util.tree_map(jnp.zeros_like, lora)
    losses, first = [], None
    for i, b in enumerate(batches, start=1):
        loss, g = grad(params, lora, b["tokens"], b["labels"])
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                          for x in jax.tree_util.tree_leaves(g)))
        g = jax.tree_util.tree_map(
            lambda x: x * jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12)), g)
        if first is None:
            first = leaf_norms(g)
        losses.append(float(loss))
        mu = jax.tree_util.tree_map(lambda m, x: B1 * m + (1 - B1) * x, mu, g)
        nu = jax.tree_util.tree_map(lambda v, x: b2 * v + (1 - b2) * x * x,
                                    nu, g)
        bc1, bc2 = 1 - B1 ** i, 1 - b2 ** i
        # parameters are held in the configuration's dtype between steps
        lora = jax.tree_util.tree_map(
            lambda p, m, v, dt: (p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)
                                 ).astype(dt).astype(jnp.float32),
            lora, mu, nu, dtypes)
    return losses, first, delta_norms(lora, lora0)


def gaps(prog, ref) -> dict:
    losses_p, grads_p, delta_p = prog
    losses_r, grads_r, delta_r = ref
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses_p, losses_r))
    med = float(np.median(list(grads_r.values())))
    moving = {k for k, v in grads_r.items() if v >= 1e-3 * med}
    return {"loss_gap": loss_gap,
            "grad_norm_gap": norm_gap(grads_p, grads_r),
            "update_norm_gap": norm_gap(delta_p, delta_r, keep=moving)}


def run(ctx) -> Observations:
    import jax
    tf = ctx.traffic
    _, model = build_model(ctx.config)
    params, lora0 = make_state(ctx.config, model, ctx.seed,
                               lora_b_scale=tf["lora_b_scale"])
    lora_start = jax.tree_util.tree_map(lambda x: x.copy(), lora0)
    tr = Trainer(ctx, params, lora0)
    k = int(tf["check_steps"])
    losses = []
    for i in range(k):
        losses.append(float(tr()))
        if i == 0:
            grads_p = {key: v / (1 - B1) for key, v in
                       leaf_norms(tr.state["mu"]).items()}
    prog = (losses, grads_p, delta_norms(tr.lora, lora_start))
    float(tr())                                   # steady step
    obs = Observations(peaks=ctx.peaks)
    obs.end_to_end["setup_s"] = ctx.setup_done()

    seconds = ctx.window_seconds()
    steps = 0
    with ctx.window():
        t0 = time.perf_counter()
        pending = tr()
        while True:
            nxt = tr()
            with span("wait"):
                pending.block_until_ready()
            steps += 1
            pending = nxt
            if time.perf_counter() - t0 >= seconds:
                break
        with span("wait"):
            pending.block_until_ready()
        steps += 1
        elapsed = time.perf_counter() - t0
    tokens = tf["batch"] * tf["seq"]
    obs.attempted = steps
    obs.end_to_end["finetune_tokens_per_s"] = steps * tokens / elapsed
    obs.memory_peak_bytes = ctx.memory_peak()
    obs.work = {"steps": steps, "elapsed_s": elapsed, "batch": tf["batch"],
                "seq": tf["seq"], "config": ctx.config}
    if ctx.trace:
        obs.trace = reduce(ctx.trace_dir)
    batches = [tr.batch(i) for i in range(k)]
    del tr
    ref = reference_steps(params, lora_start, batches, ctx.config, tf)
    obs.checks = [Check(name, v, float(tf["limits"][name]))
                  for name, v in gaps(prog, ref).items()]
    return obs


def control(ctx) -> dict:
    """The fp8 reference in the program's place: its readings against
    the float32 reference on this seed's first steps."""
    tf = ctx.traffic
    _, model = build_model(ctx.config)
    params, lora0 = make_state(ctx.config, model, ctx.seed,
                               lora_b_scale=tf["lora_b_scale"])
    make = make_batch_fn(tf)
    task = int(seeds.rng(ctx.seed, 40).integers(
        0, ctx.config["vocab_size"] // tf["task_region"]))
    batches = [make(seeds.key(ctx.seed, 41, i), task)
               for i in range(int(tf["check_steps"]))]
    ref = reference_steps(params, lora0, batches, ctx.config, tf)
    low = reference_steps(params, lora0, batches, ctx.config, tf,
                          quant="fp8")
    return gaps(low, ref)
