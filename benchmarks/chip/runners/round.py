"""Runner ``round``: a MaTU server round over seeded wire uploads.

Traffic parameters (``traffic/<name>.json``):

* ``clients``, ``tasks``, ``tasks_per_client``: N, T, K of every round;
* ``task_popularity_dirichlet``: each client draws its K distinct tasks
  with Dirichlet(a)-distributed task popularity;
* ``distinct_rounds``: rounds of uploads built in set-up; the window
  cycles over them, with fresh ``ClientUpload`` objects each round;
* ``data_size_range``: the |D_n^t| multiset;
* ``task_vector_scale``, ``client_spread``: client task vectors are
  ``scale * (base_t + spread * noise)``;
* ``chunk_clients``: null for ``MaTUServer.round``, else the chunk of
  ``MaTUServer.round_chunked``;
* ``check_rounds``: distinct rounds, drawn from the seed, whose every
  occurrence in the window keeps ``check_clients`` downlinks for the
  comparison with the reference; the kept clients rotate through a
  seeded order, so that successive kept rounds cover different
  clients;
* ``check_keep``: downlinks kept at most (the latest), copied into
  host memory that set-up allocates and touches: the window holds no
  memory that grows, so every round meets the host allocator alike;
* ``limits``: the limit of each compared number.

Every seed serves the same work: the popularity, each round's task
sets and the size multiset are one fixed draw; the seed relabels the
tasks, orders the clients, deals out the sizes and makes the values.

Uploads arrive as host numpy wire buffers (bf16 unified vector, uint32
mask words, fp32 lambdas, sizes), built by the harness's own client
unification.  A round counts once every client's downlink buffers are
in host memory.  Closed loop: rounds back to back.
"""

from __future__ import annotations

import math
import time
from typing import List

import numpy as np

from benchlib import bits, seeds
from benchlib.context import Check, Observations
from benchlib.trace import reduce, span

GENERATE_CLIENTS = 32      # clients whose uploads set-up makes in one call


def lora_d(config: dict) -> int:
    return int(config["lora_d"])


def _client_unify(base, noise_key, task_ids, scale, spread):
    """Harness-side client step: K task vectors per client -> one bf16
    unified vector, packed masks and lambdas (the MaTU uplink)."""
    import jax
    import jax.numpy as jnp
    n, k = task_ids.shape
    d = base.shape[1]
    noise = jax.random.normal(noise_key, (n, k, d), jnp.float32)
    tv = scale * (base[task_ids] + spread * noise)
    sigma = jnp.sign(jnp.sum(tv, axis=1))
    aligned = tv * sigma[:, None, :] > 0
    tau = sigma * jnp.max(jnp.where(aligned, jnp.abs(tv), 0.0), axis=1)
    masks = tv * tau[:, None, :] > 0
    lams = (jnp.sum(jnp.abs(tv), axis=-1)
            / jnp.maximum(jnp.sum(jnp.where(masks, jnp.abs(tau)[:, None, :],
                                            0.0), axis=-1), 1e-12))
    return tau.astype(jnp.bfloat16), bits.pack(masks), lams


def make_rounds(seed: int, traffic: dict, d: int) -> List[dict]:
    """The distinct rounds of uploads, as host wire buffers."""
    import jax
    import jax.numpy as jnp
    n, t, k = (traffic["clients"], traffic["tasks"],
               traffic["tasks_per_client"])
    rng = seeds.rng(seed, 1)
    fixed = np.random.default_rng(0)
    lo, hi = traffic["data_size_range"]
    size_pool = fixed.integers(lo, hi + 1, size=n * k)
    popularity = fixed.dirichlet(np.full(t, traffic["task_popularity_dirichlet"]))
    label = rng.permutation(t)
    chunk = min(n, GENERATE_CLIENTS)
    unify = jax.jit(_client_unify, static_argnums=(3, 4))
    base = jax.random.normal(seeds.key(seed, 2), (t, d), jnp.float32)
    rounds = []
    for r in range(traffic["distinct_rounds"]):
        drawn = [fixed.choice(t, size=k, replace=False, p=popularity)
                 for _ in range(n)]
        tasks = [sorted(label[drawn[i]].tolist())
                 for i in rng.permutation(n)]
        sizes = rng.permutation(size_pool).reshape(n, k).astype(np.int64)
        tid = np.asarray(tasks, np.int32)
        parts = []
        for c0 in range(0, n, chunk):
            parts.append(jax.device_get(unify(
                base, seeds.key(seed, 3, r, c0), jnp.asarray(tid[c0:c0 + chunk]),
                float(traffic["task_vector_scale"]),
                float(traffic["client_spread"]))))
        unified = np.concatenate([p[0] for p in parts])
        words = np.concatenate([p[1] for p in parts])
        lams = np.concatenate([p[2] for p in parts])
        rounds.append({"tasks": tasks, "sizes": sizes, "unified": unified,
                       "words": words, "lams": lams})
    del base
    return rounds


def uploads(rnd: dict):
    from repro.core.client import ClientUpload
    return [ClientUpload(cid, list(tl), rnd["unified"][cid],
                         rnd["words"][cid], rnd["lams"][cid],
                         rnd["sizes"][cid].tolist())
            for cid, tl in enumerate(rnd["tasks"])]


class Server:
    """The system under test: ``MaTUServer.round`` or ``round_chunked``,
    with the downlinks copied to host memory."""

    def __init__(self, traffic: dict):
        from repro.core.server import MaTUServer, MaTUServerConfig
        self.server = MaTUServer(MaTUServerConfig(n_tasks=traffic["tasks"]))
        self.chunk = traffic.get("chunk_clients")

    def round(self, rnd: dict):
        import jax
        with span("prepare"):
            ups = uploads(rnd)
        with span("round"):
            if self.chunk:
                downs, _ = self.server.round_chunked(
                    ups, chunk_clients=self.chunk)
            else:
                downs = self.server.round(ups)
        with span("to_host"):
            return jax.device_get({cid: (dl.unified, dl.masks, dl.lams)
                                   for cid, dl in downs.items()})


def compare(rnd: dict, kept, n_tasks: int, d: int) -> dict:
    """Worst gaps of one round's kept downlinks, ``[(client id,
    downlink)]``, against the reference: relative L2 gap of the bf16
    unified vector, share of mask bits that differ, relative gap of
    lambda (each the worst client)."""
    from chipref import matu_round as ref
    want = ref.round_downlinks(rnd["unified"], rnd["words"], rnd["lams"],
                               rnd["sizes"], rnd["tasks"], n_tasks, d,
                               clients=sorted({cid for cid, _ in kept}))
    return gaps(want, kept, rnd["tasks"], d)


def gaps(want: dict, kept, tasks, d: int) -> dict:
    uni = mask = lam = 0.0
    for cid, got in kept:
        if got is None:
            return {"downlink_unified_gap": math.inf,
                    "downlink_mask_mismatch": math.inf,
                    "downlink_lambda_gap": math.inf}
        wu, wm, wl = want[cid]
        gu, gm, gl = got
        gu = np.asarray(gu, np.float32)
        wu = np.asarray(wu, np.float32)
        k = len(tasks[cid])
        gm = np.asarray(bits.unpack(np.asarray(gm)[:k], d))
        gl = np.asarray(gl, np.float64)[:k]
        if gu.shape != wu.shape or gm.shape != wm.shape or gl.shape != wl.shape:
            return {"downlink_unified_gap": math.inf,
                    "downlink_mask_mismatch": math.inf,
                    "downlink_lambda_gap": math.inf}
        uni = max(uni, float(np.linalg.norm(gu - wu)
                             / max(np.linalg.norm(wu), 1e-30)))
        mask = max(mask, float(np.mean(gm != wm)))
        lam = max(lam, float(np.max(np.abs(gl - wl) / np.abs(wl))))
    return {"downlink_unified_gap": uni, "downlink_mask_mismatch": mask,
            "downlink_lambda_gap": lam}


def steady_host_allocator() -> None:
    """Fix glibc's mmap threshold at its 32 MiB maximum and its trim
    threshold at 1 GiB.  Left to itself glibc moves both as the process
    frees memory, so a round's per-client host buffers (7.2 MB each at
    d = 3,588,168) came from reused heap memory in some processes and
    from fresh, zero-filled pages in others, depending on what set-up
    had freed: on a TPU v5e host the same round_population round took
    0.92 s in one run and 1.5 s in another.  Buffers over 32 MiB are
    mapped fresh every time, as before."""
    import ctypes
    libc = ctypes.CDLL("libc.so.6")
    libc.mallopt(-3, 32 * 2**20)                  # M_MMAP_THRESHOLD
    libc.mallopt(-1, 2**30)                       # M_TRIM_THRESHOLD


class Kept:
    """Ring of downlinks kept for the comparison, ``(round index,
    client id)`` each, in buffers allocated and touched in set-up."""

    def __init__(self, slots: int, rnd: dict):
        d, (_, k, dw) = rnd["unified"].shape[1], rnd["words"].shape
        self.unified = np.ones((slots, d), rnd["unified"].dtype)
        self.masks = np.ones((slots, k, dw), np.uint32)
        self.lams = np.ones((slots, k), np.float32)
        self.slots = [None] * slots
        self.count = 0

    def add(self, idx: int, cid: int, downlink) -> None:
        s = self.count % len(self.slots)
        self.count += 1
        u, m, lam = (np.asarray(x) for x in downlink)
        ok = (u.shape == self.unified.shape[1:]
              and m.shape == self.masks.shape[1:]
              and lam.shape == self.lams.shape[1:])
        if ok:
            self.unified[s], self.masks[s], self.lams[s] = u, m, lam
        self.slots[s] = (idx, cid, ok)

    def by_round(self) -> dict:
        """``{round index: [(client id, downlink or None)]}``; None for
        a downlink of the wrong shape."""
        out = {}
        for s, entry in enumerate(self.slots):
            if entry is not None:
                idx, cid, ok = entry
                got = ((self.unified[s], self.masks[s], self.lams[s])
                       if ok else None)
                out.setdefault(idx, []).append((cid, got))
        return out


def run(ctx) -> Observations:
    steady_host_allocator()
    tf = ctx.traffic
    d = lora_d(ctx.config)
    n = tf["clients"]
    rounds = make_rounds(ctx.seed, tf, d)
    server = Server(tf)
    for r in range(min(2, len(rounds))):          # compile, then steady
        server.round(rounds[r])
    obs = Observations(peaks=ctx.peaks)
    obs.end_to_end["setup_s"] = ctx.setup_done()

    pick = seeds.rng(ctx.seed, 4)
    checked = set(pick.choice(len(rounds), size=int(tf["check_rounds"]),
                              replace=False).tolist())
    order = pick.permutation(n).tolist() * 2
    per = int(tf["check_clients"])
    ids = set(range(n))
    kept = Kept(int(tf["check_keep"]), rounds[0])
    missing = 0
    at = 0
    seconds = ctx.window_seconds()
    done = 0
    with ctx.window():
        t0 = time.perf_counter()
        while True:
            idx = done % len(rounds)
            got = server.round(rounds[idx])
            done += 1
            if got.keys() != ids:
                missing += len(ids - got.keys())
            if idx in checked:
                for cid in order[at:at + per]:
                    if cid in got:
                        kept.add(idx, cid, got[cid])
                at = (at + per) % n
            del got
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
    obs.attempted = done
    obs.end_to_end["round_clients_per_s"] = done * n / elapsed
    obs.memory_peak_bytes = ctx.memory_peak()
    obs.work = {"clients": n, "tasks": tf["tasks"],
                "tasks_per_client": tf["tasks_per_client"], "d": d,
                "rounds": done, "elapsed_s": elapsed}
    if ctx.trace:
        obs.trace = reduce(ctx.trace_dir)
    del server
    worst = {}
    for idx, pairs in kept.by_round().items():
        for name, v in compare(rounds[idx], pairs, tf["tasks"], d).items():
            worst[name] = max(worst.get(name, 0.0), v)
    obs.checks = [Check(name, worst.get(name, math.inf), float(limit))
                  for name, limit in tf["limits"].items()]
    obs.checks.append(Check("clients_missing", float(missing), 0.0))
    obs.failed = 0
    return obs


def control(ctx) -> dict:
    """The reference in bf16 put in the program's place: its readings,
    on this seed's rounds, of the numbers ``run`` compares."""
    import jax.numpy as jnp
    from chipref import matu_round as ref
    tf = ctx.traffic
    d = lora_d(ctx.config)
    rounds = make_rounds(ctx.seed, tf, d)
    worst = {}
    for rnd in rounds[:int(tf["check_rounds"])]:
        low = ref.round_downlinks(rnd["unified"], rnd["words"], rnd["lams"],
                                  rnd["sizes"], rnd["tasks"], tf["tasks"], d,
                                  dtype=jnp.bfloat16)
        kept = [(cid, (u, bits.pack(jnp.asarray(m)), l))
                for cid, (u, m, l) in low.items()]
        for name, v in compare(rnd, kept, tf["tasks"], d).items():
            worst[name] = max(worst.get(name, 0.0), v)
    return worst
