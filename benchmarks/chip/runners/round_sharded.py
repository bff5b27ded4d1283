"""Runner ``round_sharded``: a MaTU server round whose task vector is
split over the chips of one host, ``MaTUServer(mesh=make_round_mesh(
chips)).round``, checked against the blocked reference.

It takes the traffic parameters of the ``round`` runner (see
``runners/round.py``) but ``chunk_clients``, which must be null, and
shares its wire uploads, window rule, kept-downlink ring and gaps.
What differs:

* the server is built on ``make_round_mesh(chips)``: ``pack_uploads``
  puts the uploads with the taskvec sharding and the round runs under
  ``shard_map``;
* set-up makes the rounds with ``round.make_rounds``' semantics (the
  popularity, each round's task sets and the size multiset are one
  fixed draw; the seed relabels the tasks, orders the clients and makes
  the values), but draws the values on the chips: the (T, d) base and
  each chunk of clients' noise are split over the mesh on d, so each
  chip holds its slice of them, and only the wire buffers come back to
  host memory;
* the check is ``chipref/matu_round_blocked.py``, computed in blocks of
  d after the window with the round server freed;
* ``obs.peaks`` is the cell's aggregate: every peak of ``peaks.json``
  times the number of chips.  ``round.mfu_pct`` then reads the round's
  share of all the chips' bandwidth, while a kernel's roofline reads
  one chip's share: its reader counts the calls and sums the kernel
  time of every chip's device plane, and the required work of a call
  at the full d over the chips' peak is one chip's work (d / chips)
  over one chip's peak.
"""

from __future__ import annotations

import math
import time
from typing import List

import numpy as np

from benchlib import bits, load, seeds
from benchlib.context import Check, Observations
from benchlib.trace import reduce

base = load("runners", "round")
uploads, gaps, Kept = base.uploads, base.gaps, base.Kept
steady_host_allocator, lora_d = base.steady_host_allocator, base.lora_d

GENERATE_CLIENTS = 4       # clients whose uploads one set-up call makes


def _client_unify(base_tv, key, task_ids, scale, spread, *, d, axis):
    """``round._client_unify`` on one chip's d-slice under ``shard_map``:
    this slice's noise (the key folded with the slice's index), its
    coordinates past ``d`` zeroed, and the lambda sums over d added
    across the slices."""
    import jax
    import jax.numpy as jnp
    i = jax.lax.axis_index(axis)
    n, k = task_ids.shape
    width = base_tv.shape[1]
    noise = jax.random.normal(jax.random.fold_in(key, i), (n, k, width),
                              jnp.float32)
    real = i * width + jnp.arange(width) < d
    tv = jnp.where(real, scale * (base_tv[task_ids] + spread * noise), 0.0)
    sigma = jnp.sign(jnp.sum(tv, axis=1))
    aligned = tv * sigma[:, None, :] > 0
    tau = sigma * jnp.max(jnp.where(aligned, jnp.abs(tv), 0.0), axis=1)
    masks = tv * tau[:, None, :] > 0
    num = jax.lax.psum(jnp.sum(jnp.abs(tv), axis=-1), axis)
    den = jax.lax.psum(jnp.sum(jnp.where(masks, jnp.abs(tau)[:, None, :],
                                         0.0), axis=-1), axis)
    return (tau.astype(jnp.bfloat16), bits.pack(masks),
            num / jnp.maximum(den, 1e-12))


def make_rounds(seed: int, traffic: dict, d: int, mesh) -> List[dict]:
    """The distinct rounds of uploads, as host wire buffers; the values
    are drawn with their d axis split over ``mesh``, a whole number of
    mask words a chip (the last chip's slice runs past d in zeros)."""
    import functools

    import jax
    import jax.numpy as jnp
    import ml_dtypes
    from jax.sharding import NamedSharding, PartitionSpec as P
    n, t, k = (traffic["clients"], traffic["tasks"],
               traffic["tasks_per_client"])
    rng = seeds.rng(seed, 1)
    fixed = np.random.default_rng(0)
    lo, hi = traffic["data_size_range"]
    size_pool = fixed.integers(lo, hi + 1, size=n * k)
    popularity = fixed.dirichlet(np.full(t, traffic["task_popularity_dirichlet"]))
    label = rng.permutation(t)
    axis = mesh.axis_names[0]
    step = bits.WORD * mesh.devices.size
    width = -(-d // step) * step
    draw_base = jax.jit(
        lambda key: jax.random.normal(key, (t, width), jnp.float32),
        out_shardings=NamedSharding(mesh, P(None, axis)))
    unify = jax.jit(jax.shard_map(
        functools.partial(_client_unify, d=d, axis=axis,
                          scale=float(traffic["task_vector_scale"]),
                          spread=float(traffic["client_spread"])),
        mesh=mesh, in_specs=(P(None, axis), P(), P()),
        out_specs=(P(None, axis), P(None, None, axis), P()),
        check_vma=False))
    base_tv = draw_base(seeds.key(seed, 2))
    rounds = []
    for r in range(traffic["distinct_rounds"]):
        drawn = [fixed.choice(t, size=k, replace=False, p=popularity)
                 for _ in range(n)]
        tasks = [sorted(label[drawn[i]].tolist())
                 for i in rng.permutation(n)]
        sizes = rng.permutation(size_pool).reshape(n, k).astype(np.int64)
        tid = np.asarray(tasks, np.int32)
        unified = np.empty((n, d), ml_dtypes.bfloat16)
        words = np.empty((n, k, bits.n_words(d)), np.uint32)
        lams = np.empty((n, k), np.float32)
        for c0 in range(0, n, GENERATE_CLIENTS):
            c1 = min(c0 + GENERATE_CLIENTS, n)
            u, w, lam = unify(base_tv, seeds.key(seed, 3, r, c0),
                              jnp.asarray(tid[c0:c1]))
            unified[c0:c1] = np.asarray(u)[:, :d]
            words[c0:c1] = np.asarray(w)[:, :, :bits.n_words(d)]
            lams[c0:c1] = np.asarray(lam)
        rounds.append({"tasks": tasks, "sizes": sizes, "unified": unified,
                       "words": words, "lams": lams})
    del base_tv
    return rounds


class Server(base.Server):
    """``round.Server`` with the round split over ``make_round_mesh``
    of the cell's chips."""

    def __init__(self, traffic: dict, chips: int):
        from repro.core.server import MaTUServer, MaTUServerConfig
        from repro.launch.mesh import make_round_mesh
        if traffic.get("chunk_clients"):
            raise ValueError("round_sharded runs MaTUServer.round: "
                             "chunk_clients must be null")
        self.server = MaTUServer(MaTUServerConfig(n_tasks=traffic["tasks"]),
                                 mesh=make_round_mesh(chips))
        self.chunk = None


def compare(rnd: dict, kept, n_tasks: int, d: int, **kw) -> dict:
    """``round.compare`` against the blocked reference."""
    from chipref import matu_round_blocked as ref
    want = ref.round_downlinks(rnd["unified"], rnd["words"], rnd["lams"],
                               rnd["sizes"], rnd["tasks"], n_tasks, d,
                               clients=sorted({cid for cid, _ in kept}), **kw)
    return gaps(want, kept, rnd["tasks"], d)


def aggregate_peaks(peaks: dict, chips: int) -> dict:
    """Every numeric peak of one chip times ``chips``."""
    return {k: v * chips if isinstance(v, (int, float)) else v
            for k, v in peaks.items()}


def run(ctx) -> Observations:
    steady_host_allocator()
    tf = ctx.traffic
    d = lora_d(ctx.config)
    n = tf["clients"]
    server = Server(tf, ctx.cell.chips)
    rounds = make_rounds(ctx.seed, tf, d, server.server.engine.mesh)
    for r in range(min(2, len(rounds))):          # compile, then steady
        server.round(rounds[r])
    obs = Observations(peaks=aggregate_peaks(ctx.peaks, ctx.cell.chips))
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
    """The blocked reference in bf16 put in the program's place: its
    readings, on this seed's checked rounds and as many clients of each
    as a run keeps, of the numbers ``run`` compares."""
    import jax.numpy as jnp
    from chipref import matu_round_blocked as ref
    from repro.launch.mesh import make_round_mesh
    tf = ctx.traffic
    d = lora_d(ctx.config)
    rounds = make_rounds(ctx.seed, tf, d, make_round_mesh(ctx.cell.chips))
    order = seeds.rng(ctx.seed, 4).permutation(tf["clients"]).tolist()
    per = int(tf["check_clients"])
    worst = {}
    for r, rnd in enumerate(rounds[:int(tf["check_rounds"])]):
        ids = order[r * per:(r + 1) * per]
        low = ref.round_downlinks(rnd["unified"], rnd["words"], rnd["lams"],
                                  rnd["sizes"], rnd["tasks"], tf["tasks"], d,
                                  clients=ids, dtype=jnp.bfloat16)
        kept = [(cid, (u, bits.pack(jnp.asarray(m)), l))
                for cid, (u, m, l) in low.items()]
        for name, v in compare(rnd, kept, tf["tasks"], d).items():
            worst[name] = max(worst.get(name, 0.0), v)
    return worst
