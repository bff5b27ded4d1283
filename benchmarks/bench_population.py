"""Population-scale round benchmark: the chunked-slot engine over a
lazy 10^6-client Dirichlet population.

The grid samples S clients per round from a ``PopulationSplit`` of
10^6 clients (``sample_round`` rejection draws — the population itself
is never materialised) and streams them through
``RoundEngine.round_chunked`` with a discarding sink, so round memory
is O(chunk + T·d) regardless of S.  Rows report end-to-end wall time,
**clients/sec**, and the **peak-RSS delta** over the pre-round
baseline — the acceptance evidence that a ≥10^5-client round at
d = 2^20 stays within the O(chunk) memory budget (≤ 2 GB over
baseline).

Client uploads are wire-format twins cycled from a small pre-built
pool (P distinct bf16 vectors + packed uint32 mask words): the bench
measures SERVER-side ingest/fold/downlink throughput, so client-side
RNG is excluded from both the timed region and the memory budget.  Task
assignment, data sizes, and sampling still come from the lazy split,
exercised per client per pass (the engine's two-pass contract).

Full mode: d = 2^20, S ∈ {10^4, 10^5}, chunk 128.  Quick: d = 2^14,
S = 2000 — CI-speed.  Detail (including ``host_cores`` and the
baseline RSS) merges into results/bench/population.json.
"""

from __future__ import annotations

import os
import resource
import time
from typing import List

import numpy as np

from benchmarks.common import save_detail
from repro.core.client import ClientUpload
from repro.core.engine import EngineConfig, RoundEngine
from repro.data.dirichlet import PopulationSplit
from repro.kernels import bitpack

POPULATION = 1_000_000
N_TASKS = 8
K_PER_CLIENT = 2
POOL = 16


def _rss_mb() -> float:
    # ru_maxrss is KB on Linux — the high-water mark, so deltas against
    # a pre-round reading bound the round's own footprint from above
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _make_pool(d: int, seed: int = 0) -> List[tuple]:
    """P distinct wire-format (unified bf16, mask words, λ) triples —
    reused round-robin across clients so upload generation stays out
    of the measured server throughput."""
    import ml_dtypes
    rng = np.random.default_rng(seed)
    dw = bitpack.packed_width(d)
    pool = []
    for _ in range(POOL):
        uni = rng.standard_normal(d, dtype=np.float32).astype(
            ml_dtypes.bfloat16)
        words = rng.integers(0, 2**32, (K_PER_CLIENT, dw),
                             dtype=np.uint32)
        lams = rng.random(K_PER_CLIENT, dtype=np.float32) + 0.5
        pool.append((uni, words, lams))
    return pool


def _one_round(engine: RoundEngine, split: PopulationSplit, pool,
               n_sampled: int, chunk: int) -> dict:
    ids = split.sample_round(0, n_sampled)

    def gen():
        for i, c in enumerate(ids):
            c = int(c)
            uni, words, lams = pool[i % POOL]
            ts = split.tasks_for(c)
            yield ClientUpload(c, ts, uni, words[: len(ts)],
                               lams[: len(ts)],
                               split.data_sizes_for(c))

    rss0 = _rss_mb()
    t0 = time.perf_counter()
    _, _, stats = engine.round_chunked(
        gen, chunk_clients=chunk, sink=lambda links: None)
    wall = time.perf_counter() - t0
    return {
        "n_clients": int(stats["n_clients"]),
        "n_chunks": int(stats["n_chunks"]),
        "chunk_clients": chunk,
        "wall_s": wall,
        "clients_per_s": stats["n_clients"] / wall,
        "uplink_bits": int(stats["uplink_bits"]),
        "downlink_bits": int(stats["downlink_bits"]),
        "rss_before_mb": rss0,
        "rss_peak_mb": _rss_mb(),
        "rss_delta_mb": _rss_mb() - rss0,
    }


def run(quick: bool = False) -> dict:
    d = 2**14 if quick else 2**20
    grid = [2_000] if quick else [10_000, 100_000]
    chunk = 128
    split = PopulationSplit(n_clients=POPULATION, n_tasks=N_TASKS,
                            tasks_per_client=K_PER_CLIENT, seed=0)
    engine = RoundEngine(EngineConfig(n_tasks=N_TASKS))
    pool = _make_pool(d)

    baseline_mb = _rss_mb()
    rows, detail = [], {
        "host_cores": os.cpu_count(),
        "population": POPULATION,
        "d": d,
        "baseline_rss_mb": baseline_mb,
    }
    # warm the chunk-step jit signatures off the clock (tiny round)
    _one_round(engine, split, pool, min(2 * chunk, grid[0]), chunk)
    for s in grid:
        r = _one_round(engine, split, pool, s, chunk)
        key = f"population_n{s}_d{d}_c{chunk}"
        detail[key] = r
        rows.append((key, r["wall_s"] * 1e6,
                     f"clients_per_s={r['clients_per_s']:.1f} "
                     f"rss_delta_mb={r['rss_delta_mb']:.0f}"))
    save_detail("population", detail)
    return {"rows": rows, "detail": detail}
