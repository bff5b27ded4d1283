"""Chunked-slot round tests (the engine's population-scale contract).

Contract under test (see "Population-scale contract" in
``repro.core.engine``):

* **bit parity** — in "ref" mode ``round_chunked`` is bit-identical to
  the monolithic ``round`` for every chunk size (1, a non-divisor of
  N, > N), with and without staleness discounts — the carried
  scatter-folds replay the exact segment sums the monolithic round
  computes, and the λ combine tree is chunk-count-invariant.  Against
  the monolithic Pallas round (interpreted) it holds to the
  tolerance the two dispatch modes agree to.
* **streaming semantics** — uploads may be a zero-arg iterator
  factory (two engine passes, validated identical); a ``sink``
  receives per-chunk downlink dicts whose union equals the monolithic
  round's, and the returned dict stays empty (no per-client growth).
* **accounting** — uplink/downlink wire bits are invariant in the
  chunk size and equal the monolithic round's accounting.
* **slot sharding** — on 8 host devices the chunked round on the
  (4, 2) debug mesh and on the ("slots", "data") population mesh is
  bit-identical to the single-device monolithic round (subprocess,
  like tests/test_sharded_engine.py).
* **lazy population** — ``PopulationSplit`` derivations are
  order-invariant and seed-stable; ``PopulationSimulator`` honours
  ``FedConfig.eval_every`` (present since the seed, default 5) and is
  run-to-run deterministic.
* **coder pool** — the Golomb-Rice worker pool is byte-invisible:
  pooled encode/decode output is byte-identical to sequential, under
  tiny monkeypatched chunk sizes that force many independent chunks.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FIELDS = ("task_vectors", "tau_hats", "similarity", "m_hats")


def _run_sub(script: str, timeout: int = 600) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _uploads(rng, n, n_tasks, d, k_max):
    import jax.numpy as jnp
    from repro.core.client import ClientUpload
    from repro.core.unify import unify_with_modulators
    from repro.fed.compression import quantize_bf16_transport

    ups = []
    for cid in range(n):
        k = int(rng.integers(1, k_max + 1))
        tasks = sorted(rng.choice(n_tasks, size=k, replace=False).tolist())
        tvs = jnp.asarray(rng.standard_normal((k, d)), jnp.float32)
        uni, masks, lams = unify_with_modulators(tvs)
        ups.append(ClientUpload(cid, tasks, quantize_bf16_transport(uni),
                                masks, lams,
                                rng.integers(10, 200, size=k).tolist()))
    return ups


# tolerances of the ref ≡ Pallas round (tests/test_round_engine.py::
# test_dispatch_modes_agree): exact on mask words, 1e-5 on fp32 fields,
# one bf16 ulp on the wire vectors; None = bit for bit
MODE_TOL = {"ref": None, "pallas_interpret": dict(rtol=1e-5, atol=1e-5)}
BF16_TOL = dict(rtol=1e-2, atol=1e-5)


def _assert_outputs_equal(out_a, out_b, ctx, tol=None):
    for f in FIELDS:
        a, b = np.asarray(getattr(out_a, f)), np.asarray(getattr(out_b, f))
        if tol is None:
            assert a.shape == b.shape and np.array_equal(a, b), f"{ctx}: {f}"
        else:
            np.testing.assert_allclose(a, b, err_msg=f"{ctx}: {f}", **tol)


def _assert_downlinks_equal(downs_a, downs_b, ctx, tol=None):
    assert set(downs_a) == set(downs_b), ctx
    for cid, da in downs_a.items():
        db = downs_b[cid]
        for f in ("unified", "masks", "lams"):
            a, b = np.asarray(getattr(da, f)), np.asarray(getattr(db, f))
            assert a.dtype == b.dtype, f"{ctx}: client {cid} {f}"
            if tol is None or f == "masks":
                assert np.array_equal(a, b), f"{ctx}: client {cid} {f}"
            else:
                np.testing.assert_allclose(
                    a.astype(np.float32), b.astype(np.float32),
                    err_msg=f"{ctx}: client {cid} {f}",
                    **(BF16_TOL if f == "unified" else tol))


@pytest.mark.parametrize("mode", list(MODE_TOL))
@pytest.mark.parametrize("chunk", [1, 3, 64])
def test_chunked_bit_identical_ragged(mode, chunk):
    """C ∈ {1, non-divisor, > N} on an N=11 ragged round at d=1000
    (not a CHUNK_D multiple): outputs, downlinks, and wire accounting
    all match the monolithic round — bit for bit against its "ref"
    mode, to the dispatch-mode tolerance against its Pallas mode."""
    from repro.core.engine import EngineConfig, RoundEngine, pack_uploads

    n, T, d = 11, 6, 1000
    ups = _uploads(np.random.default_rng(7), n, T, d, k_max=3)
    eng = RoundEngine(EngineConfig(n_tasks=T))
    downs_m, out_m = eng.round(ups, mode=mode)
    downs_c, out_c, stats = eng.round_chunked(
        ups, chunk_clients=chunk, mode=mode)

    ctx = f"C={chunk}/{mode}"
    _assert_outputs_equal(out_m, out_c, ctx, MODE_TOL[mode])
    _assert_downlinks_equal(downs_m, downs_c, ctx, MODE_TOL[mode])
    assert stats["n_clients"] == n
    assert stats["n_chunks"] == -(-n // chunk)
    assert stats["uplink_bits"] == pack_uploads(ups, T).wire_bits(), ctx
    assert stats["downlink_bits"] == sum(
        dl.downlink_bits() for dl in downs_m.values()), ctx


@pytest.mark.parametrize("mode", list(MODE_TOL))
def test_chunked_staleness_bit_identical(mode):
    """Per-upload staleness discounts (the async slot weights) survive
    chunking — the discount weights are folded per chunk into the same
    carried accumulators: bit for bit against the "ref" monolithic
    round, to the dispatch-mode tolerance against the Pallas one."""
    from repro.core.engine import EngineConfig, RoundEngine

    n, T, d = 9, 5, 640
    ups = _uploads(np.random.default_rng(3), n, T, d, k_max=2)
    stal = [int(s) for s in np.random.default_rng(4).integers(0, 4, n)]
    eng = RoundEngine(EngineConfig(n_tasks=T))
    downs_m, out_m = eng.round(ups, mode=mode, staleness=stal)
    downs_c, out_c, _ = eng.round_chunked(
        ups, chunk_clients=4, mode=mode, staleness=stal)
    _assert_outputs_equal(out_m, out_c, "staleness", MODE_TOL[mode])
    _assert_downlinks_equal(downs_m, downs_c, "staleness", MODE_TOL[mode])


def test_chunked_factory_and_sink_stream():
    """A zero-arg iterator factory is drawn exactly twice (metadata +
    merge passes); a sink receives per-chunk downlink dicts whose
    union matches the monolithic round, and the returned dict is empty
    — the no-per-client-growth contract the population path relies
    on."""
    from repro.core.engine import EngineConfig, RoundEngine

    n, T, d = 10, 4, 512
    ups = _uploads(np.random.default_rng(11), n, T, d, k_max=2)
    eng = RoundEngine(EngineConfig(n_tasks=T))
    downs_m, out_m = eng.round(ups, mode="ref")

    calls = {"n": 0}

    def factory():
        calls["n"] += 1
        return iter(ups)

    chunks = []
    downs_c, out_c, stats = eng.round_chunked(
        factory, chunk_clients=4, mode="ref", sink=chunks.append)
    assert calls["n"] == 2
    assert downs_c == {}
    assert len(chunks) == stats["n_chunks"] == 3
    union = {}
    for links in chunks:
        assert not (set(links) & set(union))
        union.update(links)
    _assert_outputs_equal(out_m, out_c, "sink")
    _assert_downlinks_equal(downs_m, union, "sink")
    # chunked EngineOutput carries no batched downlink planes
    assert out_c.down_unified is None and out_c.down_masks is None


def test_chunked_rejects_bad_streams():
    """chunk_clients < 1, an empty round, and a factory that returns a
    different round on the second pass are all hard errors — silent
    divergence between the two passes would corrupt the fold."""
    from repro.core.engine import EngineConfig, RoundEngine

    T, d = 4, 256
    ups = _uploads(np.random.default_rng(0), 6, T, d, k_max=2)
    eng = RoundEngine(EngineConfig(n_tasks=T))
    with pytest.raises(ValueError, match="chunk_clients"):
        eng.round_chunked(ups, chunk_clients=0, mode="ref")
    with pytest.raises(ValueError, match="empty round"):
        eng.round_chunked([], chunk_clients=4, mode="ref")

    flips = {"n": 0}

    def unstable():
        flips["n"] += 1
        order = ups if flips["n"] == 1 else list(reversed(ups))
        return iter(order)

    with pytest.raises(ValueError, match="different round"):
        eng.round_chunked(unstable, chunk_clients=4, mode="ref")


_SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["REPRO_DISABLE_PALLAS"] = "1"
    import json
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.client import ClientUpload
    from repro.core.engine import EngineConfig, RoundEngine
    from repro.core.unify import unify_with_modulators
    from repro.fed.compression import quantize_bf16_transport
    from repro.launch.mesh import make_debug_mesh, make_population_mesh

    def uploads(rng, n, n_tasks, d, k_max):
        ups = []
        for cid in range(n):
            k = int(rng.integers(1, k_max + 1))
            tasks = sorted(rng.choice(n_tasks, size=k,
                                      replace=False).tolist())
            tvs = jnp.asarray(rng.standard_normal((k, d)), jnp.float32)
            uni, masks, lams = unify_with_modulators(tvs)
            ups.append(ClientUpload(cid, tasks, quantize_bf16_transport(uni),
                                    masks, lams,
                                    rng.integers(10, 200, size=k).tolist()))
        return ups

    FIELDS = ("task_vectors", "tau_hats", "similarity", "m_hats")
    meshes = {"debug4x2": make_debug_mesh((4, 2)),
              "pop_s2": make_population_mesh(slots=2)}
    report = {"devices": len(jax.devices())}
    # ragged N, d not divisible by devices*32, chunk a non-divisor
    n, T, d, chunk = 11, 6, 1000, 3
    ups = uploads(np.random.default_rng(5), n, T, d, 3)
    single = RoundEngine(EngineConfig(n_tasks=T))
    downs_m, out_m = single.round(ups)
    for mesh_name, mesh in meshes.items():
        shard = RoundEngine(EngineConfig(n_tasks=T), mesh=mesh)
        downs_c, out_c, stats = shard.round_chunked(ups, chunk_clients=chunk)
        for f in FIELDS:
            a = np.asarray(getattr(out_m, f))
            b = np.asarray(getattr(out_c, f))
            report[f"{mesh_name}/{f}"] = bool(
                a.shape == b.shape and np.array_equal(a, b))
        ok = set(downs_m) == set(downs_c)
        for cid in downs_m:
            for f in ("unified", "masks", "lams"):
                a = np.asarray(getattr(downs_m[cid], f))
                b = np.asarray(getattr(downs_c[cid], f))
                ok = ok and a.dtype == b.dtype and np.array_equal(a, b)
        report[f"{mesh_name}/downlinks"] = bool(ok)
        report[f"{mesh_name}/bits"] = bool(
            stats["downlink_bits"] == sum(
                dl.downlink_bits() for dl in downs_m.values()))
    print(json.dumps(report))
""")


def test_chunked_sharded_bit_identical_ref():
    """8-device chunked rounds — (4, 2) debug mesh and the
    ("slots", "data") population mesh — are bit-identical to the
    single-device monolithic round."""
    report = _run_sub(_SHARDED)
    assert report.pop("devices") == 8
    bad = [k for k, v in report.items() if v is not True]
    assert not bad, f"sharded chunked round diverged on: {bad}"


def test_matu_strategy_chunked_bit_identical(monkeypatch):
    """``MaTUStrategy(chunk_clients=…)`` routes the server step through
    the chunked fold and stays bit-identical to the batched path in
    ref mode — same wire buffers, same results, same bit accounting."""
    import jax.numpy as jnp
    from repro.fed.strategies import MaTUStrategy, RoundBatch, Upload

    monkeypatch.setenv("REPRO_DISABLE_PALLAS", "1")
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)

    rng = np.random.default_rng(13)
    n, T, d = 7, 5, 384
    uploads = []
    for cid in range(n):
        k = int(rng.integers(1, 3))
        tasks = sorted(rng.choice(T, size=k, replace=False).tolist())
        uploads.append(Upload(cid, tasks,
                              jnp.asarray(rng.standard_normal((k, d)),
                                          jnp.float32),
                              rng.integers(10, 100, size=k).tolist()))

    mono = MaTUStrategy(T, d)
    chun = MaTUStrategy(T, d, chunk_clients=3)
    mono.aggregate_batch(RoundBatch.from_uploads(uploads, T))
    chun.aggregate_batch(RoundBatch.from_uploads(uploads, T))

    for t in range(T):
        a = np.asarray(mono.eval_vectors(t)[0])
        b = np.asarray(chun.eval_vectors(t)[0])
        assert np.array_equal(a, b), f"task {t}"
    mono._drain()
    _assert_downlinks_equal(mono.downlinks, chun.downlinks, "strategy")
    assert mono.uplink_bits(uploads) == chun.uplink_bits(uploads)


# -- lazy population ---------------------------------------------------------

def test_population_split_deterministic_and_lazy():
    """Per-client derivations are pure functions of (seed, id): query
    order never matters, same seed reproduces, different seeds differ,
    and round sampling is valid + round-varying without ever
    materialising the population."""
    from repro.data.dirichlet import PopulationSplit

    n = 100_000
    a = PopulationSplit(n_clients=n, n_tasks=8, seed=0)
    b = PopulationSplit(n_clients=n, n_tasks=8, seed=0)
    c = PopulationSplit(n_clients=n, n_tasks=8, seed=1)

    probe = [0, 1, 99_999, 12_345, 7]
    for cid in probe:                       # a queried in probe order
        assert a.tasks_for(cid) == b.tasks_for(cid)
        assert a.data_sizes_for(cid) == b.data_sizes_for(cid)
    for cid in reversed(probe):             # b re-queried reversed
        assert a.tasks_for(cid) == b.tasks_for(cid)
        ts = a.tasks_for(cid)
        assert ts == sorted(set(ts)) and all(0 <= t < 8 for t in ts)
    assert any(a.tasks_for(cid) != c.tasks_for(cid) for cid in probe)

    s0 = a.sample_round(0, 512)
    assert np.array_equal(s0, b.sample_round(0, 512))
    assert len(np.unique(s0)) == 512
    assert s0.min() >= 0 and s0.max() < n
    assert not np.array_equal(s0, a.sample_round(1, 512))
    # k·8 ≥ n exercises the permutation fallback
    tiny = PopulationSplit(n_clients=64, n_tasks=4, seed=0)
    full = tiny.sample_round(0, 64)
    assert sorted(full.tolist()) == list(range(64))


def test_population_fixed_tasks_per_client():
    from repro.data.dirichlet import PopulationSplit

    sp = PopulationSplit(n_clients=1000, n_tasks=8, tasks_per_client=2,
                         seed=3)
    for cid in (0, 17, 999):
        assert len(sp.tasks_for(cid)) == 2
        assert len(sp.data_sizes_for(cid)) == 2


def test_population_simulator_eval_every_and_determinism():
    """The population path honours ``FedConfig.eval_every`` (default 5,
    unchanged since the seed): rounds=6 evals at [5, 6]; fault/phase
    records land every round; two identical runs are bit-identical."""
    from repro.data.dirichlet import PopulationSplit
    from repro.fed.simulator import FedConfig, PopulationSimulator

    cfg = FedConfig(rounds=6, seed=0)       # eval_every default = 5
    split = PopulationSplit(n_clients=64, n_tasks=4, tasks_per_client=2,
                            seed=0)

    def run():
        sim = PopulationSimulator(cfg, split, d=256, clients_per_round=8,
                                  chunk_clients=4)
        return sim, sim.run()

    sim1, h1 = run()
    sim2, h2 = run()
    assert h1.rounds == [5, 6]
    assert len(h1.mean_acc) == 2
    assert len(h1.fault_counts) == len(h1.phase_us) == 6
    assert all(fc["sampled"] == 8 for fc in h1.fault_counts)
    assert h1.mean_acc == h2.mean_acc
    assert np.array_equal(sim1._tv_host, sim2._tv_host)
    # the synthetic drift is actually learning: alignment moves off
    # the 0.5 random-direction baseline
    assert h1.mean_acc[-1] > 0.55


# -- bench results handling --------------------------------------------------

def test_save_detail_merges_per_leg(monkeypatch, tmp_path):
    """Bench legs re-run separately must not clobber each other's rows:
    top-level keys merge, and shared grid keys merge per SUB-key (the
    dropped engine_sharded / pipelined-rows regression)."""
    import benchmarks.common as common

    monkeypatch.setattr(common, "RESULTS_DIR", str(tmp_path))
    common.save_detail("t", {"host_cores": 1,
                             "N32": {"us_packed": 1.0, "speedup": 2.0}})
    common.save_detail("t", {"N32": {"us_sharded": 3.0},
                             "N16": {"us_packed": 4.0}})
    with open(tmp_path / "t.json") as f:
        got = json.load(f)
    assert got == {"host_cores": 1,
                   "N32": {"us_packed": 1.0, "speedup": 2.0,
                           "us_sharded": 3.0},
                   "N16": {"us_packed": 4.0}}
    # corrupt file: start fresh instead of crashing
    (tmp_path / "t.json").write_text("{not json")
    common.save_detail("t", {"a": 1})
    with open(tmp_path / "t.json") as f:
        assert json.load(f) == {"a": 1}


# -- coder pool --------------------------------------------------------------

def test_coder_pool_byte_identical(monkeypatch):
    """The worker pool must be byte-invisible: with tiny chunk sizes
    forcing many independent encode chunks / decode windows, pooled
    output is byte-identical to the sequential fallback and the
    roundtrip is exact."""
    import repro.fed.compression as comp

    rng = np.random.default_rng(42)
    d = 4096
    w = -(-d // 32)
    rows = []
    for density in (0.01, 0.2, 0.7, 0.97):
        dense = rng.random((4, d)) < density
        rows.append(np.packbits(dense, axis=1, bitorder="little")
                    .view(np.uint32)[:, :w])
    words = np.ascontiguousarray(np.concatenate(rows))

    def roundtrip():
        comp._pool, comp._pool_workers = None, 0   # force pool rebuild
        stream, sizes = comp.encode_mask_rows_with_sizes(words, d)
        dec = comp.decode_mask_rows(stream, d, words.shape[0])
        return stream, sizes, dec

    monkeypatch.setattr(comp, "_ENC_CHUNK_BITS", 1 << 12)
    monkeypatch.setattr(comp, "_DEC_WINDOW_BYTES", 1 << 9)

    monkeypatch.setenv("REPRO_CODER_WORKERS", "1")
    s_seq, z_seq, d_seq = roundtrip()
    assert comp._coder_pool() is None               # sequential fallback

    monkeypatch.setenv("REPRO_CODER_WORKERS", "4")
    s_par, z_par, d_par = roundtrip()
    assert comp._coder_pool() is not None

    comp._pool, comp._pool_workers = None, 0        # drop the tiny pool
    assert np.array_equal(s_seq, s_par)
    assert np.array_equal(z_seq, z_par)
    assert np.array_equal(d_seq, d_par)
    assert np.array_equal(d_par, words)
