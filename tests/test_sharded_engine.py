"""Taskvec-sharded round-engine tests (the engine's sharding contract).

The multi-device cases run in subprocesses with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the main test
process stays at 1 device), on the (4, 2) debug mesh — the d axis
shards 8 ways over ("data", "model").

Contract under test:

* **bit parity** — in "ref" mode the sharded round is bit-identical to
  the single-device round, on ragged rounds and on d not divisible by
  devices·32: the λ
  reductions run on the fixed 256-coord block grid with the
  shard-invariant tree, the Eq. 5 dots psum is integer-exact, and all
  other math is per-coordinate.
* **padding** — ``pad_d_for_shards`` gives every shard a power-of-two
  multiple of 256 coords (= 8 whole uint32 words: packed mask words
  never split mid-word).
* **collectives** — the traced HLO contains exactly two all-reduces
  (the (T, T) similarity dots + the fused λ block-tree roots) and no
  other collective kind.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.engine import pad_d_for_shards
from repro.kernels.ref import LAMBDA_BLOCK

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(script: str, timeout: int = 600) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_pad_d_for_shards_contract():
    """Each shard's slice is a pow2 multiple of 256 coords (8 words);
    no padding when unsharded; idempotent on already-aligned d."""
    assert pad_d_for_shards(1000, 1) == 1000
    for d in (1, 31, 300, 1000, 4096, 1 << 20, (1 << 20) + 5):
        for shards in (2, 4, 8, 256, 512):
            dp = pad_d_for_shards(d, shards)
            assert dp >= d
            per = dp // shards
            assert per * shards == dp
            assert per % LAMBDA_BLOCK == 0
            assert per % 32 == 0                       # word boundary
            blocks = per // LAMBDA_BLOCK
            assert blocks & (blocks - 1) == 0          # pow2 blocks
    assert pad_d_for_shards(8 * LAMBDA_BLOCK, 8) == 8 * LAMBDA_BLOCK


def test_pack_uploads_without_mesh_unchanged():
    """mesh=None keeps the exact PR 2 layout: no padding, no d_pad."""
    import jax.numpy as jnp
    from repro.core.client import ClientUpload
    from repro.core.engine import pack_uploads

    rng = np.random.default_rng(0)
    d = 300
    ups = [ClientUpload(0, [0, 1],
                        jnp.asarray(rng.standard_normal(d), jnp.float32),
                        jnp.asarray(rng.random((2, d)) > 0.5),
                        jnp.ones(2), [10, 20])]
    batch = pack_uploads(ups, 4)
    assert batch.d_pad is None and batch.padded_d == d
    assert batch.unified.shape == (1, d)
    assert batch.slot_masks.shape == (1, 2, -(-d // 32))


_PARITY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["REPRO_DISABLE_PALLAS"] = "1"
    import json
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.client import ClientUpload
    from repro.core.engine import EngineConfig, RoundEngine
    from repro.core.unify import unify_with_modulators
    from repro.fed.compression import quantize_bf16_transport
    from repro.launch.mesh import make_debug_mesh

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

    mesh = make_debug_mesh((4, 2))
    FIELDS = ("task_vectors", "tau_hats", "similarity", "down_lams",
              "down_unified", "down_masks", "m_hats")
    report = {"devices": len(jax.devices())}
    # ragged rounds (n not a power of two → padding rows), d not
    # divisible by devices*32 = 256 (1000, 300), and an aligned case
    for seed, (n, T, d, km) in enumerate(
            [(5, 6, 1000, 3), (3, 4, 300, 2), (4, 5, 4096, 2)]):
        ups = uploads(np.random.default_rng(seed), n, T, d, km)
        single = RoundEngine(EngineConfig(n_tasks=T))
        shard = RoundEngine(EngineConfig(n_tasks=T), mesh=mesh)
        _, out_s = single.round(ups)
        downs_h, out_h = shard.round(ups)
        for f in FIELDS:
            a = np.asarray(getattr(out_s, f))
            b = np.asarray(getattr(out_h, f))
            report[f"{d}/{f}"] = bool(a.shape == b.shape
                                      and np.array_equal(a, b))
        # downlink slicing keeps the wire dtypes per client
        dl = downs_h[ups[0].client_id]
        report[f"{d}/dl_dtype"] = (str(dl.masks.dtype) == "uint32"
                                   and str(dl.unified.dtype) == "bfloat16")
    print(json.dumps(report))
""")


_PALLAS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["REPRO_PALLAS_INTERPRET"] = "1"
    import json
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.client import ClientUpload
    from repro.core import engine
    from repro.core.engine import EngineConfig, RoundEngine
    from repro.core.unify import unify_with_modulators
    from repro.fed.compression import quantize_bf16_transport
    from repro.kernels import bitpack
    from repro.launch.mesh import make_round_mesh

    rng = np.random.default_rng(7)
    n, T, d = 6, 5, 20000      # each of 4 shards pads 5000 to 8192
    ups = []
    for cid in range(n):
        tasks = sorted(rng.choice(T, size=2, replace=False).tolist())
        tvs = jnp.asarray(rng.standard_normal((2, d)), jnp.float32)
        uni, masks, lams = unify_with_modulators(tvs)
        ups.append(ClientUpload(cid, tasks, quantize_bf16_transport(uni),
                                masks, lams,
                                rng.integers(10, 200, size=2).tolist()))
    _, single = RoundEngine(EngineConfig(n_tasks=T)).round(ups)
    # sign planes packed in column blocks inside every shard, and the
    # outputs sliced back to d a few rows at a time
    bitpack.PACK_BLOCK_BYTES, bitpack.PACK_PIECE_BYTES = 1024, 4096
    engine.SLICE_BLOCK_BYTES = 2 * 32768 * 4
    shard = RoundEngine(EngineConfig(n_tasks=T), mesh=make_round_mesh(4))
    downs, out = shard.round(ups)
    report = {"devices": len(jax.devices())}
    for f in ("alpha_num", "down_unified", "down_masks", "similarity"):
        a, b = np.asarray(getattr(single, f)), np.asarray(getattr(out, f))
        report[f] = bool(a.shape == b.shape and np.array_equal(a, b))
    for f in ("task_vectors", "down_lams"):
        a, b = np.asarray(getattr(single, f)), np.asarray(getattr(out, f))
        report[f] = float(np.max(np.abs(a - b)) / np.max(np.abs(a)))
    print(json.dumps(report))
""")


def test_sharded_pallas_round_matches_single_device():
    """The Pallas round under ``shard_map`` (interpreted, 4 devices,
    d padded per shard, sign planes packed in blocks, outputs sliced
    back to d in row blocks) hands out what
    the one-device Pallas round hands out: downlinks, agreement and
    similarity bit for bit; the (T, d) task vectors and λ to fp32
    accumulation (the Eq. 7 mixing matmul runs per shard, the λ sums
    are psum'd over the shards)."""
    rep = _run_sub(_PALLAS)
    assert rep.pop("devices") == 4
    assert rep.pop("task_vectors") < 1e-6
    assert rep.pop("down_lams") < 1e-5
    assert all(rep.values()), rep


def test_sharded_round_bit_identical_ref():
    """8-way sharded round ≡ single-device round, bit for bit, ragged
    rounds, d % (devices·32) != 0."""
    report = _run_sub(_PARITY)
    assert report.pop("devices") == 8
    bad = [k for k, v in report.items() if v is not True]
    assert not bad, f"sharded round diverged on: {bad}"


_HLO = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["REPRO_DISABLE_PALLAS"] = "1"
    import json, re
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.engine import (EngineConfig, RoundEngine,
                                   pad_d_for_shards)
    from repro.launch.mesh import make_debug_mesh
    from repro.nn.sharding import taskvec_sharding

    mesh = make_debug_mesh((4, 2))
    T, n_max, k_max, d = 6, 8, 4, 1 << 18
    eng = RoundEngine(EngineConfig(n_tasks=T), mesh=mesh)
    d_pad = pad_d_for_shards(d, eng.n_shards)
    rep = NamedSharding(mesh, P())
    args = (
        jax.ShapeDtypeStruct((n_max, d_pad), jnp.bfloat16,
                             sharding=taskvec_sharding(mesh, 2)),
        jax.ShapeDtypeStruct((n_max, k_max, d_pad // 32), jnp.uint32,
                             sharding=taskvec_sharding(mesh, 3)),
        jax.ShapeDtypeStruct((n_max, k_max), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((n_max, k_max), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((n_max, k_max), jnp.bool_, sharding=rep),
        jax.ShapeDtypeStruct((n_max, k_max), jnp.int32, sharding=rep),
    )
    txt = eng._impl("ref", d).lower(*args).compile().as_text()
    kinds = {}
    for kind in ("all-gather", "all-reduce", "reduce-scatter",
                 "all-to-all", "collective-permute"):
        n = len(re.findall(r"= \\S+ %?" + kind + r"\\(", txt))
        if n:
            kinds[kind] = n
    sim_ar = len(re.findall(r"s32\\[" + f"{T},{T}" + r"\\]\\S* %?all-reduce\\(",
                            txt))
    print(json.dumps({"kinds": kinds, "sim_allreduce": sim_ar}))
""")


def test_sharded_round_collectives():
    """The round HLO carries exactly two all-reduces — the (T, T)
    similarity dots and the λ roots — and no other collective kind."""
    report = _run_sub(_HLO)
    assert set(report["kinds"]) == {"all-reduce"}, report
    assert report["kinds"]["all-reduce"] == 2, report
    assert report["sim_allreduce"] == 1, report


_STACK = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np, jax, jax.numpy as jnp
    from repro.data.dirichlet import dirichlet_split
    from repro.data.synthetic import make_constellation
    from repro.fed.simulator import FedConfig, FedSimulator
    from repro.fed.strategies import MaTUStrategy, RoundBatch, Upload
    from repro.fed.testbed import MLPBackbone
    from repro.launch.mesh import make_debug_mesh

    mesh = make_debug_mesh((4, 2))
    report = {}

    # strategy level: sharded batched path vs single-device batched path
    rng = np.random.default_rng(7)
    n_tasks, d = 5, 1000
    uploads = []
    for cid in range(6):
        k = int(rng.integers(1, 4))
        tasks = sorted(rng.choice(n_tasks, size=k, replace=False).tolist())
        tvs = jnp.asarray(rng.standard_normal((k, d)), jnp.float32)
        uploads.append(Upload(cid, tasks, tvs,
                              rng.integers(10, 99, size=k).tolist()))
    plain = MaTUStrategy(n_tasks, d)
    plain.aggregate_batch(RoundBatch.from_uploads(uploads, n_tasks))
    shard = MaTUStrategy(n_tasks, d, mesh=mesh)
    shard.aggregate_batch(RoundBatch.from_uploads(uploads, n_tasks))
    a = np.asarray(plain.server.last_task_vectors)
    b = np.asarray(shard.server.last_task_vectors)
    # client unify λ crosses shards through one psum whose grouping
    # differs from the single-device accumulation → fp32 tolerance here
    # (engine-level parity is bitwise; see the parity test)
    report["tv_close"] = bool(np.allclose(a, b, rtol=1e-4, atol=1e-5))
    report["masks_equal"] = all(
        bool(np.array_equal(np.asarray(plain.downlinks[u.client_id].masks),
                            np.asarray(shard.downlinks[u.client_id].masks)))
        for u in uploads)
    # wire accounting must be identical: padding is traffic, not bits
    report["uplink_bits"] = (plain.uplink_bits(uploads)
                             == shard.uplink_bits(uploads))
    report["downlink_bits"] = (plain.downlink_bits()
                               == shard.downlink_bits()
                               and plain.downlink_bits() > 0)

    # simulator level: same FedSimulator script, mesh threaded through
    con = make_constellation(n_tasks=4, n_groups=2, feat_dim=16,
                             n_classes=4, conflict_pairs=[(0, 1)], seed=0)
    split = dirichlet_split(n_clients=5, n_tasks=4, n_classes=4,
                            zeta_t=0.0, seed=0)
    bb = MLPBackbone(16, hidden=24, lora_rank=4)
    cfg = FedConfig(rounds=2, local_steps=4, eval_every=2, seed=0)
    hist = FedSimulator(cfg, con, split, bb,
                        MaTUStrategy(4, bb.d), mesh=mesh).run()
    report["sim_ran"] = len(hist.mean_acc) > 0
    report["sim_downlink_mean"] = hist.mean_downlink_bits > 0
    print(json.dumps(report))
""")


def test_strategy_and_simulator_sharded():
    """MaTUStrategy/FedSimulator with a mesh: same results (fp32
    tolerance through the client-unify psum), identical measured wire
    bits, and the untouched simulator loop runs end to end."""
    report = _run_sub(_STACK)
    bad = [k for k, v in report.items() if v is not True]
    assert not bad, f"sharded strategy/simulator diverged on: {bad}"
