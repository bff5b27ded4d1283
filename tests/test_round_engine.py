"""Round-engine parity tests: the batched, kernel-dispatched engine
(repro.core.engine) against the dense ``matu_round`` reference of
``repro.core.aggregation`` (with ``core.unify`` re-unifying each
client's downlink), and across kernel dispatch modes — plus the
wire-format guarantees of the bit-packed / bf16 slot layout.

The wire contract under test (see the engine docstring): uploads are
quantised ONCE at the wire boundary — unified vectors to bf16, masks to
uint32 words — and the oracle consumes the identical values; mask bits
are decided on fp32 values before any bf16 rounding, so the engine's
downlink masks equal the oracle's bit for bit and its bf16 vectors are
the rounding of the oracle's fp32 ones.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.aggregation import matu_round, matu_round_packed
from repro.core.client import ClientUpload
from repro.core.engine import (EngineConfig, RoundEngine,
                               batched_client_unify, pack_uploads)
from repro.core.unify import unify_with_modulators
from repro.fed.compression import quantize_bf16_transport
from repro.kernels import bitpack, ops

jax.config.update("jax_platform_name", "cpu")

MODES = ["ref", "pallas_interpret"]


def bf16(x):
    return jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)


def random_uploads(rng, n, n_tasks, d, k_max, *, skew_sizes=True):
    """Ragged random round: each client holds 1..k_max distinct tasks.
    With n small vs n_tasks some tasks go unheld (partial participation).
    Unified vectors carry the bf16 wire quantisation (applied once, as
    the uplink does) so every server path consumes identical values."""
    ups = []
    for cid in range(n):
        k = int(rng.integers(1, k_max + 1))
        tasks = sorted(rng.choice(n_tasks, size=k, replace=False).tolist())
        tvs = jnp.asarray(rng.standard_normal((k, d)), jnp.float32)
        unified, masks, lams = unify_with_modulators(tvs)
        sizes = (rng.integers(10, 200, size=k).tolist() if skew_sizes
                 else [100] * k)
        ups.append(ClientUpload(cid, tasks, quantize_bf16_transport(unified),
                                masks, lams, sizes))
    return ups


def dense_oracle(uploads, n_tasks, **kw):
    """The round by the dense reference: stack the uploads into
    (N, T, d) tensors, run ``core.aggregation.matu_round`` and
    re-unify each client's task vectors with ``core.unify``.  Returns
    (RoundOutput, {client_id: (unified, masks, lams)})."""
    n, d = len(uploads), int(uploads[0].unified.shape[0])
    unified = np.stack([np.asarray(u.unified, np.float32) for u in uploads])
    masks = np.zeros((n, n_tasks, d), bool)
    lams = np.zeros((n, n_tasks), np.float32)
    member = np.zeros((n, n_tasks), bool)
    sizes = np.zeros((n, n_tasks), np.float32)
    for i, up in enumerate(uploads):
        dense = np.asarray(up.masks_dense())
        for s, t in enumerate(up.task_ids):
            masks[i, t], lams[i, t] = dense[s], up.lams[s]
            member[i, t], sizes[i, t] = True, up.data_sizes[s]
    out = matu_round(*map(jnp.asarray, (unified, masks, lams, member, sizes)),
                     **kw)
    downs = {up.client_id: unify_with_modulators(
        out.task_vectors[jnp.asarray(up.task_ids)]) for up in uploads}
    return out, downs


def assert_round_equal(oracle, out, downs, uploads, rtol=1e-5, atol=1e-6):
    """oracle = ``dense_oracle``'s pair (fp32 reference), out/downs =
    the engine's round (wire outputs)."""
    ref_out, ref_downs = oracle
    for name in ("task_vectors", "similarity", "tau_hats", "m_hats"):
        np.testing.assert_allclose(getattr(out, name), getattr(ref_out, name),
                                   rtol=rtol, atol=atol, err_msg=name)
    for up in uploads:
        (uni, masks, lams), b = ref_downs[up.client_id], downs[up.client_id]
        d = int(up.unified.shape[0])
        assert b.masks.dtype == jnp.uint32           # wire layout
        assert b.masks.shape == (len(up.task_ids), bitpack.packed_width(d))
        assert b.unified.dtype == jnp.bfloat16
        # mask bits are decided on fp32 values pre-rounding: bit-identical
        np.testing.assert_array_equal(np.asarray(masks),
                                      np.asarray(b.masks_dense()))
        # the bf16 wire vector is the rounding of the fp32 reference
        np.testing.assert_allclose(np.asarray(uni),
                                   np.asarray(b.unified, np.float32),
                                   rtol=1e-2, atol=1e-5)
        np.testing.assert_allclose(lams, b.lams, rtol=1e-4, atol=atol)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("seed,n,n_tasks,d,k_max", [
    (0, 4, 5, 128, 3),       # partial participation likely
    (1, 7, 6, 300, 3),       # d not divisible by 32 (ragged tail words)
    (2, 3, 8, 64, 2),        # heavy partial participation
    (3, 12, 5, 200, 4),      # more clients than tasks
    (4, 1, 4, 96, 2),        # single-client round
])
def test_engine_matches_legacy_server(seed, n, n_tasks, d, k_max, mode):
    """(a) wire-format engine ≡ the dense per-task reference
    (``dense_oracle``) on randomized ragged uploads, in both dispatch
    modes: task vectors, similarity, τ̂, m̂, every client's downlink."""
    rng = np.random.default_rng(seed)
    ups = random_uploads(rng, n, n_tasks, d, k_max)
    downs, out = RoundEngine(EngineConfig(n_tasks=n_tasks)).round(ups,
                                                                  mode=mode)
    assert_round_equal(dense_oracle(ups, n_tasks), out, downs, ups)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("cross_task,uniform_cross", [
    (True, False), (False, False), (True, True),
])
def test_engine_matches_legacy_ablations(cross_task, uniform_cross, mode):
    """Ablation variants (Fig. 6b) agree with the dense reference too."""
    rng = np.random.default_rng(9)
    ups = random_uploads(rng, 6, 5, 160, 3)
    eng = RoundEngine(EngineConfig(n_tasks=5, cross_task=cross_task,
                                   uniform_cross=uniform_cross))
    downs, out = eng.round(ups, mode=mode)
    oracle = dense_oracle(ups, 5, cross_task=cross_task,
                          uniform_cross=uniform_cross)
    assert_round_equal(oracle, out, downs, ups)


def test_pack_unpack_roundtrip_ragged():
    """ops.unpack_masks(pack_masks(m)) == m for d not divisible by 32,
    and tail bits of the last word are zero (the wire convention)."""
    rng = np.random.default_rng(2)
    for d in (1, 7, 31, 32, 33, 100, 257, 4096, 8191):
        m = jnp.asarray(rng.random((3, 2, d)) > 0.5)
        w = ops.pack_masks(m)
        assert w.dtype == jnp.uint32
        assert w.shape == (3, 2, bitpack.packed_width(d))
        np.testing.assert_array_equal(np.asarray(ops.unpack_masks(w, d)),
                                      np.asarray(m))
        tail = bitpack.packed_width(d) * 32 - d
        if tail:
            np.testing.assert_array_equal(
                np.asarray(w[..., -1] >> jnp.uint32(32 - tail)), 0)
        # host-side packer produces the identical bytes
        np.testing.assert_array_equal(np.asarray(w),
                                      bitpack.pack_bits_np(np.asarray(m)))


try:
    import hypothesis
    import hypothesis.extra.numpy as hnp
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @hypothesis.given(
        hnp.arrays(np.bool_, hnp.array_shapes(min_dims=1, max_dims=3,
                                              min_side=1, max_side=70)))
    @hypothesis.settings(max_examples=40, deadline=None)
    def test_pack_unpack_roundtrip_property(mask):
        d = mask.shape[-1]
        w = ops.pack_masks(jnp.asarray(mask))
        np.testing.assert_array_equal(np.asarray(ops.unpack_masks(w, d)),
                                      mask)
        np.testing.assert_array_equal(np.asarray(w), bitpack.pack_bits_np(mask))


def test_pack_uploads_empty_round_raises():
    """Satellite fix: an empty round used to die with IndexError on
    uploads[0]; it must raise a clear ValueError instead."""
    with pytest.raises(ValueError, match="empty round"):
        pack_uploads([], n_tasks=4)
    engine = RoundEngine(EngineConfig(n_tasks=4))
    with pytest.raises(ValueError, match="empty round"):
        engine.round([])


def test_engine_matches_matu_round_dense():
    """The dense reference (matu_round on the unpacked tensors, via the
    matu_round_packed wire adapter) is the engine's semantics."""
    rng = np.random.default_rng(5)
    ups = random_uploads(rng, 6, 5, 200, 3)
    packed = pack_uploads(ups, 5)
    assert packed.slot_masks.dtype == jnp.uint32
    masks, lams, member, sizes = packed.dense_tensors()
    dense = matu_round(packed.unified.astype(jnp.float32), masks, lams,
                       member, sizes)
    engine = RoundEngine(EngineConfig(n_tasks=5))
    out = engine.run_packed(packed)
    np.testing.assert_allclose(out.task_vectors, dense.task_vectors,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out.similarity, dense.similarity,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out.tau_hats, dense.tau_hats,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out.m_hats, dense.m_hats, rtol=1e-5, atol=1e-6)
    # the wire adapter reproduces the same dense reference from the
    # packed tensors directly
    dense2 = matu_round_packed(
        packed.unified,
        ops.pack_masks(masks), lams, member, sizes, packed.d)
    np.testing.assert_allclose(dense2.task_vectors, dense.task_vectors,
                               rtol=1e-6, atol=1e-7)


def test_unheld_tasks_never_transfer():
    """An unheld task contributes nothing to (and receives nothing from)
    cross-task transfer, in matu_round AND the engine."""
    rng = np.random.default_rng(6)
    n_tasks, d = 5, 150
    # all clients hold tasks 0-2 only; tasks 3-4 unheld this round
    ups = []
    for cid in range(4):
        tasks = [0, 1, 2]
        tvs = jnp.asarray(rng.standard_normal((3, d)), jnp.float32)
        unified, masks, lams = unify_with_modulators(tvs)
        ups.append(ClientUpload(cid, tasks, quantize_bf16_transport(unified),
                                masks, lams, [100] * 3))
    packed = pack_uploads(ups, n_tasks)
    masks, lams, member, sizes = packed.dense_tensors()
    dense = matu_round(packed.unified.astype(jnp.float32), masks, lams,
                       member, sizes, eps=-1.0)
    # unheld rows/cols of the (masked) similarity are exactly zero
    sim = np.asarray(dense.similarity)
    assert np.all(sim[3:] == 0) and np.all(sim[:, 3:] == 0)
    # unheld task vectors stay zero; held ones receive no zero-vector mix
    np.testing.assert_allclose(dense.task_vectors[3:], 0.0)
    engine = RoundEngine(EngineConfig(n_tasks=n_tasks, eps=-1.0))
    out = engine.run_packed(packed)
    np.testing.assert_allclose(out.task_vectors, dense.task_vectors,
                               rtol=1e-5, atol=1e-6)
    # uniform_cross ablation masks unheld tasks the same way
    uni = matu_round(packed.unified.astype(jnp.float32), masks, lams,
                     member, sizes, uniform_cross=True)
    np.testing.assert_allclose(uni.task_vectors[3:], 0.0)


def test_batched_reunify_matches_per_client():
    """(b) padded batched re-unification ≡ per-client
    unify_with_modulators on each valid slot subset — with the batched
    path emitting the wire tensors (bf16 + packed words)."""
    rng = np.random.default_rng(3)
    b, k, d = 7, 4, 250                  # d % 32 != 0: ragged tail words
    valid = rng.random((b, k)) > 0.35
    valid[:, 0] = True
    tvs = rng.standard_normal((b, k, d)).astype(np.float32)
    tvs[~valid] = 0.0
    unified, words, lams = batched_client_unify(jnp.asarray(tvs),
                                                jnp.asarray(valid))
    assert unified.dtype == jnp.bfloat16
    assert words.dtype == jnp.uint32
    assert words.shape == (b, k, bitpack.packed_width(d))
    masks = np.asarray(ops.unpack_masks(words, d))
    for i in range(b):
        sel = valid[i]
        tau, msk, lam = unify_with_modulators(jnp.asarray(tvs[i][sel]))
        # masks/λ are computed from fp32 values pre-rounding: exact
        np.testing.assert_array_equal(masks[i][sel], np.asarray(msk))
        np.testing.assert_allclose(np.asarray(lams[i])[sel], lam, rtol=1e-5)
        # the unified wire row is exactly bf16(fp32 unify)
        np.testing.assert_array_equal(np.asarray(bf16(tau)),
                                      np.asarray(unified[i]))
        assert not masks[i][~sel].any()
        np.testing.assert_allclose(np.asarray(lams[i])[~sel], 0.0)


def test_dispatch_modes_agree(monkeypatch):
    """(c) the pure-jnp path (REPRO_DISABLE_PALLAS=1) and the Pallas
    interpreter path agree on the full packed round: exact on packed
    words / integer fields, 1e-5 on fp32, 1 bf16 ulp on wire vectors."""
    rng = np.random.default_rng(4)
    ups = random_uploads(rng, 5, 4, 180, 3)
    engine = RoundEngine(EngineConfig(n_tasks=4))
    packed = pack_uploads(ups, 4)

    monkeypatch.setenv("REPRO_DISABLE_PALLAS", "1")
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert ops.resolve_mode() == "ref"
    out_ref = engine.run_packed(packed)

    monkeypatch.delenv("REPRO_DISABLE_PALLAS", raising=False)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert ops.resolve_mode() == "pallas_interpret"
    out_pal = engine.run_packed(packed)

    for name in ("task_vectors", "tau_hats", "similarity", "down_lams",
                 "n_held"):
        np.testing.assert_allclose(getattr(out_ref, name),
                                   getattr(out_pal, name),
                                   rtol=1e-5, atol=1e-5, err_msg=name)
    np.testing.assert_array_equal(np.asarray(out_ref.alpha_num),
                                  np.asarray(out_pal.alpha_num))
    np.testing.assert_array_equal(np.asarray(out_ref.down_masks),
                                  np.asarray(out_pal.down_masks))
    np.testing.assert_allclose(np.asarray(out_ref.down_unified, np.float32),
                               np.asarray(out_pal.down_unified, np.float32),
                               rtol=1e-2, atol=1e-5)


def test_static_signature_across_participation(monkeypatch):
    """Membership padding keeps the jit signature static: rounds with
    different client subsets of the same padded size hit one trace."""
    rng = np.random.default_rng(8)
    n_tasks, d = 5, 120
    engine = RoundEngine(EngineConfig(n_tasks=n_tasks))
    traces = {"n": 0}
    import repro.core.engine as engine_mod
    orig = engine_mod._round_impl

    def counting(*args, **kw):
        traces["n"] += 1
        return orig(*args, **kw)

    monkeypatch.setattr(engine_mod, "_round_impl", counting)
    engine._impls.clear()
    for trial in range(3):
        ups = random_uploads(rng, 3, n_tasks, d, 2)     # pads to n_max=4
        packed = pack_uploads(ups, n_tasks, n_max=4, k_max=2)
        engine.run_packed(packed)
    assert traces["n"] == 1, f"retraced {traces['n']}x for same padded shape"


def test_wire_bits_measured_from_buffers():
    """PackedRound.wire_bits / ClientUpload.uplink_bits report the bits
    of the actual wire tensors: 16d per bf16 vector, 32 per mask word,
    32 per scaler."""
    rng = np.random.default_rng(10)
    d = 100                                # dw = 4 words
    ups = random_uploads(rng, 3, 5, d, 2)
    packed = pack_uploads(ups, 5)
    dw = bitpack.packed_width(d)
    want = sum(16 * d + len(u.task_ids) * (32 * dw + 32) for u in ups)
    assert packed.wire_bits() == want
    wire_up = ClientUpload(0, [0, 1], bf16(np.zeros(d)),
                           jnp.zeros((2, dw), jnp.uint32), jnp.zeros(2),
                           [1, 1])
    assert wire_up.uplink_bits() == 16 * d + 2 * (32 * dw + 32)
    # the packed wire beats the paper's fp32+dense-bit scheme
    # (asymptotically (32+k)/(16+k) ≈ 1.9x at k=2)
    paper = 32 * d + 2 * (d + 32)
    assert paper / wire_up.uplink_bits() > 1.7


def test_strategy_batched_aggregate_matches_legacy_loop():
    """MaTUStrategy's pre-packed wire path ≡ per-client unify + the
    dense reference round on the same bf16 wire values."""
    from repro.fed.strategies import MaTUStrategy, RoundBatch, Upload

    rng = np.random.default_rng(11)
    n_tasks, d = 5, 140
    uploads = []
    for cid in range(6):
        k = int(rng.integers(1, 4))
        tasks = sorted(rng.choice(n_tasks, size=k, replace=False).tolist())
        tvs = jnp.asarray(rng.standard_normal((k, d)), jnp.float32)
        uploads.append(Upload(cid, tasks, tvs, rng.integers(10, 99, size=k).tolist()))

    strat = MaTUStrategy(n_tasks, d)
    strat.aggregate_batch(RoundBatch.from_uploads(uploads, n_tasks))

    wire_ups = []
    for u in uploads:
        unified, masks, lams = unify_with_modulators(u.task_vectors)
        wire_ups.append(ClientUpload(u.client_id, u.task_ids,
                                     quantize_bf16_transport(unified),
                                     masks, lams, u.data_sizes))
    ref_out, ref_downs = dense_oracle(wire_ups, n_tasks)

    np.testing.assert_allclose(strat.server.last_task_vectors,
                               ref_out.task_vectors, rtol=1e-5, atol=1e-6)
    for u in uploads:
        (uni, masks, lams) = ref_downs[u.client_id]
        b = strat.downlinks[u.client_id]
        assert b.packed
        np.testing.assert_allclose(np.asarray(uni),
                                   np.asarray(b.unified, np.float32),
                                   rtol=1e-2, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(masks),
                                      np.asarray(b.masks_dense()))
        np.testing.assert_allclose(lams, b.lams, rtol=1e-4, atol=1e-6)
