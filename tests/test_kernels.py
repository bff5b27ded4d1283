"""Pallas kernel validation: shape/dtype sweeps vs the dense reference
semantics of ``repro.core`` (``core.unify``, ``core.aggregation``), plus
hypothesis property checks.  Kernels run in interpret mode on CPU —
bit-identical semantics to the TPU lowering path.

Hypothesis is optional: property checks are skipped (not errored at
collection) in environments without it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.extra.numpy as hnp
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.aggregation import sign_similarity, task_aggregate
from repro.core.unify import unify_with_modulators_masked
from repro.kernels import bitpack, ops, ref
from repro.kernels.fused_unify import (fused_unify_packed_pallas,
                                       fused_unify_packed_rows_pallas)
from repro.kernels.sign_sim import sign_sim_packed_pallas

jax.config.update("jax_platform_name", "cpu")

SHAPES_KD = [(1, 7), (2, 100), (3, 2048), (8, 5000), (16, 7777), (5, 4096)]
DTYPES = [jnp.float32, jnp.bfloat16]
RHO = 0.4


def unify_oracle(tvs, valid):
    """``core.unify.unify_with_modulators_masked`` client by client, in
    the kernel's output form: (unified fp32 (B, d), masks bool
    (B, K, d), λ (B, K))."""
    outs = [unify_with_modulators_masked(jnp.asarray(tvs[b], jnp.float32),
                                         jnp.asarray(valid[b]))
            for b in range(tvs.shape[0])]
    return tuple(jnp.stack(x) for x in zip(*outs))


def assert_unify_matches_oracle(tvs, valid, got, *, lam_rtol=1e-5):
    """Packed fused-unify outputs (bf16 unified, mask words, num, den)
    against the dense oracle: the unified row is the bf16 rounding of
    the oracle's fp32 one and the mask words pack its masks, exactly;
    λ = num / max(den, eps) to fp32 accumulation tolerance."""
    uni, words, num, den = got
    tau, masks, lams = unify_oracle(tvs, valid)
    assert uni.dtype == jnp.bfloat16 and words.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(uni),
                                  np.asarray(tau.astype(jnp.bfloat16)))
    np.testing.assert_array_equal(np.asarray(words),
                                  np.asarray(bitpack.pack_bits(masks)))
    np.testing.assert_allclose(num / jnp.maximum(den, 1e-12), lams,
                               rtol=lam_rtol, atol=1e-6)


def slot_oracle(u, masks, lams, sizes, valid, tasks, n_tasks):
    """Eq. 3 + 4 of every task through ``core.aggregation.task_aggregate``
    on the dense per-task tensors built from a slot layout (each client
    holds a task in at most one slot).  Returns (tau_hats (T, d),
    m_hats (T, d), agreement numerators |Σ_n sgn(m_n ⊙ τ_n)| (T, d))."""
    u = np.asarray(u, np.float32)
    taus, mhats, anums = [], [], []
    for t in range(n_tasks):
        sel = valid & (tasks == t)                       # (N, K)
        member = sel.any(1)
        m_t = (masks & sel[:, :, None]).any(1)           # (N, d)
        tau, mh = task_aggregate(jnp.asarray(u), jnp.asarray(m_t),
                                 jnp.asarray((lams * sel).sum(1)),
                                 jnp.asarray(member),
                                 jnp.asarray((sizes * sel).sum(1)), RHO)
        taus.append(tau)
        mhats.append(mh)
        anums.append(np.abs(np.einsum("n,nd->d", member.astype(np.float32),
                                      np.sign(np.where(m_t, u, 0.0)))))
    return jnp.stack(taus), jnp.stack(mhats), np.stack(anums)


def m_hat_from(anum, n_t):
    alpha = anum / jnp.maximum(jnp.asarray(n_t, jnp.float32), 1.0)[:, None]
    return jnp.where(alpha >= RHO, 1.0, alpha)


def ragged_clients(key, b, k, d, dtype=jnp.float32):
    """(B, K, d) slot stacks with ragged validity (every client holds
    at least one task; invalid slots carry zeros)."""
    k1, k2 = jax.random.split(key)
    valid = jax.random.uniform(k1, (b, k)) > 0.3
    valid = valid.at[:, 0].set(True)
    tvs = jax.random.normal(k2, (b, k, d), jnp.float32)
    return jnp.where(valid[:, :, None], tvs, 0.0).astype(dtype), valid


@pytest.mark.parametrize("k,d", SHAPES_KD)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_unify_packed_sweep(k, d, dtype):
    """The client-side unify kernel against ``core.unify`` over the
    (K, d) sweep, three clients with ragged validity, fp32 and bf16
    task vectors."""
    tvs, valid = ragged_clients(jax.random.PRNGKey(k * 1000 + d), 3, k, d,
                                dtype)
    got = fused_unify_packed_pallas(tvs, valid, interpret=True)
    assert_unify_matches_oracle(tvs, valid, got)


if HAVE_HYPOTHESIS:
    @hypothesis.given(
        hnp.arrays(np.float32, hnp.array_shapes(min_dims=2, max_dims=2,
                                                min_side=1, max_side=40),
                   elements=st.floats(-100, 100, width=32)))
    @hypothesis.settings(max_examples=30, deadline=None)
    def test_fused_unify_packed_property_matches_core(arr):
        # one fixed (1, 40, 40) launch: rows past K are invalid slots,
        # columns past d zeros, so every example reuses one compile
        k, d = arr.shape
        tvs = jnp.zeros((1, 40, 40), jnp.float32).at[0, :k, :d].set(arr)
        valid = jnp.arange(40)[None] < k
        got = fused_unify_packed_pallas(tvs, valid, interpret=True)
        assert_unify_matches_oracle(tvs, valid, got, lam_rtol=1e-5)


def single_task_round(key, n, d, dtype):
    """One task, one slot a client: the first half of the clients hold
    it, the rest carry empty slots (sentinel task id 1, no words)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    u = jax.random.normal(k1, (n, d)).astype(dtype)
    n_mem = max(1, n // 2)
    valid = (np.arange(n) < n_mem)[:, None]
    masks = (np.asarray(jax.random.uniform(k2, (n, 1, d)) > 0.5)
             & valid[:, :, None])
    lams = np.asarray(jax.random.uniform(k3, (n, 1)) + 0.5) * valid
    sizes = np.asarray(jax.random.randint(k4, (n, 1), 10, 200),
                       np.float32) * valid
    tasks = np.where(valid, 0, 1).astype(np.int32)
    return u, masks, lams.astype(np.float32), sizes, valid, tasks


@pytest.mark.parametrize("n,d", [(2, 64), (4, 333), (10, 4096), (30, 9999)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_masked_agg_single_task_sweep(n, d, dtype):
    """The slot Eq. 3–4 kernel with T = 1 against
    ``core.aggregation.task_aggregate``."""
    u, masks, lams, sizes, valid, tasks = single_task_round(
        jax.random.PRNGKey(n * 7 + d), n, d, dtype)
    tau, anum = ops.masked_agg_batched_packed(
        u, bitpack.pack_bits(jnp.asarray(masks)), jnp.asarray(lams),
        jnp.asarray(sizes), jnp.asarray(valid), jnp.asarray(tasks), 1, d,
        rho=RHO, mode="pallas_interpret")
    tau_o, mh_o, anum_o = slot_oracle(u, masks, lams, sizes, valid, tasks, 1)
    np.testing.assert_allclose(tau, tau_o, rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(anum), anum_o)
    np.testing.assert_allclose(m_hat_from(anum, [valid.sum()]), mh_o,
                               rtol=1e-6)


def dense_slot_round(key, n, t, d):
    """K = T slot layout: slot k of every client holds task k, valid
    where the client is a member (the slot words are the dense
    (N, T, d/32) tensor)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    u = jax.random.normal(k1, (n, d), jnp.float32)
    member = np.asarray(jax.random.uniform(k2, (n, t)) > 0.4)
    masks = (np.asarray(jax.random.uniform(k3, (n, t, d)) > 0.5)
             & member[:, :, None])
    lams = np.asarray(jax.random.uniform(k4, (n, t)) + 0.5, np.float32)
    sizes = np.where(member, 50.0, 0.0).astype(np.float32)
    tasks = np.broadcast_to(np.arange(t, dtype=np.int32), (n, t))
    return u, masks, lams, sizes, member, tasks


@pytest.mark.parametrize("n,t,d", [(3, 2, 100), (5, 4, 2048), (8, 6, 3333)])
def test_masked_agg_batched_equals_single_launches(n, t, d):
    """The slot kernel over all T tasks at once equals T single-task
    launches of itself, one per task's slot column."""
    u, masks, lams, sizes, member, tasks = dense_slot_round(
        jax.random.PRNGKey(n * 13 + t * 7 + d), n, t, d)
    words = bitpack.pack_bits(jnp.asarray(masks))
    tau_b, anum_b = ops.masked_agg_batched_packed(
        u, words, jnp.asarray(lams), jnp.asarray(sizes), jnp.asarray(member),
        jnp.asarray(tasks), t, d, rho=RHO, mode="pallas_interpret")
    for ti in range(t):
        col = slice(ti, ti + 1)
        tau_1, anum_1 = ops.masked_agg_batched_packed(
            u, words[:, col], jnp.asarray(lams[:, col]),
            jnp.asarray(sizes[:, col]), jnp.asarray(member[:, col]),
            jnp.asarray(np.where(member[:, col], 0, 1).astype(np.int32)),
            1, d, rho=RHO, mode="pallas_interpret")
        np.testing.assert_allclose(tau_b[ti], tau_1[0], rtol=1e-4, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(anum_b[ti]),
                                      np.asarray(anum_1[0]))


@pytest.mark.parametrize("n,t,d", [(3, 2, 100), (5, 4, 4096), (8, 6, 3333)])
def test_masked_agg_batched_packed_matches_bool(n, t, d):
    """The Eq. 3–4 kernel on the K = T slot layout (slot k of every
    client holds task k, so the slot words are the dense (N, T, d/32)
    tensor) against ``core.aggregation.task_aggregate`` on the
    identical bf16-quantised values: same τ̂, and m̂ re-derived from
    the emitted agreement numerator matches."""
    u, masks, lams, sizes, member, tasks = dense_slot_round(
        jax.random.PRNGKey(n * 13 + t * 7 + d), n, t, d)
    u = u.astype(jnp.bfloat16)
    tau_p, anum = ops.masked_agg_batched_packed(
        u, bitpack.pack_bits(jnp.asarray(masks)), jnp.asarray(lams),
        jnp.asarray(sizes), jnp.asarray(member), jnp.asarray(tasks), t, d,
        rho=RHO, mode="pallas_interpret")
    tau_o, mh_o, anum_o = slot_oracle(u, masks, lams, sizes, member, tasks, t)
    np.testing.assert_allclose(tau_p, tau_o, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(anum), anum_o)
    n_t = member.sum(0)
    np.testing.assert_array_equal(np.asarray(m_hat_from(anum, n_t)),
                                  np.asarray(mh_o))
    # the numerator is an exact integer ≤ N_t
    a = np.asarray(anum)
    np.testing.assert_array_equal(a, np.round(a))
    assert (a <= np.maximum(n_t, 1)[:, None]).all()


# (N, K_max, T, d, every client holds K_max tasks, unified dtype);
# K_max = T is the dense case, T > N·K_max leaves tasks nobody holds
ROWS_CASES = {
    "ragged": (5, 2, 6, 5000, False, jnp.bfloat16),
    "unheld-tasks": (7, 1, 9, 300, False, jnp.bfloat16),
    "k-max-3": (3, 3, 4, 4096 * 2 + 7, False, jnp.bfloat16),
    "k-eq-t": (6, 4, 4, 4096, True, jnp.bfloat16),
    "n-not-pow2": (20, 2, 30, 9000, False, jnp.bfloat16),
    "n-aligned": (32, 2, 30, 4096 * 2 + 100, True, jnp.bfloat16),
    "fp32-unified": (9, 3, 5, 4096 + 1, False, jnp.float32),
}


@pytest.mark.parametrize("n,k,t,d,full,dtype", ROWS_CASES.values(),
                         ids=ROWS_CASES.keys())
def test_masked_agg_rows_matches_ref(n, k, t, d, full, dtype):
    """The Eq. 3–4 kernel over the wire slot layout against the dense
    oracle (``task_aggregate`` per task): clients hold 1..K_max distinct
    tasks, empty slots carry the sentinel task id T, no words and no
    weight, and d need not be a multiple of the kernel's block."""
    rng = np.random.default_rng(n * 1000 + k * 100 + t)
    tasks = np.full((n, k), t, np.int32)
    valid = np.zeros((n, k), bool)
    for i in range(n):
        kn = k if full else int(rng.integers(1, k + 1))
        tasks[i, :kn] = rng.permutation(t)[:kn]
        valid[i, :kn] = True
    masks = (rng.random((n, k, d)) > 0.5) & valid[:, :, None]
    u = jnp.asarray(rng.standard_normal((n, d)), dtype)
    u = u.at[:, ::11].set(0.0)                   # exercise sgn = 0
    lams = ((rng.random((n, k)) + 0.5) * valid).astype(np.float32)
    sizes = (rng.integers(1, 100, (n, k)) * valid).astype(np.float32)
    tau, anum = ops.masked_agg_batched_packed(
        u, bitpack.pack_bits(jnp.asarray(masks)), jnp.asarray(lams),
        jnp.asarray(sizes), jnp.asarray(valid), jnp.asarray(tasks), t, d,
        mode="pallas_interpret")
    tau_o, _, anum_o = slot_oracle(u, masks, lams, sizes, valid, tasks, t)
    assert tau.shape == anum.shape == (t, d)
    np.testing.assert_array_equal(np.asarray(anum), anum_o)
    np.testing.assert_allclose(tau, tau_o, rtol=1e-5, atol=1e-6)
    if not full:
        unheld = ~np.isin(np.arange(t), tasks[valid])
        assert not np.asarray(tau)[unheld].any()
        assert not np.asarray(anum)[unheld].any()


@pytest.mark.parametrize("b,k,d", [(2, 1, 64), (4, 3, 2048), (6, 4, 5000)])
def test_fused_unify_packed_matches_bool(b, k, d):
    """Packed fused unify emits exactly pack(masks) and bf16(unified)
    of ``core.unify.unify_with_modulators_masked``, with its λ; the
    "ref" streaming implementation emits the same words and vectors."""
    tvs, valid = ragged_clients(jax.random.PRNGKey(b * 31 + k * 17 + d),
                                b, k, d)
    got = fused_unify_packed_pallas(tvs, valid, interpret=True)
    assert_unify_matches_oracle(tvs, valid, got, lam_rtol=1e-6)
    u_r, w_r, _, _ = ref.fused_unify_packed_ref(tvs, valid)
    np.testing.assert_array_equal(np.asarray(u_r), np.asarray(got[0]))
    np.testing.assert_array_equal(np.asarray(w_r), np.asarray(got[1]))


@pytest.mark.parametrize("b,k,t,d", [(8, 2, 6, 5000), (4, 3, 5, 8192),
                                     (5, 4, 7, 300), (3, 1, 2, 4096)])
def test_fused_unify_rows_matches_gathered_stack(b, k, t, d):
    """The kernel that reads each client's slot rows straight from the
    (T, d) task vectors is bit-identical to gathering the (B, K, d)
    stack first (sentinel id T clamped, as ``take(mode="clip")``)."""
    rng = np.random.default_rng(b * 100 + k * 10 + t)
    tv = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    tv = tv.at[:, ::7].set(0.0)                  # exercise sgn = 0
    tids = rng.integers(0, t + 1, size=(b, k)).astype(np.int32)
    valid = jnp.asarray(tids < t)
    want = fused_unify_packed_pallas(jnp.take(tv, tids, axis=0, mode="clip"),
                                     valid, interpret=True)
    got = fused_unify_packed_rows_pallas(tv, jnp.asarray(tids), valid,
                                         interpret=True)
    for w, g in zip(want, got):
        assert w.shape == g.shape and w.dtype == g.dtype
        np.testing.assert_array_equal(np.asarray(w).view(np.uint8),
                                      np.asarray(g).view(np.uint8))


@pytest.mark.parametrize("r,d", [(1, 4096), (3, 8192), (2, 4096 * 3)])
def test_pack_tile_is_the_wire_layout(r, d):
    bits = jax.random.uniform(jax.random.PRNGKey(r + d), (r, d)) > 0.5
    np.testing.assert_array_equal(np.asarray(bitpack.pack_tile(bits)),
                                  np.asarray(bitpack.pack_bits(bits)))


@pytest.mark.parametrize("shape", [(3, 4096), (2, 5, 2048), (7, 96)])
def test_sign_bits_in_blocks_equal_one_pack(shape, monkeypatch):
    """Past ``PACK_BLOCK_BYTES`` the sign planes are packed in column
    blocks; the words are the same."""
    x = jax.random.normal(jax.random.PRNGKey(len(shape)), shape)
    x = jnp.where(jnp.abs(x) < 0.1, 0.0, x).astype(jnp.bfloat16)
    whole = bitpack.sign_bits(x)
    monkeypatch.setattr(bitpack, "PACK_BLOCK_BYTES", 64)
    monkeypatch.setattr(bitpack, "PACK_PIECE_BYTES", 256)
    blocked = jax.jit(bitpack.sign_bits)(x)
    for w, g in zip(whole, blocked):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))
    np.testing.assert_array_equal(np.asarray(whole[0]),
                                  np.asarray(bitpack.pack_bits(x > 0)))


@pytest.mark.parametrize("t,d", [(2, 50), (8, 4096), (16, 2048), (30, 10000)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_sign_sim_packed_matches_dense(t, d, dtype):
    """Popcount sign-sim on bit-planes == the fp32 sgn·sgnᵀ matmul —
    exact integers, so equality is bitwise — and, normalised through
    the dispatch op, ``core.aggregation.sign_similarity``."""
    key = jax.random.PRNGKey(t + d)
    x = jax.random.normal(key, (t, d), jnp.float32)
    x = jnp.where(jnp.abs(x) < 0.05, 0.0, x).astype(dtype)  # sgn = 0 too
    pos, nz = bitpack.sign_planes(x)
    dots = sign_sim_packed_pallas(pos, nz, interpret=True)
    s = jnp.sign(x.astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(dots), np.asarray(s @ s.T))
    want = sign_similarity(x.astype(jnp.float32))
    for mode in ("pallas_interpret", "ref"):
        np.testing.assert_allclose(ops.sign_sim_packed(pos, nz, d, mode=mode),
                                   want, rtol=1e-6)


@pytest.mark.parametrize("d", [100, 250, 4096])
def test_packed_kernel_tail_bits_zero(d):
    """Packed kernel outputs honour the wire convention: tail bits of
    the last mask word are zero for ragged d."""
    key = jax.random.PRNGKey(d)
    tvs = jax.random.normal(key, (2, 3, d), jnp.float32)
    valid = jnp.ones((2, 3), bool)
    _, words, _, _ = fused_unify_packed_pallas(tvs, valid, interpret=True)
    tail = bitpack.packed_width(d) * 32 - d
    if tail:
        np.testing.assert_array_equal(
            np.asarray(words[..., -1] >> jnp.uint32(32 - tail)), 0)


if HAVE_HYPOTHESIS:
    @hypothesis.given(
        hnp.arrays(np.bool_, hnp.array_shapes(min_dims=2, max_dims=2,
                                              min_side=1, max_side=80)))
    @hypothesis.settings(max_examples=30, deadline=None)
    def test_bitpack_roundtrip_property(mask):
        d = mask.shape[-1]
        w = bitpack.pack_bits(jnp.asarray(mask))
        np.testing.assert_array_equal(np.asarray(bitpack.unpack_bits(w, d)),
                                      mask)
        np.testing.assert_array_equal(np.asarray(w),
                                      bitpack.pack_bits_np(mask))


def test_sign_sim_packed_padding_invariance():
    """Zero-padded words (an explicit pad, and the kernel's own padding
    to its word block) leave the popcount dots unchanged."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 1000))
    pos, nz = bitpack.sign_planes(x)
    want = sign_sim_packed_pallas(pos, nz, block_w=32, interpret=True)
    pad = ((0, 0), (0, 300))
    for got in (sign_sim_packed_pallas(pos, nz, interpret=True),
                sign_sim_packed_pallas(jnp.pad(pos, pad), jnp.pad(nz, pad),
                                       block_w=128, interpret=True)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernels_match_core_semantics():
    """The round's kernels agree with repro.core (the algorithm actually
    used): client unify, Eq. 3–4 over one task and Eq. 5."""
    key = jax.random.PRNGKey(3)
    tv = jax.random.normal(key, (1, 4, 3000))
    valid = jnp.ones((1, 4), bool)
    assert_unify_matches_oracle(
        tv, valid, fused_unify_packed_pallas(tv, valid, interpret=True))

    u = jax.random.normal(key, (6, 3000))
    m = np.asarray(jax.random.uniform(jax.random.PRNGKey(4), (6, 3000)) > 0.5)
    lam = jax.random.uniform(jax.random.PRNGKey(5), (6,)) + 0.5
    member = np.arange(6) < 4
    sizes = np.where(member, 25.0, 0.0).astype(np.float32)
    tau_core, m_core = task_aggregate(u, jnp.asarray(m), lam,
                                      jnp.asarray(member), jnp.asarray(sizes),
                                      RHO)
    tau_k, anum = ops.masked_agg_batched_packed(
        u, bitpack.pack_bits(jnp.asarray(m & member[:, None]))[:, None],
        lam[:, None], jnp.asarray(sizes)[:, None],
        jnp.asarray(member)[:, None],
        jnp.asarray(np.where(member, 0, 1).astype(np.int32))[:, None], 1, 3000,
        rho=RHO, mode="pallas_interpret")
    np.testing.assert_allclose(tau_k[0], tau_core, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(m_hat_from(anum, [4])[0], m_core, rtol=1e-6)
    with pytest.raises(ValueError, match="no 'ref' dispatch"):
        ops.masked_agg_batched_packed(
            u, jnp.zeros((6, 1, 94), jnp.uint32), lam[:, None],
            jnp.asarray(sizes)[:, None], jnp.asarray(member)[:, None],
            jnp.zeros((6, 1), jnp.int32), 1, 3000, mode="ref")

    tvs = tv[0]
    pos, nz = bitpack.sign_planes(tvs)
    np.testing.assert_allclose(
        ops.sign_sim_packed(pos, nz, 3000, mode="pallas_interpret"),
        sign_similarity(tvs), rtol=1e-5)
