"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
plus hypothesis property checks.  Kernels run in interpret mode on CPU —
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

from repro.kernels import bitpack, ref
from repro.kernels.fused_unify import (fused_unify_packed_pallas,
                                       fused_unify_packed_rows_pallas,
                                       fused_unify_pallas)
from repro.kernels.masked_agg import (masked_agg_batched_pallas,
                                      masked_agg_pallas)
from repro.kernels.sign_sim import sign_sim_packed_pallas, sign_sim_pallas
from repro.kernels.unify import unify_pallas

jax.config.update("jax_platform_name", "cpu")

SHAPES_KD = [(1, 7), (2, 100), (3, 2048), (8, 5000), (16, 7777), (5, 4096)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("k,d", SHAPES_KD)
@pytest.mark.parametrize("dtype", DTYPES)
def test_unify_sweep(k, d, dtype):
    key = jax.random.PRNGKey(k * 1000 + d)
    tv = jax.random.normal(key, (k, d)).astype(dtype)
    got = unify_pallas(tv, interpret=True)
    want = ref.unify_ref(tv)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n,d", [(2, 64), (4, 333), (10, 4096), (30, 9999)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_masked_agg_sweep(n, d, dtype):
    key = jax.random.PRNGKey(n * 7 + d)
    k1, k2, k3 = jax.random.split(key, 3)
    u = jax.random.normal(k1, (n, d)).astype(dtype)
    m = (jax.random.uniform(k2, (n, d)) > 0.5).astype(dtype)
    lam = (jax.random.uniform(k3, (n,)) + 0.5).astype(jnp.float32)
    n_mem = max(1, n // 2)
    gam = jnp.where(jnp.arange(n) < n_mem, 1.0 / n_mem, 0.0)
    t1, m1 = masked_agg_pallas(u, m, lam, gam, rho=0.4, interpret=True)
    t2, m2 = ref.masked_agg_ref(u, m, lam, gam, 0.4)
    np.testing.assert_allclose(t1, t2, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(m1, m2, rtol=1e-6)


@pytest.mark.parametrize("t,d", [(2, 50), (8, 4096), (16, 2048), (30, 10000)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_sign_sim_sweep(t, d, dtype):
    key = jax.random.PRNGKey(t + d)
    x = jax.random.normal(key, (t, d)).astype(dtype)
    got = sign_sim_pallas(x, interpret=True)
    want = ref.sign_sim_ref(x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


if HAVE_HYPOTHESIS:
    @hypothesis.given(
        hnp.arrays(np.float32, hnp.array_shapes(min_dims=2, max_dims=2,
                                                min_side=1, max_side=40),
                   elements=st.floats(-100, 100, width=32)))
    @hypothesis.settings(max_examples=30, deadline=None)
    def test_unify_property_matches_ref(arr):
        tv = jnp.asarray(arr)
        np.testing.assert_allclose(unify_pallas(tv, interpret=True),
                                   ref.unify_ref(tv), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,t,d", [(3, 2, 100), (5, 4, 2048), (8, 6, 3333)])
def test_masked_agg_batched_sweep(n, t, d):
    """Whole-round kernel vs its oracle and vs T single-task launches."""
    key = jax.random.PRNGKey(n * 13 + t * 7 + d)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    u = jax.random.normal(k1, (n, d), jnp.float32)
    member = jax.random.uniform(k2, (n, t)) > 0.4
    m = ((jax.random.uniform(k3, (n, t, d)) > 0.5)
         & member[:, :, None]).astype(jnp.float32)
    lam = jax.random.uniform(k4, (n, t)) + 0.5
    sizes = jnp.where(member, 50.0, 0.0)
    gam = sizes / jnp.maximum(jnp.sum(sizes, 0, keepdims=True), 1e-12)

    tau_k, mh_k = masked_agg_batched_pallas(u, m, lam, gam, member,
                                            rho=0.4, interpret=True)
    tau_r, mh_r = ref.masked_agg_batched_ref(u, m, lam, gam, member, 0.4)
    np.testing.assert_allclose(tau_k, tau_r, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(mh_k, mh_r, rtol=1e-6)
    for ti in range(t):
        tau_1, mh_1 = masked_agg_pallas(u, m[:, ti], lam[:, ti], gam[:, ti],
                                        rho=0.4, interpret=True)
        np.testing.assert_allclose(tau_k[ti], tau_1, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(mh_k[ti], mh_1, rtol=1e-6)


@pytest.mark.parametrize("b,k,d", [(2, 1, 64), (4, 3, 2048), (6, 4, 5000)])
def test_fused_unify_sweep(b, k, d):
    """Fused unify+mask+λ kernel vs oracle, with ragged validity."""
    key = jax.random.PRNGKey(b * 31 + k * 17 + d)
    k1, k2 = jax.random.split(key)
    valid = jax.random.uniform(k1, (b, k)) > 0.3
    valid = valid.at[:, 0].set(True)            # every client holds ≥ 1 task
    tvs = jax.random.normal(k2, (b, k, d), jnp.float32)
    tvs = jnp.where(valid[:, :, None], tvs, 0.0)

    u_k, m_k, num_k, den_k = fused_unify_pallas(tvs, valid, interpret=True)
    u_r, m_r, num_r, den_r = ref.fused_unify_ref(tvs, valid)
    np.testing.assert_allclose(u_k, u_r, rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(m_k > 0.5), np.asarray(m_r))
    np.testing.assert_allclose(num_k, num_r, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(den_k, den_r, rtol=1e-5, atol=1e-6)


# -- packed (wire-format) kernels ------------------------------------------

@pytest.mark.parametrize("n,t,d", [(3, 2, 100), (5, 4, 4096), (8, 6, 3333)])
def test_masked_agg_batched_packed_matches_bool(n, t, d):
    """Packed-mask kernel ≡ bool kernel on the K = T slot layout (slot k
    of every client holds task k, so the slot words are the dense
    (N, T, d/32) tensor): same τ̂, and m̂ re-derived from the emitted
    agreement numerator matches bit for bit."""
    key = jax.random.PRNGKey(n * 13 + t * 7 + d)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    u = jax.random.normal(k1, (n, d), jnp.float32)
    member = jax.random.uniform(k2, (n, t)) > 0.4
    m = ((jax.random.uniform(k3, (n, t, d)) > 0.5)
         & member[:, :, None])
    lam = jax.random.uniform(k4, (n, t)) + 0.5
    sizes = jnp.where(member, 50.0, 0.0)
    gam = sizes / jnp.maximum(jnp.sum(sizes, 0, keepdims=True), 1e-12)
    tasks = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (n, t))

    from repro.kernels import ops

    words = bitpack.pack_bits(m)
    # both dispatch modes of the packed op, through the ops contract
    tau_p, anum = ops.masked_agg_batched_packed(
        u.astype(jnp.bfloat16), words, lam, sizes, member, tasks, t, d,
        rho=0.4, mode="pallas_interpret")
    tau_r, anum_r = ops.masked_agg_batched_packed(
        u.astype(jnp.bfloat16), words, lam, sizes, member, tasks, t, d,
        rho=0.4, mode="ref")
    np.testing.assert_allclose(tau_p, tau_r, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(anum), np.asarray(anum_r))
    # the bool comparator consumes the identical bf16-quantised values
    tau_b, mh_b = masked_agg_batched_pallas(
        u.astype(jnp.bfloat16).astype(jnp.float32), m.astype(jnp.float32),
        lam, gam, member, rho=0.4, interpret=True)
    np.testing.assert_allclose(tau_p, tau_b, rtol=1e-5, atol=1e-6)
    n_t = jnp.maximum(jnp.sum(member.astype(jnp.float32), 0), 1.0)
    alpha = anum / n_t[:, None]
    mh_p = jnp.where(alpha >= 0.4, 1.0, alpha)
    np.testing.assert_array_equal(np.asarray(mh_p), np.asarray(mh_b))
    # the numerator is an exact integer ≤ N_t
    a = np.asarray(anum)
    np.testing.assert_array_equal(a, np.round(a))
    assert (a <= np.asarray(n_t)[:, None]).all()


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
    oracle: clients hold 1..K_max distinct tasks, empty slots carry the
    sentinel task id T, no words and no weight, and d need not be a
    multiple of the kernel's block."""
    from repro.kernels import ops

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
    lams = jnp.asarray((rng.random((n, k)) + 0.5) * valid, jnp.float32)
    sizes = jnp.asarray(rng.integers(1, 100, (n, k)) * valid, jnp.float32)
    args = (u, bitpack.pack_bits(jnp.asarray(masks)), lams, sizes,
            jnp.asarray(valid), jnp.asarray(tasks), t, d)
    tau, anum = ops.masked_agg_batched_packed(*args, mode="pallas_interpret")
    tau_r, anum_r = ops.masked_agg_batched_packed(*args, mode="ref")
    assert tau.shape == anum.shape == (t, d)
    np.testing.assert_array_equal(np.asarray(anum), np.asarray(anum_r))
    np.testing.assert_allclose(tau, tau_r, rtol=1e-5, atol=1e-6)
    if not full:
        unheld = ~np.isin(np.arange(t), tasks[valid])
        assert not np.asarray(tau)[unheld].any()
        assert not np.asarray(anum)[unheld].any()


@pytest.mark.parametrize("b,k,d", [(2, 1, 64), (4, 3, 2048), (6, 4, 5000)])
def test_fused_unify_packed_matches_bool(b, k, d):
    """Packed fused unify emits exactly pack(bool masks) and
    bf16(fp32 unified) of the bool kernel, with identical num/den."""
    key = jax.random.PRNGKey(b * 31 + k * 17 + d)
    k1, k2 = jax.random.split(key)
    valid = jax.random.uniform(k1, (b, k)) > 0.3
    valid = valid.at[:, 0].set(True)
    tvs = jax.random.normal(k2, (b, k, d), jnp.float32)
    tvs = jnp.where(valid[:, :, None], tvs, 0.0)

    u_p, words, num_p, den_p = fused_unify_packed_pallas(tvs, valid,
                                                         interpret=True)
    u_b, m_b, num_b, den_b = fused_unify_pallas(tvs, valid, interpret=True)
    assert u_p.dtype == jnp.bfloat16 and words.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(u_b.astype(jnp.bfloat16)),
                                  np.asarray(u_p))
    np.testing.assert_array_equal(np.asarray(bitpack.pack_bits(m_b > 0.5)),
                                  np.asarray(words))
    np.testing.assert_allclose(num_p, num_b, rtol=1e-6)
    np.testing.assert_allclose(den_p, den_b, rtol=1e-6)
    # ref packed oracle agrees too
    u_r, w_r, num_r, den_r = ref.fused_unify_packed_ref(tvs, valid)
    np.testing.assert_array_equal(np.asarray(u_r), np.asarray(u_p))
    np.testing.assert_array_equal(np.asarray(w_r), np.asarray(words))


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
def test_sign_sim_packed_matches_dense(t, d):
    """Popcount sign-sim on bit-planes == the fp32 sgn·sgnᵀ matmul —
    exact integers, so equality is bitwise."""
    key = jax.random.PRNGKey(t + d)
    x = jax.random.normal(key, (t, d), jnp.float32)
    x = jnp.where(jnp.abs(x) < 0.05, 0.0, x)     # exercise sgn = 0
    pos, nz = bitpack.sign_planes(x)
    dots = sign_sim_packed_pallas(pos, nz, interpret=True)
    want = jnp.sign(x) @ jnp.sign(x).T
    np.testing.assert_array_equal(np.asarray(dots), np.asarray(want))
    # and via the dispatch op, normalised: ≡ sign_sim_ref
    from repro.kernels import ops
    sim = ops.sign_sim_packed(pos, nz, d, mode="pallas_interpret")
    np.testing.assert_allclose(sim, ref.sign_sim_ref(x), rtol=1e-6)
    sim_ref = ops.sign_sim_packed(pos, nz, d, mode="ref")
    np.testing.assert_allclose(sim_ref, ref.sign_sim_ref(x), rtol=1e-6)


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


def test_sign_sim_padding_invariance():
    """d-padding must not change S (sgn(0)=0 contributes nothing)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 1000))
    s1 = sign_sim_pallas(x, block_d=512, interpret=True)
    s2 = sign_sim_pallas(x, block_d=2048, interpret=True)  # heavy padding
    np.testing.assert_allclose(s1, s2, rtol=1e-6)


def test_kernels_match_core_semantics():
    """Kernel outputs agree with repro.core (the algorithm actually used)."""
    from repro.core.aggregation import sign_similarity, task_aggregate
    from repro.core.unify import unify

    key = jax.random.PRNGKey(3)
    tv = jax.random.normal(key, (4, 3000))
    np.testing.assert_allclose(unify_pallas(tv, interpret=True), unify(tv),
                               rtol=1e-5, atol=1e-6)

    u = jax.random.normal(key, (6, 3000))
    m = jax.random.uniform(jax.random.PRNGKey(4), (6, 3000)) > 0.5
    lam = jax.random.uniform(jax.random.PRNGKey(5), (6,)) + 0.5
    member = jnp.arange(6) < 4
    sizes = jnp.where(member, 25.0, 0.0)
    tau_core, m_core = task_aggregate(u, m, lam, member, sizes, 0.4)
    gam = jnp.where(member, 0.25, 0.0)
    tau_k, m_k = masked_agg_pallas(u, m.astype(u.dtype), lam, gam,
                                   rho=0.4, interpret=True)
    np.testing.assert_allclose(tau_k, tau_core, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(m_k, m_core, rtol=1e-6)

    np.testing.assert_allclose(sign_sim_pallas(tv, interpret=True),
                               sign_similarity(tv), rtol=1e-5)
