"""Pure-jnp streaming implementations of the kernel ops — the "ref"
dispatch of ``repro.kernels.ops`` and the round XLA runs on CPU/GPU.

The dense reference semantics they are tested against live in
``repro.core.aggregation`` and ``repro.core.unify``.

The streaming round function (``matu_round_slots_packed_ref``) is also
the body the sharded engine runs per shard under ``shard_map``: with
``axis_name`` set it receives the local d-slice of every d-axis tensor
and reconstructs the few genuinely global quantities with explicit
collectives — the Eq. 5 (T, T) sign dots by one ``psum``
(integer-exact under any reduction order) and the λ
numerator/denominator totals by the shard-invariant block-tree
reduction below (bit-identical to the single-device round).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import bitpack


# d-axis streaming chunk for the CPU reference path: the per-chunk
# working set ((N, K, dc) fp32 products, (T, dc) accumulators) stays
# cache-resident, mirroring the Pallas kernels' VMEM grid over d.
CHUNK_D = 1 << 14

# Fixed block grid for the λ numerator/denominator reductions over d:
# partial sums are taken per LAMBDA_BLOCK consecutive coords and the
# totals combined by a power-of-two-aligned binary tree over block
# index (``_tree_total``).  Because the grid and tree depend only on
# the block index — never on chunk width or shard count — the λ totals
# of the sharded round are bit-identical to the single-device round's,
# provided shard boundaries land on block boundaries (the engine pads d
# so every shard holds a power-of-two number of whole blocks).  One
# block is 8 uint32 mask words, so block alignment subsumes the wire
# format's 32-bit word-boundary rule (``bitpack.WORD_BITS``).
LAMBDA_BLOCK = 256
assert LAMBDA_BLOCK % bitpack.WORD_BITS == 0


def _chunked(d: int, chunk: int):
    """Pick an effective chunk (≤ requested, covering small d in one
    step) and the padded length.  Chunks are always power-of-two
    multiples of LAMBDA_BLOCK, so the λ block grid tiles every chunk."""
    c = min(chunk, max(LAMBDA_BLOCK, 1 << (d - 1).bit_length()))
    pad = (-d) % c
    return c, d + pad


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def _block_partials(x: jax.Array) -> jax.Array:
    """(..., c) -> (..., c // LAMBDA_BLOCK) per-block partial sums over
    the fixed λ block grid (c is a multiple of LAMBDA_BLOCK)."""
    s = x.shape
    return jnp.sum(x.reshape(s[:-1] + (s[-1] // LAMBDA_BLOCK, LAMBDA_BLOCK)),
                   axis=-1)


def _tree_total(p: jax.Array) -> jax.Array:
    """(..., L) -> (...,): canonical binary-tree sum, pairing elements
    (2i, 2i+1) at every level after zero-padding L to a power of two.

    The grouping depends only on the index grid, so any zero-padded
    extension of the same nonneg partials gives the bit-identical total
    (x + 0.0 is exact) — the property the shard-parity contract rests
    on."""
    L = p.shape[-1]
    Lp = _next_pow2(L)
    if Lp != L:
        p = jnp.pad(p, [(0, 0)] * (p.ndim - 1) + [(0, Lp - L)])
    while p.shape[-1] > 1:
        p = p[..., 0::2] + p[..., 1::2]
    return p[..., 0]


def _shard_offset(axis_name, axis_sizes) -> jax.Array:
    """Flat taskvec shard index of the executing device, major→minor in
    spec order — matches the d-axis layout of a dim sharded over the
    same mesh-axis tuple."""
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    idx = jnp.int32(0)
    for a, s in zip(names, axis_sizes):
        idx = idx * s + lax.axis_index(a)
    return idx


def _lam_totals(parts, axis_name=None, axis_sizes=()):
    """Finish the λ reductions from per-block partial buffers.

    Each ``parts`` entry is (..., n_blk_local) nonneg fp32 partials on
    the fixed LAMBDA_BLOCK grid.  Local blocks reduce by the canonical
    tree; under ``shard_map`` (axis_name set) the per-shard roots are
    scattered into a (n_shards,)-slot vector — exact, single contributor
    per slot — combined by ONE ``psum`` covering every λ array, and the
    tree finishes over the shard axis.  With power-of-two shard counts
    and whole power-of-two block counts per shard this is the exact
    canonical tree over the global block grid: bit-identical to the
    single-device reduction."""
    loc = tuple(_tree_total(p) for p in parts)
    if axis_name is None:
        return loc
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    n_sh = int(np.prod(axis_sizes))
    off = _shard_offset(names, axis_sizes)
    # every λ root rides ONE all-reduce: flatten + concat the roots,
    # scatter into this shard's slot column, psum, tree over shards
    flat = jnp.concatenate([x.reshape(-1) for x in loc])
    scat = lax.dynamic_update_slice_in_dim(
        jnp.zeros((flat.shape[0], n_sh), flat.dtype), flat[:, None], off,
        axis=1)
    total = _tree_total(lax.psum(scat, names))
    out, at = [], 0
    for x in loc:
        out.append(total[at:at + x.size].reshape(x.shape))
        at += x.size
    return tuple(out)


def _unify_block(x, vf):
    """Eq. 2 + modulators on one (…, K, dc) block; vf (…, K) float."""
    xm = x * vf[..., None]
    sigma = jnp.sign(jnp.sum(xm, axis=-2))
    aligned = (xm * sigma[..., None, :]) > 0
    mu = jnp.max(jnp.where(aligned, jnp.abs(xm), 0.0), axis=-2)
    tau = sigma * mu
    mask = ((x * tau[..., None, :]) > 0) & (vf[..., None] > 0)
    maskf = mask.astype(jnp.float32)
    num = jnp.sum(jnp.abs(xm), axis=-1)
    den = jnp.sum(maskf * jnp.abs(tau)[..., None, :], axis=-1)
    return tau, mask, num, den


def fused_unify_packed_ref(task_vectors: jax.Array, valid: jax.Array, *,
                           chunk: int = CHUNK_D):
    """Fused unify + task-mask + λ-scaler, batched over clients:
    consumes bf16 (or fp32) slot-packed task vectors and emits the
    uplink wire tensors — bf16 unified vectors and bit-packed uint32
    mask words.

    task_vectors (B, K, d) bf16/fp32 (garbage/zero in invalid slots);
    valid (B, K) bool.  Invalid slots are zeroed before the sign
    election, so row b equals per-client
    ``unify_with_modulators(task_vectors[b, valid[b]])``.  Streams the
    d axis in cache-sized chunks (one fori_loop writing into
    pre-allocated buffers in place).  All compute is fp32 per chunk
    (inputs are upcast chunk by chunk, never as a whole), mask bits are
    decided on the fp32 values BEFORE the unified vector is rounded to
    bf16, and λ num/den stay fp32.  Returns (unified (B, d) bf16,
    mask_words (B, K, ceil(d/32)) uint32, num (B, K), den (B, K)) with
    λ = num / max(den, eps) left to the caller (invalid slots give
    num = den = 0 → λ = 0).
    """
    b, k, d = task_vectors.shape
    chunk, dp = _chunked(d, chunk)
    dwc, dwp = chunk // 32, dp // 32
    x_p = task_vectors
    if dp != d:
        x_p = jnp.pad(x_p, ((0, 0), (0, 0), (0, dp - d)))
    vf = valid.astype(jnp.float32)

    # the unified carry stays fp32 inside the loop — a bf16 carry
    # defeats XLA's in-place buffer aliasing on CPU (each iteration
    # copies the whole buffer); the wire rounding is one streaming
    # cast after the loop
    def step(c, carry):
        uni, msk, num, den = carry
        off = c * chunk
        x = jax.lax.dynamic_slice_in_dim(x_p, off, chunk, axis=2)
        tau, mask, num_c, den_c = _unify_block(x.astype(jnp.float32), vf)
        words = bitpack.pack_bits(mask)
        uni = jax.lax.dynamic_update_slice_in_dim(uni, tau, off, axis=1)
        msk = jax.lax.dynamic_update_slice_in_dim(msk, words, c * dwc, axis=2)
        return uni, msk, num + num_c, den + den_c

    uni, msk, num, den = jax.lax.fori_loop(
        0, dp // chunk, step,
        (jnp.zeros((b, dp), jnp.float32),
         jnp.zeros((b, k, dwp), jnp.uint32),
         jnp.zeros((b, k), jnp.float32), jnp.zeros((b, k), jnp.float32)))
    return (uni[:, :d].astype(jnp.bfloat16),
            msk[:, :, :bitpack.packed_width(d)], num, den)


def alpha_dtype(n: int):
    """Narrowest dtype holding the Eq. 3 agreement numerator
    |Σ_n sgn(m ⊙ τ_n)| ≤ N_t ≤ n (an exact small integer)."""
    return jnp.uint8 if n <= 255 else jnp.int32


def matu_round_slots_packed_ref(unified: jax.Array, slot_mask_words: jax.Array,
                                slot_lams: jax.Array, slot_sizes: jax.Array,
                                slot_valid: jax.Array, slot_tasks: jax.Array,
                                n_tasks: int, d: int, *, rho: float,
                                eps: float, kappa: int,
                                cross_task: bool = True,
                                uniform_cross: bool = False,
                                chunk: int = CHUNK_D,
                                axis_name=None, axis_sizes=(),
                                d_norm: int = 0):
    """The full MaTU server round (Eq. 3–7 + downlink re-unification)
    over slot-packed uploads, streamed in two cache-blocked passes.

    Layout: unified (N, d); slot_mask_words (N, K, ceil(d/32)); slot_lams
    / slot_sizes / slot_valid (N, K); slot_tasks (N, K) int32 with the
    sentinel ``n_tasks`` in invalid slots.  Work is O(Σ_n k_n · d), not
    the dense O(N·T·d), because per-task reductions are segment-sums
    over slot rows rather than masked sums over all clients.

    Pass 1 streams each d-chunk once: Eq. 3 agreement + Eq. 4 merge via
    segment-sum into a cache-resident (T+1, dc) accumulator (sentinel
    bucket swallows invalid slots), Eq. 5 sign dots accumulated on the
    fly.  The (T, T) weight logic runs between passes.  Pass 2 streams
    chunks again: Eq. 6 mix + Eq. 7 combine, then gathers each chunk's
    fresh task vectors straight into the fused downlink re-unification
    while they are still cache-hot.  Every big tensor stays in its
    transport layout end to end —

    * ``unified`` (N, d) arrives bf16 and is upcast fp32 one chunk at a
      time (never materialised dense);
    * ``slot_mask_words`` (N, K, ceil(d/32)) uint32 packed masks; the
      Eq. 3 sign election runs on bitwise ANDs of mask words against the
      sign bit-planes of τ_n, and only the two AND products are expanded
      to fp32 (the mask itself is never unpacked separately: the merge
      selector m·[τ≠0] is their sum, exact because τ=0 contributes 0);
    * Eq. 5 sign dots accumulate by popcount over the packed sign
      planes of τ̂ (exact integers — identical to the fp32 matmul);
    * m̂ is never materialised: pass 1 stores the agreement numerator
      |Σ sgn| as one byte per coordinate (exact; see ``alpha_dtype``)
      and pass 2 re-derives m̂ = 1[α ≥ ρ] ∨ α with the identical fp32
      division, so both passes see bit-identical values;
    * the downlink re-unification emits bf16 unified vectors and packed
      mask words — the downlink wire format — with mask bits and λ
      num/den decided on fp32 values before the bf16 rounding.

    Under ``shard_map`` (``axis_name`` set, with the mesh axis sizes in
    ``axis_sizes``) every d-axis tensor is the executing shard's slice,
    ``d`` is the LOCAL unpacked count, and ``d_norm`` carries the global
    feature count for the Eq. 5 1/d normalisation.  The Eq. 5 popcount
    dots cross shards through one integer ``psum`` (exact under any
    reduction order) and the λ num/den totals through the single
    ``_lam_totals`` psum — per-coordinate math never communicates.

    Returns (task_vectors (T, d) fp32, tau_hats (T, d) fp32,
    alpha_num (T, d) uint8, n_t (T,) fp32, similarity (T, T),
    down_unified (N, d) bf16, down_mask_words (N, K, ceil(d/32)),
    down_num (N, K), down_den (N, K)).
    """
    n, k, dw_in = slot_mask_words.shape
    m_rows = n * k
    chunk, dp = _chunked(d, chunk)
    dwc, dwp = chunk // 32, dp // 32
    n_blk, blkc = dp // LAMBDA_BLOCK, chunk // LAMBDA_BLOCK
    n_seg = n_tasks + 1
    a_dt = alpha_dtype(n)
    d_norm = d_norm or d

    ids = slot_tasks.reshape(m_rows)
    vf = slot_valid.reshape(m_rows).astype(jnp.float32)
    sizes = slot_sizes.reshape(m_rows).astype(jnp.float32) * vf
    totals = jax.ops.segment_sum(sizes, ids, num_segments=n_seg)
    gam = sizes / jnp.maximum(totals[ids], 1e-12)
    glv = gam * slot_lams.reshape(m_rows).astype(jnp.float32) * vf
    n_t = jax.ops.segment_sum(vf, ids, num_segments=n_seg)[:n_tasks]
    held = n_t > 0

    u_p = unified                       # stays bf16; upcast per chunk
    m_w = slot_mask_words
    if dp != d:
        u_p = jnp.pad(u_p, ((0, 0), (0, dp - d)))
    if dwp != dw_in:
        m_w = jnp.pad(m_w, ((0, 0), (0, 0), (0, dwp - dw_in)))

    glv_nk = glv.reshape(n, k)
    n_t_max = jnp.maximum(n_t, 1.0)

    # ---- pass 1: Eq. 3 + 4 per chunk, Eq. 5 popcount dots ----------------
    # one unpack per chunk (to int8 — the sign election is pure small-
    # integer algebra: int8 bits × int8 signs, exact) feeds both the
    # Eq. 3 election and the Eq. 4 merge; the packed words never exist
    # in fp32 outside this cache-resident block.  The fp32 merge is one
    # whole-round segment-sum in global row order (the order the
    # chunked round's scatter fold repeats); the sign sum is
    # integer-exact under any order.
    def pass1(c, carry):
        tau_buf, anum_buf, dots = carry
        off = c * chunk
        uc = jax.lax.dynamic_slice_in_dim(u_p, off, chunk,
                                          axis=1).astype(jnp.float32)
        mw = jax.lax.dynamic_slice_in_dim(m_w, c * dwc, dwc, axis=2)
        mi8 = bitpack.unpack_bits(mw, chunk, jnp.int8)         # (N, K, dc)
        signs = (mi8 * jnp.sign(uc).astype(jnp.int8)[:, None, :])
        a_num = jax.ops.segment_sum(
            signs.reshape(m_rows, chunk).astype(jnp.int32), ids,
            num_segments=n_seg)[:n_tasks].astype(jnp.float32)
        recon = mi8.astype(jnp.float32) * (glv_nk[:, :, None]
                                           * uc[:, None, :])
        tau_pre = jax.ops.segment_sum(recon.reshape(m_rows, chunk), ids,
                                      num_segments=n_seg)[:n_tasks]
        a_abs = jnp.abs(a_num)
        alpha = a_abs / n_t_max[:, None]
        m_hat = jnp.where(alpha >= rho, 1.0, alpha)
        tau = tau_pre * m_hat
        pos_t, nz_t = bitpack.sign_planes(tau)
        dots = dots + bitpack.packed_sign_dots(pos_t, nz_t)
        tau_buf = jax.lax.dynamic_update_slice_in_dim(tau_buf, tau, off,
                                                      axis=1)
        anum_buf = jax.lax.dynamic_update_slice_in_dim(
            anum_buf, a_abs.astype(a_dt), off, axis=1)
        return tau_buf, anum_buf, dots

    tau_hats, anum_buf, dots = jax.lax.fori_loop(
        0, dp // chunk, pass1,
        (jnp.zeros((n_tasks, dp), jnp.float32),
         jnp.zeros((n_tasks, dp), a_dt),
         jnp.zeros((n_tasks, n_tasks), jnp.int32)))

    if axis_name is not None:
        # the one tensor collective of the sharded round: the (T, T)
        # popcount dots are exact integers, so the psum is bit-identical
        # to the single-device accumulation under any reduction order
        dots = lax.psum(dots, axis_name)

    heldf = held.astype(jnp.float32)
    sim = 0.5 * (dots.astype(jnp.float32) / d_norm + 1.0) \
        * heldf[None, :] * heldf[:, None]
    weights = cross_weights_ref(sim, held, eps=eps, kappa=kappa,
                                cross_task=cross_task,
                                uniform_cross=uniform_cross)
    total_w = jnp.sum(weights, axis=1, keepdims=True)
    norm_w = weights / jnp.maximum(total_w, 1e-12)
    has = (total_w > 0).astype(jnp.float32)

    c1 = (1.0 / (1.0 + has))
    c2 = (has / (1.0 + has))

    # ---- pass 2: Eq. 6 + 7 per chunk, downlink re-unify while hot --------
    # m̂ is re-derived from the byte-wide agreement numerator with the
    # same fp32 division pass 1 used — bit-identical, 4x less traffic.
    # Invalid slots gather the appended all-zero sentinel row (ids ==
    # n_tasks), which zeroes them exactly — no per-element validity
    # masking anywhere in the block.
    def pass2(c, carry):
        tv_buf, uni_buf, dmask_buf, num_p, den_p = carry
        off = c * chunk
        tau = jax.lax.dynamic_slice_in_dim(tau_hats, off, chunk, axis=1)
        anum = jax.lax.dynamic_slice_in_dim(anum_buf, off, chunk, axis=1)
        alpha = anum.astype(jnp.float32) / n_t_max[:, None]
        m_hat = jnp.where(alpha >= rho, 1.0, alpha)
        tv = c1 * tau + c2 * (m_hat * (norm_w @ tau))
        num_p = jax.lax.dynamic_update_slice_in_dim(
            num_p, _block_partials(jnp.abs(tv)), c * blkc, axis=1)
        tv_ext = jnp.concatenate([tv, jnp.zeros((1, chunk), jnp.float32)], 0)
        # one (N, K, dc) gather feeds the σ election and the per-slot
        # sweep (the sweep slices it — no re-gather per slot).  Sign
        # agreement is decided by sign algebra, not fp products —
        # aligned ⟺ x·σ > 0 exactly, and relu(x·σ) = |x| on aligned
        # coords exactly (σ = ±1) — so per-slot work stays in L2-sized
        # (N, dc) tiles.  x·τ_n > 0 ⟺ aligned ∧ μ > 0 (exact up to
        # fp32 underflow of the x·τ product, where the algebraic sign
        # is used); on the mask |τ_n| = |σ|·μ = μ exactly, so the λ
        # denominator sums μ directly.
        x = jnp.take(tv_ext, ids, axis=0).reshape(n, k, chunk)
        sigma = jnp.sign(jnp.sum(x, axis=1))                   # (N, dc)
        posm = sigma > 0
        negm = sigma < 0
        als = []
        mu = jnp.zeros((n, chunk), jnp.float32)
        for kk in range(k):
            x_k = x[:, kk, :]                                  # (N, dc)
            al_k = ((x_k > 0) & posm) | ((x_k < 0) & negm)
            mu = jnp.maximum(mu, jnp.where(al_k, jnp.abs(x_k), 0.0))
            als.append(al_k)
        tau_n = sigma * mu
        mupos = mu[:, None, :] > 0
        dmask = jnp.stack(als, axis=1) & mupos     # zero slots: never set
        den_c = _block_partials(jnp.where(dmask, mu[:, None, :], 0.0))
        tv_buf = jax.lax.dynamic_update_slice_in_dim(tv_buf, tv, off, axis=1)
        # fp32 carry (see fused_unify_packed_ref): the bf16 wire
        # rounding happens in one streaming cast after the loop
        uni_buf = jax.lax.dynamic_update_slice_in_dim(uni_buf, tau_n, off,
                                                      axis=1)
        dmask_buf = jax.lax.dynamic_update_slice_in_dim(
            dmask_buf, bitpack.pack_bits(dmask), c * dwc, axis=2)
        den_p = jax.lax.dynamic_update_slice_in_dim(den_p, den_c, c * blkc,
                                                    axis=2)
        return tv_buf, uni_buf, dmask_buf, num_p, den_p

    tv_buf, uni_buf, dmask_buf, num_p, den_p = jax.lax.fori_loop(
        0, dp // chunk, pass2,
        (jnp.zeros((n_tasks, dp), jnp.float32),
         jnp.zeros((n, dp), jnp.float32),
         jnp.zeros((n, k, dwp), jnp.uint32),
         jnp.zeros((n_tasks, n_blk), jnp.float32),
         jnp.zeros((n, k, n_blk), jnp.float32)))
    # λ totals on the shard-invariant block grid (one psum when sharded)
    num_t, den = _lam_totals((num_p, den_p), axis_name, axis_sizes)
    num = jnp.concatenate([num_t, jnp.zeros((1,),
                                            jnp.float32)])[ids].reshape(n, k)

    dw = bitpack.packed_width(d)
    return (tv_buf[:, :d], tau_hats[:, :d], anum_buf[:, :d], n_t, sim,
            uni_buf[:, :d].astype(jnp.bfloat16), dmask_buf[:, :, :dw],
            num, den)


def cross_weights_ref(sim: jax.Array, held: jax.Array, *, eps: float,
                      kappa: int, cross_task: bool,
                      uniform_cross: bool) -> jax.Array:
    """Eq. 6 neighbourhood weights from the held-masked similarity —
    the shared (T, T)-sized logic of every round path (server, dense
    reference, chunked slot round)."""
    heldf = held.astype(sim.dtype)
    if not cross_task:
        return jnp.zeros_like(sim)
    if uniform_cross:
        t = sim.shape[0]
        w = (1.0 - jnp.eye(t, dtype=sim.dtype)) * heldf[None, :] * heldf[:, None]
        return w / jnp.maximum(jnp.sum(w, 1, keepdims=True), 1.0)
    return topk_weights_ref(sim, eps, kappa)


def topk_weights_ref(sim: jax.Array, eps: float, kappa: int) -> jax.Array:
    """Eq. 6 neighbourhood Z^t as a (T, T) weight matrix (mirror of
    ``repro.core.aggregation.topk_similar``)."""
    t = sim.shape[0]
    offdiag = sim * (1.0 - jnp.eye(t, dtype=sim.dtype))
    eligible = jnp.where(offdiag > eps, offdiag, 0.0)
    k = min(kappa, t - 1) if t > 1 else 0
    if k == 0:
        return jnp.zeros_like(sim)
    vals, _ = jax.lax.top_k(eligible, k)
    thresh = vals[:, -1:]
    keep = (eligible >= thresh) & (eligible > 0)
    return jnp.where(keep, eligible, 0.0)


def cross_task_combine_ref(tau_hats: jax.Array, m_hats: jax.Array,
                           sim_weights: jax.Array):
    """Eq. 6 + Eq. 7 (mirror of ``cross_task_aggregate`` +
    ``combine_round``): normalised cross-task mix, then the overview's
    averaging.  Returns (task_vectors (T, d), tau_tildes (T, d))."""
    total = jnp.sum(sim_weights, axis=1, keepdims=True)
    norm_w = sim_weights / jnp.maximum(total, 1e-12)
    tau_tildes = m_hats * jnp.einsum("ts,sd->td", norm_w, tau_hats)
    has = (total > 0).astype(tau_hats.dtype)
    task_vectors = (tau_hats + tau_tildes * has) / (1.0 + has)
    return task_vectors, tau_tildes


# ---------------------------------------------------------------------------
# Chunked-slot hierarchical aggregation: the client-axis streaming round.
#
# The monolithic round above materialises every slot tensor for the
# whole round — O(N·K·d) — which caps the client axis.  The four
# functions below split the identical math into per-chunk folds over
# carried accumulators so a round's memory is O(chunk + T·d)
# regardless of N:
#
#   phase A  ``matu_chunk_scalars_ref``    per chunk: fold sizes / valid
#            counts into (T+1,) totals (the Eq. 4 γ normaliser needs
#            global per-task size totals before any merge work).
#   phase B  ``matu_merge_chunk_packed_ref``  per chunk: fold the
#            Eq. 3 sign votes and Eq. 4 merge partials into carried
#            (T+1, dp) accumulators.
#   finish   ``matu_finish_packed_ref``  once: Eq. 3 α/m̂, Eq. 5 sign
#            dots, Eq. 6 weights, Eq. 7 combine and the λ numerator
#            from the accumulators — no slot tensors involved.
#   phase C  ``matu_downlink_chunk_packed_ref``  per chunk: downlink
#            re-unification of one client chunk from the finished task
#            vectors (each slot row lives in exactly one chunk, so this
#            phase is embarrassingly parallel over rows).
#
# Chunk-count invariance (the bit-identity contract): every fp32
# client-axis reduction is ONE global sequential scatter fold —
# ``acc.at[ids].add(x_chunk)`` carried across chunks applies the same
# adds in the same global row order as the monolithic round's
# whole-round ``segment_sum`` (XLA applies scatter updates in row
# order on CPU), so the accumulated totals are bitwise equal for ANY
# contiguous chunking, including a jitted fixed-shape chunk step with
# sentinel-padded tail rows (padding rows carry the sentinel task id,
# so their zeros land in the swallowed (T+1)-th bucket, never in a task
# row).  The Eq. 3 votes and Eq. 5 dots are exact integers (order
# free), and every d-axis reduction (λ block partials, CHUNK_D
# streaming grid) keeps the monolithic grid — identical op shapes,
# identical lowering, bitwise-identical results.  Under ``shard_map``
# the merge fold never splits the client axis across devices (each
# shard folds every row of its d-slice locally — no collectives);
# the finish crosses shards exactly like the monolithic round (integer
# dots psum + the shard-invariant ``_lam_totals`` tree) and phase C
# adds one λ-denominator psum per chunk.
# ---------------------------------------------------------------------------


def matu_chunk_scalars_ref(slot_sizes: jax.Array, slot_valid: jax.Array,
                           slot_tasks: jax.Array, totals_acc: jax.Array,
                           nt_acc: jax.Array):
    """Phase-A chunk step: fold one chunk's data sizes and validity
    counts into the carried (T+1,) fp32 accumulators.

    slot_sizes/slot_valid/slot_tasks (C, K); ``totals_acc`` accumulates
    Σ size·valid per task (the γ normaliser), ``nt_acc`` the Eq. 3
    membership count N_t.  Returns the updated (totals_acc, nt_acc).
    """
    m_rows = slot_sizes.shape[0] * slot_sizes.shape[1]
    ids = slot_tasks.reshape(m_rows)
    vf = slot_valid.reshape(m_rows).astype(jnp.float32)
    sizes = slot_sizes.reshape(m_rows).astype(jnp.float32) * vf
    return totals_acc.at[ids].add(sizes), nt_acc.at[ids].add(vf)


def matu_merge_chunk_packed_ref(unified: jax.Array, slot_mask_words: jax.Array,
                                slot_lams: jax.Array, slot_sizes: jax.Array,
                                slot_valid: jax.Array, slot_tasks: jax.Array,
                                totals: jax.Array, a_acc: jax.Array,
                                tau_acc: jax.Array, *, d: int,
                                chunk: int = CHUNK_D):
    """Phase-B chunk step: fold one client chunk's Eq. 3 sign votes
    (int32, exact) and Eq. 4 merge partials (fp32, global row order)
    into the carried (T+1, dp) accumulators.

    ``totals`` is the phase-A global size total (T+1,) — the γ weights
    need it before any merge work, which is why the chunked round makes
    two passes over the upload stream.  ``a_acc`` (T+1, dp) int32 and
    ``tau_acc`` (T+1, dp) fp32 are carried across chunks; the d-axis
    streaming grid is the monolithic round's (``_chunked``).  Under
    ``shard_map`` every d-axis tensor is the local slice and ``d`` the
    local count — the fold has no collectives.
    """
    n, k, dw_in = slot_mask_words.shape
    m_rows = n * k
    chunk, dp = _chunked(d, chunk)
    dwc, dwp = chunk // 32, dp // 32

    ids = slot_tasks.reshape(m_rows)
    vf = slot_valid.reshape(m_rows).astype(jnp.float32)
    sizes = slot_sizes.reshape(m_rows).astype(jnp.float32) * vf
    gam = sizes / jnp.maximum(totals[ids], 1e-12)
    glv = gam * slot_lams.reshape(m_rows).astype(jnp.float32) * vf
    glv_nk = glv.reshape(n, k)

    u_p = unified                       # stays bf16; upcast per chunk
    m_w = slot_mask_words
    if dp != d:
        u_p = jnp.pad(u_p, ((0, 0), (0, dp - d)))
    if dwp != dw_in:
        m_w = jnp.pad(m_w, ((0, 0), (0, 0), (0, dwp - dw_in)))

    def fold(c, carry):
        a_acc, tau_acc = carry
        off = c * chunk
        uc = lax.dynamic_slice_in_dim(u_p, off, chunk,
                                      axis=1).astype(jnp.float32)
        mw = lax.dynamic_slice_in_dim(m_w, c * dwc, dwc, axis=2)
        mi8 = bitpack.unpack_bits(mw, chunk, jnp.int8)         # (C, K, dc)
        signs = (mi8 * jnp.sign(uc).astype(jnp.int8)[:, None, :])
        a_blk = lax.dynamic_slice_in_dim(a_acc, off, chunk, axis=1)
        a_blk = a_blk.at[ids].add(
            signs.reshape(m_rows, chunk).astype(jnp.int32))
        a_acc = lax.dynamic_update_slice_in_dim(a_acc, a_blk, off, axis=1)
        recon = mi8.astype(jnp.float32) * (glv_nk[:, :, None]
                                           * uc[:, None, :])
        t_blk = lax.dynamic_slice_in_dim(tau_acc, off, chunk, axis=1)
        t_blk = t_blk.at[ids].add(recon.reshape(m_rows, chunk))
        tau_acc = lax.dynamic_update_slice_in_dim(tau_acc, t_blk, off, axis=1)
        return a_acc, tau_acc

    return lax.fori_loop(0, dp // chunk, fold, (a_acc, tau_acc))


def matu_finish_packed_ref(a_acc: jax.Array, tau_acc: jax.Array,
                           nt_acc: jax.Array, n_clients: int, *, n_tasks: int,
                           d: int, rho: float, eps: float, kappa: int,
                           cross_task: bool = True,
                           uniform_cross: bool = False,
                           chunk: int = CHUNK_D,
                           axis_name=None, axis_sizes=(), d_norm: int = 0):
    """Finish the chunked round from the accumulated partials:
    Eq. 3 α/m̂ from the integer vote accumulator (same fp32 division as
    the monolithic round), Eq. 5 popcount dots, Eq. 6 weights, Eq. 7
    combine, and the λ numerator totals on the shard-invariant block
    grid.  ``n_clients`` is the whole round's client count — it picks
    the same ``alpha_dtype`` the monolithic round would.

    Returns (task_vectors (T, d), tau_hats (T, d), alpha_num (T, d),
    n_t (T,), similarity (T, T), num_t (T,) λ numerator totals).
    """
    chunk, dp = _chunked(d, chunk)
    n_blk, blkc = dp // LAMBDA_BLOCK, chunk // LAMBDA_BLOCK
    a_dt = alpha_dtype(n_clients)
    d_norm = d_norm or d
    n_t = nt_acc[:n_tasks]
    held = n_t > 0
    n_t_max = jnp.maximum(n_t, 1.0)

    def pass1(c, carry):
        tau_buf, anum_buf, dots = carry
        off = c * chunk
        a_num = lax.dynamic_slice_in_dim(
            a_acc, off, chunk, axis=1)[:n_tasks].astype(jnp.float32)
        tau_pre = lax.dynamic_slice_in_dim(tau_acc, off, chunk,
                                           axis=1)[:n_tasks]
        a_abs = jnp.abs(a_num)
        alpha = a_abs / n_t_max[:, None]
        m_hat = jnp.where(alpha >= rho, 1.0, alpha)
        tau = tau_pre * m_hat
        pos_t, nz_t = bitpack.sign_planes(tau)
        dots = dots + bitpack.packed_sign_dots(pos_t, nz_t)
        tau_buf = jax.lax.dynamic_update_slice_in_dim(tau_buf, tau, off,
                                                      axis=1)
        anum_buf = jax.lax.dynamic_update_slice_in_dim(
            anum_buf, a_abs.astype(a_dt), off, axis=1)
        return tau_buf, anum_buf, dots

    tau_hats, anum_buf, dots = jax.lax.fori_loop(
        0, dp // chunk, pass1,
        (jnp.zeros((n_tasks, dp), jnp.float32),
         jnp.zeros((n_tasks, dp), a_dt),
         jnp.zeros((n_tasks, n_tasks), jnp.int32)))

    if axis_name is not None:
        dots = lax.psum(dots, axis_name)

    heldf = held.astype(jnp.float32)
    sim = 0.5 * (dots.astype(jnp.float32) / d_norm + 1.0) \
        * heldf[None, :] * heldf[:, None]
    weights = cross_weights_ref(sim, held, eps=eps, kappa=kappa,
                                cross_task=cross_task,
                                uniform_cross=uniform_cross)
    total_w = jnp.sum(weights, axis=1, keepdims=True)
    norm_w = weights / jnp.maximum(total_w, 1e-12)
    has = (total_w > 0).astype(jnp.float32)
    c1 = (1.0 / (1.0 + has))
    c2 = (has / (1.0 + has))

    def pass2(c, carry):
        tv_buf, num_p = carry
        off = c * chunk
        tau = jax.lax.dynamic_slice_in_dim(tau_hats, off, chunk, axis=1)
        anum = jax.lax.dynamic_slice_in_dim(anum_buf, off, chunk, axis=1)
        alpha = anum.astype(jnp.float32) / n_t_max[:, None]
        m_hat = jnp.where(alpha >= rho, 1.0, alpha)
        tv = c1 * tau + c2 * (m_hat * (norm_w @ tau))
        num_p = jax.lax.dynamic_update_slice_in_dim(
            num_p, _block_partials(jnp.abs(tv)), c * blkc, axis=1)
        tv_buf = jax.lax.dynamic_update_slice_in_dim(tv_buf, tv, off, axis=1)
        return tv_buf, num_p

    tv_buf, num_p = jax.lax.fori_loop(
        0, dp // chunk, pass2,
        (jnp.zeros((n_tasks, dp), jnp.float32),
         jnp.zeros((n_tasks, n_blk), jnp.float32)))
    num_t, = _lam_totals((num_p,), axis_name, axis_sizes)
    return (tv_buf[:, :d], tau_hats[:, :d], anum_buf[:, :d], n_t, sim, num_t)


def matu_downlink_chunk_packed_ref(task_vectors: jax.Array,
                                   slot_tasks: jax.Array, num_t: jax.Array,
                                   *, d: int, chunk: int = CHUNK_D,
                                   axis_name=None, axis_sizes=()):
    """Phase-C chunk step: downlink re-unification of one client chunk
    from the finished task vectors — the monolithic round's pass 2
    per-slot sweep, restricted to this chunk's rows (each slot
    row lives in exactly one chunk, so per-row results are trivially
    chunk-invariant; the λ denominator rides the same shard-invariant
    block tree, one psum per chunk when sharded).  Invalid slots gather
    the appended all-zero sentinel row exactly as the monolithic round
    does.  Returns (down_unified (C, d) bf16, down_mask_words
    (C, K, ceil(d/32)), down_num (C, K), down_den (C, K))."""
    n, k = slot_tasks.shape
    m_rows = n * k
    chunk, dp = _chunked(d, chunk)
    dwc, dwp = chunk // 32, dp // 32
    n_blk, blkc = dp // LAMBDA_BLOCK, chunk // LAMBDA_BLOCK
    ids = slot_tasks.reshape(m_rows)
    tv_p = task_vectors
    if dp != d:
        tv_p = jnp.pad(tv_p, ((0, 0), (0, dp - d)))

    def step(c, carry):
        uni_buf, dmask_buf, den_p = carry
        off = c * chunk
        tv = lax.dynamic_slice_in_dim(tv_p, off, chunk, axis=1)
        tv_ext = jnp.concatenate([tv, jnp.zeros((1, chunk), jnp.float32)], 0)
        x = jnp.take(tv_ext, ids, axis=0).reshape(n, k, chunk)
        sigma = jnp.sign(jnp.sum(x, axis=1))                   # (C, dc)
        posm = sigma > 0
        negm = sigma < 0
        als = []
        mu = jnp.zeros((n, chunk), jnp.float32)
        for kk in range(k):
            x_k = x[:, kk, :]                                  # (C, dc)
            al_k = ((x_k > 0) & posm) | ((x_k < 0) & negm)
            mu = jnp.maximum(mu, jnp.where(al_k, jnp.abs(x_k), 0.0))
            als.append(al_k)
        tau_n = sigma * mu
        mupos = mu[:, None, :] > 0
        dmask = jnp.stack(als, axis=1) & mupos     # zero slots: never set
        den_c = _block_partials(jnp.where(dmask, mu[:, None, :], 0.0))
        uni_buf = jax.lax.dynamic_update_slice_in_dim(uni_buf, tau_n, off,
                                                      axis=1)
        dmask_buf = jax.lax.dynamic_update_slice_in_dim(
            dmask_buf, bitpack.pack_bits(dmask), c * dwc, axis=2)
        den_p = jax.lax.dynamic_update_slice_in_dim(den_p, den_c, c * blkc,
                                                    axis=2)
        return uni_buf, dmask_buf, den_p

    uni_buf, dmask_buf, den_p = jax.lax.fori_loop(
        0, dp // chunk, step,
        (jnp.zeros((n, dp), jnp.float32),
         jnp.zeros((n, k, dwp), jnp.uint32),
         jnp.zeros((n, k, n_blk), jnp.float32)))
    den, = _lam_totals((den_p,), axis_name, axis_sizes)
    num = jnp.concatenate([num_t, jnp.zeros((1,),
                                            jnp.float32)])[ids].reshape(n, k)
    dw = bitpack.packed_width(d)
    return (uni_buf[:, :d].astype(jnp.bfloat16), dmask_buf[:, :, :dw],
            num, den)


# ---------------------------------------------------------------------------
# Serving: modulated LoRA matmul (reference semantics of the fused
# repro.kernels.modulated_matmul Pallas kernel).
# ---------------------------------------------------------------------------


def modulated_weight(base: jax.Array, tau: jax.Array, bits: jax.Array,
                     lam: jax.Array) -> jax.Array:
    """Effective adapter leaf ``base + lam * m * tau``, rounded the way
    the materialised adapter ``tree_add(lora0, unflatten(modulate(...)))``
    rounds it: the delta is formed in fp32 and cast to the leaf dtype
    (``unflatten``), then added in fp32 and cast back (``tree_add`` —
    XLA adds bf16 through fp32).  ``(lam * bits) * tau`` is bitwise
    ``lam * where(m, tau, 0)`` for bits in {0, 1}.  The single
    definition shared by the fused kernel and this module's oracle."""
    delta = (lam * bits * tau.astype(jnp.float32)).astype(base.dtype)
    return (base.astype(jnp.float32)
            + delta.astype(jnp.float32)).astype(base.dtype)


def modulated_matmul_ref(x: jax.Array, base: jax.Array, tau: jax.Array,
                         words: jax.Array, lam: jax.Array) -> jax.Array:
    """Per-request modulated LoRA matmul, the unpack-then-matmul oracle.

    x (B, ..., K); base/tau (K, N) in the adapter leaf dtype (the base
    adapter leaf and the unified-vector slice reshaped to the leaf);
    words (B, W) uint32 bit-packed modulator bits of the leaf, row-major
    over (K, N); lam (B,) fp32 per-request scalers.  Returns
    (B, ..., N) in ``result_type(x, base)``:

        y_b = x_b @ (base + lam_b * m_b * tau)

    The effective weight is materialised per request here (the extra
    HBM pass the fused kernel removes), rounded as
    :func:`modulated_weight` says — so serving paths built from either
    form compute the same adapter and the same contraction.
    """
    b = x.shape[0]
    k, n = base.shape
    bits = bitpack.unpack_bits(words, k * n, jnp.float32).reshape(b, k, n)
    w_eff = modulated_weight(base[None], tau[None], bits,
                             lam[:, None, None])
    return jnp.einsum("b...k,bkn->b...n", x, w_eff)
