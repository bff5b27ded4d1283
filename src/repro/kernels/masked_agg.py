"""Pallas TPU kernel: fused Eq. 3 + Eq. 4 (agreement mask + task merge).

Per task t the server computes, over the N_t member clients,
  α_j  = |Σ_n sgn(m_n ⊙ τ_n)_j| / N_t
  m̂_j  = 1 if α_j ≥ ρ else α_j
  τ̂_j  = m̂_j · Σ_n γ_n λ_n (m_n ⊙ τ_n)_j

A naive composition reads the (N, d) stack three times (sign-sum,
agreement compare, weighted sum) and materialises two (N, d)
intermediates in HBM.  The kernel streams each (N, BD) block through
VMEM once, producing both outputs — HBM traffic drops from ~5·N·d to
(N+2)·d words.

The per-client scalars (λ, γ) are small (N ≤ 64) and ride fully
resident; ρ is compile-time static.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import bitpack

BLOCK_D = 2048
# packed kernels tile 32 mask bits per word: 4096 elements = 128 words,
# exactly one uint32 lane tile
BLOCK_D_PACKED = 4096


def _masked_agg_kernel(u_ref, m_ref, lam_ref, gam_ref, tau_ref, mhat_ref, *, rho):
    u = u_ref[...].astype(jnp.float32)            # (N, BD)
    m = m_ref[...].astype(jnp.float32)            # (N, BD)
    lam = lam_ref[...].astype(jnp.float32)        # (N,)
    gam = gam_ref[...].astype(jnp.float32)        # (N,)
    member = (gam > 0).astype(jnp.float32)
    n_t = jnp.maximum(jnp.sum(member), 1.0)
    masked = u * m
    signs = jnp.sign(masked)
    alpha = jnp.abs(jnp.sum(member[:, None] * signs, axis=0)) / n_t
    m_hat = jnp.where(alpha >= rho, 1.0, alpha)
    weighted = jnp.sum((gam * lam)[:, None] * masked, axis=0)
    tau_ref[...] = (weighted * m_hat).astype(tau_ref.dtype)
    mhat_ref[...] = m_hat.astype(mhat_ref.dtype)


def _masked_agg_batched_kernel(u_ref, m_ref, lam_ref, gam_ref, mem_ref,
                               tau_ref, mhat_ref, *, rho):
    u = u_ref[...].astype(jnp.float32)            # (N, BD)
    m = m_ref[...].astype(jnp.float32)            # (N, BD)
    lam = lam_ref[0]                              # (N, 1)
    gam = gam_ref[0]
    mem = mem_ref[0]
    n_t = jnp.maximum(jnp.sum(mem), 1.0)
    masked = u * m
    alpha = jnp.abs(jnp.sum(mem * jnp.sign(masked), axis=0,
                            keepdims=True)) / n_t
    m_hat = jnp.where(alpha >= rho, 1.0, alpha)
    weighted = jnp.sum((gam * lam) * masked, axis=0, keepdims=True)
    tau_ref[0] = (weighted * m_hat).astype(tau_ref.dtype)
    mhat_ref[0] = m_hat.astype(mhat_ref.dtype)


def _task_major_scalars(*scalars):
    """(N, T) per-(client, task) scalars -> (T, N, 1) fp32 columns, so
    one task's block (1, N, 1) spans the whole trailing two dims and
    broadcasts against an (N, BD) tile without a relayout."""
    return [jnp.transpose(x.astype(jnp.float32))[:, :, None] for x in scalars]


def _batched_specs(n, block_d, nblk, row_block):
    """Block specs shared by both batched kernels, grid (T, dp/BD).  The
    (N, T, ·) mask tensor is viewed as (N, T·w) — a free row-major
    reshape — so task i's block j is column block ``i·nblk + j`` and
    the last two block dims are (N, lane tile), never (1, ·)."""
    scalar = pl.BlockSpec((1, n, 1), lambda i, j: (i, 0, 0))
    in_specs = [
        pl.BlockSpec((n, block_d), lambda i, j: (0, j)),
        pl.BlockSpec((n, row_block), lambda i, j: (0, i * nblk + j)),
        scalar, scalar, scalar,
    ]
    out = pl.BlockSpec((1, 1, block_d), lambda i, j: (i, 0, j))
    return in_specs, [out, out]


@functools.partial(jax.jit, static_argnames=("rho", "block_d", "interpret"))
def masked_agg_batched_pallas(unified: jax.Array, masks: jax.Array,
                              lams: jax.Array, gammas: jax.Array,
                              members: jax.Array, *, rho: float = 0.4,
                              block_d: int = BLOCK_D, interpret: bool):
    """Whole-round Eq. 3 + Eq. 4: every task in one launch.

    unified (N, d); masks (N, T, d) {0,1} (zero rows off-membership);
    lams/gammas/members (N, T).  ``members`` is the explicit A(n, t)
    allocation (the agreement denominator N_t counts members even when
    their data weight is zero, matching ``matu_round``).

    Grid is (T, d/BD): each program streams one (N, BD) lane block of
    one task through VMEM, so the (N, T, d) mask tensor is read exactly
    once and no (T, d) intermediate ever round-trips to HBM.
    Returns (tau_hats (T, d), m_hats (T, d)) in fp32.
    """
    n, d = unified.shape
    t = masks.shape[1]
    pad = (-d) % block_d
    if pad:
        unified = jnp.pad(unified, ((0, 0), (0, pad)))
        masks = jnp.pad(masks, ((0, 0), (0, 0), (0, pad)))
    dp = d + pad
    nblk = dp // block_d
    kernel = functools.partial(_masked_agg_batched_kernel, rho=rho)
    in_specs, out_specs = _batched_specs(n, block_d, nblk, block_d)
    tau, m_hat = pl.pallas_call(
        kernel,
        grid=(t, nblk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((t, 1, dp), jnp.float32)] * 2,
        interpret=interpret,
        name="masked_agg_batched_pallas",
    )(unified, masks.astype(unified.dtype).reshape(n, t * dp),
      *_task_major_scalars(lams, gammas, members))
    return tau[:, 0, :d], m_hat[:, 0, :d]


def _masked_agg_batched_packed_kernel(u_ref, mw_ref, pos_ref, neg_ref,
                                      lam_ref, gam_ref, mem_ref,
                                      tau_ref, anum_ref, *, rho):
    u = u_ref[...].astype(jnp.float32)              # (N, BD)
    w = mw_ref[...]                                 # (N, BW) uint32
    lam = lam_ref[0]                                # (N, 1)
    gam = gam_ref[0]
    mem = mem_ref[0]
    n_t = jnp.maximum(jnp.sum(mem), 1.0)
    # sgn(m ⊙ τ_n) via word-wide ANDs against τ_n's sign bit-planes
    # (packed ONCE per d-block outside the kernel — every task row of
    # the grid reuses them): bit(m & pos) − bit(m & neg); the merge
    # reuses the same planes — m ⊙ τ = τ·(bit(m&pos) + bit(m&neg))
    # exactly (τ = 0 contributes 0)
    sp = bitpack.unpack_tile(w & pos_ref[...])      # (N, BD) f32 {0,1}
    sn = bitpack.unpack_tile(w & neg_ref[...])
    a_num = jnp.abs(jnp.sum(mem * (sp - sn), axis=0, keepdims=True))
    m_hat = jnp.where(a_num / n_t >= rho, 1.0, a_num / n_t)
    weighted = jnp.sum((gam * lam) * (u * (sp + sn)), axis=0, keepdims=True)
    tau_ref[0] = (weighted * m_hat).astype(tau_ref.dtype)
    anum_ref[0] = a_num.astype(anum_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rho", "block_d", "interpret"))
def masked_agg_batched_packed_pallas(unified: jax.Array, mask_words: jax.Array,
                                     lams: jax.Array, gammas: jax.Array,
                                     members: jax.Array, *, rho: float = 0.4,
                                     block_d: int = BLOCK_D_PACKED,
                                     interpret: bool):
    """Wire-format twin of :func:`masked_agg_batched_pallas`: the
    (N, T, d) mask tensor arrives as bit-packed uint32 words
    (N, T, ceil(d/32)) and is expanded 32-bits-per-word inside VMEM —
    HBM mask traffic drops 8x vs the bool layout and 32x vs fp32.
    ``unified`` may be bf16 (the uplink wire dtype); each tile is upcast
    to fp32 in VMEM.

    Instead of m̂ this kernel emits the Eq. 3 agreement *numerator*
    |Σ_n sgn(m_n ⊙ τ_n)| — an exact small integer (≤ N) from which the
    caller re-derives m̂ = 1[α ≥ ρ] ∨ α with the identical fp32 division
    (and can store it at one byte per coordinate).
    Returns (tau_hats (T, d) fp32, alpha_num (T, d) fp32).
    """
    n, d = unified.shape
    t = mask_words.shape[1]
    pad = (-d) % block_d
    dp = d + pad
    dwp = dp // 32
    if pad:
        unified = jnp.pad(unified, ((0, 0), (0, pad)))
    if mask_words.shape[2] != dwp:
        mask_words = jnp.pad(
            mask_words, ((0, 0), (0, 0), (0, dwp - mask_words.shape[2])))
    bw = block_d // 32
    nblk = dp // block_d
    # τ_n's sign bit-planes are task-independent: pack them once here
    # (tiny (N, dwp) words) instead of once per task row in-kernel.
    # The comparisons run on the wire dtype directly — bf16 > 0 decides
    # exactly like its fp32 upcast, so no dense fp32 copy is made.
    pos_w, neg_w = bitpack.sign_bits(unified)
    kernel = functools.partial(_masked_agg_batched_packed_kernel, rho=rho)
    in_specs, out_specs = _batched_specs(n, block_d, nblk, bw)
    plane = pl.BlockSpec((n, bw), lambda i, j: (0, j))
    in_specs[2:2] = [plane, plane]
    tau, anum = pl.pallas_call(
        kernel,
        grid=(t, nblk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((t, 1, dp), jnp.float32)] * 2,
        interpret=interpret,
        name="masked_agg_batched_packed_pallas",
    )(unified, mask_words.reshape(n, t * dwp), pos_w, neg_w,
      *_task_major_scalars(lams, gammas, members))
    return tau[:, 0, :d], anum[:, 0, :d]


@functools.partial(jax.jit, static_argnames=("rho", "block_d", "interpret"))
def masked_agg_pallas(unified: jax.Array, masks: jax.Array, lams: jax.Array,
                      gammas: jax.Array, *, rho: float = 0.4,
                      block_d: int = BLOCK_D, interpret: bool):
    """unified (N,d); masks (N,d) {0,1}; lams/gammas (N,).

    gammas must be the normalised membership weights (0 for
    non-members); N_t is inferred as the count of positive gammas.
    Returns (tau_hat (d,), m_hat (d,)) in fp32.
    """
    n, d = unified.shape
    pad = (-d) % block_d
    if pad:
        unified = jnp.pad(unified, ((0, 0), (0, pad)))
        masks = jnp.pad(masks, ((0, 0), (0, pad)))
    dp = d + pad
    kernel = functools.partial(_masked_agg_kernel, rho=rho)
    tau, m_hat = pl.pallas_call(
        kernel,
        grid=(dp // block_d,),
        in_specs=[
            pl.BlockSpec((n, block_d), lambda i: (0, i)),
            pl.BlockSpec((n, block_d), lambda i: (0, i)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block_d,), lambda i: (i,)),
            pl.BlockSpec((block_d,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((dp,), jnp.float32),
            jax.ShapeDtypeStruct((dp,), jnp.float32),
        ],
        interpret=interpret,
        name="masked_agg_pallas",
    )(unified, masks.astype(unified.dtype), lams, gammas)
    return tau[:d], m_hat[:d]
