"""Pallas TPU kernel: fused unify + task-mask + λ-scaler (Eq. 2 + §3.2
modulators), batched over clients.

Downlink construction re-unifies every client's task vectors each
round.  Composed from the three reference ops this reads the (K, d)
stack three times (unify, mask, scaler) and materialises the unified
vector plus the mask stack in HBM between passes; per round that is
O(N·K·d) extra traffic on the server's hottest loop.  This kernel
streams each client's (K, BD) tile through VMEM once and emits the
unified block, the mask block, and the partial λ numerator/denominator
sums in a single pass.

Layout: grid (B, d/BD), d innermost so the per-(client, slot) scalar
accumulators (num, den) are revisited across the d sweep (zeroed on the
first step, accumulated after — same pattern as the sign_sim kernel).
Slot validity handles ragged k_n: invalid slots are zeroed before the
sign election and excluded from masks, so outputs match per-client
``unify_with_modulators`` on the valid rows exactly.

Masks are emitted as fp32 {0, 1} (bool outputs hit int8 tiling
constraints for small K); the dispatch layer casts back to bool.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import bitpack

BLOCK_D = 2048
BLOCK_D_PACKED = 4096       # 128 uint32 words per tile (one lane tile)


def _unify_tile(tv_ref, valid_ref, num_ref, den_ref):
    """Shared tile body: returns (τ (1, BD), mask (K, BD) fp32 {0,1})
    and folds this tile's λ partial sums into the (K, 1) num/den
    blocks.  Every operand is 2-D (K, ·) or (·, 1), the layouts Mosaic
    tiles without moving data between lanes and sublanes."""
    x = tv_ref[0].astype(jnp.float32)               # (K, BD)
    v = valid_ref[0]                                # (K, 1) fp32 {0,1}
    xm = x * v
    sigma = jnp.sign(jnp.sum(xm, axis=0, keepdims=True))
    aligned = (xm * sigma) > 0.0
    mu = jnp.max(jnp.where(aligned, jnp.abs(xm), 0.0), axis=0, keepdims=True)
    tau = sigma * mu
    mask = ((x * tau) > 0.0).astype(jnp.float32) * v

    @pl.when(pl.program_id(1) == 0)
    def _init():
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    num_ref[0] += jnp.sum(jnp.abs(xm), axis=1, keepdims=True)
    den_ref[0] += jnp.sum(mask * jnp.abs(tau), axis=1, keepdims=True)
    return tau, mask


def _fused_unify_kernel(tv_ref, valid_ref, uni_ref, mask_ref, num_ref, den_ref):
    tau, mask = _unify_tile(tv_ref, valid_ref, num_ref, den_ref)
    uni_ref[0] = tau.astype(uni_ref.dtype)
    mask_ref[0] = mask.astype(mask_ref.dtype)


def _fused_unify_packed_kernel(tv_ref, valid_ref, uni_ref, mask_ref,
                               num_ref, den_ref):
    # mask bits decided on the fp32 tau BEFORE the bf16 rounding of the
    # emitted unified vector — bit-identical to the bool/fp32 kernel
    tau, mask = _unify_tile(tv_ref, valid_ref, num_ref, den_ref)
    uni_ref[0] = tau.astype(uni_ref.dtype)
    mask_ref[0] = bitpack.pack_tile(mask)


def _fused_unify_call(task_vectors, valid, *, block_d, interpret, packed):
    """Grid (B, d/BD) launch shared by both variants.  Per-client rows
    carry a unit axis — unified (B, 1, dp), valid/num/den (B, K, 1) — and
    packed words are emitted as (B, K, dp/128, 4) (see
    ``bitpack.pack_tile``), so the last two block dims always equal the
    array's or tile (8, 128).  Returns the padded-d outputs."""
    b, k, d = task_vectors.shape
    pad = (-d) % block_d
    if pad:
        task_vectors = jnp.pad(task_vectors, ((0, 0), (0, 0), (0, pad)))
    dp = d + pad
    if packed:
        kernel, uni_dtype = _fused_unify_packed_kernel, jnp.bfloat16
        name = "fused_unify_packed_pallas"
        mask_spec = pl.BlockSpec((1, k, block_d // 128, 4),
                                 lambda i, j: (i, 0, j, 0))
        mask_out = jax.ShapeDtypeStruct((b, k, dp // 128, 4), jnp.uint32)
    else:
        kernel, uni_dtype = _fused_unify_kernel, jnp.float32
        name = "fused_unify_pallas"
        mask_spec = pl.BlockSpec((1, k, block_d), lambda i, j: (i, 0, j))
        mask_out = jax.ShapeDtypeStruct((b, k, dp), jnp.float32)
    scalar_spec = pl.BlockSpec((1, k, 1), lambda i, j: (i, 0, 0))
    unified, masks, num, den = pl.pallas_call(
        kernel,
        grid=(b, dp // block_d),
        in_specs=[
            pl.BlockSpec((1, k, block_d), lambda i, j: (i, 0, j)),
            scalar_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_d), lambda i, j: (i, 0, j)),
            mask_spec,
            scalar_spec,
            scalar_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, dp), uni_dtype),
            mask_out,
            jax.ShapeDtypeStruct((b, k, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, k, 1), jnp.float32),
        ],
        interpret=interpret,
        name=name,
    )(task_vectors, valid.astype(jnp.float32).reshape(b, k, 1))
    return unified[:, 0], masks, num[:, :, 0], den[:, :, 0]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_unify_pallas(task_vectors: jax.Array, valid: jax.Array, *,
                       block_d: int = BLOCK_D, interpret: bool):
    """task_vectors (B, K, d); valid (B, K) bool/{0,1}.

    Returns (unified (B, d), masks (B, K, d) fp32 {0,1}, num (B, K),
    den (B, K)); λ = num / max(den, eps) is computed by the caller so
    eps policy stays in one place (invalid slots: num = den = 0).
    Zero-padding d is safe: padded lanes contribute nothing to num/den
    and are sliced off the streamed outputs.
    """
    d = task_vectors.shape[-1]
    unified, masks, num, den = _fused_unify_call(
        task_vectors, valid, block_d=block_d, interpret=interpret,
        packed=False)
    return unified[:, :d], masks[:, :, :d], num, den


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_unify_packed_pallas(task_vectors: jax.Array, valid: jax.Array, *,
                              block_d: int = BLOCK_D_PACKED,
                              interpret: bool):
    """Wire-format variant of :func:`fused_unify_pallas`: consumes bf16
    (or fp32) slot stacks and emits the wire tensors directly — bf16
    unified vectors and bit-packed uint32 mask words, packed 32 lanes
    per word inside the kernel so the (B, K, d) mask never exists in
    HBM at more than 1 bit per element.

    Returns (unified (B, d) bf16, mask_words (B, K, ceil(d/32)) uint32,
    num (B, K), den (B, K)); λ = num / max(den, eps) is left to the
    caller.  Compute is fp32 per tile; mask bits and num/den are derived
    from the fp32 values before the bf16 rounding — masks are
    bit-identical to the bool kernel's, while num/den accumulate over
    4096-wide tiles (vs the bool kernel's 2048) so they match to fp32
    accumulation tolerance, not bitwise, for d > 2048.
    """
    b, k, d = task_vectors.shape
    unified, words, num, den = _fused_unify_call(
        task_vectors, valid, block_d=block_d, interpret=interpret,
        packed=True)
    words = words.reshape(b, k, -1)[:, :, :bitpack.packed_width(d)]
    return unified[:, :d], words, num, den
