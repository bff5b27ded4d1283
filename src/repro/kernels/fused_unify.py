"""Pallas TPU kernels: fused unify + task-mask + λ-scaler (Eq. 2 + §3.2
modulators), batched over clients, emitting the wire layout.

Downlink construction re-unifies every client's task vectors each
round.  Composed from the three reference ops this reads the (K, d)
stack three times (unify, mask, scaler) and materialises the unified
vector plus the mask stack in HBM between passes; per round that is
O(N·K·d) extra traffic on the server's hottest loop.  These kernels
stream each client's (K, BD) tile through VMEM once and emit the bf16
unified block, the mask block packed 32 bits a uint32 word, and the
partial λ numerator/denominator sums in a single pass.

:func:`fused_unify_packed_pallas` reads a (B, K, d) slot stack (the
client side); :func:`fused_unify_packed_rows_pallas` reads each
client's slot rows out of the (T, d) task vectors (the server's
downlink).  Slot validity handles ragged k_n: invalid slots are zeroed
before the sign election and excluded from masks, so outputs match
per-client ``unify_with_modulators`` on the valid rows exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import bitpack

BLOCK_D_PACKED = 4096       # 128 uint32 words per tile (one lane tile)


def _unify_tile(x, v):
    """Tile body of both kernels over one client's (K, BD) task-vector tile and
    its (K, 1) fp32 {0,1} slot validity: returns (τ (1, BD), mask
    (K, BD) fp32 {0,1}, and this tile's λ partial sums num, den (K, 1)).
    Every operand is 2-D (K, ·) or (·, 1), the layouts Mosaic tiles
    without moving data between lanes and sublanes."""
    x = x.astype(jnp.float32)                       # (K, BD)
    xm = x * v
    sigma = jnp.sign(jnp.sum(xm, axis=0, keepdims=True))
    aligned = (xm * sigma) > 0.0
    mu = jnp.max(jnp.where(aligned, jnp.abs(xm), 0.0), axis=0, keepdims=True)
    tau = sigma * mu
    mask = ((x * tau) > 0.0).astype(jnp.float32) * v
    num = jnp.sum(jnp.abs(xm), axis=1, keepdims=True)
    den = jnp.sum(mask * jnp.abs(tau), axis=1, keepdims=True)
    return tau, mask, num, den


def _fused_unify_packed_kernel(tv_ref, valid_ref, uni_ref, mask_ref,
                               num_ref, den_ref):
    # mask bits decided on the fp32 tau BEFORE the bf16 rounding of the
    # emitted unified vector
    tau, mask, num, den = _unify_tile(tv_ref[0], valid_ref[0])

    # the client's (1, K, 1) num/den blocks are revisited across the d
    # sweep (grid axis 1): zeroed on the first step, accumulated after
    @pl.when(pl.program_id(1) == 0)
    def _init():
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    num_ref[0] += num
    den_ref[0] += den
    uni_ref[0] = tau.astype(uni_ref.dtype)
    mask_ref[0] = bitpack.pack_tile(mask)


def _fused_unify_rows_kernel(tids_ref, tv_ref, valid_ref, uni_ref, mask_ref,
                             num_ref, den_ref, *, k):
    """Packed kernel reading each client's slot rows out of the (T, BD)
    task-vector tile of its d-block; grid (d/BD, B), clients innermost,
    so each tile is read from HBM once for all clients.  valid, num and
    den are whole-array blocks, resident for the whole grid; client i's
    λ sums accumulate over the d-blocks in the same order as the
    (B, K, d) kernel's."""
    i = pl.program_id(1)
    x = jnp.concatenate([tv_ref[pl.ds(tids_ref[i * k + s], 1), :]
                         for s in range(k)], axis=0)        # (K, BD)
    tau, mask, num, den = _unify_tile(x, valid_ref[i])

    @pl.when(pl.program_id(0) == 0)
    def _init():
        num_ref[i] = jnp.zeros_like(num)
        den_ref[i] = jnp.zeros_like(den)

    num_ref[i] += num
    den_ref[i] += den
    uni_ref[0] = tau.astype(uni_ref.dtype)
    mask_ref[0] = bitpack.pack_tile(mask)


def _fused_unify_call(task_vectors, valid, *, block_d, interpret):
    """Grid (B, d/BD) launch.  Per-client rows carry a unit axis —
    unified (B, 1, dp), valid/num/den (B, K, 1) — and packed words are
    emitted along the lanes, (B, K, dp/32) (see ``bitpack.pack_tile``;
    ``block_d`` a multiple of 4096), so the last two block dims always
    equal the array's or tile (8, 128).  Returns the padded-d outputs."""
    b, k, d = task_vectors.shape
    pad = (-d) % block_d
    if pad:
        task_vectors = jnp.pad(task_vectors, ((0, 0), (0, 0), (0, pad)))
    dp = d + pad
    scalar_spec = pl.BlockSpec((1, k, 1), lambda i, j: (i, 0, 0))
    unified, masks, num, den = pl.pallas_call(
        _fused_unify_packed_kernel,
        grid=(b, dp // block_d),
        in_specs=[
            pl.BlockSpec((1, k, block_d), lambda i, j: (i, 0, j)),
            scalar_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_d), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, k, block_d // 32), lambda i, j: (i, 0, j)),
            scalar_spec,
            scalar_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, dp), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, k, dp // 32), jnp.uint32),
            jax.ShapeDtypeStruct((b, k, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, k, 1), jnp.float32),
        ],
        interpret=interpret,
        name="fused_unify_packed_pallas",
    )(task_vectors, valid.astype(jnp.float32).reshape(b, k, 1))
    return unified[:, 0], masks, num[:, :, 0], den[:, :, 0]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_unify_packed_pallas(task_vectors: jax.Array, valid: jax.Array, *,
                              block_d: int = BLOCK_D_PACKED,
                              interpret: bool):
    """task_vectors (B, K, d) bf16 or fp32 slot stacks; valid (B, K)
    bool/{0,1}.  Emits the wire tensors directly — bf16 unified vectors
    and bit-packed uint32 mask words, packed 32 lanes per word inside
    the kernel so the (B, K, d) mask never exists in HBM at more than 1
    bit per element.

    Returns (unified (B, d) bf16, mask_words (B, K, ceil(d/32)) uint32,
    num (B, K), den (B, K)); λ = num / max(den, eps) is left to the
    caller so eps policy stays in one place (invalid slots: num = den =
    0).  Compute is fp32 per tile; mask bits and num/den are derived
    from the fp32 values before the bf16 rounding.  Zero-padding d is
    safe: padded lanes contribute nothing to num/den and are sliced off
    the streamed outputs.
    """
    d = task_vectors.shape[-1]
    unified, words, num, den = _fused_unify_call(
        task_vectors, valid, block_d=block_d, interpret=interpret)
    return unified[:, :d], words[:, :, :bitpack.packed_width(d)], num, den


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_unify_packed_rows_pallas(task_vectors: jax.Array,
                                   slot_tasks: jax.Array, valid: jax.Array,
                                   *, block_d: int = BLOCK_D_PACKED,
                                   interpret: bool):
    """:func:`fused_unify_packed_pallas` of the slot stack
    ``task_vectors[slot_tasks]`` without materialising it: task_vectors
    (T, d), slot_tasks (B, K) int (clamped to [0, T), as a ``take``
    with ``mode="clip"``), valid (B, K).  Each client's tile holds the
    same values as the gathered stack's, so the outputs are
    bit-identical to gathering first; the kernel reads each slot's row
    straight from the (T, d) array, and B·K·d·4 bytes of HBM (4.3 GB a
    chip for one taskvec shard of qwen2.5-32b's round) are never
    allocated."""
    b, k = slot_tasks.shape
    t, d = task_vectors.shape
    pad = (-d) % block_d
    if pad:
        task_vectors = jnp.pad(task_vectors, ((0, 0), (0, pad)))
    dp = d + pad
    # valid, num and den: whole-array blocks, resident for the whole grid
    whole = pl.BlockSpec((b, k, 1), lambda j, i, _: (0, 0, 0))
    unified, words, num, den = pl.pallas_call(
        functools.partial(_fused_unify_rows_kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(dp // block_d, b),
            in_specs=[pl.BlockSpec((t, block_d), lambda j, i, _: (0, j)),
                      whole],
            out_specs=[
                pl.BlockSpec((1, 1, block_d), lambda j, i, _: (i, 0, j)),
                pl.BlockSpec((1, k, block_d // 32), lambda j, i, _: (i, 0, j)),
                whole,
                whole,
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, dp), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, k, dp // 32), jnp.uint32),
            jax.ShapeDtypeStruct((b, k, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, k, 1), jnp.float32),
        ],
        interpret=interpret,
        name="fused_unify_packed_rows_pallas",
    )(jnp.clip(slot_tasks, 0, t - 1).reshape(b * k).astype(jnp.int32),
      task_vectors, valid.astype(jnp.float32).reshape(b, k, 1))
    return (unified[:, 0, :d], words[:, :, :bitpack.packed_width(d)],
            num[:, :, 0], den[:, :, 0])
