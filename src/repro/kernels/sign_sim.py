"""Pallas TPU kernel: sign-conflict task similarity (Eq. 5) by popcount.

The jnp form is an elementwise sign + (T, d) @ (d, T) in fp32.  At
full-fine-tune scale d ~ 10⁸ and T ~ 30, so the op is a skinny
memory-bound matmul.  The kernel reads the packed sign bit-planes of
τ̂ (32 coordinates a word, ``bitpack.sign_planes``) and accumulates the
(T, T) dots by popcount algebra — exact integers at 1/32 of the
element count.

Grid iterates over the words; the (T, T) output block is revisited
every step (accumulation pattern: zero on first step, add afterwards).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_W = 512           # uint32 words per grid step of the packed kernel


def _sign_sim_packed_kernel(pos_ref, nz_ref, acc_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the popcount identity lives in ONE place (bitpack) — the kernel
    # tile is exactly the (T, BW) shape the helper operates on
    from repro.kernels import bitpack
    dots = bitpack.packed_sign_dots(pos_ref[...], nz_ref[...])
    acc_ref[...] += dots.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def sign_sim_packed_pallas(pos: jax.Array, nz: jax.Array, *,
                           block_w: int = BLOCK_W,
                           interpret: bool) -> jax.Array:
    """Eq. 5 sign dots from packed sign bit-planes: ``pos``/``nz`` are
    (T, w) uint32 planes with bit j set iff τ̂_j > 0 / τ̂_j ≠ 0 (see
    ``repro.kernels.bitpack.sign_planes``).

    Per word the dot contribution is pure popcount algebra —
    popcnt(both) − 2·popcnt(both & (pos ⊕ pos')) — an exact integer
    identical to the fp32 sgn·sgnᵀ matmul, at 1/32 the element count.
    Zero padding of the planes contributes nothing.  Returns the raw
    (T, T) dots in fp32; the caller normalises by the *unpacked* d:
    S = ½(dots/d + 1).
    """
    t, w = pos.shape
    pad = (-w) % block_w
    if pad:
        pos = jnp.pad(pos, ((0, 0), (0, pad)))
        nz = jnp.pad(nz, ((0, 0), (0, pad)))
    wp = w + pad
    return pl.pallas_call(
        _sign_sim_packed_kernel,
        grid=(wp // block_w,),
        in_specs=[pl.BlockSpec((t, block_w), lambda i: (0, i)),
                  pl.BlockSpec((t, block_w), lambda i: (0, i))],
        out_specs=pl.BlockSpec((t, t), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, t), jnp.float32),
        interpret=interpret,
        name="sign_sim_packed_pallas",
    )(pos, nz)
