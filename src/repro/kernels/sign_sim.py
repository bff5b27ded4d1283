"""Pallas TPU kernel: sign-conflict task similarity (Eq. 5) as an MXU matmul.

The jnp form is an elementwise sign + (T, d) @ (d, T) in fp32.  At
full-fine-tune scale d ~ 10⁸ and T ~ 30, so the op is a skinny
memory-bound matmul.  The kernel tiles d, signs each (T, BD) tile in
VMEM, and accumulates the (T, T) partial product across the grid —
the sign tile never round-trips to HBM (the XLA version materialises
the full sgn(T) matrix first: 2× traffic).

Grid iterates over d; the (T, T) output block is revisited every step
(accumulation pattern: zero on first step, add afterwards).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_D = 2048
BLOCK_W = 512           # uint32 words per grid step of the packed kernel


def _sign_sim_kernel(x_ref, acc_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)          # (T, BD)
    s = jnp.sign(x)
    acc_ref[...] += jnp.dot(s, s.T, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def sign_sim_pallas(tau_hats: jax.Array, *, block_d: int = BLOCK_D,
                    interpret: bool) -> jax.Array:
    """(T, d) -> (T, T) similarity in [0, 1]. Zero-padding d is safe:
    sgn(0)·sgn(0) = 0 contributes nothing."""
    t, d = tau_hats.shape
    pad = (-d) % block_d
    if pad:
        tau_hats = jnp.pad(tau_hats, ((0, 0), (0, pad)))
    dp = d + pad
    dots = pl.pallas_call(
        _sign_sim_kernel,
        grid=(dp // block_d,),
        in_specs=[pl.BlockSpec((t, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((t, t), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, t), jnp.float32),
        interpret=interpret,
    )(tau_hats)
    return 0.5 * (dots / d + 1.0)


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
    """Eq. 5 sign dots from packed sign bit-planes (the wire-format
    form of :func:`sign_sim_pallas`): ``pos``/``nz`` are (T, w) uint32
    planes with bit j set iff τ̂_j > 0 / τ̂_j ≠ 0 (see
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
    )(pos, nz)
