"""Pallas TPU kernel: task unification (Eq. 2).

The server re-unifies per-client task vectors every round; at
full-fine-tune scale d is the model size, so this is a pure
memory-bound streaming op.  The jnp reference reads the (K, d) stack
~5× (sum, sign, abs, compare, max); this kernel streams each (K, BD)
block through VMEM once and fuses sign-election + aligned max-|.| into
a single pass — the arithmetic intensity is fixed, the win is HBM
traffic.

Blocking: grid over d in BD=2048 lanes (16 × 128, aligned to the VPU
8×128 vregs); K rides along entirely in VMEM (K ≤ 64 in practice:
VMEM use = K·BD·4B ≤ 512 KiB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_D = 2048


def _unify_kernel(tv_ref, out_ref):
    x = tv_ref[...].astype(jnp.float32)          # (K, BD)
    total = jnp.sum(x, axis=0)
    sigma = jnp.sign(total)
    aligned = (x * sigma[None, :]) > 0.0
    mu = jnp.max(jnp.where(aligned, jnp.abs(x), 0.0), axis=0)
    out_ref[...] = (sigma * mu).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def unify_pallas(task_vectors: jax.Array, *, block_d: int = BLOCK_D,
                 interpret: bool) -> jax.Array:
    """(K, d) -> (d,). Pads d to a lane multiple internally."""
    k, d = task_vectors.shape
    pad = (-d) % block_d
    if pad:
        task_vectors = jnp.pad(task_vectors, ((0, 0), (0, pad)))
    dp = d + pad
    out = pl.pallas_call(
        _unify_kernel,
        grid=(dp // block_d,),
        in_specs=[pl.BlockSpec((k, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((block_d,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((dp,), jnp.float32),
        interpret=interpret,
    )(task_vectors)
    return out[:d]
