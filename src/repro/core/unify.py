"""Task unification and task-specific modulators (paper §3.1–3.2).

All functions operate on *flat* task vectors — pytrees are flattened
with :func:`repro.common.tree_flatten_vector` so the client and server
agree on the layout of the d-dimensional space.  Everything is
jit-able and shards elementwise over the ``taskvec`` logical axis.

Kernel-accelerated versions (Pallas) live in ``repro.kernels``; these
jnp implementations are the reference semantics and the CPU path.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def unify(task_vectors: jax.Array) -> jax.Array:
    """"Task unification" (Eq. 2, after Huang et al. 2024 EMR-merging).

    task_vectors: (K, d) stacked task vectors.
    Returns the unified vector tau = sigma ⊙ mu where
    sigma = sgn(Σ_k τ_k) and mu_j = max_k |τ_kj| over sign-aligned k.
    """
    sigma = jnp.sign(jnp.sum(task_vectors, axis=0))
    aligned = (task_vectors * sigma[None, :]) > 0
    mu = jnp.max(jnp.abs(task_vectors) * aligned, axis=0)
    return sigma * mu


def task_mask(task_vector: jax.Array, unified: jax.Array) -> jax.Array:
    """Binary modulator mask m^t = (τ^t ⊙ τ > 0) — bool (d,) or (K, d)."""
    return (task_vector * unified) > 0


def task_scaler(task_vector: jax.Array, mask: jax.Array,
                unified: jax.Array, eps: float = 1e-12) -> jax.Array:
    """Rescaler λ^t = Σ|τ^t| / Σ|m^t ⊙ τ| (scalar, or (K,) if batched)."""
    num = jnp.sum(jnp.abs(task_vector), axis=-1)
    den = jnp.sum(jnp.abs(jnp.where(mask, unified, 0.0)), axis=-1)
    return num / jnp.maximum(den, eps)


def modulators(task_vectors: jax.Array, unified: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """Masks (K, d) bool and scalers (K,) for stacked task vectors."""
    masks = task_mask(task_vectors, unified[None, :])
    lams = task_scaler(task_vectors, masks, unified[None, :])
    return masks, lams


def modulate(unified: jax.Array, mask: jax.Array, lam: jax.Array) -> jax.Array:
    """Reconstruct a task vector: τ̇^t = λ^t · m^t ⊙ τ (paper §3.2).

    ``mask`` may be dense bool or the bit-packed uint32 wire rows
    (``ceil(d/32)`` words, LSB-first) a :class:`ClientDownlink` now
    carries — packed rows are unpacked here, at the point of use, so
    the downlink itself never holds an 8x-inflated bool tensor.  A bf16
    wire ``unified`` is upcast so the reconstruction runs in fp32.
    """
    if mask.dtype == jnp.uint32:
        from repro.kernels import bitpack
        mask = bitpack.unpack_bits(mask, unified.shape[-1])
    unified = unified.astype(jnp.float32)
    return lam[..., None] * jnp.where(mask, unified, 0.0) if jnp.ndim(lam) \
        else lam * jnp.where(mask, unified, 0.0)


def unify_with_modulators(task_vectors: jax.Array
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Client-side upload construction: (τ_n, masks, λs) from (K, d)."""
    tau = unify(task_vectors)
    masks, lams = modulators(task_vectors, tau)
    return tau, masks, lams


def unify_masked(task_vectors: jax.Array, valid: jax.Array) -> jax.Array:
    """Padding-aware unification: Eq. 2 over the rows where ``valid``.

    task_vectors (K, d); valid (K,) bool.  Invalid rows are zeroed
    before the sign election, which is exactly equivalent to dropping
    them (zeros change neither the sign sum nor the aligned max), so
    ``unify_masked(x, v) == unify(x[v])``.  This is the reference
    semantics of the fused batched kernel
    (:func:`repro.kernels.ops.fused_unify_packed`).
    """
    x = task_vectors * valid.astype(task_vectors.dtype)[:, None]
    sigma = jnp.sign(jnp.sum(x, axis=0))
    aligned = (x * sigma[None, :]) > 0
    mu = jnp.max(jnp.abs(x) * aligned, axis=0)
    return sigma * mu


def unify_with_modulators_masked(task_vectors: jax.Array, valid: jax.Array
                                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Padding-aware ``unify_with_modulators`` for one slot-packed
    client: invalid slots yield all-False mask rows and λ = 0."""
    tau = unify_masked(task_vectors, valid)
    masks = task_mask(task_vectors, tau[None, :]) & valid[:, None]
    num = jnp.sum(jnp.abs(task_vectors * valid.astype(task_vectors.dtype)[:, None]),
                  axis=-1)
    den = jnp.sum(jnp.abs(jnp.where(masks, tau[None, :], 0.0)), axis=-1)
    lams = num / jnp.maximum(den, 1e-12)
    return tau, masks, lams
