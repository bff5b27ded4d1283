"""Pallas TPU kernel: per-request modulated LoRA matmul for serving.

The multi-tenant decode path applies each request's task modulator to
the shared LoRA leaf at matmul time:

    y_b = x_b @ (base + lam_b * m_b * tau)

The reference route first materialises every request's effective
weight in HBM (unpack the mask words to fp32, three elementwise passes
over (B, K, N)) and only then runs the batched matmul.  This kernel
streams one request per grid step: the packed uint32 words expand to
{0, 1} lanes in VMEM (``bitpack.unpack_tile``, one word row per weight
row), the λ-scale and the add onto the base leaf fuse into the same
tile, and the MXU consumes
the effective weight without it ever existing in HBM — applying a
modulator costs no extra HBM pass beyond reading base/tau once per
request.

Layout: grid (B,); whole (S, K) / (K, N) blocks per step (LoRA leaves
are small — K or N is the rank r, so a full leaf fits VMEM easily).
Bit order: ``words[b]`` is the row-major (K, N) mask of request b in
the repo's LSB-first uint32 layout (``repro.kernels.bitpack``);
``K * N`` must be word-aligned (% 32 == 0) — the router only routes
leaf pairs that qualify and falls back to the dense path otherwise.

Bit-parity: the effective weight is built by ``ref.modulated_weight``
— rounded to the leaf dtype exactly as the materialised adapter is —
so the fused product matches the unpack-then-matmul oracle
(``ref.modulated_matmul_ref``) bitwise; the dot contraction is the same
shape in both (tested in tests/test_serve_multitenant.py, ref +
pallas_interpret).

``routed_matmul_pallas`` is the dense-routed twin: per-request weights
already materialised (B, K, N), the same grid and the same in-kernel
contraction (``_lora_dot``).  On the chip the two routed forms must
agree bitwise, and an XLA einsum does not contract in the Mosaic dot's
order (at S = 1 XLA even rewrites it into a multiply-reduce), so a bf16
product can round differently and flip a greedy token; sharing the dot
removes that difference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import bitpack
from repro.kernels.ref import modulated_weight


def _lora_dot(x, w, dtype):
    """(S, K) @ (K, N) accumulated in fp32, cast to ``dtype``: the one
    contraction of both routed kernels."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(dtype)


def _modulated_matmul_kernel(x_ref, base_ref, tau_ref, words_ref, lam_ref,
                             out_ref):
    k, n = base_ref.shape
    # one row of words per weight row: unpacking fills that row's lanes
    m = bitpack.unpack_tile(words_ref[0], jnp.float32)[:, :n]   # (K, N)
    w_eff = modulated_weight(base_ref[...], tau_ref[...], m, lam_ref[0])
    out_ref[0] = _lora_dot(x_ref[0], w_eff, out_ref.dtype)


def _routed_matmul_kernel(x_ref, w_ref, out_ref):
    out_ref[0] = _lora_dot(x_ref[0], w_ref[0], out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def modulated_matmul_pallas(x: jax.Array, base: jax.Array, tau: jax.Array,
                            words: jax.Array, lam: jax.Array, *,
                            interpret: bool) -> jax.Array:
    """x (B, S, K); base/tau (K, N) in the adapter leaf dtype; words
    (B, ceil(K*N/32)) uint32; lam (B,).  Returns (B, S, N) in
    ``result_type(x, base)`` = x_b @ (base + lam_b·m_b·tau), the weight
    rounded as ``ref.modulated_weight`` says and the product accumulated
    in fp32 — what ``routed_matmul_pallas`` computes from the
    materialised weights.

    ``K * N`` must be a multiple of 32 (word-aligned leaf); the
    dispatch layer enforces it.  The words are re-aligned to one word
    row per weight row (``bitpack.row_words``) before the launch, and
    λ rides as a (B, 1, 1) column, so every block's last two dims equal
    the array's.
    """
    b, s, k = x.shape
    k2, n = base.shape
    assert k == k2, (x.shape, base.shape)
    rw = bitpack.row_words(words, k, n)                      # (B, K, wr)
    out = pl.pallas_call(
        _modulated_matmul_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, s, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
            pl.BlockSpec((1, k, rw.shape[-1]), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, s, n), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, n),
                                       jnp.result_type(x.dtype, base.dtype)),
        interpret=interpret,
    )(x, base, tau, rw, lam.astype(jnp.float32).reshape(b, 1, 1))
    return out


@functools.partial(jax.jit, static_argnames=("interpret",))
def routed_matmul_pallas(x: jax.Array, w: jax.Array, *,
                         interpret: bool) -> jax.Array:
    """x (B, S, K) @ w (B, K, N), one request per grid step.  Returns
    (B, S, N) in ``result_type(x, w)``, accumulated in fp32."""
    b, s, k = x.shape
    n = w.shape[-1]
    assert w.shape == (b, k, n), (x.shape, w.shape)
    return pl.pallas_call(
        _routed_matmul_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, s, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, k, n), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, s, n), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, n),
                                       jnp.result_type(x.dtype, w.dtype)),
        interpret=interpret,
    )(x, w)
