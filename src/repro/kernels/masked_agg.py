"""Pallas TPU kernel: fused Eq. 3 + Eq. 4 (agreement mask + task merge).

Per task t the server computes, over the N_t member clients,
  α_j  = |Σ_n sgn(m_n ⊙ τ_n)_j| / N_t
  m̂_j  = 1 if α_j ≥ ρ else α_j
  τ̂_j  = m̂_j · Σ_n γ_n λ_n (m_n ⊙ τ_n)_j

A naive composition reads the (N, d) stack three times (sign-sum,
agreement compare, weighted sum) and materialises two (N, d)
intermediates in HBM.  The kernel streams each (N, BD) block through
VMEM once, producing both outputs.

:func:`masked_agg_batched_packed_rows_pallas` reads the wire slot
layout as uploaded: (N, d) unified vectors and (N, K, d/32) mask words,
K slots per client, each slot naming its task.  Its grid runs over d
alone.  A step unpacks the words of every slot of one d-block once —
N·K rows, only the member work — and folds the slots into their tasks
with a 0/1 slot → task matmul on the MXU, so HBM traffic is one read of
the unified vectors and the slot words and one write of the two (T, d)
outputs; no dense (N, T, ·) tensor is made.

The per-slot scalars (γ·λ) are small (N ≤ 64) and ride fully resident;
ρ is compile-time static.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import bitpack

# the kernel tiles 32 mask bits per word: 4096 elements = 128 words,
# exactly one uint32 lane tile
BLOCK_D_PACKED = 4096


def _split3(x):
    """fp32 (R, C) -> three bf16 pieces whose sum is ``x`` exactly: each
    piece keeps the top 8 significant bits of what is left (the low 16
    bits of a float's word cut away by a mask, so no rounding step can
    be folded away), and 3 x 8 bits hold an fp32 significand."""
    def top(v):
        w = jax.lax.bitcast_convert_type(v, jnp.uint32)
        return jax.lax.bitcast_convert_type(w & jnp.uint32(0xFFFF0000),
                                            jnp.float32)
    hi = top(x)
    mid = top(x - hi)
    lo = x - hi - mid
    return [p.astype(jnp.bfloat16) for p in (hi, mid, lo)]


def _masked_agg_rows_kernel(u_ref, w_ref, wt_ref, sel_ref, nt_ref,
                            tau_ref, anum_ref, *, rho):
    """One d-block of every task.  Each slot row's mask words are
    unpacked once; its weighted values w·m·u (split into three exact
    bf16 pieces) and its signed votes m·sgn(u) are folded into their
    task on the MXU by the 0/1 slot → task selector, fp32 accumulation,
    products exact."""
    u = u_ref[...].astype(jnp.float32)                  # (N, BD)
    sgn = jnp.sign(u)
    acc = None
    for k in range(w_ref.shape[0]):
        bits = bitpack.tile_bits(w_ref[k])              # (N, BD) bool
        x = jnp.where(bits, wt_ref[k] * u, 0.0)
        rhs = jnp.concatenate(
            _split3(x) + [jnp.where(bits, sgn, 0.0).astype(jnp.bfloat16)],
            axis=0)                                     # (4N, BD)
        part = jnp.dot(sel_ref[k], rhs, preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part      # (2·Tp, BD)
    t = tau_ref.shape[0]
    tp = acc.shape[0] // 2
    a_num = jnp.abs(acc[tp:tp + t])
    alpha = a_num / nt_ref[:t]
    m_hat = jnp.where(alpha >= rho, 1.0, alpha)
    tau_ref[...] = acc[:t] * m_hat
    anum_ref[...] = a_num


@functools.partial(jax.jit, static_argnames=("n_tasks", "rho", "interpret"))
def masked_agg_batched_packed_rows_pallas(unified: jax.Array,
                                          slot_mask_words: jax.Array,
                                          slot_weights: jax.Array,
                                          slot_tasks: jax.Array,
                                          slot_valid: jax.Array, *,
                                          n_tasks: int, rho: float = 0.4,
                                          interpret: bool):
    """Whole-round Eq. 3 + Eq. 4 straight from the wire slot layout.

    unified (N, d) (bf16 on the wire; any float dtype); slot_mask_words
    (N, K, ceil(d/32)) uint32, LSB-first; slot_weights (N, K) fp32, the
    slot's γ·λ; slot_tasks (N, K) int (``n_tasks`` is the sentinel of an
    empty slot); slot_valid (N, K).  Member slots are the valid ones
    whose task is in range; only they are folded, and N_t counts them.

    Grid (ceil(d/4096),): each step reads one (N, 4096) block of
    ``unified`` and the (K, N, 128) words of every slot, once, and
    writes the (T, 4096) blocks of both outputs, once.  The slot → task
    fold is a (2·Tp, 4N) @ (4N, 4096) matmul per slot column; a task
    nobody holds has an all-zero selector row, so it gets τ̂ = 0 and a
    numerator of 0.  HBM traffic is N·d·2 + N·K·d/8 bytes read and
    2·T·d·4 written; no (N, T, ·) tensor exists.  The last block may
    run past d: its extra lanes read whatever lies there and are never
    written back.

    Returns (tau_hats (T, d) fp32, alpha_num (T, d) fp32): the Eq. 3
    numerator |Σ_n sgn(m_n ⊙ τ_n)| is an exact small integer, from
    which the caller re-derives m̂ with the same fp32 division.
    """
    n, d = unified.shape
    k = slot_mask_words.shape[1]
    t = n_tasks
    tp = -(-t // 8) * 8             # vote rows start on a sublane tile
    np_ = -(-n // 16) * 16          # bf16 sublane tile: aligned concat
    onehot = ((slot_tasks[:, :, None] == jnp.arange(t))
              & slot_valid[:, :, None])                 # (N, K, T)
    nt = jnp.ones((tp, 1), jnp.float32).at[:t, 0].set(
        jnp.maximum(jnp.sum(onehot, axis=(0, 1), dtype=jnp.float32), 1.0))
    sel = jnp.zeros((k, tp, np_), jnp.bfloat16).at[:, :t, :n].set(
        jnp.transpose(onehot, (1, 2, 0)).astype(jnp.bfloat16))
    zero = jnp.zeros_like(sel)
    # rows [0, Tp): Σ of the three value pieces; rows [Tp, 2Tp): votes
    sel = jnp.concatenate([jnp.concatenate([sel, sel, sel, zero], axis=2),
                           jnp.concatenate([zero, zero, zero, sel], axis=2)],
                          axis=1)                       # (K, 2Tp, 4Np)
    words = jnp.swapaxes(slot_mask_words, 0, 1)         # (K, N, dw)
    weights = jnp.swapaxes(slot_weights.astype(jnp.float32), 0, 1)
    if np_ != n:
        unified = jnp.pad(unified, ((0, np_ - n), (0, 0)))
        words = jnp.pad(words, ((0, 0), (0, np_ - n), (0, 0)))
        weights = jnp.pad(weights, ((0, 0), (0, np_ - n)))
    weights = weights[:, :, None]                       # (K, Np, 1)
    bd = BLOCK_D_PACKED
    whole = lambda a: pl.BlockSpec(a.shape, lambda j: (0,) * a.ndim)  # noqa: E731
    out = pl.BlockSpec((t, bd), lambda j: (0, j))
    return pl.pallas_call(
        functools.partial(_masked_agg_rows_kernel, rho=rho),
        grid=(pl.cdiv(d, bd),),
        in_specs=[pl.BlockSpec((np_, bd), lambda j: (0, j)),
                  pl.BlockSpec((k, np_, bd // 32), lambda j: (0, 0, j)),
                  whole(weights), whole(sel), whole(nt)],
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((t, d), jnp.float32)] * 2,
        interpret=interpret,
        name="masked_agg_batched_packed_rows_pallas",
    )(unified, words, weights, sel, nt)
