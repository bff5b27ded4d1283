"""Pallas TPU kernel: chunkwise-parallel mLSTM (beyond-paper extension).

The xLSTM architecture's hot loop is the stabilised chunkwise mLSTM
(repro.nn.ssm.mlstm_chunkwise).  The jnp version materialises the
(B, H, L, L) decay matrix and five intermediate (B, H, L, ·) tensors in
HBM per chunk; this kernel keeps the whole per-(batch, head) chunk
working set — q/k/v tiles, the L×L decay mask, and the recurrent
(C, n, m) state — resident in VMEM, streaming each input tile exactly
once.

Grid: (B·H, n_chunks) with the chunk dimension sequential ("arbitrary")
so the (C, n, m) state persists in VMEM scratch across chunks of the
same (batch, head) program.  MXU work: the three L×Dk / L×L / L×Dv
matmuls per chunk.  For TPU lowering, L and the head dims should be
lane-aligned (multiples of 8×128 tiles); the ops-level wrapper pads.
Validated in interpret mode against the jnp oracle and the step
recurrence (tests/test_kernels_mlstm.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mlstm_chunk_kernel(q_ref, k_ref, v_ref, i_ref, f_ref, h_ref,
                        c_scr, n_scr, m_scr, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        c_scr[...] = jnp.zeros_like(c_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.full_like(m_scr, -1e30)

    q = q_ref[0].astype(jnp.float32)        # (L, Dk)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)        # (L, Dv)
    ic = i_ref[0].astype(jnp.float32)       # (L,)
    fc = f_ref[0].astype(jnp.float32)

    C, n, m = c_scr[...], n_scr[...], m_scr[0]

    log_f = -jax.nn.softplus(-fc)
    bcum = jnp.cumsum(log_f)
    c = ic - bcum
    cmax = jax.lax.cummax(c, axis=0)
    m_t = bcum + jnp.maximum(m, cmax)                       # (L,)

    scale_inter = jnp.exp(bcum + m - m_t)                   # (L,)
    h_inter = (q @ C) * scale_inter[:, None]                # (L, Dv)
    qn_inter = (q @ n[:, None])[:, 0] * scale_inter         # (L,)

    pos = jax.lax.iota(jnp.int32, chunk)
    causal = pos[:, None] >= pos[None, :]
    d_log = bcum[:, None] - bcum[None, :] + ic[None, :]
    d_mat = jnp.where(causal, jnp.exp(d_log - m_t[:, None]), 0.0)
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    w = d_mat * scores
    h_intra = jnp.dot(w, v, preferred_element_type=jnp.float32)
    qn_intra = jnp.sum(w, axis=-1)

    qn = qn_inter + qn_intra
    denom = jnp.maximum(jnp.abs(qn), jnp.exp(-m_t))[:, None]
    h_ref[0] = ((h_inter + h_intra) / denom).astype(h_ref.dtype)

    total = bcum[-1]
    m_next = jnp.maximum(m + total, total + jnp.max(c))
    wgt = jnp.exp(total - bcum + ic - m_next)               # (L,)
    c_scr[...] = (jnp.exp(m + total - m_next) * C
                  + jnp.dot(k.T * wgt[None, :], v,
                            preferred_element_type=jnp.float32))
    n_scr[...] = jnp.exp(m + total - m_next) * n + (k.T * wgt[None, :]).sum(1)
    m_scr[0] = m_next


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunkwise_pallas(q, k, v, i_pre, f_pre, *, chunk: int = 64,
                           interpret: bool):
    """q,k (BH, S, Dk); v (BH, S, Dv); i_pre/f_pre (BH, S) -> h (BH, S, Dv).

    Zero initial state (block-local form used inside the LM); S padded
    to a chunk multiple with i=-inf / f=+40 identity steps.
    """
    bh, s, dk = q.shape
    dv = v.shape[-1]
    pad = (-s) % chunk
    if pad:
        z = ((0, 0), (0, pad), (0, 0))
        q, k, v = jnp.pad(q, z), jnp.pad(k, z), jnp.pad(v, z)
        i_pre = jnp.pad(i_pre, ((0, 0), (0, pad)), constant_values=-1e30)
        f_pre = jnp.pad(f_pre, ((0, 0), (0, pad)), constant_values=40.0)
    sp = s + pad
    nc = sp // chunk

    kernel = functools.partial(_mlstm_chunk_kernel, chunk=chunk)
    h = pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk), lambda b, c: (b, c)),
            pl.BlockSpec((1, chunk), lambda b, c: (b, c)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sp, dv), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),   # C carry
            pltpu.VMEM((dk,), jnp.float32),      # n carry
            pltpu.VMEM((1,), jnp.float32),       # m carry
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, i_pre, f_pre)
    return h[:, :s]
