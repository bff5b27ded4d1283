"""Plain reference of one MaTU server round, computed in blocks of d so
that a task vector too large for one chip's memory can be checked.

It computes what ``chipref/matu_round.py`` computes (arXiv:2502.06376
§3.2, Eq. 3-7, and the downlink re-unification), with the same
per-coordinate operations in the same order, at a stated precision
(float32 for the reference, bfloat16 for its control; matmuls at
``highest``).  It imports nothing of the program.  Two passes over the
blocks ``[b0, b0 + block)`` of d, each block on one device:

* pass 1: Eq. 3-4 per task over the block, then the block's Eq. 5 sign
  dots;
* pass 2: Eq. 3-4 again, Eq. 6-7 with the similarity of pass 1, and
  the downlinks of the requested clients over the block.

Where it departs from ``matu_round.py``, only the order of summation
changes:

* Eq. 5: ``matu_round`` sums the sign products of all d coordinates in
  one float32 matmul, which stops being exact past 2**24 coordinates;
  here each block's matmul sums at most 2**24 of them (exact in
  float32) and the blocks' integer dots are summed as int64 on the
  host;
* downlink lambdas: each client's numerator and denominator are summed
  per block in the stated precision, and the block sums are added in
  float64 on the host.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import bits
from chipref.matu_round import HIGHEST, _donor_weights

# coordinates per block: at most 2**24, so one block's sign dots are
# exact in float32; 2**23 keeps a block's (T, block) float32 arrays at
# 1 GB each on one chip
BLOCK = 1 << 23


def _members(tasks: Sequence[Sequence[int]],
             n_tasks: int) -> Dict[int, List[Tuple[int, int]]]:
    members: Dict[int, List[Tuple[int, int]]] = {t: [] for t in range(n_tasks)}
    for n, tl in enumerate(tasks):
        for slot, t in enumerate(tl):
            members[t].append((n, slot))
    return members


def _merge(ub, wb, lams, sizes, members, n_tasks: int, width: int, *,
           rho: float, dtype):
    """Eq. 3-4 over one block: (tau_hat (T, width), m_hat (T, width),
    held (T,)), each task's members in slot order as in
    ``matu_round.task_vectors``."""
    zeros = jnp.zeros((width,), dtype)
    tau_hats, m_hats, held = [], [], []
    for t in range(n_tasks):
        mem = members[t]
        if not mem:
            tau_hats.append(zeros)
            m_hats.append(zeros)
            held.append(False)
            continue
        size = np.array([sizes[n][s] for n, s in mem], np.float64)
        gamma = jnp.asarray(size, dtype) / jnp.sum(jnp.asarray(size, dtype))
        acc_sign, acc_val = zeros, zeros
        for i, (n, s) in enumerate(mem):
            m = bits.unpack(wb[n, s], width)
            mu = jnp.where(m, ub[n], jnp.zeros((), dtype))
            acc_sign = acc_sign + jnp.sign(mu)
            acc_val = acc_val + (gamma[i] * jnp.asarray(lams[n][s], dtype)) * mu
        alpha = jnp.abs(acc_sign) / jnp.asarray(len(mem), dtype)
        m_hat = jnp.where(alpha >= rho, jnp.ones((), dtype), alpha)
        tau_hats.append(acc_val * m_hat)
        m_hats.append(m_hat)
        held.append(True)
    return jnp.stack(tau_hats), jnp.stack(m_hats), held


def _blocks(d: int, block: int):
    if block % bits.WORD or not 0 < block <= 1 << 24:
        raise ValueError(f"block {block}: a multiple of {bits.WORD} "
                         f"of at most 2**24 coordinates")
    for b0 in range(0, d, block):
        yield b0, min(b0 + block, d)


def _put(unified, words, b0: int, b1: int, dtype):
    """One block of every client's unified vector (as ``dtype``) and
    mask words, on the default device."""
    ub = jnp.asarray(np.ascontiguousarray(unified[:, b0:b1])).astype(dtype)
    wb = jnp.asarray(np.ascontiguousarray(
        words[:, :, b0 // bits.WORD:bits.n_words(b1)]))
    return ub, wb


def round_downlinks(unified: np.ndarray, words: np.ndarray, lams, sizes,
                    tasks, n_tasks: int, d: int, *, clients=None,
                    block: int = BLOCK, rho: float = 0.4, eps: float = 0.5,
                    kappa: int = 3, dtype=jnp.float32):
    """The downlinks of one round's ``clients`` (every client when
    None), keyed by client id: (bf16 unified (d,), bool masks (k, d),
    float64 lambdas (k,)), as ``matu_round.round_downlinks`` returns
    them.  ``unified`` (N, d) and ``words`` (N, K, ceil(d/32)) are the
    host wire buffers."""
    unified, words = np.asarray(unified), np.asarray(words)
    members = _members(tasks, n_tasks)
    ids = list(range(len(tasks)) if clients is None else clients)
    with jax.default_matmul_precision("highest"):
        dots = np.zeros((n_tasks, n_tasks), np.int64)
        held = None
        for b0, b1 in _blocks(d, block):
            ub, wb = _put(unified, words, b0, b1, dtype)
            tau_hat, _, held = _merge(ub, wb, lams, sizes, members, n_tasks,
                                      b1 - b0, rho=rho, dtype=dtype)
            signs = jnp.sign(tau_hat)
            part = jnp.matmul(signs, signs.T, precision=HIGHEST)
            dots += np.rint(np.asarray(part, np.float64)).astype(np.int64)
        heldf = jnp.asarray(held, dtype)
        sim = (0.5 * (jnp.asarray(dots, dtype) / jnp.asarray(d, dtype) + 1.0)
               * heldf[None, :] * heldf[:, None])
        w = jnp.asarray(_donor_weights(np.asarray(sim, np.float64), eps,
                                       kappa), dtype)
        total = jnp.sum(w, axis=1, keepdims=True)
        share = w / jnp.maximum(total, 1e-12)
        has = (total > 0).astype(dtype)

        out = {c: (np.zeros((d,), jnp.bfloat16),
                   np.zeros((len(tasks[c]), d), bool),
                   np.zeros(len(tasks[c]), np.float64),
                   np.zeros(len(tasks[c]), np.float64)) for c in ids}
        for b0, b1 in _blocks(d, block):
            ub, wb = _put(unified, words, b0, b1, dtype)
            tau_hat, m_hat, _ = _merge(ub, wb, lams, sizes, members,
                                       n_tasks, b1 - b0, rho=rho, dtype=dtype)
            mixed = jnp.matmul(share, tau_hat, precision=HIGHEST)
            tv = (tau_hat + m_hat * mixed * has) / (1.0 + has)
            for c in ids:
                uni, masks, num, den = out[c]
                u, m, n_part, d_part = _downlink(tv[jnp.asarray(tasks[c])])
                uni[b0:b1], masks[:, b0:b1] = u, m
                num += n_part
                den += d_part
    return {c: (uni, masks, num / np.maximum(den, 1e-12))
            for c, (uni, masks, num, den) in out.items()}


def _downlink(tvs: jax.Array):
    """``matu_round.downlink`` over one block of one client's (k, width)
    task vectors, with the lambda numerator and denominator left as the
    block's sums (float64 on the host)."""
    sigma = jnp.sign(jnp.sum(tvs, axis=0))
    aligned = (tvs * sigma[None, :]) > 0
    mu = jnp.max(jnp.where(aligned, jnp.abs(tvs), jnp.zeros((), tvs.dtype)),
                 axis=0)
    tau_u = sigma * mu
    masks = (tvs * tau_u[None, :]) > 0
    num = jnp.sum(jnp.abs(tvs), axis=-1)
    den = jnp.sum(jnp.abs(jnp.where(masks, tau_u[None, :],
                                    jnp.zeros((), tvs.dtype))), axis=-1)
    return (np.asarray(tau_u.astype(jnp.bfloat16)), np.asarray(masks),
            np.asarray(num, np.float64), np.asarray(den, np.float64))
