"""Plain reference of one MaTU server round (arXiv:2502.06376 §3.2,
Eq. 3-7) and of the downlink re-unification, from wire uploads.

Straightforward ``jax.numpy`` at a stated precision, one task and one
client at a time; it imports nothing of the program.  Semantics, as
the paper states them, with Eq. 6 normalised over the donors and Eq. 7
as the average the paper's overview describes:

* Eq. 3: per task t, over its member slots n,
  alpha = |sum_n sgn(m_n^t * tau_n)| / N_t and m_hat = 1 where
  alpha >= rho, else alpha;
* Eq. 4: tau_hat = m_hat * sum_n gamma_n lambda_n m_n^t * tau_n with
  gamma_n = |D_n^t| / sum |D^t|;
* Eq. 5: S = (sgn(tau_hat) sgn(tau_hat)^T / d + 1) / 2 over the tasks
  held this round (zero rows and columns otherwise);
* Eq. 6: per row, the top-kappa off-diagonal S > eps (ties kept), each
  donor weighted by S / sum S; tau_tilde = m_hat * sum S' tau_hat';
* Eq. 7: tau = (tau_hat + tau_tilde) / 2 where a task has donors,
  tau_hat otherwise;
* downlink of a client holding tasks t_1..t_k: tau_u = sgn(sum tau_t)
  * max |tau_t| over the sign-aligned t; mask m_t = tau_t * tau_u > 0;
  lambda_t = sum |tau_t| / max(sum |m_t * tau_u|, 1e-12); tau_u goes
  out as bf16.

``dtype`` is the precision of every operation (float32 for the
reference, bfloat16 for its control); matmuls run at ``highest``.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import bits

HIGHEST = jax.lax.Precision.HIGHEST


def task_vectors(unified: np.ndarray, words: np.ndarray, lams: np.ndarray,
                 sizes: np.ndarray, tasks: Sequence[Sequence[int]],
                 n_tasks: int, d: int, *, rho: float = 0.4,
                 eps: float = 0.5, kappa: int = 3,
                 dtype=jnp.float32) -> jax.Array:
    """Eq. 3-7: the round's (T, d) task vectors."""
    members: Dict[int, List[Tuple[int, int]]] = {t: [] for t in range(n_tasks)}
    for n, tl in enumerate(tasks):
        for slot, t in enumerate(tl):
            members[t].append((n, slot))
    zeros = jnp.zeros((d,), dtype)
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
            u = jnp.asarray(unified[n]).astype(dtype)
            m = bits.unpack(jnp.asarray(words[n][s]), d)
            mu = jnp.where(m, u, jnp.zeros((), dtype))
            acc_sign = acc_sign + jnp.sign(mu)
            acc_val = acc_val + (gamma[i] * jnp.asarray(lams[n][s], dtype)) * mu
        alpha = jnp.abs(acc_sign) / jnp.asarray(len(mem), dtype)
        m_hat = jnp.where(alpha >= rho, jnp.ones((), dtype), alpha)
        tau_hats.append(acc_val * m_hat)
        m_hats.append(m_hat)
        held.append(True)
    tau_hat = jnp.stack(tau_hats)
    m_hat = jnp.stack(m_hats)
    heldf = jnp.asarray(held, dtype)

    signs = jnp.sign(tau_hat)
    dots = jnp.matmul(signs, signs.T, precision=HIGHEST)
    sim = (0.5 * (dots / jnp.asarray(d, dtype) + 1.0)
           * heldf[None, :] * heldf[:, None])
    weights = _donor_weights(np.asarray(sim, np.float64), eps, kappa)
    w = jnp.asarray(weights, dtype)
    total = jnp.sum(w, axis=1, keepdims=True)
    mixed = jnp.matmul(w / jnp.maximum(total, 1e-12), tau_hat,
                       precision=HIGHEST)
    has = (total > 0).astype(dtype)
    return (tau_hat + m_hat * mixed * has) / (1.0 + has)


def _donor_weights(sim: np.ndarray, eps: float, kappa: int) -> np.ndarray:
    """Eq. 6 neighbourhoods: top-kappa off-diagonal S > eps per row, ties
    at the kappa-th value kept."""
    t = sim.shape[0]
    k = min(kappa, t - 1)
    out = np.zeros_like(sim)
    if k <= 0:
        return out
    for i in range(t):
        row = sim[i].copy()
        row[i] = 0.0
        eligible = np.where(row > eps, row, 0.0)
        thresh = np.sort(eligible)[::-1][k - 1]
        keep = (eligible >= thresh) & (eligible > 0)
        out[i] = np.where(keep, eligible, 0.0)
    return out


def downlink(tvs: jax.Array, d: int) -> Tuple[np.ndarray, np.ndarray,
                                             np.ndarray]:
    """Re-unify one client's (k, d) task vectors: (bf16 unified (d,),
    bool masks (k, d), lambdas (k,)) on the host."""
    sigma = jnp.sign(jnp.sum(tvs, axis=0))
    aligned = (tvs * sigma[None, :]) > 0
    mu = jnp.max(jnp.where(aligned, jnp.abs(tvs), jnp.zeros((), tvs.dtype)),
                 axis=0)
    tau_u = sigma * mu
    masks = (tvs * tau_u[None, :]) > 0
    num = jnp.sum(jnp.abs(tvs), axis=-1)
    den = jnp.sum(jnp.abs(jnp.where(masks, tau_u[None, :],
                                    jnp.zeros((), tvs.dtype))), axis=-1)
    lams = num / jnp.maximum(den, 1e-12)
    return (np.asarray(tau_u.astype(jnp.bfloat16)), np.asarray(masks),
            np.asarray(lams, np.float64))


def round_downlinks(unified, words, lams, sizes, tasks, n_tasks: int,
                    d: int, *, clients=None, dtype=jnp.float32, **kw):
    """The downlinks of one round's ``clients`` (every client when
    None), keyed by client id."""
    with jax.default_matmul_precision("highest"):
        tv = task_vectors(unified, words, lams, sizes, tasks, n_tasks, d,
                          dtype=dtype, **kw)
        ids = range(len(tasks)) if clients is None else clients
        return {c: downlink(tv[jnp.asarray(tasks[c])], d) for c in ids}
