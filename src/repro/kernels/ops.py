"""Dispatch layer over the Pallas kernels — the ONLY entry point the
round engine (repro.core.engine) uses for Eq. 2–7 math.

Three dispatch modes:

  "pallas"            natively-compiled kernels (TPU backend)
  "pallas_interpret"  kernel bodies executed by the Pallas interpreter
                      (bit-identical to the TPU lowering; validation
                      path, far too slow for the CPU hot loop)
  "ref"               pure-jnp streaming implementations
                      (repro.kernels.ref) — the fast XLA path on CPU/GPU

Resolution (``resolve_mode``): ``REPRO_DISABLE_PALLAS=1`` forces "ref"
everywhere; on TPU the default is "pallas"; elsewhere the default is
"ref" unless ``REPRO_PALLAS_INTERPRET=1`` opts into interpreter-mode
validation.  Every op also takes an explicit ``mode=`` so jitted
callers (the round engine) can resolve once per call and key their jit
cache on it instead of re-reading the environment at trace time.

Every op works on the wire layout (bf16 vectors, uint32 mask words;
see ``repro.kernels.bitpack``).  The dense reference semantics the ops
are tested against live in ``repro.core.aggregation`` and
``repro.core.unify``.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import bitpack, ref
from repro.kernels.fused_unify import (fused_unify_packed_pallas,
                                       fused_unify_packed_rows_pallas)
from repro.kernels.masked_agg import masked_agg_batched_packed_rows_pallas
from repro.kernels.modulated_matmul import (modulated_matmul_pallas,
                                            routed_matmul_pallas)
from repro.kernels.sign_sim import sign_sim_packed_pallas

MODES = ("pallas", "pallas_interpret", "ref")


def resolve_mode() -> str:
    """Pick the dispatch mode for the current process/backend."""
    if os.environ.get("REPRO_DISABLE_PALLAS", "0") == "1":
        return "ref"
    if jax.default_backend() == "tpu":
        return "pallas"
    if os.environ.get("REPRO_PALLAS_INTERPRET", "0") == "1":
        return "pallas_interpret"
    return "ref"


def _norm(mode: Optional[str]) -> str:
    mode = mode or resolve_mode()
    if mode not in MODES:
        raise ValueError(f"unknown kernel dispatch mode {mode!r}; "
                         f"expected one of {MODES}")
    return mode


def fused_unify_raw(task_vectors: jax.Array, valid: jax.Array, *,
                    mode: Optional[str] = None):
    """Division-free core of :func:`fused_unify_packed`: returns
    (unified, mask words, num, den) with the λ division left to the
    caller — the hook the sharded engine needs to ``psum`` the
    per-shard λ partial sums before dividing."""
    mode = _norm(mode)
    if mode == "ref":
        return ref.fused_unify_packed_ref(task_vectors, valid)
    return fused_unify_packed_pallas(
        task_vectors, valid, interpret=(mode == "pallas_interpret"))


def pack_masks(masks: jax.Array, *, mode: Optional[str] = None) -> jax.Array:
    """(..., d) bool -> (..., ceil(d/32)) uint32, LSB-first — THE wire
    layout (see ``repro.kernels.bitpack`` for the bit convention).
    Identical in every dispatch mode: packing is pure elementwise bit
    algebra, already optimal under XLA."""
    _norm(mode)
    return bitpack.pack_bits(masks)


def unpack_masks(words: jax.Array, d: int, *,
                 mode: Optional[str] = None) -> jax.Array:
    """Inverse of :func:`pack_masks` — the ONLY sanctioned route back to
    dense bool masks.  Test/diagnostic helper: the round path computes
    on packed words directly and never calls this."""
    _norm(mode)
    return bitpack.unpack_bits(words, d)


def fused_unify_packed(task_vectors: jax.Array, valid: jax.Array, *,
                       eps: float = 1e-12, mode: Optional[str] = None):
    """Batched unify + task-mask + λ-scaler over slot-packed clients,
    emitting the uplink wire tensors.

    task_vectors (B, K, d) fp32/bf16; valid (B, K) bool.  Returns
    (unified (B, d) bf16, mask_words (B, K, ceil(d/32)) uint32,
    lams (B, K) fp32) — row b equals
    ``unify_with_modulators(task_vectors[b, valid[b]])`` on the valid
    slots, the unified vector rounded to bf16 after the mask bits and λ
    were decided on its fp32 value; invalid slots give zero mask rows
    and λ = 0.  λ accumulates over the Pallas kernels' 4096-wide tiles
    and the ref path's ``CHUNK_D`` chunks, so the modes agree on it to
    fp32 accumulation tolerance.
    """
    uni, words, num, den = fused_unify_raw(task_vectors, valid, mode=mode)
    lams = num / jnp.maximum(den, eps)
    return uni, words, lams


def masked_agg_batched_packed(unified, slot_mask_words, slot_lams, slot_sizes,
                              slot_valid, slot_tasks, n_tasks: int, d: int,
                              *, rho: float = 0.4,
                              mode: Optional[str] = None):
    """Whole-round Eq. 3 + Eq. 4 over the wire slot layout: unified
    (N, d) bf16 (fp32 tolerated), slot_mask_words (N, K, ceil(d/32))
    uint32, per-slot scalars (N, K) as :func:`matu_round_slots_packed`.
    Returns (tau_hats (T, d), alpha_num (T, d)) — m̂ is derivable as
    ``where(alpha_num/max(N_t,1) >= rho, 1, ·)``.  The kernel folds each
    member slot into its task straight from the slot words.  Pallas
    modes only: the "ref" round streams Eq. 3–4 inside
    ``ref.matu_round_slots_packed_ref``."""
    mode = _norm(mode)
    if mode == "ref":
        raise ValueError("masked_agg_batched_packed has no 'ref' dispatch; "
                         "the ref round streams Eq. 3-4 itself")
    return masked_agg_batched_packed_rows_pallas(
        unified, slot_mask_words,
        _slot_gammas(slot_sizes, slot_valid, slot_tasks, n_tasks) * slot_lams,
        slot_tasks, slot_valid, n_tasks=n_tasks, rho=rho,
        interpret=(mode == "pallas_interpret"))


def sign_sim_packed(pos: jax.Array, nz: jax.Array, d: int, *,
                    mode: Optional[str] = None) -> jax.Array:
    """Eq. 5 similarity from packed sign bit-planes (popcount form);
    ``d`` is the unpacked feature count for the 1/d normalisation."""
    mode = _norm(mode)
    if mode == "ref":
        dots = bitpack.packed_sign_dots(pos, nz).astype(jnp.float32)
    else:
        dots = sign_sim_packed_pallas(
            pos, nz, interpret=(mode == "pallas_interpret"))
    return 0.5 * (dots / d + 1.0)


def modulated_matmul(x: jax.Array, base: jax.Array, tau: jax.Array,
                     words: jax.Array, lam: jax.Array, *,
                     mode: Optional[str] = None) -> jax.Array:
    """Serving: per-request modulated LoRA matmul,
    ``y_b = x_b @ (base + lam_b · m_b ⊙ tau)`` with the modulator mask
    kept bit-packed until VMEM (fused word-unpack + λ-scale + matmul —
    no per-request effective weight in HBM).

    x (B, S, K); base/tau (K, N) in the adapter leaf dtype; words
    (B, ceil(K·N/32)) uint32 row-major (K, N) mask bits in the LSB-first
    wire layout; lam (B,) fp32.  Returns (B, S, N) in
    ``result_type(x, base)``.  ``K · N`` must be word-aligned
    (% 32 == 0) — the serve router only routes qualifying leaves here.
    The "ref" dispatch is the unpack-then-matmul oracle; all modes are
    bit-identical (see tests/test_serve_multitenant.py).
    """
    mode = _norm(mode)
    k, n = base.shape
    if (k * n) % 32:
        raise ValueError(f"modulated_matmul needs a word-aligned leaf "
                         f"(K*N % 32 == 0), got {(k, n)}")
    if mode == "ref":
        return ref.modulated_matmul_ref(x, base, tau, words, lam)
    return modulated_matmul_pallas(x, base, tau, words, lam,
                                   interpret=(mode == "pallas_interpret"))


def routed_matmul(x: jax.Array, w: jax.Array, *,
                  mode: Optional[str] = None) -> jax.Array:
    """Serving: per-request LoRA matmul over materialised weights,
    ``y_b = x_b @ w_b`` — the dense-routed form of
    :func:`modulated_matmul`.  x (B, S, K); w (B, K, N).  The kernel
    modes contract exactly as the fused kernel does, so the two routed
    forms agree bitwise on the chip; "ref" is the batched einsum.
    """
    mode = _norm(mode)
    if mode == "ref":
        return jnp.einsum("bsk,bkn->bsn", x, w)
    return routed_matmul_pallas(x, w, interpret=(mode == "pallas_interpret"))


def _slot_gammas(slot_sizes, slot_valid, slot_tasks, n_tasks: int):
    """Per-slot γ: a valid slot's size over its task's total (0 for
    invalid slots; the sentinel task id gathers its own total)."""
    n, k = slot_tasks.shape
    ids = slot_tasks.reshape(n * k)
    sizes = slot_sizes.reshape(n * k).astype(jnp.float32) * slot_valid.reshape(
        n * k)
    totals = jax.ops.segment_sum(sizes, ids, num_segments=n_tasks + 1)
    return (sizes / jnp.maximum(totals[ids], 1e-12)).reshape(n, k)


def _apply_slot_weights(slot_lams, slot_sizes, slot_weights):
    """Staleness-discount pre-scaling (async rounds): per-slot weights
    w ∈ (0, 1] scale both the modulator λ (the slot's reconstructed
    vector shrinks toward zero) and the γ size weight (the slot loses
    share in the Eq. 3 normalization) BEFORE the weighted values enter
    the masked-agg / λ block-partial kernels — so no kernel needs a new
    operand.  ``w = 1`` is bitwise exact (IEEE multiply by 1.0), which
    is what keeps the zero-staleness async round bit-identical to the
    sync one."""
    if slot_weights is None:
        return slot_lams, slot_sizes
    w = slot_weights.astype(jnp.float32)
    lams = None if slot_lams is None else slot_lams * w
    return lams, slot_sizes * w


def _round_slots_pallas(unified, slot_mask_words, slot_lams, slot_sizes,
                        slot_valid, slot_tasks, n_tasks, d, *, rho, eps,
                        kappa, cross_task, uniform_cross, mode,
                        axis_name=None, d_norm=0):
    """Kernel-path round: compose the Eq. 3–4 kernel, the popcount
    sign-sim and the row-reading fused-unify kernels over the wire slot
    layout.  The Eq. 3–4 kernel reads each d-block of the (N, d)
    unified vectors and of the (N, K, d/32) slot words once and folds
    every member slot into its task on the MXU, so no dense per-task
    mask tensor is made.  The mask stays 1 bit/element in HBM
    end to end; words are expanded to lanes only inside VMEM tiles.
    With ``axis_name`` set this is a ``shard_map`` body on the local
    d-slice: the popcount dots (exact integers) and the λ num/den
    partial sums are psum'd."""
    interp = (mode == "pallas_interpret")
    tau_hats, a_num = masked_agg_batched_packed(
        unified, slot_mask_words, slot_lams, slot_sizes, slot_valid,
        slot_tasks, n_tasks, d, rho=rho, mode=mode)
    n_t = jax.ops.segment_sum(
        slot_valid.reshape(-1).astype(jnp.float32), slot_tasks.reshape(-1),
        num_segments=n_tasks + 1)[:n_tasks]
    held = n_t > 0
    heldf = held.astype(jnp.float32)
    alpha = a_num / jnp.maximum(n_t, 1.0)[:, None]
    m_hats = jnp.where(alpha >= rho, 1.0, alpha)

    pos, nz = bitpack.sign_planes(tau_hats)
    dots = sign_sim_packed_pallas(pos, nz, interpret=interp)
    if axis_name is not None:
        dots = jax.lax.psum(dots, axis_name)
    sim = (0.5 * (dots / (d_norm or d) + 1.0)
           * heldf[None, :] * heldf[:, None])
    weights = ref.cross_weights_ref(sim, held, eps=eps, kappa=kappa,
                                    cross_task=cross_task,
                                    uniform_cross=uniform_cross)
    task_vectors, _tau_tildes = ref.cross_task_combine_ref(tau_hats, m_hats,
                                                           weights)
    # each client's slot rows are read straight from the task vectors
    # (sentinel slot ids clamped; the valid mask zeroes their output)
    uni, dwords, num, den = fused_unify_packed_rows_pallas(
        task_vectors, slot_tasks, slot_valid, interpret=interp)
    if axis_name is not None:
        num, den = jax.lax.psum((num, den), axis_name)
    a_u8 = a_num.astype(ref.alpha_dtype(slot_valid.shape[0]))
    return (task_vectors, tau_hats, a_u8, n_t, sim, uni, dwords, num, den)


def matu_round_slots_packed(unified, slot_mask_words, slot_lams, slot_sizes,
                            slot_valid, slot_tasks, n_tasks: int, d: int, *,
                            rho: float = 0.4, eps: float = 0.5,
                            kappa: int = 3, cross_task: bool = True,
                            uniform_cross: bool = False,
                            lam_eps: float = 1e-12,
                            mode: Optional[str] = None,
                            slot_weights=None,
                            axis_name=None, axis_sizes=(), d_norm: int = 0):
    """The full MaTU server round (Eq. 3–7 + downlink re-unification)
    over wire-format slot uploads — the single entry point of
    :class:`repro.core.engine.RoundEngine`'s monolithic round.

    Layout: ``unified`` (N, d) bf16 (fp32 tolerated), ``slot_mask_words``
    (N, K, ceil(d/32)) uint32 bit-packed masks (LSB-first, zero tail
    bits — see ``repro.kernels.bitpack``); ``slot_lams`` /
    ``slot_sizes`` / ``slot_valid`` (N, K); ``slot_tasks`` (N, K) int32
    with the sentinel ``n_tasks`` in invalid slots.  ``d`` is static
    (the word axis cannot express it).

    "ref" runs the two-pass cache-blocked streaming round (O(Σk_n · d)
    work, d-chunked so accumulators stay cache-resident); the Pallas
    modes compose the kernels over the slot layout as it is
    (:func:`_round_slots_pallas`).  Returns (task_vectors fp32,
    tau_hats fp32, alpha_num uint8, n_held, similarity, down_unified bf16,
    down_mask_words uint32, down_lams) — m̂ is re-derivable from
    (alpha_num, n_held, ρ) and never materialised in fp32 on the hot
    path; τ̃ as before is (2τ − τ̂) on rows with donors.

    ``axis_name`` / ``axis_sizes`` / ``d_norm`` make the op a
    ``shard_map`` body over the taskvec axis — ``d`` is then the LOCAL
    unpacked count of this shard's slice (a multiple of 32; see the
    engine's sharding contract) and ``d_norm`` the global one.

    ``slot_weights`` (optional, (N, K) fp32) is the async staleness
    discount — see :func:`_apply_slot_weights`.
    """
    mode = _norm(mode)
    slot_lams, slot_sizes = _apply_slot_weights(slot_lams, slot_sizes,
                                                slot_weights)
    kw = dict(rho=rho, eps=eps, kappa=kappa, cross_task=cross_task,
              uniform_cross=uniform_cross)
    if mode == "ref":
        out = ref.matu_round_slots_packed_ref(
            unified, slot_mask_words, slot_lams, slot_sizes, slot_valid,
            slot_tasks, n_tasks, d, axis_name=axis_name,
            axis_sizes=axis_sizes, d_norm=d_norm, **kw)
    else:
        out = _round_slots_pallas(
            unified, slot_mask_words, slot_lams, slot_sizes, slot_valid,
            slot_tasks, n_tasks, d, mode=mode, axis_name=axis_name,
            d_norm=d_norm, **kw)
    (task_vectors, tau_hats, alpha_num, n_held, sim,
     down_unified, down_mask_words, num, den) = out
    down_lams = num / jnp.maximum(den, lam_eps)
    return (task_vectors, tau_hats, alpha_num, n_held, sim,
            down_unified, down_mask_words, down_lams)


# ---------------------------------------------------------------------------
# Chunked-slot hierarchical aggregation (client-axis streaming round).
#
# Every dispatch mode routes to the streaming jnp implementation in
# ``repro.kernels.ref`` — the chunk folds are scatter-adds and
# cache-blocked elementwise sweeps that XLA already emits optimally,
# and the chunk-count-invariance contract (chunked ≡ monolithic
# bitwise in ref mode) is defined against that implementation.  The
# Pallas kernels remain the monolithic round's accelerated path.
# ---------------------------------------------------------------------------


def matu_chunk_scalars(slot_sizes, slot_valid, slot_tasks, totals_acc,
                       nt_acc, *, slot_weights=None,
                       mode: Optional[str] = None):
    """Phase A of the chunked round: fold one chunk's per-task size
    totals (γ normaliser) and membership counts (Eq. 3 N_t) into the
    carried (T+1,) accumulators.  ``slot_weights`` applies the async
    staleness discount to the sizes exactly as the monolithic round
    does (:func:`_apply_slot_weights`)."""
    _norm(mode)
    _, slot_sizes = _apply_slot_weights(None, slot_sizes, slot_weights)
    return ref.matu_chunk_scalars_ref(slot_sizes, slot_valid, slot_tasks,
                                      totals_acc, nt_acc)


def matu_merge_chunk_packed(unified, slot_mask_words, slot_lams, slot_sizes,
                            slot_valid, slot_tasks, totals, a_acc, tau_acc,
                            d: int, *, slot_weights=None,
                            mode: Optional[str] = None):
    """Phase B of the chunked round: fold one client chunk's Eq. 3 sign
    votes and Eq. 4 merge partials into the carried accumulators
    (``totals`` from phase A): ``a_acc`` is (T+1, dp) int32 (exact sign
    votes), ``tau_acc`` (T+1, dp) fp32; ``d`` is static (local count
    under ``shard_map``)."""
    _norm(mode)
    slot_lams, slot_sizes = _apply_slot_weights(slot_lams, slot_sizes,
                                                slot_weights)
    return ref.matu_merge_chunk_packed_ref(unified, slot_mask_words,
                                           slot_lams, slot_sizes, slot_valid,
                                           slot_tasks, totals, a_acc,
                                           tau_acc, d=d)


def matu_finish_packed(a_acc, tau_acc, nt_acc, n_clients: int, *,
                       n_tasks: int, d: int, rho: float = 0.4,
                       eps: float = 0.5, kappa: int = 3,
                       cross_task: bool = True, uniform_cross: bool = False,
                       mode: Optional[str] = None,
                       axis_name=None, axis_sizes=(), d_norm: int = 0):
    """Finish the chunked round from the accumulators: returns
    (task_vectors, tau_hats, alpha_num, n_t, similarity, num_t).
    ``n_clients`` is the round's total client count (it picks the
    monolithic ``alpha_dtype``)."""
    _norm(mode)
    return ref.matu_finish_packed_ref(a_acc, tau_acc, nt_acc, n_clients,
                                      n_tasks=n_tasks, d=d, rho=rho, eps=eps,
                                      kappa=kappa, cross_task=cross_task,
                                      uniform_cross=uniform_cross,
                                      axis_name=axis_name,
                                      axis_sizes=axis_sizes, d_norm=d_norm)


def matu_downlink_chunk_packed(task_vectors, slot_tasks, num_t, d: int, *,
                               lam_eps: float = 1e-12,
                               mode: Optional[str] = None,
                               axis_name=None, axis_sizes=()):
    """Phase C: downlink re-unification of one client chunk from the
    finished task vectors.  Returns (down_unified (C, d) bf16,
    down_mask_words (C, K, ceil(d/32)) uint32, down_lams (C, K)) — the
    λ division is the monolithic round's ``num / max(den, lam_eps)``."""
    _norm(mode)
    uni, dwords, num, den = ref.matu_downlink_chunk_packed_ref(
        task_vectors, slot_tasks, num_t, d=d,
        axis_name=axis_name, axis_sizes=axis_sizes)
    down_lams = num / jnp.maximum(den, lam_eps)
    return uni, dwords, down_lams
