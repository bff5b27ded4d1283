"""Batched, kernel-backed MaTU round engine (paper §3.2, Eq. 3–7).

One jit-compiled pipeline replaces the three divergent server paths the
repo used to carry (the Python-loop ``MaTUServer.round``, the dense
``matu_round`` reference, and the unused Pallas kernels):

  pack  →  Eq. 3+4 batched agreement/merge  →  Eq. 5 sign similarity
        →  Eq. 6+7 cross-task transfer      →  batched downlink
           re-unification (fused unify + mask + λ kernel)

All tensor math dispatches through
:func:`repro.kernels.ops.matu_round_slots_packed` (packed Pallas
kernels on TPU; the two-pass cache-blocked packed streaming round on
CPU/GPU); ``matu_round`` in :mod:`repro.core.aggregation` remains the
dense reference semantics the engine is tested against.

Padding contract
----------------
A round's ragged ``List[ClientUpload]`` is packed into fixed-shape
*slot* tensors so participation sampling keeps a static jit signature:

* client axis: padded to ``n_max`` (next power of two ≥ N by default);
  padding rows have all-invalid slots, so they drop out of every
  reduction.
* slot axis: each client's held tasks occupy the first k_n of
  ``k_max`` slots (next power of two ≥ max k_n); invalid slots carry
  zero masks/λ/sizes and the sentinel task id T.  Per-task reductions
  are segment-sums keyed by slot task id — the sentinel bucket (index
  T of T+1 segments) swallows all padding; downlink gathers clamp the
  sentinel and the slot-valid mask zeroes its output.
* task axis: always the full registry size T.  Tasks with no member
  this round produce τ̂ = 0, m̂ = 0 (``matu_round`` semantics — the
  legacy server reported m̂ = 1 for unheld tasks, which is unobservable
  downstream) and are masked out of the similarity matrix so
  cross-task transfer never mixes in zero vectors.

Wire format
-----------
The slot tensors ARE the uplink/downlink wire format — what the engine
holds in memory is byte-identical to what a client transmits, so
communication accounting is measured off the buffers rather than
simulated:

* **masks** travel bit-packed: ``uint32`` words of shape
  ``(n_max, k_max, ceil(d/32))``, 32 mask bits per word, **LSB-first**
  (element j of a d-length mask is bit ``j % 32`` of word ``j // 32``;
  see ``repro.kernels.bitpack`` for the single definition).  Tail bits
  of the last word — elements ``d .. 32*ceil(d/32)`` — are always
  zero; producers enforce it and popcount consumers rely on it.
* **unified / task vectors** travel bf16 (``jnp.bfloat16`` storage);
  all round *compute* is fp32 — kernels upcast one cache/VMEM tile at
  a time, and every sign-derived quantity (modulator mask bits, m̂,
  similarity) plus λ num/den is computed from fp32 values *before* the
  outgoing bf16 rounding.  Consequently packed↔bool parity is exact
  on identical (already bf16-quantised) inputs: masks, m̂, and
  similarity are bit-identical in every mode (per-coordinate
  decisions, independent of tile/chunk grouping), bf16 vector outputs
  are the bf16 rounding of the fp32 ones, and λs are bit-identical on
  the streaming ref round (same CHUNK_D accumulation grouping as the
  bool round).  On the Pallas paths the packed kernels tile d at 4096
  (128 uint32 lanes) vs the bool kernels' 2048, so the λ num/den
  partial sums group differently across tiles — λ agrees to fp32
  accumulation tolerance (~1e-6 relative) there, not bitwise.
* **m̂** is not part of the wire and is not materialised in fp32:
  the engine carries the Eq. 3 agreement numerator (an exact integer
  ≤ N_t) at one byte per coordinate and re-derives
  m̂ = 1[α ≥ ρ] ∨ α on demand (``EngineOutput.m_hats``).
* λ / sizes stay fp32 scalars (k per client, 32 bits each on the
  paper's accounting).

Task-vector layout contract
---------------------------
The engine never sees a model: the d-axis it merges coordinate-by-
coordinate is DEFINED upstream by each backbone's
:class:`~repro.common.tree.TaskVectorSpace` manifest (LoRA delta
leaves in canonical tree order, each raveled C-order into a contiguous
``[offset, offset + size)`` slice).  That makes layout agreement a
precondition, not a property the engine can check numerically — so it
is enforced at the edges: the manifest ``fingerprint`` rides every
upload, and the strategy layer refuses to aggregate
(``TaskVectorLayoutError``) when a client's fingerprint disagrees with
the server's expectation for any task it holds.  Mixed-architecture
rounds zero-pad every client's vector to a common d that is a multiple
of 256 coordinates (``8 × bitpack.WORD_BITS`` = one ``LAMBDA_BLOCK``),
so shorter manifests end exactly on a packed-word AND λ-block
boundary: pad coordinates are zero in every row, contribute nothing to
any reduction, and the packed/bool parity guarantees above carry over
to padded rounds unchanged.

The bool/fp32 slot layout is retained behind ``pack_uploads(...,
packed=False)`` as the A/B baseline and parity oracle
(``benchmarks/bench_round_engine.py`` measures both).

**Entropy-coded layer (optional, host edge only).**  On top of the
packed words sits an invertible Golomb-Rice coder
(:mod:`repro.fed.compression`): each mask row becomes one
self-describing record — a 5-byte header (polarity bit, raw-escape
bit, 5-bit Rice parameter, uint32 run count) followed by the Rice
payload (unary quotients then fixed-width remainders, LSB-first,
byte-padded), or the raw packed words verbatim when Rice would expand
(so coded ≤ raw + header at any density).  Decode needs only ``d`` and
the bytes.  The coded layer never enters the jitted round:
``pack_uploads`` decodes coded (uint8) uploads into slot words at the
host edge, and ``RoundEngine.downlinks(code_masks=True)`` encodes the
downlink rows back to streams; biased modulator masks (P(1) ≈ 0.75 on
own tasks) go out at ~0.82 bits/coord, measured off the actual byte
streams.  ``code_masks=False`` (default) keeps the raw packed wire as
the A/B toggle.

The slot layout keeps the packed footprint and the round's work at
O(Σ k_n · d) — the same asymptotics as the legacy ragged loop — while
the dense (N, T, ·) tensors the Pallas kernels and ``matu_round``
consume are derived on demand (``PackedRound.dense_tensors`` /
scatter inside the kernel path).

The jit cache is keyed on (shape signature, dispatch mode, d); the
mode is resolved from the environment once per call (see
``ops.resolve_mode``) so ``REPRO_DISABLE_PALLAS`` /
``REPRO_PALLAS_INTERPRET`` A/B checks never collide in the cache.

Host pipeline
-------------
``RoundEngine.round_stream`` runs a sequence of rounds through a
two-deep host/device pipeline: while round r's jitted step executes on
the device (jax dispatch is asynchronous), the host finishes round
r−1 (block → batched downlink encode → yield) and then packs/decodes
round r+1's uploads.  The contract:

* **buffer ownership** — ``pack_uploads`` stages its big host tensors
  (unified, slot_masks) in a :class:`SlotStage`; the pipeline
  alternates TWO stages, so the stage refilled for round r+1 is the
  one round r−1 used — and round r−1 was explicitly blocked
  (``jax.block_until_ready`` on its whole ``EngineOutput``) before
  that refill begins.  A staging buffer is therefore never written
  while a device step that may alias it (CPU ``jnp.asarray`` can be
  zero-copy) is in flight.  Fresh (non-staged) allocations — the small
  per-slot tensors, and everything in the ``pipeline=False`` path —
  need no discipline: they are never reused.
* **block_until_ready** — the ONLY sync points are the per-round drain
  (block on round r−1's outputs before encoding its downlinks) and
  the implicit ``np.asarray`` of downlink tensors inside
  ``downlinks``.  Dispatch order on a single device serialises the
  steps, so draining r−1 after dispatching r leaves the device busy
  throughout.
* **escape hatch** — ``pipeline=False`` runs pack → block → downlink
  strictly sequentially with fresh buffers.  Both paths execute the
  identical numpy/XLA computations in a different order, so pipelined
  rounds are **bit-identical** to sequential ones (the A/B contract
  tests/test_pipeline.py enforces, mirroring the sharded ≡
  single-device contract above).
* **timings** — each yielded round carries a ``phase_us`` dict
  (``pack`` / ``decode`` / ``encode`` / ``device`` microseconds;
  ``device`` is dispatch→ready wall, which under the pipeline
  overlaps the host phases of its neighbours).  ``pack`` / ``decode``
  / ``encode`` are the self times of the ``repro.common.trace`` spans
  of the same names, which a profiler trace also records.

``round_stream`` pulls upload round r+1 before yielding round r, so
the input iterable must not depend on the previous round's downlinks —
replay/bench traffic qualifies; the simulator's closed training loop
instead pipelines via the strategy's deferred drain
(``MaTUStrategy(pipeline=True)``), which overlaps the dispatched round
with the simulator's own bookkeeping under the same blocking contract.

Sharding contract
-----------------
With a mesh, one engine call runs distributed over the ``taskvec``
logical axis (``repro.nn.sharding``: d shards over every mesh axis the
rule names — ("pod", "data", "model") on the production pods, all 8
host devices on the CI debug mesh):

* **layout** — every d-axis tensor (``unified``, ``slot_masks``,
  ``down_unified``, ``down_masks``, τ̂/τ/α) splits on its LAST axis
  into ``n_shards`` contiguous slices; per-slot scalars (λ, sizes,
  task ids, validity) are replicated.  ``pack_uploads`` /
  ``pack_from_slots`` / ``batched_client_unify`` place the buffers
  with the matching ``NamedSharding`` at the wire boundary, so the
  round never reshards.
* **padding** — d is zero-padded to ``pad_d_for_shards(d, n_shards)``:
  each shard holds a power-of-two multiple of 256 coords.  256 coords
  = 8 uint32 words (``bitpack.WORD_BITS`` — packed mask words are
  never split mid-word, the wire layout stays the single source of
  truth) and one λ reduction block (``ref.LAMBDA_BLOCK``).  Padded
  coords carry zero masks/vectors and drop out of every reduction;
  outputs are sliced back to d.
* **collectives** — ``_round_impl`` runs ``ops.matu_round_slots`` /
  ``_packed`` under ``shard_map``; per-coordinate math (Eq. 3, 4, 6, 7
  and the downlink re-unification) never crosses shards.  Exactly two
  reductions do: one integer psum of the Eq. 5 (T, T) popcount dots
  (exact under any order), and one psum of the λ numerator/denominator
  block-tree roots (``ref._lam_totals``).  Everything derived from the
  per-client scalars (γ, N_t, held) is computed replicated.  No
  all-gather / all-to-all / reduce-scatter appears in the round HLO.
* **parity** — the λ reductions run on a fixed 256-coord block grid
  combined by a shard-count-invariant binary tree, so the sharded
  round is **bit-identical** to the single-device round in "ref" mode
  for both the packed and bool layouts (power-of-two shard counts).
  On the Pallas paths masks/m̂/similarity stay bit-identical and λ
  agrees to fp32 accumulation tolerance (the PR 2 tile caveat).

Population-scale contract
-------------------------
``RoundEngine.round_chunked`` streams a round of N uploads through a
fixed-shape chunk buffer of C clients, so a round's memory is
**O(chunk + T·d), independent of N** — the client-axis twin of the
d-sharding above.  The Eq. 3/4 agreement numerators (integer sign
votes), Eq. 5 popcount dot partials, per-task size totals, and the λ
num/den block partials are all associative folds, split into four
phases (``repro.kernels.ref``, chunked section):

* **phase A** (scalars): per-task size totals + membership counts fold
  into (T+1,) accumulators — the Eq. 4 γ normaliser needs the *global*
  totals before any merge work, which is why the engine makes two
  passes over the upload stream (``uploads`` may be a zero-arg
  callable returning a fresh iterator — the population simulator
  re-derives sampled clients on demand and never materialises the
  round).
* **phase B** (merge): each chunk packs into the SAME slot layout as
  the monolithic round (one ``SlotStage``, blocked before refill) and
  folds sign votes + γλ-weighted merge partials into carried
  (T+1, dp) accumulators via one jitted chunk step reused across
  chunks (the last chunk is padded — same static signature, padding
  rows carry the sentinel task id so their contributions land in the
  swallowed (T+1)-th segment).
* **finish**: Eq. 3 α/m̂, Eq. 5 dots, Eq. 6 weights, Eq. 7 combine and
  the λ numerator from the accumulators alone — no slot tensor in
  sight.
* **phase C** (downlink): per chunk, re-unification from the finished
  task vectors; each slot row lives in exactly one chunk, so this is
  embarrassingly parallel over rows.  ``sink`` streams each chunk's
  ``ClientDownlink``s out instead of holding N of them.

**Chunk-count invariance** (the bit-identity rule, extending PR 3's
shard-count-invariant λ tree): every fp32 client-axis reduction is ONE
global sequential scatter fold — the carried ``acc.at[ids].add``
applies the same adds in the same global row order as the monolithic
round's whole-round segment-sum, for ANY contiguous chunking; the
integer votes/dots are order-free; and every d-axis reduction keeps
the monolithic grid (``CHUNK_D`` streaming blocks, ``LAMBDA_BLOCK`` λ
tree).  Hence chunked ≡ monolithic **bit for bit** in ref mode for
both layouts — masks, λ, vectors, and the measured wire bits
(tests/test_chunked_engine.py), for chunk sizes 1, non-divisors of N,
and > N alike.

**2-D (slots × taskvec) mesh**: on a ``make_population_mesh`` the
"slots" axis shards the chunk's client/slot rows in phase C (and the
ingest buffers ride along) while the taskvec axes keep sharding d;
phase B never splits the client fold across devices (that would change
the fp32 accumulation order) — each shard folds every row of its
d-slice locally, so the merge step has NO collectives and the whole
round keeps the monolithic collective budget: one integer dots psum +
one λ-num roots psum in the finish, plus one λ-den roots psum per
chunk in phase C.

Async & fault model
-------------------
The engine itself is stateless per round and keyed by ``(n_max, k_max,
d, mode)`` — exactly what a buffered async server needs: the admission
queue (``repro.fed.systems.AdmissionQueue``) drains whatever has
arrived by the current tick into the SAME fixed-shape slot tensors, so
the jit caches reuse across ticks regardless of which clients made it.

* **staleness discount** — a buffered upload dispatched at round q and
  folded at round r carries staleness ``s = r − q``; its slots get the
  weight ``w = δ**s`` (``δ = STALENESS_DISCOUNT``), attached as
  ``PackedRound.slot_weights`` and applied inside the jitted round as
  ``λ·w`` and ``size·w`` before the Eq. 3 masked-agg / λ block
  partials (``ops._apply_slot_weights``).  Discounting the λ shrinks
  the stale slot's reconstructed vector; discounting the size shrinks
  its share of the γ normalization — fresh uploads win both ways.
* **sync ≡ async equivalence** — with an always-available, zero-
  latency, zero-fault trace (``ClientSystems.ideal``) every upload has
  ``s = 0`` so ``w = 1``; the weighted trace multiplies by 1.0 (exact
  under IEEE 754) and the drain order equals the sync selection order,
  so the async round is **bit-identical** to the sync one — unified
  vectors, λ, masks, and the measured History bits
  (tests/test_async_fed.py).  ``slot_weights=None`` (every synchronous
  caller) never traces the multiply at all.
* **fault injection & quarantine** — corrupted coded uploads are the
  wire's problem, not the engine's: the async strategy validates each
  client's stream (CRC frame + entropy decode,
  ``repro.fed.systems.wrap_stream`` / ``CodedStreamError``) BEFORE
  packing and simply leaves quarantined clients out of the batch; the
  engine never sees malformed bytes.  Empty rounds (everyone dropped)
  never reach ``pack_uploads`` — the simulator skips-and-carries.
* **dark tasks** — a task with no admitted member this round produces
  τ̂ = 0 and a zeroed similarity row (the padding contract above);
  the async strategy carries last-seen per-task vectors and decays
  them toward the unified vector instead of evaluating the zeros (see
  ``AsyncMaTUStrategy``).
"""

from __future__ import annotations

import functools
import itertools
import time
from dataclasses import dataclass
from typing import (Callable, Dict, Iterable, List, NamedTuple, Optional,
                    Sequence, Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.trace import span
from repro.core.aggregation import EPS_DEFAULT, KAPPA_DEFAULT, RHO_DEFAULT
from repro.core.client import ClientDownlink, ClientUpload
from repro.kernels import bitpack, ops
from repro.kernels.ref import CHUNK_D, LAMBDA_BLOCK, _chunked, _next_pow2
from repro.nn.sharding import slot_axes, taskvec_axes, taskvec_sharding

# default async staleness discount δ: a buffered upload folded s rounds
# after dispatch enters Eq. 3 with weight δ**s (see "Async & fault
# model" in the module docstring); δ**0 = 1 keeps fresh uploads exact.
STALENESS_DISCOUNT = 0.5


@dataclass(frozen=True)
class EngineConfig:
    n_tasks: int
    rho: float = RHO_DEFAULT
    eps: float = EPS_DEFAULT
    kappa: int = KAPPA_DEFAULT
    cross_task: bool = True
    uniform_cross: bool = False


@dataclass
class PackedRound:
    """Fixed-shape slot tensors for one round + host-side metadata.

    In the default wire layout ``unified`` is bf16 and ``slot_masks``
    holds bit-packed uint32 words (``packed`` is True); the legacy
    bool/fp32 layout (``pack_uploads(..., packed=False)``) is kept for
    A/B benchmarks and parity tests.
    """
    client_ids: List[int]            # actual clients, row order
    task_ids: List[List[int]]        # per client, slot order
    unified: jax.Array               # (n_max, d) bf16 (wire) | fp32 (bool A/B)
    slot_masks: jax.Array            # (n_max, k_max, ceil(d/32)) uint32 | (…, d) bool
    slot_lams: jax.Array             # (n_max, k_max) fp32
    slot_sizes: jax.Array            # (n_max, k_max) fp32
    slot_tasks: jax.Array            # (n_max, k_max) int32; T = invalid sentinel
    slot_valid: jax.Array            # (n_max, k_max) bool
    n_tasks: int
    d: int                           # unpacked feature count (static)
    # d after the taskvec-shard padding (pad_d_for_shards); equals d
    # when packed without a mesh.  The d-axis tensors above carry THIS
    # width; wire accounting and output slicing use the true ``d``.
    d_pad: Optional[int] = None
    # per-slot staleness-discount weights (n_max, k_max) fp32, or None
    # for the synchronous (all-fresh) round.  Applied inside the jitted
    # round as λ·w and size·w before the Eq. 3 / λ block partials (see
    # ``ops._apply_slot_weights``); w ≡ 1 is bitwise identical to None.
    slot_weights: Optional[jax.Array] = None

    @property
    def n_clients(self) -> int:
        return len(self.client_ids)

    @property
    def padded_d(self) -> int:
        return self.d_pad or self.d

    @property
    def packed(self) -> bool:
        """True when the slot tensors are in the wire layout."""
        return self.slot_masks.dtype == jnp.uint32

    def wire_bits(self) -> int:
        """Measured uplink size of the real (non-padding) slots: the
        bits actually occupied by this round's wire buffers (bf16
        unified + packed mask words + fp32 λ per slot).  For the bool
        A/B layout this reports the paper's fp32+dense-bit accounting
        (32d + k(d+32)) — the scheme those buffers implement."""
        from repro.core.client import paper_link_bits
        total = 0
        for tasks in self.task_ids:
            k = len(tasks)
            if self.packed:
                total += bitpack.wire_bits(
                    self.d, k,
                    vec_bytes_per_elem=self.unified.dtype.itemsize)
            else:
                total += paper_link_bits(self.d, k)
        return total

    def dense_tensors(self):
        """Scatter to the dense per-task layout ``matu_round`` consumes:
        (masks (N, T, d) bool, lams (N, T), member (N, T), sizes (N, T)).
        Test/diagnostic helper — the hot path never materialises this
        on CPU.  Delegates to the single slot→dense contract in
        :func:`repro.kernels.ops.slots_to_dense` (packed masks go
        through the one sanctioned ``ops.unpack_masks`` route)."""
        masks = (ops.unpack_masks(self.slot_masks, self.d)
                 if self.packed else self.slot_masks)
        return ops.slots_to_dense(masks, self.slot_lams,
                                  self.slot_sizes, self.slot_valid,
                                  self.slot_tasks, self.n_tasks)


class EngineOutput(NamedTuple):
    """Round results.  Neither τ̃ nor m̂ is materialised on the hot
    path: τ̃ is (2·task_vectors − tau_hats) on rows with donors, and m̂
    is re-derived from the exact byte-wide agreement numerator via the
    ``m_hats`` property.  The packed path fills (alpha_num, n_held);
    the bool A/B path fills ``m_hats_dense`` instead."""
    task_vectors: jax.Array          # (T, d) τ^{t,r+1} fp32
    tau_hats: jax.Array              # (T, d) fp32
    similarity: jax.Array            # (T, T), held-masked
    down_unified: jax.Array          # (n_max, d) bf16 (wire) | fp32
    down_masks: jax.Array            # (n_max, k_max, ceil(d/32)) uint32 | (…, d) bool
    down_lams: jax.Array             # (n_max, k_max)
    alpha_num: Optional[jax.Array] = None    # (T, d) uint8 — |Σ sgn(m⊙τ)|
    n_held: Optional[jax.Array] = None       # (T,) fp32 member counts
    rho: float = RHO_DEFAULT
    m_hats_dense: Optional[jax.Array] = None  # (T, d) fp32 (bool path only)

    @property
    def m_hats(self) -> jax.Array:
        """Eq. 3 averaged task masks m̂ (T, d) fp32 — identical (bit for
        bit) to the value the round used internally: the same fp32
        division α = |Σ sgn| / max(N_t, 1) both passes performed."""
        if self.m_hats_dense is not None:
            return self.m_hats_dense
        alpha = (self.alpha_num.astype(jnp.float32)
                 / jnp.maximum(self.n_held, 1.0)[:, None])
        return jnp.where(alpha >= self.rho, 1.0, alpha)


def _round_up_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def pad_d_for_shards(d: int, n_shards: int) -> int:
    """Padded feature count for a taskvec-sharded round: each of the
    ``n_shards`` contiguous d-slices is a power-of-two multiple of 256
    coords — word-aligned for the packed wire layout (256 = 8 ×
    ``bitpack.WORD_BITS``) and block-aligned for the shard-invariant λ
    reduction grid (``ref.LAMBDA_BLOCK``), which is what makes the
    sharded λs bit-identical to the single-device round's.  Identity
    when unsharded."""
    if n_shards <= 1:
        return d
    per_shard_blocks = _next_pow2(-(-d // (n_shards * LAMBDA_BLOCK)))
    return n_shards * LAMBDA_BLOCK * per_shard_blocks


def _mesh_layout(mesh: Optional[Mesh]):
    """(axes, sizes, n_shards) of the taskvec rule on this mesh."""
    if mesh is None:
        return (), (), 1
    axes = taskvec_axes(mesh)
    sizes = tuple(int(mesh.shape[a]) for a in axes)
    return axes, sizes, int(np.prod(sizes)) if axes else 1


class SlotStage:
    """Reusable host staging buffers for :func:`pack_uploads`.

    Holds the round's BIG host tensors (unified vectors, slot mask
    words) keyed by name, reallocating only when the shape signature
    changes — so a steady-state round stream refills warm pages instead
    of faulting fresh hundred-MB allocations every round.  Ownership
    contract (see "Host pipeline" in the module docstring): because CPU
    ``jnp.asarray`` may be zero-copy, a stage must not be refilled
    while a device step that consumed its buffers is still in flight —
    ``RoundEngine.round_stream`` alternates two stages and blocks round
    r−1 before round r+1 touches its stage.
    """

    def __init__(self) -> None:
        self._bufs: Dict[str, np.ndarray] = {}

    def alloc(self, name: str, shape: tuple, dtype) -> np.ndarray:
        buf = self._bufs.get(name)
        if (buf is None or buf.shape != tuple(shape)
                or buf.dtype != np.dtype(dtype)):
            buf = np.empty(shape, dtype)
            self._bufs[name] = buf
        return buf


def pack_uploads(uploads: Sequence[ClientUpload], n_tasks: int, *,
                 n_max: Optional[int] = None,
                 k_max: Optional[int] = None,
                 packed: bool = True,
                 mesh: Optional[Mesh] = None,
                 stage: Optional[SlotStage] = None,
                 phase_us: Optional[Dict[str, float]] = None) -> PackedRound:
    """Pack a ragged round of uploads into the engine's slot layout.

    Pure data movement (numpy fills + ``np.packbits`` of O(Σ k_n · d)
    *bits* for the masks, one host→device transfer per tensor); all
    math stays inside the jitted round.  ``packed=False`` selects the
    legacy bool/fp32 layout (A/B baseline).  A client's bool masks are
    bit-packed and its unified vector rounded to bf16 here — this IS
    the uplink quantisation, applied once at the wire boundary.

    Entropy-coded (uint8 stream) uploads are decoded here at the host
    edge in ONE batched ``decode_mask_rows`` call across every coded
    client — records are self-delimiting, so the concatenated streams
    decode to exactly the per-client rows (the jitted round never sees
    the coded layer).

    ``stage`` reuses a :class:`SlotStage`'s big staging buffers
    (pipeline path — see the buffer-ownership contract); ``phase_us``
    accumulates ``pack`` / ``decode`` host microseconds into the given
    dict.

    With ``mesh``, d is zero-padded to ``pad_d_for_shards`` and every
    d-axis tensor is placed with its taskvec ``NamedSharding`` (packed
    mask words split on whole 8-word blocks — never mid-word); scalars
    are replicated onto the mesh.  See the sharding contract above.
    """
    if not uploads:
        raise ValueError("pack_uploads: empty round (no uploads) — "
                         "sample at least one client or skip the round")
    with span("round.pack", phase_us, "pack"):
        n = len(uploads)
        d = int(uploads[0].unified.shape[0])
        _, _, n_shards = _mesh_layout(mesh)
        d_pad = pad_d_for_shards(d, n_shards)
        n_max = n_max or _round_up_pow2(n)
        k_max = k_max or _round_up_pow2(max(len(u.task_ids) for u in uploads))
        if n_max < n:
            raise ValueError(f"n_max={n_max} < round size {n}")

        # one batched host-edge decode for ALL coded clients: streams
        # concatenate (records self-delimit) and split back by row count
        ks = [len(u.task_ids) for u in uploads]
        masks_np = [np.asarray(u.masks) for u in uploads]
        coded = [i for i, m in enumerate(masks_np) if m.dtype == np.uint8]
        if coded:
            from repro.fed.compression import decode_mask_rows
            with span("round.decode", phase_us, "decode"):
                rows = decode_mask_rows(
                    masks_np[coded[0]] if len(coded) == 1
                    else np.concatenate([masks_np[i] for i in coded]),
                    d, sum(ks[i] for i in coded))
                off = 0
                for i in coded:
                    masks_np[i] = rows[off:off + ks[i]]
                    off += ks[i]
        elif phase_us is not None:
            phase_us.setdefault("decode", 0.0)

        # np.empty + zero only the padding: the valid region is fully
        # overwritten below, so a full np.zeros would write the big
        # mask/vector buffers twice for nothing.  With a stage the same
        # (possibly dirty) buffers come back each round — the explicit
        # padding writes below are exactly the re-zeroing reuse needs.
        # host-side bf16 fill for the wire layout (ml_dtypes ships with
        # jax): halves the host→device transfer and skips the device cast
        vec_dtype = np.float32
        if packed:
            import ml_dtypes
            vec_dtype = ml_dtypes.bfloat16
        alloc = stage.alloc if stage is not None else (
            lambda _name, shape, dtype: np.empty(shape, dtype))
        unified = alloc("unified", (n_max, d_pad), vec_dtype)
        unified[n:] = 0.0
        unified[:, d:] = 0.0
        if packed:
            dw = bitpack.packed_width(d)
            wpad = bitpack.packed_width(d_pad)
            slot_masks = alloc("slot_masks", (n_max, k_max, wpad), np.uint32)
            slot_masks[n:] = 0
            if wpad > dw:
                slot_masks[:n, :, dw:] = 0
        else:
            slot_masks = alloc("slot_masks", (n_max, k_max, d_pad), bool)
            slot_masks[n:] = False
            slot_masks[:, :, d:] = False
        slot_lams = np.zeros((n_max, k_max), np.float32)
        slot_sizes = np.zeros((n_max, k_max), np.float32)
        slot_tasks = np.full((n_max, k_max), n_tasks, np.int32)
        slot_valid = np.zeros((n_max, k_max), bool)

        for i, up in enumerate(uploads):
            k = ks[i]
            unified[i, :d] = np.asarray(up.unified)
            m = masks_np[i]
            if packed:
                # accept either bool masks (legacy clients — packed here at
                # the wire boundary) or already-packed words
                slot_masks[i, :k, :dw] = (m if m.dtype == np.uint32
                                          else bitpack.pack_bits_np(m))
                slot_masks[i, k:, :dw] = 0
            else:
                slot_masks[i, :k, :d] = (bitpack.unpack_bits_np(m, d)
                                         if m.dtype == np.uint32 else m)
                slot_masks[i, k:] = False
            slot_lams[i, :k] = np.asarray(up.lams, np.float32)
            slot_sizes[i, :k] = np.asarray(up.data_sizes, np.float32)
            slot_tasks[i, :k] = up.task_ids
            slot_valid[i, :k] = True

        arrays = (unified, slot_masks, slot_lams, slot_sizes, slot_tasks,
                  slot_valid)
        with span("round.h2d"):
            if n_shards > 1:
                rep = NamedSharding(mesh, P())
                put = (taskvec_sharding(mesh, 2), taskvec_sharding(mesh, 3),
                       rep, rep, rep, rep)
                uni, masks, lams, sizes, tasks, valid = (
                    jax.device_put(a, s) for a, s in zip(arrays, put))
            else:
                uni, masks, lams, sizes, tasks, valid = map(jnp.asarray,
                                                            arrays)
        return PackedRound([u.client_id for u in uploads],
                           [list(u.task_ids) for u in uploads],
                           uni, masks, lams, sizes, tasks, valid,
                           n_tasks, d, d_pad if n_shards > 1 else None)


def pack_from_slots(client_ids: List[int], task_ids: List[List[int]],
                    unified: jax.Array, slot_masks: jax.Array,
                    slot_lams: jax.Array, slot_tasks: jax.Array,
                    slot_valid: jax.Array, slot_sizes: jax.Array,
                    n_tasks: int, *, d: Optional[int] = None,
                    mesh: Optional[Mesh] = None,
                    slot_weights: Optional[jax.Array] = None) -> PackedRound:
    """Build a PackedRound from already-batched slot tensors (the
    strategy's pre-packed upload path) — zero copies, the slot layout
    IS the engine's native layout.  ``slot_masks`` may be uint32 wire
    words (``batched_client_unify`` output) or legacy dense bool.

    ``d`` is the true feature count when the d-axis tensors already
    carry the taskvec-shard padding (``batched_client_unify`` with a
    mesh emits them padded + sharded); with ``mesh`` given and
    *unpadded* tensors, the pad + sharded placement happens here.

    ``slot_weights`` (optional (n, k_max) fp32) attaches the async
    staleness discount to the round (replicated under a mesh)."""
    packed = slot_masks.dtype == jnp.uint32
    width = int(unified.shape[-1])
    d = d or width
    _, _, n_shards = _mesh_layout(mesh)
    d_pad = pad_d_for_shards(d, n_shards)
    if width not in (d, d_pad):
        raise ValueError(f"pack_from_slots: unified width {width} matches "
                         f"neither d={d} nor the shard-padded {d_pad}")
    if n_shards > 1 and width != d_pad:
        unified = jnp.pad(unified, ((0, 0), (0, d_pad - width)))
        w_pad = (d_pad // 32 - slot_masks.shape[-1] if packed
                 else d_pad - slot_masks.shape[-1])
        slot_masks = jnp.pad(slot_masks,
                             ((0, 0), (0, 0), (0, w_pad)))
    if n_shards > 1:
        rep = NamedSharding(mesh, P())
        unified = jax.device_put(unified, taskvec_sharding(mesh, 2))
        slot_masks = jax.device_put(slot_masks, taskvec_sharding(mesh, 3))
        put_rep = lambda x: jax.device_put(x, rep)  # noqa: E731
    else:
        put_rep = lambda x: x  # noqa: E731
    if slot_weights is not None:
        slot_weights = put_rep(jnp.asarray(slot_weights, jnp.float32))
    return PackedRound(client_ids, task_ids, unified, slot_masks,
                       put_rep(slot_lams.astype(jnp.float32)),
                       put_rep(slot_sizes.astype(jnp.float32)),
                       put_rep(slot_tasks.astype(jnp.int32)),
                       put_rep(slot_valid),
                       n_tasks, d, d_pad if n_shards > 1 else None,
                       slot_weights)


def _round_impl(unified, slot_masks, slot_lams, slot_sizes, slot_valid,
                slot_tasks, slot_weights=None, *, cfg: EngineConfig,
                mode: str, d: int,
                mesh: Optional[Mesh] = None,
                axes: Tuple[str, ...] = (),
                axis_sizes: Tuple[int, ...] = ()):
    """The whole server step, traced once per (shapes, mode, d, mesh).
    The mask dtype selects the wire-format (uint32) or bool A/B path;
    with a (mesh, taskvec axes) pair the op runs under ``shard_map``
    per the engine's sharding contract.  ``slot_weights`` (async
    staleness discount, replicated under a mesh) pre-scales λ and sizes
    inside ``ops`` — omitted entirely from the trace when None, so the
    synchronous jit programs are untouched."""
    kw = dict(rho=cfg.rho, eps=cfg.eps, kappa=cfg.kappa,
              cross_task=cfg.cross_task, uniform_cross=cfg.uniform_cross,
              mode=mode)
    packed = slot_masks.dtype == jnp.uint32
    n_shards = int(np.prod(axis_sizes)) if axes else 1
    if mesh is None or n_shards == 1:
        kw["slot_weights"] = slot_weights
        if packed:
            return ops.matu_round_slots_packed(
                unified, slot_masks, slot_lams, slot_sizes, slot_valid,
                slot_tasks, cfg.n_tasks, d, **kw)
        return ops.matu_round_slots(
            unified, slot_masks, slot_lams, slot_sizes, slot_valid,
            slot_tasks, cfg.n_tasks, **kw)

    d_pad = int(unified.shape[-1])
    d_local = d_pad // n_shards
    ax = axes[0] if len(axes) == 1 else axes
    s2, s3, rep = P(None, ax), P(None, None, ax), P()
    kw.update(axis_name=axes, axis_sizes=axis_sizes, d_norm=d)

    if packed:
        def body(u, m, lam, sz, val, tid, *w):
            return ops.matu_round_slots_packed(
                u, m, lam, sz, val, tid, cfg.n_tasks, d_local,
                slot_weights=w[0] if w else None, **kw)
        # (tv, τ̂, α_num, n_held, sim, down_uni, down_words, down_lams)
        out_specs = (s2, s2, s2, rep, rep, s2, s3, rep)
    else:
        def body(u, m, lam, sz, val, tid, *w):
            return ops.matu_round_slots(
                u, m, lam, sz, val, tid, cfg.n_tasks,
                slot_weights=w[0] if w else None, **kw)
        # (tv, τ̂, m̂, sim, down_uni, down_masks, down_lams)
        out_specs = (s2, s2, s2, rep, s2, s3, rep)

    in_specs = (s2, s3, rep, rep, rep, rep)
    operands = (unified, slot_masks, slot_lams, slot_sizes, slot_valid,
                slot_tasks)
    if slot_weights is not None:
        in_specs += (rep,)
        operands += (slot_weights,)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*operands)


def _assemble_downlinks(client_ids: List[int], task_ids: List[List[int]],
                        d: int, down_unified, down_masks, down_lams, *,
                        code_masks: bool = False,
                        phase_us: Optional[Dict[str, float]] = None
                        ) -> Dict[int, ClientDownlink]:
    """Slice batched downlink tensors back to ragged per-client
    ClientDownlinks — the shared back half of ``RoundEngine.downlinks``
    and each ``round_chunked`` phase-C chunk.  With ``code_masks`` the
    mask rows of ALL the given clients are entropy-coded in one batched
    call and split back by per-row record sizes (records self-delimit,
    so each slice is byte-identical to encoding that client alone)."""
    with span("round.assemble"):
        streams: Optional[List[jax.Array]] = None
        if code_masks:
            from repro.fed.compression import encode_mask_rows_with_sizes
            with span("round.encode", phase_us, "encode"):
                dm = np.asarray(down_masks)
                if dm.dtype != np.uint32:     # bool A/B layout
                    dm = bitpack.pack_bits_np(dm)
                ks = [len(t) for t in task_ids]
                rows = dm[np.repeat(np.arange(len(ks)), ks),
                          np.concatenate([np.arange(k, dtype=np.int64)
                                          for k in ks])]
                stream, sizes = encode_mask_rows_with_sizes(rows, d)
                ends = np.cumsum(sizes)
                streams, b0, r0 = [], 0, 0
                for k in ks:
                    b1 = int(ends[r0 + k - 1]) if k else b0
                    streams.append(jnp.asarray(stream[b0:b1]))
                    b0, r0 = b1, r0 + k
        result: Dict[int, ClientDownlink] = {}
        for i, cid in enumerate(client_ids):
            k = len(task_ids[i])
            rows_i = streams[i] if code_masks else down_masks[i, :k]
            result[cid] = ClientDownlink(down_unified[i], rows_i,
                                         down_lams[i, :k])
        return result


# -- chunked-round jit bodies (population-scale contract) --------------------
# Module-level (not closures) so tests can monkeypatch them, mirroring
# ``_round_impl``; each is traced once per (shapes, mode, d, mesh).

def _chunk_scalars_impl(slot_sizes, slot_valid, slot_tasks, totals, nt_acc,
                        slot_weights=None, *, mode: str):
    """Phase-A chunk step (replicated scalars — no shard_map needed)."""
    return ops.matu_chunk_scalars(slot_sizes, slot_valid, slot_tasks,
                                  totals, nt_acc,
                                  slot_weights=slot_weights, mode=mode)


def _merge_chunk_impl(unified, slot_masks, slot_lams, slot_sizes, slot_valid,
                      slot_tasks, totals, a_acc, tau_acc, slot_weights=None,
                      *, mode: str, d: int, mesh: Optional[Mesh] = None,
                      axes: Tuple[str, ...] = (),
                      axis_sizes: Tuple[int, ...] = ()):
    """Phase-B chunk step.  Under a mesh each taskvec shard folds EVERY
    chunk row of its local d-slice — the client fold is never split
    across devices (that would change the fp32 accumulation order), so
    the step has no collectives."""
    packed = slot_masks.dtype == jnp.uint32
    n_shards = int(np.prod(axis_sizes)) if axes else 1
    if mesh is None or n_shards == 1:
        if packed:
            return ops.matu_merge_chunk_packed(
                unified, slot_masks, slot_lams, slot_sizes, slot_valid,
                slot_tasks, totals, a_acc, tau_acc, d,
                slot_weights=slot_weights, mode=mode)
        return ops.matu_merge_chunk(
            unified, slot_masks, slot_lams, slot_sizes, slot_valid,
            slot_tasks, totals, a_acc, tau_acc,
            slot_weights=slot_weights, mode=mode)

    d_local = int(unified.shape[-1]) // n_shards
    ax = axes[0] if len(axes) == 1 else axes
    s2, s3, rep = P(None, ax), P(None, None, ax), P()

    def body(u, m, lam, sz, val, tid, tot, a, ta, *w):
        w0 = w[0] if w else None
        if packed:
            return ops.matu_merge_chunk_packed(u, m, lam, sz, val, tid,
                                               tot, a, ta, d_local,
                                               slot_weights=w0, mode=mode)
        return ops.matu_merge_chunk(u, m, lam, sz, val, tid, tot, a, ta,
                                    slot_weights=w0, mode=mode)

    in_specs = (s2, s3, rep, rep, rep, rep, rep, s2, s2)
    operands = (unified, slot_masks, slot_lams, slot_sizes, slot_valid,
                slot_tasks, totals, a_acc, tau_acc)
    if slot_weights is not None:
        in_specs += (rep,)
        operands += (slot_weights,)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=(s2, s2), check_vma=False)(*operands)


def _finish_impl(a_acc, tau_acc, nt_acc, *, cfg: EngineConfig, mode: str,
                 d: int, packed: bool, n_for_dtype: int,
                 mesh: Optional[Mesh] = None, axes: Tuple[str, ...] = (),
                 axis_sizes: Tuple[int, ...] = ()):
    """Chunked-round finish: Eq. 3 α/m̂ → Eq. 5 dots → Eq. 6/7 → λ
    numerator, from the accumulators alone.  The only collectives of
    the whole merge+finish pipeline live here (integer dots psum + λ
    roots psum), exactly the monolithic round's budget."""
    kw = dict(n_tasks=cfg.n_tasks, rho=cfg.rho, eps=cfg.eps,
              kappa=cfg.kappa, cross_task=cfg.cross_task,
              uniform_cross=cfg.uniform_cross, mode=mode)
    n_shards = int(np.prod(axis_sizes)) if axes else 1
    if mesh is None or n_shards == 1:
        if packed:
            return ops.matu_finish_packed(a_acc, tau_acc, nt_acc,
                                          n_for_dtype, d=d, **kw)
        return ops.matu_finish(a_acc, tau_acc, nt_acc, d=d, **kw)

    d_local = int(a_acc.shape[-1]) // n_shards
    ax = axes[0] if len(axes) == 1 else axes
    s2, rep = P(None, ax), P()
    kw.update(axis_name=axes, axis_sizes=axis_sizes, d_norm=d)

    def body(a, ta, nt):
        if packed:
            return ops.matu_finish_packed(a, ta, nt, n_for_dtype,
                                          d=d_local, **kw)
        return ops.matu_finish(a, ta, nt, d=d_local, **kw)

    # (tv, τ̂, α_num | m̂, n_t, sim, num_t)
    return jax.shard_map(body, mesh=mesh, in_specs=(s2, s2, rep),
                         out_specs=(s2, s2, s2, rep, rep, rep),
                         check_vma=False)(a_acc, tau_acc, nt_acc)


def _downlink_chunk_impl(task_vectors, slot_valid, slot_tasks, num_t, *,
                         cfg: EngineConfig, mode: str, d: int, packed: bool,
                         mesh: Optional[Mesh] = None,
                         axes: Tuple[str, ...] = (),
                         axis_sizes: Tuple[int, ...] = (),
                         row_axes: Tuple[str, ...] = ()):
    """Phase-C chunk step: downlink re-unification of one client chunk.
    This is where the 2-D (slots × taskvec) mesh composes: ``row_axes``
    (the fed_slots rule) shard the chunk's client rows, the taskvec
    axes shard d, and the λ-denominator roots psum over the taskvec
    axes only (rows never mix)."""
    n_shards = int(np.prod(axis_sizes)) if axes else 1
    if mesh is None or (n_shards == 1 and not row_axes):
        if packed:
            return ops.matu_downlink_chunk_packed(task_vectors, slot_tasks,
                                                  num_t, d, mode=mode)
        return ops.matu_downlink_chunk(task_vectors, slot_valid, slot_tasks,
                                       num_t, n_tasks=cfg.n_tasks, mode=mode)

    d_local = (int(task_vectors.shape[-1]) // n_shards
               if n_shards > 1 else d)
    ax = (axes[0] if len(axes) == 1 else axes) if n_shards > 1 else None
    rx = (row_axes[0] if len(row_axes) == 1 else row_axes) \
        if row_axes else None
    rep = P()
    kw: Dict[str, object] = dict(mode=mode)
    if n_shards > 1:
        kw.update(axis_name=axes, axis_sizes=axis_sizes)

    def body(tv, val, tid, nt):
        if packed:
            return ops.matu_downlink_chunk_packed(tv, tid, nt, d_local, **kw)
        return ops.matu_downlink_chunk(tv, val, tid, nt,
                                       n_tasks=cfg.n_tasks, **kw)

    in_specs = (P(None, ax), P(rx, None), P(rx, None), rep)
    out_specs = (P(rx, ax), P(rx, None, ax), P(rx, None))
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(
                             task_vectors, slot_valid, slot_tasks, num_t)


class RoundEngine:
    """Stateless per-round executor; owns only jit caches (one per
    (dispatch mode, d) — shapes are handled by jax.jit's own cache)
    and, optionally, the mesh the round shards over (see the sharding
    contract in the module docstring)."""

    def __init__(self, cfg: EngineConfig, mesh: Optional[Mesh] = None):
        self.cfg = cfg
        self._impls: Dict[tuple, object] = {}
        self.use_mesh(mesh)

    def use_mesh(self, mesh: Optional[Mesh]) -> None:
        """Install (or clear) the taskvec mesh; resets the jit caches —
        the traced program embeds the shard_map layout."""
        self.mesh = mesh
        self._axes, self._axis_sizes, self.n_shards = _mesh_layout(mesh)
        self._slot_axes = slot_axes(mesh) if mesh is not None else ()
        self.slot_shards = (int(np.prod([mesh.shape[a]
                                         for a in self._slot_axes]))
                            if self._slot_axes else 1)
        self._impls.clear()

    def _impl(self, mode: str, d: int):
        fn = self._impls.get((mode, d))
        if fn is None:
            import repro.core.engine as _mod
            fn = jax.jit(functools.partial(
                _mod._round_impl, cfg=self.cfg, mode=mode, d=d,
                mesh=self.mesh, axes=self._axes,
                axis_sizes=self._axis_sizes))
            self._impls[(mode, d)] = fn
        return fn

    def run_packed(self, packed: PackedRound, *,
                   mode: Optional[str] = None) -> EngineOutput:
        mode = mode or ops.resolve_mode()
        d_pad = pad_d_for_shards(packed.d, self.n_shards)
        if packed.padded_d != d_pad:
            raise ValueError(
                f"run_packed: batch padded to d={packed.padded_d} but the "
                f"engine's mesh shards {self.n_shards} ways (wants {d_pad}) "
                f"— pack with the same mesh the engine holds")
        args = (packed.unified, packed.slot_masks, packed.slot_lams,
                packed.slot_sizes, packed.slot_valid, packed.slot_tasks)
        if packed.slot_weights is not None:
            # the weighted trace is a separate jit entry (extra operand)
            # — the synchronous program is never re-traced or perturbed
            args += (packed.slot_weights,)
        with span("round.dispatch"):
            out = self._impl(mode, packed.d)(*args)
        if d_pad != packed.d:
            out = _slice_outputs(out, packed.d, packed.packed)
        if packed.packed:
            (tv, tau, a_num, n_held, sim, du, dm, dl) = out
            return EngineOutput(tv, tau, sim, du, dm, dl,
                                alpha_num=a_num, n_held=n_held,
                                rho=self.cfg.rho)
        (tv, tau, m_hats, sim, du, dm, dl) = out
        return EngineOutput(tv, tau, sim, du, dm, dl,
                            rho=self.cfg.rho, m_hats_dense=m_hats)

    def downlinks(self, packed: PackedRound, out: EngineOutput, *,
                  code_masks: bool = False,
                  phase_us: Optional[Dict[str, float]] = None
                  ) -> Dict[int, ClientDownlink]:
        """Slice the batched downlink tensors back to ragged per-client
        ClientDownlinks (views, no compute).  Mask rows stay in the
        packed wire format; clients unpack on use (``modulate``).

        With ``code_masks`` every client's mask rows are entropy-coded
        at this host edge in ONE batched ``encode_mask_rows_with_sizes``
        call (the Golomb-Rice wire layer, ``repro.fed.compression``) and
        the concatenated stream is split back into per-client streams by
        the per-row record sizes — records self-delimit, so each slice
        is byte-identical to encoding that client alone.  Clients decode
        on use (``ClientDownlink.mask_row``) and downlink bits are
        measured off the actual stream.  ``phase_us`` accumulates the
        ``encode`` host microseconds."""
        return _assemble_downlinks(packed.client_ids, packed.task_ids,
                                   packed.d, out.down_unified,
                                   out.down_masks, out.down_lams,
                                   code_masks=code_masks,
                                   phase_us=phase_us)

    def round(self, uploads: Sequence[ClientUpload], *,
              mode: Optional[str] = None, packed: bool = True,
              code_masks: bool = False,
              staleness: Optional[Sequence[int]] = None,
              staleness_discount: float = STALENESS_DISCOUNT
              ) -> Tuple[Dict[int, ClientDownlink], EngineOutput]:
        """Pack → run → unpack: the drop-in replacement for the legacy
        per-task Python loop in ``MaTUServer.round``.  ``packed=False``
        runs the bool/fp32 A/B layout; ``code_masks=True`` emits
        entropy-coded downlink masks (coded uploads are accepted and
        decoded by ``pack_uploads`` regardless of this flag).

        ``staleness`` (one int per upload, async buffered rounds)
        attaches the per-slot discount ``staleness_discount**s`` to the
        round — see "Async & fault model" in the module docstring."""
        with span("round"):
            batch = pack_uploads(uploads, self.cfg.n_tasks, packed=packed,
                                 mesh=self.mesh)
            if staleness is not None:
                n_max, k_max = batch.slot_valid.shape
                w = np.ones((n_max, k_max), np.float32)
                w[:len(uploads)] = (np.float32(staleness_discount)
                                    ** np.asarray(staleness,
                                                  np.float32))[:, None]
                if self.n_shards > 1:
                    batch.slot_weights = jax.device_put(
                        w, NamedSharding(self.mesh, P()))
                else:
                    batch.slot_weights = jnp.asarray(w)
            out = self.run_packed(batch, mode=mode)
            return self.downlinks(batch, out, code_masks=code_masks), out

    def _chunk_impls(self, mode: str, d: int, packed: bool,
                     n_for_dtype: int):
        """Jitted (scalars, merge, finish, downlink) chunk steps, cached
        like ``_impl`` — one static signature reused across every chunk
        of every round with this (mode, layout, d).  The big carried
        accumulators are donated so the fold updates in place."""
        key = ("chunked", mode, d, packed, n_for_dtype)
        fns = self._impls.get(key)
        if fns is None:
            import repro.core.engine as _mod
            common = dict(mesh=self.mesh, axes=self._axes,
                          axis_sizes=self._axis_sizes)
            scal = jax.jit(
                functools.partial(_mod._chunk_scalars_impl, mode=mode),
                donate_argnums=(3, 4))
            merge = jax.jit(
                functools.partial(_mod._merge_chunk_impl, mode=mode, d=d,
                                  **common),
                donate_argnums=(7, 8))
            # finish is NOT donated: its (T, dp) outputs have different
            # shapes/dtypes from the accumulators, so donation would
            # only raise "unusable donated buffer" noise
            finish = jax.jit(
                functools.partial(_mod._finish_impl, cfg=self.cfg,
                                  mode=mode, d=d, packed=packed,
                                  n_for_dtype=n_for_dtype, **common))
            down = jax.jit(
                functools.partial(_mod._downlink_chunk_impl, cfg=self.cfg,
                                  mode=mode, d=d, packed=packed,
                                  row_axes=self._slot_axes, **common))
            fns = (scal, merge, finish, down)
            self._impls[key] = fns
        return fns

    def round_chunked(self, uploads, *, chunk_clients: int,
                      mode: Optional[str] = None, packed: bool = True,
                      code_masks: bool = False,
                      staleness: Optional[Sequence[int]] = None,
                      staleness_discount: float = STALENESS_DISCOUNT,
                      k_max: Optional[int] = None,
                      sink: Optional[Callable[
                          [Dict[int, ClientDownlink]], None]] = None,
                      phase_us: Optional[Dict[str, float]] = None
                      ) -> Tuple[Dict[int, ClientDownlink], EngineOutput,
                                 Dict[str, int]]:
        """Run one round by streaming uploads through a fixed-shape
        chunk buffer of ``chunk_clients`` clients — memory is
        O(chunk + T·d), independent of N, and the result is
        BIT-identical to ``round`` in ref mode (see "Population-scale
        contract" in the module docstring).

        ``uploads`` is a sequence of ClientUploads or a zero-arg
        callable returning a fresh iterator over them — the engine
        makes two passes (the Eq. 4 γ normaliser needs global size
        totals before any merge work), and a callable lets the
        population simulator re-derive sampled clients on demand
        instead of materialising the round.

        ``sink`` (optional) receives each phase-C chunk's
        ``{client_id: ClientDownlink}`` dict as it is produced; with a
        sink the returned downlink dict is empty, so no per-client
        state accumulates.  The returned ``EngineOutput`` carries the
        global results (task_vectors / tau_hats / similarity / m̂) with
        the downlink fields None — per-client downlinks only exist
        chunk-at-a-time.  The stats dict reports the measured
        ``uplink_bits`` / ``downlink_bits`` (identical to the
        monolithic round's accounting), ``n_clients`` and ``n_chunks``.
        """
        with span("round"):
            mode = mode or ops.resolve_mode()
            C = int(chunk_clients)
            if C < 1:
                raise ValueError(f"round_chunked: chunk_clients={C} < 1")
            make_iter = (uploads if callable(uploads)
                         else (lambda: iter(uploads)))

            # -- pass 0: chunk metadata (client ids / task ids / sizes
            # only — O(N·k) host scalars, no d-axis tensor touched)
            metas: List[tuple] = []
            cur_ids: List[int] = []
            cur_tasks: List[List[int]] = []
            cur_sizes: List[np.ndarray] = []
            cur_stal: List[float] = []
            stal_it = iter(staleness) if staleness is not None else None
            d = None
            k_seen, n_clients = 1, 0

            def _flush():
                metas.append((list(cur_ids), list(cur_tasks), list(cur_sizes),
                              list(cur_stal) if stal_it is not None else None))
                cur_ids.clear(), cur_tasks.clear()
                cur_sizes.clear(), cur_stal.clear()

            with span("round.meta"):
                for up in make_iter():
                    if d is None:
                        d = int(up.unified.shape[0])
                    tids = list(up.task_ids)
                    k_seen = max(k_seen, len(tids))
                    cur_ids.append(up.client_id)
                    cur_tasks.append(tids)
                    cur_sizes.append(np.asarray(up.data_sizes, np.float32))
                    if stal_it is not None:
                        cur_stal.append(next(stal_it))
                    n_clients += 1
                    if len(cur_ids) == C:
                        _flush()
                if cur_ids:
                    _flush()
            if n_clients == 0:
                raise ValueError("round_chunked: empty round (no uploads) — "
                                 "sample at least one client or skip the "
                                 "round")
            if k_max is None:
                k_max = _round_up_pow2(k_seen)
            elif k_max < k_seen:
                raise ValueError(f"round_chunked: k_max={k_max} < max client "
                                 f"task count {k_seen}")
            # pow2 chunk rows, ≥ the slot-shard count so phase-C row
            # sharding always divides evenly
            c_pad = max(_round_up_pow2(C), self.slot_shards)
            n_seg = self.cfg.n_tasks + 1
            d_pad = pad_d_for_shards(d, self.n_shards)
            # accumulator width: the sharded padding, or the monolithic
            # round's own CHUNK_D streaming-grid padding — identical grids
            # are what make chunked ≡ monolithic bitwise
            dp = d_pad if self.n_shards > 1 else _chunked(d, CHUNK_D)[1]
            # same α-numerator dtype decision as the monolithic round
            # (keyed on its default n_max = next pow2 ≥ N)
            n_for_dtype = _round_up_pow2(n_clients)
            scal, merge, finish, down = self._chunk_impls(
                mode, d, packed, n_for_dtype if packed else 0)

            def _scalar_chunk(ids_, tasks_, sizes_, stal_):
                sz = np.zeros((c_pad, k_max), np.float32)
                tk = np.full((c_pad, k_max), self.cfg.n_tasks, np.int32)
                vd = np.zeros((c_pad, k_max), bool)
                for i, (tl, sl) in enumerate(zip(tasks_, sizes_)):
                    k = len(tl)
                    sz[i, :k] = sl
                    tk[i, :k] = tl
                    vd[i, :k] = True
                w = None
                if stal_ is not None:
                    w = np.ones((c_pad, k_max), np.float32)
                    w[:len(ids_)] = (np.float32(staleness_discount)
                                     ** np.asarray(stal_,
                                                   np.float32))[:, None]
                    w = jnp.asarray(w)
                return jnp.asarray(sz), jnp.asarray(tk), jnp.asarray(vd), w

            # -- phase A: fold the (T+1,) size totals / membership counts
            totals = jnp.zeros((n_seg,), jnp.float32)
            nt_acc = jnp.zeros((n_seg,), jnp.float32)
            w_chunks: List[Optional[jax.Array]] = []
            with span("round.meta"):
                for ids_, tasks_, sizes_, stal_ in metas:
                    sz, tk, vd, w = _scalar_chunk(ids_, tasks_, sizes_, stal_)
                    w_chunks.append(w)
                    args = (sz, vd, tk, totals, nt_acc)
                    totals, nt_acc = (scal(*args, w) if w is not None
                                      else scal(*args))

            # -- phase B: second pass over the stream, fold merge partials
            a_acc = jnp.zeros((n_seg, dp),
                              jnp.int32 if packed else jnp.float32)
            tau_acc = jnp.zeros((n_seg, dp), jnp.float32)
            stage = SlotStage()
            stream = make_iter()
            uplink_bits = 0
            for ci, (ids_, tasks_, sizes_, stal_) in enumerate(metas):
                ups = list(itertools.islice(stream, len(ids_)))
                if [u.client_id for u in ups] != ids_:
                    raise ValueError(
                        "round_chunked: the upload factory returned a "
                        "different round on the second pass — it must be "
                        "deterministic (same clients, same order)")
                batch = pack_uploads(ups, self.cfg.n_tasks, n_max=c_pad,
                                     k_max=k_max, packed=packed,
                                     mesh=self.mesh,
                                     stage=stage, phase_us=phase_us)
                uplink_bits += batch.wire_bits()
                args = (batch.unified, batch.slot_masks, batch.slot_lams,
                        batch.slot_sizes, batch.slot_valid, batch.slot_tasks,
                        totals, a_acc, tau_acc)
                if w_chunks[ci] is not None:
                    args += (w_chunks[ci],)
                with span("round.dispatch"):
                    a_acc, tau_acc = merge(*args)
                # the dispatched step may alias the staged host buffers
                # zero-copy (CPU jnp.asarray) — block before the refill
                with span("round.wait"):
                    jax.block_until_ready(tau_acc)

            # -- finish: Eq. 3/5/6/7 + λ numerator from the accumulators
            with span("round.dispatch"):
                tv, tau_hats, third, n_t, sim, num_t = finish(a_acc, tau_acc,
                                                              nt_acc)
            tv_run = tv                # keeps the shard padding for phase C
            if self.n_shards > 1 and d_pad != d:
                tv, tau_hats, third = tv[:, :d], tau_hats[:, :d], third[:, :d]
            if packed:
                out = EngineOutput(tv, tau_hats, sim, None, None, None,
                                   alpha_num=third, n_held=n_t,
                                   rho=self.cfg.rho)
            else:
                out = EngineOutput(tv, tau_hats, sim, None, None, None,
                                   rho=self.cfg.rho, m_hats_dense=third)

            # -- phase C: per-chunk downlink re-unification, streamed out
            dw = bitpack.packed_width(d)
            downlinks: Dict[int, ClientDownlink] = {}
            downlink_bits = 0
            for ids_, tasks_, sizes_, stal_ in metas:
                tk = np.full((c_pad, k_max), self.cfg.n_tasks, np.int32)
                vd = np.zeros((c_pad, k_max), bool)
                for i, tl in enumerate(tasks_):
                    tk[i, :len(tl)] = tl
                    vd[i, :len(tl)] = True
                with span("round.dispatch"):
                    du, dm, dl = down(tv_run, jnp.asarray(vd), jnp.asarray(tk),
                                      num_t)
                if self.n_shards > 1 and d_pad != d:
                    du = du[:, :d]
                    dm = dm[:, :, :dw] if packed else dm[:, :, :d]
                links = _assemble_downlinks(ids_, tasks_, d, du, dm, dl,
                                            code_masks=code_masks,
                                            phase_us=phase_us)
                downlink_bits += sum(link.downlink_bits()
                                     for link in links.values())
                if sink is not None:
                    sink(links)
                else:
                    downlinks.update(links)

            stats = {"uplink_bits": uplink_bits,
                     "downlink_bits": downlink_bits,
                     "n_clients": n_clients, "n_chunks": len(metas),
                     "chunk_clients": C}
            return downlinks, out, stats

    def round_stream(self, rounds, *, mode: Optional[str] = None,
                     packed: bool = True, code_masks: bool = False,
                     pipeline: bool = True):
        """Run an iterable of upload rounds through the two-deep host
        pipeline (see "Host pipeline" in the module docstring): while
        the device executes round r, the host drains round r−1 (block
        → batched downlink encode → yield) and packs/decodes round
        r+1's uploads into the alternate :class:`SlotStage`.

        Yields ``(downlinks, out, phase_us)`` per round, in input
        order; ``phase_us`` maps ``pack`` / ``decode`` / ``encode`` /
        ``device`` to host microseconds (``device`` is dispatch→ready
        wall — under the pipeline it overlaps its neighbours' host
        phases).  ``pipeline=False`` is the strictly-sequential escape
        hatch, bit-identical by construction.  Rounds are pulled one
        ahead of yields, so the iterable must not depend on the
        previous round's downlinks (replay/bench traffic)."""
        if not pipeline:
            for ups in rounds:
                phase: Dict[str, float] = {}
                batch = pack_uploads(ups, self.cfg.n_tasks, packed=packed,
                                     mesh=self.mesh, phase_us=phase)
                t0 = time.perf_counter()
                out = self.run_packed(batch, mode=mode)
                jax.block_until_ready(out)
                phase["device"] = (time.perf_counter() - t0) * 1e6
                yield (self.downlinks(batch, out, code_masks=code_masks,
                                      phase_us=phase), out, phase)
            return

        stages = (SlotStage(), SlotStage())
        prev = None
        for r, ups in enumerate(rounds):
            phase: Dict[str, float] = {}
            # host pack/decode of round r overlaps round r−1's device
            # step; stage r%2 was last consumed by round r−2, which was
            # drained (blocked) before this point — never in flight
            batch = pack_uploads(ups, self.cfg.n_tasks, packed=packed,
                                 mesh=self.mesh, stage=stages[r % 2],
                                 phase_us=phase)
            out = self.run_packed(batch, mode=mode)      # async dispatch
            pend = (batch, out, phase, time.perf_counter())
            if prev is not None:
                yield self._drain_round(prev, code_masks)
            prev = pend
        if prev is not None:
            yield self._drain_round(prev, code_masks)

    def _drain_round(self, pend, code_masks: bool):
        """Block on a dispatched round and materialise its downlinks —
        the host-side half the pipeline overlaps with the NEXT round's
        device step."""
        batch, out, phase, t_disp = pend
        jax.block_until_ready(out)
        phase["device"] = (time.perf_counter() - t_disp) * 1e6
        return (self.downlinks(batch, out, code_masks=code_masks,
                               phase_us=phase), out, phase)


def _slice_outputs(out: tuple, d: int, packed: bool) -> tuple:
    """Slice a sharded round's padded d-axis outputs back to the true
    feature count (mask words to ceil(d/32) — padded coords carry zero
    bits, so the wire tail-bit convention holds).  Dispatched outside
    the round jit, on the already-sharded device buffers."""
    dw = bitpack.packed_width(d)
    if packed:
        (tv, tau, a_num, n_held, sim, du, dm, dl) = out
        return (tv[:, :d], tau[:, :d], a_num[:, :d], n_held, sim,
                du[:, :d], dm[:, :, :dw], dl)
    (tv, tau, m_hats, sim, du, dm, dl) = out
    return (tv[:, :d], tau[:, :d], m_hats[:, :d], sim,
            du[:, :d], dm[:, :, :d], dl)


# -- batched client-side unification ----------------------------------------

@functools.lru_cache(maxsize=None)
def _client_unify_jit(mode: str, packed: bool):
    fn = ops.fused_unify_packed if packed else ops.fused_unify
    return jax.jit(functools.partial(fn, mode=mode))


@functools.lru_cache(maxsize=None)
def _client_unify_sharded_jit(mode: str, packed: bool, mesh: Mesh,
                              eps: float = 1e-12):
    """shard_map'd fused unify: per-shard kernels on the local d-slice,
    one psum for the λ num/den partial sums (λ matches the unsharded
    call to fp32 accumulation tolerance; masks / bf16 vectors are
    per-coordinate and bit-identical)."""
    axes, _, _ = _mesh_layout(mesh)
    ax = axes[0] if len(axes) == 1 else axes
    s2, s3, rep = P(None, ax), P(None, None, ax), P()

    def body(tv, valid):
        uni, masks, num, den = ops.fused_unify_raw(tv, valid, packed=packed,
                                                   mode=mode)
        num, den = jax.lax.psum((num, den), axes)
        return uni, masks, num / jnp.maximum(den, eps)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(s3, rep),
                                 out_specs=(s2, s3, rep), check_vma=False))


def batched_client_unify(task_vectors: jax.Array, valid: jax.Array, *,
                         mode: Optional[str] = None, packed: bool = True,
                         mesh: Optional[Mesh] = None):
    """All clients' upload construction in one fused call.

    task_vectors (N, k_max, d) zero-padded stacks; valid (N, k_max).
    By default emits the uplink wire format:
    (unified (N, d) **bf16**, mask_words (N, k_max, ceil(d/32))
    **uint32**, lams (N, k_max) fp32) — row n equals
    ``unify_with_modulators(task_vectors[n, valid[n]])`` with the
    unified vector rounded to bf16 *after* the masks/λ were derived
    from it in fp32.  ``packed=False`` returns the legacy
    (fp32, bool, fp32) triple.

    With ``mesh``, d is zero-padded to ``pad_d_for_shards`` and the
    call runs under ``shard_map``; the returned d-axis tensors keep the
    padded width and the taskvec sharding — exactly what
    ``pack_from_slots(..., d=true_d, mesh=mesh)`` expects.
    """
    mode = mode or ops.resolve_mode()
    _, _, n_shards = _mesh_layout(mesh)
    if n_shards == 1:
        return _client_unify_jit(mode, packed)(task_vectors, valid)
    d = int(task_vectors.shape[-1])
    d_pad = pad_d_for_shards(d, n_shards)
    if d_pad != d:
        task_vectors = jnp.pad(task_vectors,
                               ((0, 0), (0, 0), (0, d_pad - d)))
    task_vectors = jax.device_put(task_vectors, taskvec_sharding(mesh, 3))
    valid = jax.device_put(valid, NamedSharding(mesh, P()))
    return _client_unify_sharded_jit(mode, packed, mesh)(task_vectors, valid)
