"""ModulatorStore: the multi-tenant serving state — one unified vector,
T cheap modulators, zero per-task checkpoints.

The paper's deployment story (§3.2): after federation the server ships
ONE unified task vector τ plus per-task lightweight modulators
(binary mask m^t, scaler λ^t); a task's adapter is reconstructed as
``lora0 + unflatten(λ^t · m^t ⊙ τ)``.  The store is that story made
resident:

* the unified vector is held ONCE, in its wire dtype (bf16 off a
  packed downlink) — upcast to fp32 only at materialisation, exactly
  like :func:`repro.core.unify.modulate`;
* per task id it holds a bit-packed uint32 mask row (LSB-first wire
  words — bool downlink rows are packed on ingest, entropy-coded
  streams decode straight to words, dense bools never become resident)
  and one fp32 λ;
* materialised task adapters (model-space LoRA pytrees) live in a
  bounded LRU — the working set of hot tasks — and are rebuilt on
  demand from the packed state on a miss.

Ingest is the handoff from a :class:`repro.core.server.MaTUServer`
round (``serving_downlink``): a :class:`ClientDownlink` whose rows are
task ids.  The store refuses a downlink whose ``TaskVectorSpace``
fingerprint does not match its own manifest (same abort-before-use
handshake the round path runs), and refuses an *unstamped* downlink
unless the caller passes ``unchecked=True`` explicitly.

``storage_report`` measures the MaTU win: resident bytes
(base adapter + unified vector + T packed modulators) vs what
per-task-checkpoint serving would hold resident (T full fp32 adapter
pytrees) — the ≥5x headline at T=30 in
``results/bench/serving.json``.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.tree import (TaskVectorLayoutError, TaskVectorSpace,
                               tree_add)
from repro.core.client import ClientDownlink
from repro.core.unify import modulate
from repro.kernels import bitpack

PyTree = Any


class ModulatorStore:
    """Task-id-keyed modulator cache backing the multi-tenant decoder.

    ``space`` is the serving model's layout manifest
    (:class:`TaskVectorSpace` over the LoRA template); ``lora0`` the
    base adapter pytree the deltas apply to (the standard A-gaussian /
    B-zero init — τ = 0 reconstructs the pretrained point).
    ``capacity`` bounds the LRU of materialised task pytrees.
    """

    def __init__(self, space: TaskVectorSpace, lora0: PyTree, *,
                 capacity: int = 8):
        if capacity < 1:
            raise ValueError(f"LRU capacity must be >= 1, got {capacity}")
        self.space = space
        self.lora0 = lora0
        self.capacity = capacity
        self.unified: Optional[jax.Array] = None       # (d,) wire dtype
        self._words: Dict[int, jax.Array] = {}         # t -> (W,) uint32
        self._lams: Dict[int, jax.Array] = {}          # t -> fp32 scalar
        self._lru: "OrderedDict[int, PyTree]" = OrderedDict()
        self._tau_tree: Optional[PyTree] = None        # fp32 unflatten cache
        self.hits = 0
        self.misses = 0
        self.materializations = 0

    # -- ingest ---------------------------------------------------------
    def ingest(self, downlink: ClientDownlink,
               task_ids: Optional[Iterable[int]] = None, *,
               unchecked: bool = False) -> List[int]:
        """Install a round's unified vector + modulators.

        ``downlink`` rows map to ``task_ids`` (row i ↔ task_ids[i];
        default ``0..k-1``, the ``serving_downlink`` convention).  The
        downlink's layout fingerprint must match this store's manifest;
        a downlink with no fingerprint is refused unless
        ``unchecked=True``.  Masks become resident as packed uint32
        words whatever layout they arrive in; stale LRU entries for the
        refreshed tasks are dropped.  Returns the installed task ids.
        """
        if downlink.fingerprint is None:
            if not unchecked:
                raise TaskVectorLayoutError(
                    "refusing to serve an unstamped downlink (no layout "
                    "fingerprint); pass unchecked=True to override")
        else:
            self.space.require_compatible(downlink.fingerprint,
                                          context="serving store ingest")
        d = int(downlink.unified.shape[-1])
        if d < self.space.d:
            raise TaskVectorLayoutError(
                f"downlink vector has {d} coords, serving manifest needs "
                f"d={self.space.d}")
        k = int(downlink.lams.shape[0])
        ids = list(range(k)) if task_ids is None else [int(t) for t in task_ids]
        if len(ids) != k:
            raise ValueError(f"{len(ids)} task ids for {k} modulator rows")
        if downlink.coded:
            words = downlink.mask_row(slice(0, k))  # decoded words, cached
        elif downlink.packed:
            words = downlink.masks
        else:
            words = bitpack.pack_bits(downlink.masks)
        self.unified = downlink.unified
        self._tau_tree = None
        for i, t in enumerate(ids):
            self._words[t] = words[i]
            self._lams[t] = jnp.asarray(downlink.lams[i], jnp.float32)
            self._lru.pop(t, None)          # stale materialisation out
        return ids

    # -- lookup ---------------------------------------------------------
    @property
    def task_ids(self) -> List[int]:
        return sorted(self._words)

    def __contains__(self, task_id: int) -> bool:
        return int(task_id) in self._words

    def _require(self, task_id: int) -> int:
        t = int(task_id)
        if t not in self._words:
            raise KeyError(f"task {t} has no resident modulator "
                           f"(known: {self.task_ids})")
        return t

    def mask_words(self, task_id: int) -> jax.Array:
        """Packed (ceil(d/32),) uint32 modulator row — stays packed."""
        return self._words[self._require(task_id)]

    def lam(self, task_id: int) -> jax.Array:
        return self._lams[self._require(task_id)]

    def delta(self, task_id: int) -> jax.Array:
        """Flat fp32 modulated delta λ^t · m^t ⊙ τ (the packed row is
        unpacked here, at point of use)."""
        t = self._require(task_id)
        return modulate(self.unified, self._words[t], self._lams[t])

    def tau_tree(self) -> PyTree:
        """The unified vector as a model-space pytree in the leaf
        dtypes (the fused router's per-leaf τ operand), unflattened once
        per ingest."""
        if self.unified is None:
            raise ValueError("store has no unified vector (ingest first)")
        if self._tau_tree is None:
            self._tau_tree = self.space.unflatten(
                self.unified.astype(jnp.float32))
        return self._tau_tree

    def adapter(self, task_id: int) -> PyTree:
        """Materialised task adapter ``lora0 + unflatten(delta)``, via
        the LRU (hit: no recompute; miss: rebuild from packed state and
        possibly evict the least-recently-used task)."""
        t = self._require(task_id)
        if t in self._lru:
            self.hits += 1
            self._lru.move_to_end(t)
            return self._lru[t]
        self.misses += 1
        self.materializations += 1
        adapter = tree_add(self.lora0, self.space.unflatten(self.delta(t)))
        self._lru[t] = adapter
        while len(self._lru) > self.capacity:
            self._lru.popitem(last=False)
        return adapter

    def cached_task_ids(self) -> List[int]:
        """LRU contents, least- to most-recently used (test hook)."""
        return list(self._lru)

    # -- storage accounting ---------------------------------------------
    def resident_bytes(self) -> int:
        """Bytes the store keeps resident: base adapter + the unified
        vector (wire dtype) + per task one packed mask row + one fp32 λ.
        LRU materialisations are a bounded working-set cache, not part
        of the serving state, and are excluded (set ``capacity=1`` to
        make them negligible)."""
        base = sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                   for l in jax.tree_util.tree_leaves(self.lora0))
        uni = int(self.unified.size) * self.unified.dtype.itemsize \
            if self.unified is not None else 0
        mods = sum(int(w.size) * 4 + 4 for w in self._words.values())
        return base + uni + mods

    def checkpoint_bytes(self) -> int:
        """What per-task-checkpoint serving holds resident instead: one
        full fp32 adapter pytree per task (each is lora0 + delta — same
        shape as lora0, 4 bytes per coordinate)."""
        per_task = 4 * self.space.d
        return len(self._words) * per_task

    def storage_report(self) -> Dict[str, float]:
        resident = self.resident_bytes()
        ckpt = self.checkpoint_bytes()
        return {
            "tasks": len(self._words),
            "d": self.space.d,
            "resident_bytes": resident,
            "checkpoint_bytes": ckpt,
            "ratio": (ckpt / resident) if resident else float("inf"),
            "lru_capacity": self.capacity,
            "lru_hits": self.hits,
            "lru_misses": self.misses,
            "materializations": self.materializations,
        }
