"""Multi-tenant MaTU serving: one backbone, one unified vector, T
cheap modulators.

Serving contract
----------------
1. **Store handoff.**  After a federated round,
   ``MaTUServer.serving_downlink(fingerprint=space.fingerprint)``
   re-unifies the full task-vector set into one all-tasks
   :class:`~repro.core.client.ClientDownlink` (row t ↔ task id t) and
   :meth:`ModulatorStore.ingest` makes it resident: the unified vector
   ONCE in its wire dtype, per task a bit-packed uint32 mask row + an
   fp32 λ.  Masks stay packed until point of use — the downlink
   carries packed words, or with ``code_masks`` an entropy-coded
   stream that decodes straight to words.
   The store verifies the downlink's ``TaskVectorSpace`` fingerprint
   against its own manifest before serving anything and refuses an
   unstamped downlink unless explicitly overridden — the same
   abort-before-use handshake the aggregation path runs.

2. **Routing.**  Task ids are DATA, not trace constants.
   :func:`~repro.serve.router.route_batch` resolves a batch's per-
   request task ids eagerly (outside jit) into a routed LoRA pytree:
   dense-routed (per-request adapters from the store's LRU, stacked on
   axis 1 behind the layers axis) or fused (packed per-leaf mask bits
   re-aligned with ``bitpack.slice_bits`` + per-request λ; the
   modulated weight ``base + λ·m⊙τ`` is built in VMEM by the
   ``ops.modulated_matmul`` kernel, fused into the LoRA matmul).
   Dense-routed is bit-identical to single-tenant decode with the
   dense unpacked modulator; fused matches unpack-then-matmul
   bitwise within one compiled program and token-for-token end to
   end (see ``router`` docstring for the fma rounding caveat).

3. **Cache keying.**  The jitted decode program is keyed ONLY on
   shapes — batch size, prompt length, generation config — never on
   task ids or the task mix.  A :class:`~repro.serve.router.
   MultiTenantDecoder` therefore compiles once per (B, S) and reuses
   that one program across every mix (``compile_count()`` asserts it).
   Materialised adapters live in the store's bounded LRU; evictions
   rebuild from packed state on the next request, cheap and off the
   decode hot path.

``generate`` is the sampling loop itself (single jitted ``lax.scan``),
shared by single-task and multi-tenant callers.
"""

from repro.serve.generate import GenerationConfig, generate
from repro.serve.router import MultiTenantDecoder, route_batch
from repro.serve.store import ModulatorStore

__all__ = ["GenerationConfig", "generate", "ModulatorStore",
           "MultiTenantDecoder", "route_batch"]
