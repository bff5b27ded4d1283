"""MaTU stateless server (paper §3.2 "Many-tasks Aggregation").

The server keeps NO client state across rounds — it consumes the
round's uploads, runs Eq. 3–7, and emits per-client downlinks (unified
vector + modulators for that client's tasks).  Task identity (the
|T|-sized registry) is the only global it needs.

``round`` is a thin wrapper over :class:`repro.core.engine.RoundEngine`:
uploads are packed into padded batch tensors and the whole Eq. 3–7
pipeline runs as one jitted, kernel-dispatched call (see engine module
docstring for the padding contract).  The dense reference semantics
the engine is tested against is :func:`repro.core.aggregation.matu_round`.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.aggregation import EPS_DEFAULT, KAPPA_DEFAULT, RHO_DEFAULT
from repro.core.client import ClientDownlink, ClientUpload
from repro.core.engine import EngineConfig, EngineOutput, PackedRound, RoundEngine
from repro.core.unify import unify_with_modulators


@dataclass
class MaTUServerConfig:
    n_tasks: int
    rho: float = RHO_DEFAULT
    eps: float = EPS_DEFAULT
    kappa: int = KAPPA_DEFAULT
    cross_task: bool = True
    uniform_cross: bool = False


class MaTUServer:
    def __init__(self, cfg: MaTUServerConfig, mesh=None):
        """``mesh``: optional jax Mesh — the round then runs sharded
        over the taskvec axis (see the engine's sharding contract);
        None keeps the single-device path byte-for-byte."""
        self.cfg = cfg
        self.engine = RoundEngine(EngineConfig(
            n_tasks=cfg.n_tasks, rho=cfg.rho, eps=cfg.eps, kappa=cfg.kappa,
            cross_task=cfg.cross_task, uniform_cross=cfg.uniform_cross),
            mesh=mesh)
        self.last_similarity: Optional[jax.Array] = None
        self.last_task_vectors: Optional[jax.Array] = None

    def use_mesh(self, mesh) -> None:
        """Install (or clear) the taskvec mesh on the round engine."""
        self.engine.use_mesh(mesh)

    def round(self, uploads: List[ClientUpload], *,
              code_masks: bool = False,
              staleness: Optional[List[int]] = None
              ) -> Dict[int, ClientDownlink]:
        """One server step through the batched round engine.
        ``code_masks`` emits entropy-coded downlink mask streams
        (coded uploads are decoded at pack time either way).

        ``staleness`` (async buffered rounds: one int per upload, the
        rounds elapsed since the upload was dispatched) folds late
        uploads with the staleness-discounted λ — see "Async & fault
        model" in the engine module docstring.  None (every synchronous
        caller) keeps the sync jit programs byte-for-byte."""
        downs, out = self.engine.round(uploads, code_masks=code_masks,
                                       staleness=staleness)
        self._record(out)
        return downs

    def round_chunked(self, uploads, *, chunk_clients: int,
                      code_masks: bool = False,
                      staleness: Optional[List[int]] = None,
                      k_max: Optional[int] = None,
                      sink=None) -> Tuple[Dict[int, ClientDownlink],
                                          Dict[str, int]]:
        """Population-scale server step: stream ``uploads`` (a sequence
        or a zero-arg iterator factory) through the engine's fixed-shape
        chunk buffer — memory O(chunk + T·d) independent of the round's
        client count, bit-identical to :meth:`round` in ref mode (the
        engine's "Population-scale contract").  ``sink``, when given,
        receives each chunk's downlink dict as produced and the
        returned dict stays empty (no per-client state accumulates).
        Returns ``(downlinks, stats)`` with the measured wire-bit
        accounting in ``stats``."""
        downs, out, stats = self.engine.round_chunked(
            uploads, chunk_clients=chunk_clients, code_masks=code_masks,
            staleness=staleness, k_max=k_max, sink=sink)
        self._record(out)
        return downs, stats

    def round_packed(self, packed: PackedRound, *,
                     code_masks: bool = False) -> Dict[int, ClientDownlink]:
        """Server step over an already-packed batch (the strategy's
        pre-packed upload path — skips ``pack_uploads`` entirely)."""
        out = self.start_round(packed)
        return self.finish_round(packed, out, code_masks=code_masks)

    def start_round(self, packed: PackedRound) -> EngineOutput:
        """Dispatch the jitted round WITHOUT materialising downlinks —
        the overlap half of ``round_packed``.  jax dispatch is async,
        so this returns immediately with in-flight arrays; pair with
        :meth:`finish_round` (the pipelined strategy defers that drain
        so the device step overlaps host bookkeeping)."""
        out = self.engine.run_packed(packed)
        self._record(out)
        return out

    def finish_round(self, packed: PackedRound, out: EngineOutput, *,
                     code_masks: bool = False,
                     phase_us: Optional[Dict[str, float]] = None
                     ) -> Dict[int, ClientDownlink]:
        """Materialise per-client downlinks from a dispatched round
        (blocks on the downlink tensors; batched Golomb-Rice encode
        when ``code_masks``)."""
        return self.engine.downlinks(packed, out, code_masks=code_masks,
                                     phase_us=phase_us)

    def _record(self, out: EngineOutput) -> None:
        self.last_similarity = out.similarity
        self.last_task_vectors = out.task_vectors

    def serving_downlink(self, *, code_masks: bool = False,
                         fingerprint: Optional[str] = None
                         ) -> ClientDownlink:
        """Serving handoff: re-unify the LAST round's full task-vector
        set into one all-tasks downlink for a
        :class:`repro.serve.store.ModulatorStore` — row ``t`` of the
        modulators is task id ``t`` (the store keys on position).

        Ships the wire layout: bf16 unified vector and bit-packed
        uint32 mask words, or with ``code_masks`` the rows
        entropy-coded into a Golomb-Rice byte stream.  ``fingerprint``
        stamps the layout manifest the task vectors were flattened
        through (``TaskVectorSpace.fingerprint``) so the store can
        verify the handoff before serving anything.
        """
        if self.last_task_vectors is None:
            raise ValueError("serving_downlink needs a completed round "
                             "(no task vectors recorded yet)")
        tvs = self.last_task_vectors
        unified, masks, lams = unify_with_modulators(tvs)
        if code_masks:
            import numpy as np
            from repro.fed.compression import encode_mask_rows
            from repro.kernels.bitpack import pack_bits_np
            d = int(unified.shape[0])
            stream = encode_mask_rows(pack_bits_np(np.asarray(masks)), d)
            return ClientDownlink(unified.astype(jnp.bfloat16),
                                  jnp.asarray(stream), lams,
                                  fingerprint=fingerprint)
        from repro.kernels.bitpack import pack_bits
        return ClientDownlink(unified.astype(jnp.bfloat16),
                              pack_bits(masks), lams,
                              fingerprint=fingerprint)
