"""Per-request task routing: one decode batch, many tasks.

``route_batch`` turns a per-request task-id list into the *routed*
LoRA pytree the model consumes, in one of two forms (both detected by
``repro.nn.module.Dense.__call__`` — task ids are DATA resolved here,
eagerly, outside jit, so the compiled decode program depends only on
the batch size, never on which tasks are in the mix):

dense-routed (``fused=False``)
    Each request's materialised adapter (``store.adapter`` — LRU-cached
    ``lora0 + unflatten(λ·m⊙τ)``) is gathered and stacked along a new
    per-request axis at position 1: leaves go ``(L, ...) -> (L, B, ...)``
    so the model's layers-scan still slices axis 0 and every Dense sees
    per-request ``(B, in, r)`` factors.

fused (``fused=True``)
    No adapter is materialised at all.  For every Dense LoRA site the
    routed tree carries ``{"base", "tau", "words"}`` per factor — the
    shared base leaf, the unified vector's model-space slice, and each
    request's *packed* mask bits for that leaf, re-aligned out of the
    whole-d wire row with ``bitpack.slice_bits`` (never unpacked on the
    host) — plus per-request ``lam`` and the densely reconstructed
    per-request ``alpha``.  The modulated weight
    ``base + λ·m⊙τ`` is then built in VMEM by the
    ``ops.modulated_matmul`` kernel, fused into the LoRA matmul.
    Sites whose per-layer factor size is not word-aligned (% 32 != 0)
    fall back to dense-routed leaves for that site only.

Dense-routed is bit-identical to single-tenant decode with the dense
unpacked modulator: ``(λ·m)⊙τ`` is IEEE-exact ``λ·where(m, τ, 0)``
for mask bits in {0, 1}, and the per-request batched einsum contracts
identically to the broadcast one.  The fused form builds each
effective weight with ``ref.modulated_weight`` — the materialised
adapter's own rounding to the leaf dtype — and both forms contract
through the same kernel dot (``ops.modulated_matmul`` /
``ops.routed_matmul``), so the fused form is bit-identical to
unpack-then-matmul *within the same compiled program* and
token-identical end to end.  With fp32 leaves its weights can still
sit one rounding of the modulated delta off the dense path's, because
XLA contracts the in-jit build into an fma (the product feeds the add
unrounded) while a materialised adapter rounds it first; a bf16 leaf's
cast between the two prevents that — tests/test_serve_multitenant.py
pins down all three contracts.

``MultiTenantDecoder`` is the serving front end: it routes a batch,
runs :func:`repro.serve.generate.generate` through ONE jitted program
reused across task mixes, and exposes the compile count so the
one-program contract is testable.
"""

from __future__ import annotations

import functools
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import bitpack
from repro.kernels.ref import modulated_weight
from repro.serve.generate import GenerationConfig, generate
from repro.serve.store import ModulatorStore

PyTree = Any


def _lora_sites(node, prefix: str = ""):
    """Yield ``(path_prefix, site_dict)`` for every Dense LoRA site —
    a dict node carrying array leaves ``a``/``b`` (+ ``alpha``) — in a
    nested lora pytree.  Paths match the TaskVectorSpace rendering."""
    if not isinstance(node, dict):
        return
    if "a" in node and "b" in node and not isinstance(node["a"], dict):
        yield prefix, node
        return
    for key in node:
        sub = f"{prefix}/{key}" if prefix else str(key)
        yield from _lora_sites(node[key], sub)


def _stack_requests(adapters: Sequence[PyTree]) -> PyTree:
    """Stack per-request adapter pytrees along a new axis 1 — after the
    leading layers axis, so the model's unit scan still slices layers
    and each slice carries the per-request axis first."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=1), *adapters)


def _layer_words(rows: jax.Array, offset: int, per_layer: int,
                 n_layers: int) -> jax.Array:
    """(B, W_wire) whole-d packed rows -> (L, B, ceil(per_layer/32))
    re-aligned per-layer mask words of one manifest leaf (the leaf's
    flat block is C-order over (L, ...) — layer l owns bits
    ``[offset + l·per_layer, offset + (l+1)·per_layer)``)."""
    return jnp.stack([bitpack.slice_bits(rows, offset + l * per_layer,
                                         per_layer)
                      for l in range(n_layers)], axis=0)


def _site_dense_routed(site0, tau_site, rows, lam, space, prefix):
    """Dense-routed fallback for one site: reconstruct each request's
    leaves ``leaf0 + λ·m⊙τ`` densely and lay them out (L, B, ...)."""
    out = {}
    for key, leaf0 in site0.items():
        spec = space.by_path(f"{prefix}/{key}")
        bits = bitpack.unpack_bits(
            bitpack.slice_bits(rows, spec.offset, spec.size),
            spec.size, jnp.float32).reshape((rows.shape[0],) + spec.shape)
        lam_b = lam.reshape((-1,) + (1,) * len(spec.shape))
        val = modulated_weight(leaf0[None], tau_site[key][None], bits, lam_b)
        out[key] = jnp.moveaxis(val, 0, 1)  # (B, L, ...) -> (L, B, ...)
    return out


def route_batch(store: ModulatorStore, task_ids: Sequence[int], *,
                fused: bool = False) -> PyTree:
    """Routed LoRA pytree for one batch of per-request task ids (see
    module docstring for the two forms).  Runs eagerly: task ids are
    resolved to arrays here so the jitted decode program never traces
    on them."""
    ids = [int(t) for t in task_ids]
    if not ids:
        raise ValueError("route_batch needs at least one request")
    if not fused:
        return _stack_requests([store.adapter(t) for t in ids])

    space = store.space
    tau_tree = store.tau_tree()
    rows = jnp.stack([store.mask_words(t) for t in ids])      # (B, W)
    lam = jnp.stack([store.lam(t) for t in ids])              # (B,)

    def build(node0, tau_node, prefix=""):
        if (isinstance(node0, dict) and "a" in node0 and "b" in node0
                and not isinstance(node0["a"], dict)):
            return build_site(node0, tau_node, prefix)
        return {k: build(node0[k], tau_node[k],
                         f"{prefix}/{k}" if prefix else str(k))
                for k in node0}

    def build_site(site0, tau_site, prefix):
        a_spec = space.by_path(f"{prefix}/a")
        b_spec = space.by_path(f"{prefix}/b")
        n_layers = a_spec.shape[0]
        a_sz = int(np.prod(a_spec.shape[1:]))
        b_sz = int(np.prod(b_spec.shape[1:]))
        if a_sz % 32 or b_sz % 32:
            return _site_dense_routed(site0, tau_site, rows, lam, space,
                                      prefix)
        fusedsite = {
            "a": {"base": site0["a"],
                  "tau": tau_site["a"],
                  "words": _layer_words(rows, a_spec.offset, a_sz, n_layers)},
            "b": {"base": site0["b"],
                  "tau": tau_site["b"],
                  "words": _layer_words(rows, b_spec.offset, b_sz, n_layers)},
            "lam": jnp.broadcast_to(lam[None, :], (n_layers, len(ids))),
        }
        if "alpha" in site0:
            al_spec = space.by_path(f"{prefix}/alpha")
            bits = bitpack.unpack_bits(
                bitpack.slice_bits(rows, al_spec.offset, al_spec.size),
                al_spec.size, jnp.float32)                    # (B, L)
            alpha_eff = modulated_weight(site0["alpha"][None, :],
                                         tau_site["alpha"][None, :], bits,
                                         lam[:, None])
            fusedsite["alpha"] = alpha_eff.T                  # (L, B)
        return fusedsite

    return build(store.lora0, tau_tree)


class MultiTenantDecoder:
    """Batched multi-tenant decode front end over one backbone.

    One instance = one compiled decode program per (batch, prompt)
    shape, reused across every task mix: routing happens eagerly in
    :func:`route_batch`, so the jitted generation only ever sees
    fixed-shape routed-lora pytrees.  ``compile_count()`` exposes the
    jit cache size — the one-program contract is asserted in tests.
    """

    def __init__(self, model, params, store: ModulatorStore, *,
                 fused: bool = False,
                 cfg: GenerationConfig = GenerationConfig()):
        self.model = model
        self.params = params
        self.store = store
        self.fused = fused
        self.cfg = cfg
        self._gen = jax.jit(functools.partial(generate, model),
                            static_argnames=("cfg", "max_len"))

    def generate(self, prompts: jax.Array, task_ids: Sequence[int], *,
                 rng: Optional[jax.Array] = None,
                 max_len: Optional[int] = None) -> jax.Array:
        """prompts (B, S) int32 + per-request task ids (len B) ->
        (B, S + max_new_tokens) through the routed decode program."""
        b = int(prompts.shape[0])
        if len(task_ids) != b:
            raise ValueError(f"{len(task_ids)} task ids for batch {b}")
        lora = route_batch(self.store, task_ids, fused=self.fused)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        max_len = max_len or (int(prompts.shape[1])
                              + self.cfg.max_new_tokens + 8)
        return self._gen(self.params, lora, prompts, self.cfg, rng=rng,
                         max_len=max_len)

    def compile_count(self) -> int:
        """Number of compiled decode programs behind this decoder."""
        return int(self._gen._cache_size())
