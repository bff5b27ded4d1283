"""Backbones for federated experiments, exposing a flat LoRA task-vector
space (the d-dimensional space MaTU operates in).

Implementations:

* :class:`ArchBackbone` — the general form: wraps ANY reduced config-zoo
  model (``ArchConfig.reduced().build()`` — lm / encdec / ssm / moe /
  vlm / hybrid — or the bespoke vit_b32 ``ViTConfig``) behind the flat
  task-vector interface.  Features come from the model's real forward
  pass; ``lin_features`` linearises that same forward with ``jax.jvp``.
* :class:`MLPBackbone` — fast CPU testbed used by the paper-claim
  benchmarks (frozen 2-layer MLP + LoRA on both layers).
* :class:`ViTBackbone` — ``ArchBackbone("vit_b32")`` with the historical
  constructor, kept for the integration test and the quickstart.

Every backbone exposes:
  d                     — task-vector dimension
  space                 — the :class:`~repro.common.tree.TaskVectorSpace`
                          layout manifest for d
  fingerprint           — the manifest fingerprint (layout agreement)
  features(tv, x)       — (B, feat_out) features under flat LoRA vector tv
  features_tree(dt, x)  — same features from the model-space delta pytree
                          (the pytree-aware trainer's path)
  lin_features(tv, x)   — NTK-linearised features at the pretrained
                          point (jax.jvp), for the NTK-FedAvg baseline
  split_point           — index splitting "shared" vs "personal" slices
                          of the flat vector (FedPer)

Task-vector layout contract
---------------------------
The flat d-axis every backbone exposes is DEFINED by its
:class:`~repro.common.tree.TaskVectorSpace` manifest: LoRA adapter
leaves (delta over the standard A-gaussian/B-zero init, so τ = 0 is
exactly the pretrained point) in canonical tree order, each raveled
C-order into a contiguous ``[offset, offset + size)`` slice.  The
manifest's ``fingerprint`` is the layout identity: holders of the same
task must agree on it before a round (the simulator/strategy refuse to
aggregate otherwise — ``TaskVectorLayoutError``), because the engine
merges task vectors coordinate by coordinate.  Which matmuls carry
adapters is declared per family in ``configs.base`` (``lora_targets``)
and verified against the manifest at backbone construction.  Mixed
rounds flatten each client's delta through its own manifest and
zero-pad to the round's common d — a multiple of 256 coords
(``8 × bitpack.WORD_BITS`` = one ``ref.LAMBDA_BLOCK``, the PR 3
word-boundary rule), so the packed uint32 wire words and the λ
reduction blocks of every backbone's prefix stay aligned.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.tree import (TaskVectorSpace, tree_add, tree_dot,
                               tree_flatten_vector, tree_unflatten_vector)
from repro.configs.base import (ZOO_FAMILIES, check_lora_targets, load_arch,
                                lora_targets_for)

# the word-boundary rule: common-d padding quantum for mixed rounds
# (8 × bitpack.WORD_BITS == ref.LAMBDA_BLOCK)
D_BOUNDARY = 256


def round_up_d(d: int, boundary: int = D_BOUNDARY) -> int:
    """Round a task-vector dimension up to the wire word boundary."""
    return -(-int(d) // boundary) * boundary


class MLPBackbone:
    def __init__(self, feat_dim: int, hidden: int = 64, lora_rank: int = 4,
                 seed: int = 0):
        k = jax.random.PRNGKey(seed)
        k1, k2, k3, k4 = jax.random.split(k, 4)
        self.w1 = jax.random.normal(k1, (feat_dim, hidden)) / math.sqrt(feat_dim)
        self.w2 = jax.random.normal(k2, (hidden, hidden)) / math.sqrt(hidden)
        self.rank = lora_rank
        # The task vector is a DELTA over the standard LoRA init
        # (A gaussian, B zero): τ = 0 is exactly the pretrained point,
        # and gradients flow (A=B=0 would be a saddle).
        self.lora0 = {
            "l1": {"a": jax.random.normal(k3, (feat_dim, lora_rank)) / math.sqrt(feat_dim),
                   "b": jnp.zeros((lora_rank, hidden))},
            "l2": {"a": jax.random.normal(k4, (hidden, lora_rank)) / math.sqrt(hidden),
                   "b": jnp.zeros((lora_rank, hidden))},
        }
        self.space = TaskVectorSpace.from_tree(self.lora0)
        self.template = self.space.template()
        self.d = self.space.d
        self.fingerprint = self.space.fingerprint
        self.feat_out = hidden
        # FedPer split: layer-1 LoRA shared, layer-2 LoRA personal
        self.split_point = int(self.template["l1"]["a"].size + self.template["l1"]["b"].size)

    def _unflatten(self, tv: jax.Array):
        delta = tree_unflatten_vector(tv, self.template)
        return tree_add(self.lora0, delta)

    def features_tree(self, delta, x: jax.Array) -> jax.Array:
        l = tree_add(self.lora0, delta)
        h = x @ (self.w1 + l["l1"]["a"] @ l["l1"]["b"])
        h = jax.nn.gelu(h)
        h = h @ (self.w2 + l["l2"]["a"] @ l["l2"]["b"])
        return jax.nn.gelu(h)

    def features(self, tv: jax.Array, x: jax.Array) -> jax.Array:
        return self.features_tree(tree_unflatten_vector(tv, self.template), x)

    def lin_features(self, tv: jax.Array, x: jax.Array) -> jax.Array:
        zero = jnp.zeros_like(tv)
        f0, jvp_out = jax.jvp(lambda v: self.features(v, x), (zero,), (tv,))
        return f0 + jvp_out


class ArchBackbone:
    """Flat LoRA task-vector interface over any reduced zoo model.

    ``arch`` is a config-zoo id (``qwen2-0.5b``, ``whisper-large-v3``,
    ``xlstm-1.3b``, ``granite-moe-3b-a800m``, …) or ``vit_b32``.  The
    pretrained point is the model's random init; the task vector is the
    flat delta over the standard LoRA init (A gaussian, B zero), laid
    out by ``self.space`` (see the module's layout contract).

    Features are the model's REAL forward pass:

    * vit — patches through the ViT trunk, CLS features;
    * lm-kind (dense/moe/ssm/hybrid/vlm) — the synthetic feature vector
      enters as ``ctx_len`` projected ``extra_embeds`` positions ahead
      of one query token; features are the final hidden state at the
      query position (so they depend on every block's adapters);
    * encdec (audio) — the feature vector enters as projected encoder
      frames; features are the decoder's final hidden state (through
      cross-attention, so encoder AND decoder adapters matter).

    The input projection is a fixed random matrix — part of the frozen
    backbone, never trained.
    """

    def __init__(self, arch: str, feat_dim: Optional[int] = None, *,
                 seed: int = 0, ctx_len: int = 4, reduced: bool = True):
        self.arch = arch
        self.kind: str
        k = jax.random.PRNGKey(seed)
        if arch in ("vit", "vit_b32"):
            from repro.configs.vit_b32 import CONFIG, build, reduced_vit
            cfg = reduced_vit() if reduced else CONFIG
            self.cfg = cfg
            self.model = build(cfg)
            self.kind = "vit"
            self.params = self.model.init(k)
            self.lora0 = self.model.lora_init(jax.random.PRNGKey(seed + 1),
                                              cfg.lora_rank)
            self.feat_out = cfg.d_model
            self.feat_dim = cfg.patch_dim * cfg.n_patches
        else:
            cfg = load_arch(arch)
            self.cfg = cfg = cfg.reduced() if reduced else cfg
            am = cfg.build()
            self.model = am.model
            self.kind = am.kind          # "lm" | "encdec"
            self.params = am.init(k)
            self.lora0 = am.lora_init(jax.random.PRNGKey(seed + 1))
            self.feat_out = cfg.d_model
            if feat_dim is None:
                raise ValueError(f"{arch}: feat_dim is required for "
                                 "lm/encdec backbones")
            self.feat_dim = int(feat_dim)
            self.ctx_len = int(ctx_len)
            # fixed random input projection: synthetic features ->
            # ctx_len pseudo-token embeddings (frozen, untrained)
            pk = jax.random.fold_in(k, 0xF0)
            self.in_proj = (jax.random.normal(
                pk, (self.feat_dim, self.ctx_len * cfg.d_model))
                / math.sqrt(self.feat_dim)).astype(jnp.float32)

        self.space = TaskVectorSpace.from_tree(self.lora0)
        self.template = self.space.template()
        self.d = self.space.d
        self.fingerprint = self.space.fingerprint
        # declared targeting rules vs the actual manifest — fail loudly
        # at construction, not mid-round
        check_lora_targets(lora_targets_for(self.cfg),
                           [l.path for l in self.space.leaves],
                           context=f"{arch}")
        # FedPer split at the leaf boundary nearest d/2
        half = self.d // 2
        self.split_point = min((l.offset for l in self.space.leaves
                                if l.offset >= half), default=half)

    # -- feature paths ------------------------------------------------------
    def _embed_ctx(self, x: jax.Array) -> jax.Array:
        b = x.shape[0]
        return (x @ self.in_proj).reshape(b, self.ctx_len,
                                          self.cfg.d_model)

    def features_tree(self, delta, x: jax.Array) -> jax.Array:
        """(B, feat_out) features from the model-space delta pytree."""
        lora = tree_add(self.lora0, delta)
        if self.kind == "vit":
            # x arrives either flat (B, n_patches*patch_dim) or
            # patch-sized (B, patch_dim) — the latter is tiled across
            # patches, which keeps synthetic rotation tasks undoable by
            # patch-level LoRA.
            cfg = self.cfg
            if x.shape[-1] == cfg.patch_dim:
                patches = jnp.broadcast_to(
                    x[:, None, :], (x.shape[0], cfg.n_patches, cfg.patch_dim))
            else:
                patches = x.reshape(x.shape[0], cfg.n_patches, cfg.patch_dim)
            return self.model.features(self.params, patches, lora=lora)
        b = x.shape[0]
        tokens = jnp.zeros((b, 1), jnp.int32)
        ctx = self._embed_ctx(x)
        if self.kind == "encdec":
            hidden = self.model.forward(self.params, tokens, ctx, lora=lora,
                                        return_hidden=True)
            return hidden[:, -1]
        hidden, _aux = self.model.forward(self.params, tokens, lora=lora,
                                          extra_embeds=ctx,
                                          return_hidden=True)
        return hidden[:, -1]

    def features(self, tv: jax.Array, x: jax.Array) -> jax.Array:
        return self.features_tree(self.space.unflatten(tv), x)

    def lin_features(self, tv: jax.Array, x: jax.Array) -> jax.Array:
        zero = jnp.zeros_like(tv)
        f0, jvp_out = jax.jvp(lambda v: self.features(v, x), (zero,), (tv,))
        return f0 + jvp_out


class ViTBackbone(ArchBackbone):
    """The paper's model family (ViT + LoRA) behind the historical
    constructor; now just :class:`ArchBackbone` on vit_b32."""

    def __init__(self, seed: int = 0, reduced: bool = True):
        super().__init__("vit_b32", seed=seed, reduced=reduced)


def make_zoo_backbones(feat_dim: int, families=None, *, seed: int = 0,
                       ctx_len: int = 4) -> Dict[str, ArchBackbone]:
    """One :class:`ArchBackbone` per zoo family (``ZOO_FAMILIES``).

    ``feat_dim`` must equal the reduced vit patch_dim (32) when the vit
    family is included — the synthetic constellation feeds every
    backbone the same (B, feat_dim) batches."""
    out: Dict[str, ArchBackbone] = {}
    for fam in (families or list(ZOO_FAMILIES)):
        arch = ZOO_FAMILIES[fam]
        bb = ArchBackbone(arch, feat_dim=None if fam == "vit" else feat_dim,
                          seed=seed, ctx_len=ctx_len)
        if fam == "vit" and bb.cfg.patch_dim != feat_dim:
            raise ValueError(
                f"vit patch_dim {bb.cfg.patch_dim} != feat_dim {feat_dim}: "
                "the constellation must feed patch-sized features")
        out[fam] = bb
    return out
