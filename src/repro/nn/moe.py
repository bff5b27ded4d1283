"""Mixture-of-Experts FFN with TPU-native expert parallelism.

Used by deepseek-v2 (2 shared + 160 routed, top-6, MLA attention) and
granite-3b-moe (40 routed, top-8).

Design (DESIGN.md §5): under tensor parallelism the token activations
are already replicated across the ``model`` mesh axis.  We exploit that
replication instead of an all-to-all: inside a ``shard_map`` over the
mesh, every model-shard selects — from its *replicated* local tokens —
the rows routed to *its* slice of the experts (local scatter into an
(E_local, C, d) capacity buffer), runs its experts, scatters results
back to token order, and a single ``psum`` over ``model`` combines the
partial outputs.  That psum replaces BOTH the EP combine all-to-all and
the usual TP FFN all-reduce, so MoE costs the same collective as a
dense TP FFN.

When the expert count does not divide the model axis (granite: 40 on a
16-way axis), we fall back to *token-parallel* MoE: tokens are split
over ``model`` along the sequence axis, every shard runs all (small)
experts on its token slice, and an ``all_gather`` over ``model``
restores the sequence.  Decode steps (S=1) run fully replicated — the
work is negligible.

Routed experts are frozen under PEFT (LoRA attaches to attention +
shared experts), keeping MaTU task vectors dense — see DESIGN.md §4.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.nn.module import Module, dense_init
from repro.nn.mlp import SwiGLU
from repro.nn.sharding import current_mesh

PyTree = Any

def _round8(x: int) -> int:
    return max(8, ((x + 7) // 8) * 8)


class MoE(Module):
    def __init__(
        self,
        d_model: int,
        d_ff: int,
        n_experts: int,
        top_k: int,
        *,
        n_shared: int = 0,
        shared_d_ff: Optional[int] = None,
        capacity_factor: float = 1.25,
        dtype=jnp.float32,
    ):
        self.d_model, self.d_ff = d_model, d_ff
        self.n_experts, self.top_k = n_experts, top_k
        self.n_shared = n_shared
        self.capacity_factor = capacity_factor
        self.dtype = dtype
        self.shared = (
            SwiGLU(d_model, (shared_d_ff or d_ff) * n_shared, dtype=dtype) if n_shared else None
        )

    # -- params (experts stacked on a leading E axis) ---------------------
    def init(self, key):
        kr, kg, ku, kd, ks = jax.random.split(key, 5)
        e, d, f = self.n_experts, self.d_model, self.d_ff
        p = {
            "router": {"w": dense_init(kr, d, e, dtype=self.dtype)},
            "experts": {
                "gate": jax.vmap(lambda k: dense_init(k, d, f, dtype=self.dtype))(jax.random.split(kg, e)),
                "up": jax.vmap(lambda k: dense_init(k, d, f, dtype=self.dtype))(jax.random.split(ku, e)),
                "down": jax.vmap(lambda k: dense_init(k, f, d, dtype=self.dtype))(jax.random.split(kd, e)),
            },
        }
        if self.shared is not None:
            p["shared"] = self.shared.init(ks)
        return p

    def axes(self):
        ep = self._expert_parallel()
        # Expert-parallel: experts over `model`; additionally the embed
        # dim is sharded over `data` at REST (ZeRO-3 style — the
        # shard_map boundary all-gathers one layer's slice per scan
        # step).  Without EP (granite): per-expert ffn dim over `model`.
        e_ax = "experts" if ep else None
        emb_ax = "expert_embed" if ep else "embed"
        f_ax = None if ep else "moe_mlp"
        a = {
            "router": {"w": ("embed", None)},
            "experts": {
                "gate": (e_ax, emb_ax, f_ax),
                "up": (e_ax, emb_ax, f_ax),
                "down": (e_ax, f_ax, emb_ax),
            },
        }
        if self.shared is not None:
            a["shared"] = self.shared.axes()
        return a

    def lora_init(self, key, rank: int):
        return {"shared": self.shared.lora_init(key, rank)} if self.shared is not None else {}

    def lora_axes(self):
        return {"shared": self.shared.lora_axes()} if self.shared is not None else {}

    # -- mesh helpers ------------------------------------------------------
    def _mesh_info(self):
        mesh = current_mesh()
        if mesh is None or "model" not in mesh.shape:
            return None
        return mesh

    def _expert_parallel(self, mesh=None) -> bool:
        mesh = mesh or self._mesh_info()
        if mesh is None:
            return False
        return self.n_experts % mesh.shape["model"] == 0

    # -- local (per-shard) MoE compute ------------------------------------
    def _local_moe(self, router_w, experts, xt, e0: int, n_local: int, cap: int):
        """xt (T, d) local tokens; experts hold slices [e0, e0+n_local).

        Returns (out (T, d), aux_loss scalar). Scatter-based dispatch:
        loops over the k choices (unrolled, k<=8) so peak extra memory
        is one (T, d) buffer instead of (T*k, d).
        """
        t, d = xt.shape
        logits = jnp.einsum("td,de->te", xt, router_w).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, self.top_k)  # (T, k)
        gate_vals = (gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)).astype(xt.dtype)

        # position of each (token, choice) within its local expert's capacity
        flat_e = gate_idx.reshape(-1)  # (T*k,) global expert ids, row-major (token-major)
        local = (flat_e >= e0) & (flat_e < e0 + n_local)
        le = jnp.where(local, flat_e - e0, n_local)  # dummy bin for foreign rows
        onehot = jax.nn.one_hot(le, n_local + 1, dtype=jnp.int32)
        pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=-1)  # (T*k,)
        keep = local & (pos < cap)
        le_c = jnp.where(keep, le, n_local)       # scatter drops land in row n_local
        pos_c = jnp.where(keep, pos, 0)

        le_k = le_c.reshape(t, self.top_k)
        pos_k = pos_c.reshape(t, self.top_k)
        keep_k = keep.reshape(t, self.top_k)

        buf = jnp.zeros((n_local + 1, cap, d), xt.dtype)
        for j in range(self.top_k):
            buf = buf.at[le_k[:, j], pos_k[:, j]].add(xt * keep_k[:, j, None].astype(xt.dtype))
        buf = buf[:n_local]  # (E_local, C, d)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, experts["gate"]))
        h = h * jnp.einsum("ecd,edf->ecf", buf, experts["up"])
        eout = jnp.einsum("ecf,efd->ecd", h, experts["down"])
        eout = jnp.concatenate([eout, jnp.zeros((1, cap, d), eout.dtype)], axis=0)

        out = jnp.zeros((t, d), xt.dtype)
        for j in range(self.top_k):
            rows = eout[le_k[:, j], pos_k[:, j]]  # (T, d); dummy row = 0
            out = out + rows * (gate_vals[:, j] * keep_k[:, j].astype(xt.dtype))[:, None]

        # Switch-style load-balance aux (over local view of the router)
        me = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], self.n_experts, dtype=jnp.float32), axis=0)
        ce = jnp.mean(probs, axis=0)
        aux = self.n_experts * jnp.sum(me * ce)
        return out, aux

    def capacity(self, n_tokens: int) -> int:
        return _round8(int(self.capacity_factor * n_tokens * self.top_k / self.n_experts))

    def _chunked_local_moe(self, router_w, experts, xt, e0, n_local,
                           token_chunk: int = 8192):
        """PERF-3: scan over token chunks so the dispatch buffers
        ((E_local, C, d) + the k unrolled (T, d) scatter/gather rows)
        scale with the chunk, not the full local token count — measured
        ~2x peak-memory reduction on deepseek-v2 train_4k.  Capacity is
        enforced per chunk (slightly stricter than global capacity;
        standard practice)."""
        t, d = xt.shape
        if t <= token_chunk or t % token_chunk != 0:
            cap = self.capacity(t)
            return self._local_moe(router_w, experts, xt, e0, n_local, cap)
        n_chunks = t // token_chunk
        cap = self.capacity(token_chunk)

        @jax.checkpoint
        def body(carry, xc):
            out, aux = self._local_moe(router_w, experts, xc, e0, n_local, cap)
            return carry, (out, aux)

        _, (outs, auxs) = jax.lax.scan(
            body, None, xt.reshape(n_chunks, token_chunk, d))
        return outs.reshape(t, d), jnp.mean(auxs)

    # -- public call -------------------------------------------------------
    def __call__(self, params, x, lora: Optional[PyTree] = None):
        """x (B, S, d) -> (B, S, d). Sets ``self.last_aux``."""
        lora = lora or {}
        b, s, d = x.shape
        mesh = self._mesh_info()

        if mesh is None:
            xt = x.reshape(b * s, d)
            out, aux = self._local_moe(
                params["router"]["w"], params["experts"], xt, 0, self.n_experts,
                self.capacity(b * s))
            y = out.reshape(b, s, d)
        else:
            y, aux = self._sharded_moe(params, x, mesh)

        if self.shared is not None:
            y = y + self.shared(params["shared"], x, lora.get("shared"))
        self.last_aux = aux
        return y

    def _sharded_moe(self, params, x, mesh):
        b, s, d = x.shape
        n_model = mesh.shape["model"]
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        n_data = 1
        for a in batch_axes:
            n_data *= mesh.shape[a]
        batch_spec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
        b_shard = batch_spec if b % max(n_data, 1) == 0 and n_data > 1 else None
        b_loc = b // n_data if b_shard is not None else b

        ep = self._expert_parallel(mesh)
        token_parallel = (not ep) and (s % n_model == 0) and s > 1

        all_axes = tuple(mesh.axis_names)

        if ep:
            n_local = self.n_experts // n_model
            x_spec = P(b_shard, None, None)
            e_spec = {"gate": P("model", None, None), "up": P("model", None, None),
                      "down": P("model", None, None)}

            def fn(router_w, experts, xs):
                idx = jax.lax.axis_index("model")
                xt = xs.reshape(-1, d)
                out, aux = self._chunked_local_moe(router_w, experts, xt,
                                                   idx * n_local, n_local)
                out = jax.lax.psum(out, "model")
                return out.reshape(xs.shape), jax.lax.pmean(aux, all_axes)
        elif token_parallel:
            cap = self.capacity(b_loc * (s // n_model))
            x_spec = P(b_shard, "model", None)
            e_spec = {"gate": P(None, None, None), "up": P(None, None, None),
                      "down": P(None, None, None)}

            def fn(router_w, experts, xs):
                xt = xs.reshape(-1, d)
                out, aux = self._local_moe(router_w, experts, xt, 0, self.n_experts, cap)
                return out.reshape(xs.shape), jax.lax.pmean(aux, all_axes)
        else:
            # replicated over model (decode steps / tiny S): every shard
            # computes all experts on its batch slice.
            cap = self.capacity(b_loc * s)
            x_spec = P(b_shard, None, None)
            e_spec = {"gate": P(None, None, None), "up": P(None, None, None),
                      "down": P(None, None, None)}

            def fn(router_w, experts, xs):
                xt = xs.reshape(-1, d)
                out, aux = self._local_moe(router_w, experts, xt, 0, self.n_experts, cap)
                return out.reshape(xs.shape), jax.lax.pmean(aux, all_axes)

        y, aux = jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(P(), e_spec, x_spec),
            out_specs=(x_spec, P()),
            check_vma=False,
        )(params["router"]["w"], params["experts"], x)
        return y, aux
