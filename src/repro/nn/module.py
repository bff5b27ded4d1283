"""Minimal functional module system (pure JAX, no flax).

A ``Module`` is a config-carrying object with three methods:

* ``init(key) -> params``      nested dict of jnp arrays
* ``axes() -> axes``           same structure, leaves = logical-axis tuples
* ``__call__(params, ...)``    pure function of (params, inputs)

Parameters are plain pytrees, so optimizers, task vectors, LoRA and
checkpointing all operate with ``jax.tree_util`` directly.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

PyTree = Any


def _split(key, n):
    return jax.random.split(key, n)


class Module:
    """Base class; subclasses define init/axes/__call__."""

    name: str = ""

    def init(self, key) -> PyTree:  # pragma: no cover - abstract
        raise NotImplementedError

    def axes(self) -> PyTree:  # pragma: no cover - abstract
        raise NotImplementedError

    # -- helpers ---------------------------------------------------------
    def init_stacked(self, key, n: int) -> PyTree:
        """Stack ``n`` independent inits along a leading ``layers`` axis."""
        keys = _split(key, n)
        return jax.vmap(self.init)(keys)

    def stacked_axes(self) -> PyTree:
        ax = self.axes()
        return jax.tree_util.tree_map(
            lambda a: ("layers",) + tuple(a or ()),
            ax,
            is_leaf=lambda x: x is None or isinstance(x, tuple),
        )


def dense_init(key, in_dim: int, out_dim: int, *, dtype=jnp.float32,
               scale: Optional[float] = None) -> jax.Array:
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim)) * scale).astype(dtype)


class Dense(Module):
    """y = x @ W (+ b). LoRA-aware: pass a mirrored ``lora`` subtree."""

    def __init__(self, in_dim: int, out_dim: int, *, bias: bool = False,
                 axes: Tuple[Optional[str], Optional[str]] = (None, None),
                 dtype=jnp.float32, scale: Optional[float] = None):
        self.in_dim, self.out_dim, self.bias = in_dim, out_dim, bias
        self._axes, self.dtype, self.scale = axes, dtype, scale

    def init(self, key):
        p = {"w": dense_init(key, self.in_dim, self.out_dim, dtype=self.dtype, scale=self.scale)}
        if self.bias:
            p["b"] = jnp.zeros((self.out_dim,), self.dtype)
        return p

    def axes(self):
        a = {"w": self._axes}
        if self.bias:
            a["b"] = (self._axes[1],)
        return a

    def __call__(self, params, x, lora: Optional[PyTree] = None):
        w = params["w"]
        y = jnp.einsum("...i,io->...o", x, w)
        if lora is not None and "a" in lora:
            a = lora["a"]
            if isinstance(a, dict) or a.ndim == 3:
                # multi-tenant routed forms (repro.serve.router)
                y = y + self._lora_routed(x, lora)
            else:
                # LoRA: y += (x @ A) @ B * (alpha / r); A:(in,r) B:(r,out)
                r = a.shape[-1]
                scaling = lora.get("alpha", jnp.asarray(float(r), x.dtype)) / r
                y = y + jnp.einsum("...r,ro->...o", jnp.einsum("...i,ir->...r", x, a), lora["b"]) * scaling
        if self.bias:
            y = y + params["b"]
        return y

    @staticmethod
    def _lora_routed(x, lora):
        """Multi-tenant LoRA branch; ``x`` is (B, in) or (B, S, in) and
        per-request ``alpha`` (and ``lam``) are (B,).  Two forms:

        * dense-routed — ``a``/``b`` are materialised per-request
          factors (B, in, r)/(B, r, out);
        * fused — each factor is ``{"base", "tau", "words"}`` and the
          ``ops.modulated_matmul`` kernel builds ``base + lam·m⊙tau``
          in VMEM (word-unpack + λ-scale fused into the dot), rounded
          as the dense form's ``lora0 + unflatten(modulate(...))`` leaf
          is rounded.

        Both contract through the same kernel dot and share every op
        after it, so the two forms compute the same arithmetic."""
        from repro.kernels import ops as _kops  # local: keep nn dep-free
        a, b = lora["a"], lora["b"]
        squeeze = x.ndim == 2
        x3 = x[:, None, :] if squeeze else x
        if isinstance(a, dict):
            r = a["base"].shape[-1]
            h = _kops.modulated_matmul(x3, a["base"], a["tau"], a["words"],
                                       lora["lam"])
            yl = _kops.modulated_matmul(h, b["base"], b["tau"], b["words"],
                                        lora["lam"])
        else:
            r = a.shape[-1]
            yl = _kops.routed_matmul(_kops.routed_matmul(x3, a), b)
        yl = yl * (lora["alpha"].astype(x.dtype) / r)[:, None, None]
        return yl[:, 0] if squeeze else yl

    # LoRA factory -------------------------------------------------------
    def lora_init(self, key, rank: int, *, alpha: Optional[float] = None, dtype=None):
        dtype = dtype or self.dtype
        ka, _ = _split(key, 2)
        return {
            "a": (jax.random.normal(ka, (self.in_dim, rank)) / math.sqrt(self.in_dim)).astype(dtype),
            "b": jnp.zeros((rank, self.out_dim), dtype),
            "alpha": jnp.asarray(float(alpha if alpha is not None else rank), dtype),
        }

    def lora_axes(self):
        return {"a": (self._axes[0], "lora"), "b": ("lora", self._axes[1]), "alpha": None}


class Embedding(Module):
    def __init__(self, vocab: int, dim: int, *, dtype=jnp.float32,
                 axes: Tuple[str, str] = ("vocab", "embed")):
        self.vocab, self.dim, self.dtype, self._axes = vocab, dim, dtype, axes

    def init(self, key):
        return {"table": (jax.random.normal(key, (self.vocab, self.dim)) * 0.02).astype(self.dtype)}

    def axes(self):
        return {"table": self._axes}

    def __call__(self, params, ids):
        return jnp.take(params["table"], ids, axis=0)

    def attend(self, params, x):
        """Tied readout: logits = x @ table^T."""
        return jnp.einsum("...d,vd->...v", x, params["table"])


class RMSNorm(Module):
    def __init__(self, dim: int, *, eps: float = 1e-6, dtype=jnp.float32):
        self.dim, self.eps, self.dtype = dim, eps, dtype

    def init(self, key):
        del key
        return {"scale": jnp.ones((self.dim,), self.dtype)}

    def axes(self):
        return {"scale": ("embed",)}

    def __call__(self, params, x):
        dt = x.dtype
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (y * params["scale"].astype(jnp.float32)).astype(dt)


class LayerNorm(Module):
    def __init__(self, dim: int, *, eps: float = 1e-5, dtype=jnp.float32):
        self.dim, self.eps, self.dtype = dim, eps, dtype

    def init(self, key):
        del key
        return {"scale": jnp.ones((self.dim,), self.dtype),
                "bias": jnp.zeros((self.dim,), self.dtype)}

    def axes(self):
        return {"scale": ("embed",), "bias": ("embed",)}

    def __call__(self, params, x):
        dt = x.dtype
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + self.eps)
        return (y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)).astype(dt)
