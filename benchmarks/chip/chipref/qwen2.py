"""Plain reference of the Qwen2 decoder with LoRA (arXiv:2407.10671; the
published ``config.json``), in float32 at ``highest`` matmul precision.

One layer, as published: x += o_proj(attn(rope(q), rope(k), v)) with
q/k/v projections carrying biases, 2 KV heads shared by groups of query
heads, causal softmax in float32 scaled by 1/sqrt(head_dim), RMSNorm
(eps from the config) before attention and before the SwiGLU MLP
(down(silu(gate) * up)); rotate-half RoPE with theta from the config;
a final RMSNorm and the tied embedding as the output head.  LoRA adds
``(x @ A) @ B * alpha / r`` to q_proj, o_proj and down_proj.

Weights are read by name from the stacked parameter tree the benchmark
made from the seed (``embed/table``, ``units/blk/...`` with a leading
layer axis).  It imports nothing of the program.  Layers run under a
``lax.scan`` with each layer rematerialised, so a whole model's
gradients fit beside the weights.

``quant="fp8"`` is the control: every projection's weights and inputs
are rounded to float8_e4m3 (per output channel and per row scales)
before the product, the precision below the configuration's bf16.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fp8(x, axis):
    """Round to float8_e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def matmul(x, w, quant: Optional[str] = None):
    """x (..., i) @ w (i, o) in float32."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x = _fp8(x, -1)
        w = _fp8(w, 0)
    return jnp.einsum("...i,io->...o", x, w, precision=HIGHEST)


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def rope(x, positions, theta):
    """x (B, S, H, D), rotate-half convention."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, :, None] * inv   # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _lora(x, site, quant):
    if site is None:
        return 0.0
    r = site["a"].shape[-1]
    h = matmul(x, site["a"], quant)
    return matmul(h, site["b"], quant) * (site["alpha"].astype(jnp.float32) / r)


def layer(x, p, lo, cfg, positions, quant=None):
    """One decoder layer on x (B, S, d) float32."""
    b, s, _ = x.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nh
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    at, lat = p["mixer"], (lo or {}).get("mixer", {})
    h = rms_norm(x, p["norm1"]["scale"], eps)
    q = matmul(h, at["wq"]["w"], quant) + at["wq"]["b"] + _lora(h, lat.get("wq"), quant)
    k = matmul(h, at["wk"]["w"], quant) + at["wk"]["b"]
    v = matmul(h, at["wv"]["w"], quant) + at["wv"]["b"]
    q = rope(q.reshape(b, s, nh, hd), positions, theta)
    k = rope(k.reshape(b, s, nkv, hd), positions, theta)
    v = v.reshape(b, s, nkv, hd)
    q = q.reshape(b, s, nkv, nh // nkv, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k, precision=HIGHEST)
    scores = scores / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bkgqs,bskd->bqkgd", probs, v, precision=HIGHEST)
    ctx = ctx.reshape(b, s, nh * hd)
    x = x + matmul(ctx, at["wo"]["w"], quant) + _lora(ctx, lat.get("wo"), quant)
    f, lf = p["ffn"], (lo or {}).get("ffn", {})
    h = rms_norm(x, p["norm2"]["scale"], eps)
    g = jax.nn.silu(matmul(h, f["gate"]["w"], quant)) * matmul(h, f["up"]["w"], quant)
    return x + matmul(g, f["down"]["w"], quant) + _lora(g, lf.get("down"), quant)


def hidden(params, lora, tokens, cfg, quant=None):
    """Final-normed hidden states (B, S, d) float32."""
    b, s = tokens.shape
    x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    units = params["units"]["blk"]
    lunits = None if lora is None else lora["units"]["blk"]

    @jax.checkpoint
    def body(x, xs):
        p, lo = xs
        return layer(x, p, lo, cfg, positions, quant), None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(body, x, (units, lunits))
        return rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])


def logits(params, lora, tokens, cfg, quant=None):
    """(B, S, vocab) float32 logits through the tied head."""
    x = hidden(params, lora, tokens, cfg, quant)
    return matmul(x, params["embed"]["table"].T, quant)


def loss(params, lora, tokens, labels, cfg, *, chunk: int = 256,
         quant=None):
    """Mean next-token cross-entropy, the head applied in sequence
    chunks so the logits of a whole batch never exist at once."""
    x = hidden(params, lora, tokens, cfg, quant)
    b, s, d = x.shape
    chunk = min(chunk, s)
    table = params["embed"]["table"]
    xs = x.reshape(b, s // chunk, chunk, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, s // chunk, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def body(acc, inp):
        xc, lc = inp
        lg = matmul(xc, table.T, quant)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, lc[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(lse - gold), None

    with jax.default_matmul_precision("highest"):
        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls))
    return total / (b * s)
