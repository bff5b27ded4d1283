"""Required work of one multi-tenant generate call: prefill of B
prompts, then greedy decode, each request with its own LoRA adapter."""

from __future__ import annotations

from benchlib import load

shapes = load("work", "qwen2_shapes")


def required(cfg: dict, batch: int, prompt: int, new: int):
    """(flops, bytes) of one generate call (prefill + new - 1 decode
    steps).  Bytes: the base weights once per step, the B per-request
    adapters (bf16) once per step, the KV cache read at each decode
    step.  Flops: 2 per weight and adapter entry per token, causal
    attention in the prefill, attention over the cache per decode
    token."""
    d, nh, nkv, hd, ff, layers, vocab = shapes.dims(cfg)
    steps = new                       # the prefill and new - 1 decode steps
    w = shapes.weight_bytes(cfg)
    adapters = batch * 2 * shapes.lora_params(cfg)
    kv_token = layers * 2 * nkv * hd * 2          # bytes of one position
    ctx_sum = sum(prompt + t for t in range(1, new))
    nbytes = steps * (w + adapters) + batch * kv_token * (prompt + ctx_sum)
    per_token = 2 * (shapes.matmul_params(cfg) + shapes.lora_params(cfg))
    flops = batch * per_token * (prompt + new - 1)
    flops += batch * layers * 2 * prompt * prompt * nh * hd
    flops += batch * layers * 4 * nh * hd * ctx_sum
    return flops, nbytes


def lora_required(cfg: dict, batch: int, prompt: int, new: int):
    """(flops, bytes) of the modulated LoRA contractions of one generate
    call: each step reads every request's adapter factors once and
    multiplies them with that request's tokens."""
    adapters = batch * 2 * shapes.lora_params(cfg)
    flops = batch * 2 * shapes.lora_params(cfg) * (prompt + new - 1)
    return flops, adapters * new
