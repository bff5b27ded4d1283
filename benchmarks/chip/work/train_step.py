"""Required operations of one LoRA fine-tune step on a Qwen2 decoder."""

from __future__ import annotations

from benchlib import load

shapes = load("work", "qwen2_shapes")


def required(cfg: dict, batch: int, seq: int):
    """(flops, bytes) of one step on batch x seq tokens.

    Per token: forward 2 and backward to the activations 2 flops per
    frozen matmul weight (the base gets no weight gradient); the LoRA
    factors 6 per entry (they also get weight gradients); causal
    attention forward 2 * seq * d per layer (QK^T and PV over the lower
    triangle) and twice that backward.  Recomputation (remat) does not
    count.  Bytes: the weights read forward and backward."""
    d, nh, nkv, hd, ff, layers, vocab = shapes.dims(cfg)
    tokens = batch * seq
    flops = tokens * (4 * shapes.matmul_params(cfg)
                      + 6 * shapes.lora_params(cfg))
    attn_fwd = batch * layers * 2 * seq * seq * nh * hd
    flops += 3 * attn_fwd
    nbytes = 2 * shapes.weight_bytes(cfg)
    return flops, nbytes
