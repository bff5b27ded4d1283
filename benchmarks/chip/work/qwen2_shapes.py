"""Matmul sizes of a Qwen2 decoder from its configuration file."""

from __future__ import annotations


def dims(cfg: dict):
    d = cfg["hidden_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // nh
    return d, nh, nkv, hd, cfg["intermediate_size"], cfg["num_hidden_layers"], \
        cfg["vocab_size"]


def matmul_params(cfg: dict) -> int:
    """Weights that multiply activations per token: the layers'
    projections and the output head (a tied head counted once, the
    embedding lookup not at all)."""
    d, nh, nkv, hd, ff, layers, vocab = dims(cfg)
    per_layer = d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * ff
    return layers * per_layer + vocab * d


def lora_params(cfg: dict) -> int:
    """LoRA factor entries on q_proj, o_proj and down_proj."""
    d, nh, nkv, hd, ff, layers, vocab = dims(cfg)
    r = cfg["lora"]["rank"]
    return layers * r * ((d + nh * hd) + (nh * hd + d) + (ff + d))


def lora_sites(cfg: dict):
    """(in, out) of each LoRA site of one layer."""
    d, nh, nkv, hd, ff, layers, vocab = dims(cfg)
    return [(d, nh * hd), (nh * hd, d), (ff, d)]


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of the weights a forward pass reads (embedding table once)."""
    return itemsize * matmul_params(cfg)
