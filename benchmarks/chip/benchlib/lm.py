"""The program's language model for a configuration file, and seeded
weights in its parameter layout."""

from __future__ import annotations

import dataclasses

from benchlib import weights


def build_model(config: dict):
    """The program's model for a configuration file, its published
    widths checked against the file."""
    from repro.configs.base import load_arch
    prog = config["program"]
    cfg = dataclasses.replace(load_arch(prog["arch"]), **prog["overrides"])
    want = {"d_model": config["hidden_size"], "d_ff": config["intermediate_size"],
            "n_heads": config["num_attention_heads"],
            "n_kv_heads": config["num_key_value_heads"],
            "n_layers": config["num_hidden_layers"],
            "vocab": config["vocab_size"],
            "tie_embeddings": config["tie_word_embeddings"],
            "lora_rank": config["lora"]["rank"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"program config {cfg.name} {got} != published {want}")
    return cfg, cfg.build()


def make_state(config: dict, model, seed: int, *, lora_b_scale: float):
    """Seeded base weights and LoRA tree in the program's layout."""
    import jax
    pshape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    lshape = jax.eval_shape(model.lora_init, jax.random.PRNGKey(0))
    params = weights.random_tree(pshape, seed, 10)
    lora = weights.random_tree(lshape, seed, 11, lora_b_scale=lora_b_scale,
                               lora_alpha=float(config["lora"]["alpha"]))
    return params, lora
