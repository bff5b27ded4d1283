"""Random weights from a seed, made on the device in one jitted call, in
the dtype of each leaf of a shape tree (``jax.eval_shape`` of the
program's ``init``).  The scale of each leaf follows its name:

* ``table`` (embedding): N(0, 0.02);
* ``scale`` (norms): 1;
* ``w`` and LoRA ``a``: N(0, 1/fan_in), fan_in the second-to-last axis;
* ``b`` of a projection (bias): N(0, 0.02);
* LoRA ``b`` (rank axis first): N(0, (``lora_b_scale`` / rank)^2);
* ``alpha``: ``lora_alpha``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchlib import seeds


def _path(key_path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in key_path)


def _leaf(path: str, sds, key, lora_b_scale: float, lora_alpha: float):
    name = path.rsplit("/", 1)[-1]
    shape, dtype = sds.shape, sds.dtype
    normal = lambda s: (jax.random.normal(key, shape, jnp.float32) * s)
    if name == "table":
        x = normal(0.02)
    elif name == "scale":
        x = jnp.ones(shape, jnp.float32)
    elif name == "alpha":
        return jnp.full(shape, lora_alpha, dtype)
    elif name in ("w", "a"):
        x = normal(1.0 / float(shape[-2]) ** 0.5)
    elif name == "b" and len(shape) >= 3:      # LoRA B: (layers, rank, out)
        x = normal(lora_b_scale / float(shape[-2]))
    elif name == "b":
        x = normal(0.02)
    else:
        raise ValueError(f"no initialisation rule for leaf {path!r}")
    return x.astype(dtype)


def random_tree(shapes, seed: int, tag: int, *, lora_b_scale: float = 0.0,
                lora_alpha: float = 16.0):
    """Fill a tree of ``jax.ShapeDtypeStruct`` with seeded values."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [_path(p) for p, _ in flat]
    structs = [s for _, s in flat]

    @jax.jit
    def make(key):
        leaves = [_leaf(p, s, jax.random.fold_in(key, i), lora_b_scale,
                        lora_alpha)
                  for i, (p, s) in enumerate(zip(paths, structs))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return make(seeds.key(seed, tag))
