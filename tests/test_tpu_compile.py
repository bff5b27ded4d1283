"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

No chip is attached: the TPU compiler (libtpu) compiles for a
*described* ``v5e:2x2`` topology and refuses what the chip would
refuse — block shapes that break the (8, 128) tiling rule, casts and
reshapes Mosaic cannot lower, programs that exceed device memory.
Interpret-mode tests cannot see any of that.

Shapes are the real ones: the server round at N = 32 clients, T = 30
tasks, K = 2 tasks per client, at d = 2^20 and at the task-vector d of
qwen2-0.5b at its published widths (rank-16 LoRA on mixer/wq,
mixer/wo, ffn/down: d = 3,588,168), and at one chip's shard of
qwen2.5-32b's round over four chips (16,777,216 coordinates); the
serving kernel on qwen2-0.5b's LoRA leaves, fused and dense-routed.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and under a
multi-worker pytest run only the worker given this file loads it.
This is the only file that describes a topology.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitpack
from repro.kernels.fused_unify import (fused_unify_packed_pallas,
                                       fused_unify_packed_rows_pallas)
from repro.kernels.masked_agg import masked_agg_batched_packed_rows_pallas
from repro.kernels.modulated_matmul import (modulated_matmul_pallas,
                                            routed_matmul_pallas)
from repro.kernels.sign_sim import sign_sim_packed_pallas

N, T, K = 32, 30, 2
HBM_BYTES = 16 * 10**9          # one v5e chip
QWEN_D = 3_588_168


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def qwen_lora_leaves():
    """(K, N) per-layer shapes of qwen2-0.5b's LoRA factors, from the
    published config (eval_shape: nothing is allocated)."""
    from repro.common.tree import TaskVectorSpace
    from repro.configs.base import load_arch
    model = load_arch("qwen2-0.5b").build()
    tree = jax.eval_shape(lambda: model.lora_init(jax.random.PRNGKey(0)))
    space = TaskVectorSpace.from_tree(jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, s.dtype), tree))
    assert space.d == QWEN_D
    return {leaf.path: leaf.shape[1:] for leaf in space.leaves}


D_CASES = {"d=2^20": 1 << 20, "d=qwen2-0.5b": QWEN_D}


def compile_for_chip(fn, one_chip, *shapes):
    """Lower + compile ``fn`` for the described chip; check the native
    kernel is in the program and the program fits one chip's HBM."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled


# one chip's taskvec shard of qwen2.5-32b's round over four chips
# (d = 54,526,144 padded to 4 x 16,777,216)
ROWS_D_CASES = dict(D_CASES, **{"d=qwen2.5-32b/4": 1 << 24})


@pytest.mark.parametrize("d", ROWS_D_CASES.values(), ids=ROWS_D_CASES.keys())
def test_fused_unify_packed_compiles(one_chip, d):
    compile_for_chip(
        lambda tv, v: fused_unify_packed_pallas(tv, v, interpret=False),
        one_chip, ((N, K, d), jnp.float32), ((N, K), jnp.bool_))


@pytest.mark.parametrize("d", ROWS_D_CASES.values(), ids=ROWS_D_CASES.keys())
def test_fused_unify_packed_rows_compiles(one_chip, d):
    compile_for_chip(
        lambda tv, tid, v: fused_unify_packed_rows_pallas(tv, tid, v,
                                                          interpret=False),
        one_chip, ((T, d), jnp.float32), ((N, K), jnp.int32),
        ((N, K), jnp.bool_))


@pytest.mark.parametrize("d", ROWS_D_CASES.values(), ids=ROWS_D_CASES.keys())
def test_masked_agg_batched_packed_rows_compiles(one_chip, d):
    compile_for_chip(
        lambda u, w, wt, tid, v: masked_agg_batched_packed_rows_pallas(
            u, w, wt, tid, v, n_tasks=T, interpret=False),
        one_chip, ((N, d), jnp.bfloat16),
        ((N, K, bitpack.packed_width(d)), jnp.uint32),
        ((N, K), jnp.float32), ((N, K), jnp.int32), ((N, K), jnp.bool_))


@pytest.mark.parametrize("d", ROWS_D_CASES.values(), ids=ROWS_D_CASES.keys())
def test_sign_sim_packed_compiles(one_chip, d):
    w = bitpack.packed_width(d)
    compile_for_chip(
        lambda pos, nz: sign_sim_packed_pallas(pos, nz, interpret=False),
        one_chip, ((T, w), jnp.uint32), ((T, w), jnp.uint32))


QWEN_FACTORS = ["mixer/wq/a", "mixer/wq/b", "ffn/down/a"]
SERVE_BATCH = 8


def _factor_shape(qwen_lora_leaves, leaf):
    (shape,) = [s for p, s in qwen_lora_leaves.items() if p.endswith(leaf)]
    return shape


@pytest.mark.parametrize("seq", [1, 32], ids=["decode", "prefill"])
@pytest.mark.parametrize("leaf", QWEN_FACTORS)
def test_modulated_matmul_compiles(one_chip, qwen_lora_leaves, leaf, seq):
    """Every distinct LoRA factor shape of qwen2-0.5b (wo shares wq's),
    in the model's bf16, for a batch of 8 requests."""
    k, n = _factor_shape(qwen_lora_leaves, leaf)
    b = SERVE_BATCH
    compile_for_chip(
        lambda x, base, tau, w, lam: modulated_matmul_pallas(
            x, base, tau, w, lam, interpret=False),
        one_chip, ((b, seq, k), jnp.bfloat16), ((k, n), jnp.bfloat16),
        ((k, n), jnp.bfloat16),
        ((b, bitpack.packed_width(k * n)), jnp.uint32), ((b,), jnp.float32))


@pytest.mark.parametrize("seq", [1, 32], ids=["decode", "prefill"])
@pytest.mark.parametrize("leaf", QWEN_FACTORS)
def test_routed_matmul_compiles(one_chip, qwen_lora_leaves, leaf, seq):
    """The dense-routed twin over materialised per-request factors."""
    k, n = _factor_shape(qwen_lora_leaves, leaf)
    b = SERVE_BATCH
    compile_for_chip(
        lambda x, w: routed_matmul_pallas(x, w, interpret=False),
        one_chip, ((b, seq, k), jnp.bfloat16), ((b, k, n), jnp.bfloat16))
