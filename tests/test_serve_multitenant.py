"""Multi-tenant serving subsystem: ModulatorStore + task routing +
fused modulated matmul.

The parity contracts under test (see repro/serve docstrings):

* dense-routed mixed-task decode is BITWISE identical to decoding each
  request single-tenant with the dense unpacked modulator — for packed
  AND bool downlink layouts;
* the fused ``modulated_matmul`` kernel is BITWISE identical to
  unpack-then-matmul within one compiled program (ref and
  pallas_interpret modes);
* the fused routed decode emits identical TOKENS to dense-routed, its
  weights within one rounding of the modulated delta (XLA contracts
  the in-jit ``base + λ·m⊙τ`` build into an fma — the product feeds
  the add unrounded — where the materialised adapter rounds it first;
  no barrier suppresses the contraction on CPU);
* ONE compiled decode program serves every task mix (task ids are
  data, not trace constants);
* the store refuses fingerprint-mismatched or unstamped downlinks,
  bounds its LRU, and holds ≥5x less resident than per-task
  checkpoints at T=30.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.tree import TaskVectorLayoutError, TaskVectorSpace, tree_add
from repro.configs.base import SHAPES, load_arch
from repro.core.client import ClientDownlink, ClientUpload
from repro.core.server import MaTUServer, MaTUServerConfig
from repro.core.unify import modulate
from repro.kernels import bitpack, ops, ref
from repro.serve import (GenerationConfig, ModulatorStore, MultiTenantDecoder,
                         generate, route_batch)
from repro.serve.generate import _sample

jax.config.update("jax_platform_name", "cpu")

N_TASKS = 4
GEN_CFG = GenerationConfig(max_new_tokens=5, temperature=0.0)


# ---------------------------------------------------------------------------
# shared serving rig: reduced qwen2 + one REAL federated round
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _rig():
    cfg = load_arch("qwen2-0.5b").reduced()
    model = cfg.build(SHAPES["decode_32k"])
    params = model.init(jax.random.PRNGKey(0))
    lora0 = model.lora_init(jax.random.PRNGKey(1))
    space = TaskVectorSpace.from_tree(lora0)

    # one real server round: one single-task client per task
    rng = np.random.default_rng(7)
    uploads = []
    for t in range(N_TASKS):
        vec = jnp.asarray(0.05 * rng.standard_normal(space.d), jnp.float32)
        uploads.append(ClientUpload(
            client_id=t, task_ids=[t], unified=vec,
            masks=jnp.ones((1, space.d), bool),
            lams=jnp.ones((1,), jnp.float32), data_sizes=[64],
            fingerprint=space.fingerprint))
    server = MaTUServer(MaTUServerConfig(n_tasks=N_TASKS))
    server.round(uploads)

    prompts = jax.random.randint(jax.random.PRNGKey(3), (N_TASKS, 8),
                                 1, cfg.vocab)
    return cfg, model, params, lora0, space, server, prompts


def _store_from(server, space, lora0, *, packed, capacity=8):
    dl = server.serving_downlink(packed=packed,
                                 fingerprint=space.fingerprint)
    store = ModulatorStore(space, lora0, capacity=capacity)
    store.ingest(dl)
    return store, dl


def _oracle_adapter(dl, space, lora0, t):
    """The dense unpacked modulator path, independent of the store."""
    delta = modulate(dl.unified, dl.masks[t], dl.lams[t])
    return tree_add(lora0, space.unflatten(delta))


# ---------------------------------------------------------------------------
# kernel layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("start,length", [(0, 992), (37, 129), (32, 64),
                                          (991, 1), (100, 0), (982, 10)])
def test_slice_bits_matches_unpack_oracle(start, length):
    rng = np.random.default_rng(start * 1000 + length)
    d = 992
    bits = rng.random((3, d)) < 0.5
    words = jnp.asarray(bitpack.pack_bits_np(bits))
    got = bitpack.slice_bits(words, start, length)
    want = bitpack.pack_bits_np(bits[:, start:start + length])
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("mode", ["ref", "pallas_interpret"])
def test_modulated_matmul_bitwise_vs_unpack_then_matmul(mode):
    """Fused kernel == unpack-then-matmul oracle, compared where the
    comparison is meaningful: inside jit, how serving actually runs."""
    rng = np.random.default_rng(0)
    B, S, K, N = 3, 5, 32, 16
    x = jnp.asarray(rng.standard_normal((B, S, K)), jnp.float32)
    base = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
    tau = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
    m = rng.random((B, K * N)) < 0.6
    words = jnp.asarray(bitpack.pack_bits_np(m))
    lam = jnp.asarray(rng.standard_normal(B), jnp.float32)

    def oracle(x, base, tau, words, lam):
        bits = bitpack.unpack_bits(words, K * N, jnp.float32).reshape(B, K, N)
        w_eff = base[None] + lam[:, None, None] * bits * tau[None]
        return jnp.einsum("bsk,bkn->bsn", x, w_eff)

    got = jax.jit(functools.partial(ops.modulated_matmul, mode=mode))(
        x, base, tau, words, lam)
    want = jax.jit(oracle)(x, base, tau, words, lam)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("mode", ["ref", "pallas_interpret"])
def test_routed_matmul_bitwise_vs_einsum_and_fused(mode):
    """The dense-routed kernel over materialised bf16 weights == the
    batched einsum, and == the fused kernel fed the packed modulator
    those weights were built from: the two routed forms contract
    alike."""
    rng = np.random.default_rng(1)
    B, S, K, N = 3, 5, 32, 16
    x = jnp.asarray(rng.standard_normal((B, S, K)), jnp.bfloat16)
    base = jnp.asarray(rng.standard_normal((K, N)), jnp.bfloat16)
    tau = jnp.asarray(rng.standard_normal((K, N)), jnp.bfloat16)
    m = rng.random((B, K, N)) < 0.6
    words = jnp.asarray(bitpack.pack_bits_np(m.reshape(B, K * N)))
    lam = jnp.asarray(rng.standard_normal(B), jnp.float32)
    w = ref.modulated_weight(base[None], tau[None],
                             jnp.asarray(m, jnp.float32), lam[:, None, None])

    got = jax.jit(functools.partial(ops.routed_matmul, mode=mode))(x, w)
    want = jax.jit(lambda x, w: jnp.einsum("bsk,bkn->bsn", x, w))(x, w)
    fused = jax.jit(functools.partial(ops.modulated_matmul, mode=mode))(
        x, base, tau, words, lam)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(fused, np.float32))


def test_modulated_matmul_rejects_misaligned():
    x = jnp.zeros((1, 2, 3))
    base = jnp.zeros((3, 5))          # 15 bits: not word-aligned
    with pytest.raises(ValueError, match="word-aligned"):
        ops.modulated_matmul(x, base, jnp.zeros((3, 5)),
                             jnp.zeros((1, 1), jnp.uint32),
                             jnp.zeros((1,)), mode="ref")


# ---------------------------------------------------------------------------
# store: ingest layouts, fingerprint handshake, LRU
# ---------------------------------------------------------------------------

def test_store_ingest_all_layouts_agree():
    _, _, _, lora0, space, server, _ = _rig()
    packed_dl = server.serving_downlink(packed=True,
                                        fingerprint=space.fingerprint)
    bool_dl = server.serving_downlink(packed=False,
                                      fingerprint=space.fingerprint)
    coded_dl = server.serving_downlink(code_masks=True,
                                       fingerprint=space.fingerprint)
    stores = []
    for dl in (packed_dl, bool_dl, coded_dl):
        s = ModulatorStore(space, lora0)
        assert s.ingest(dl) == list(range(N_TASKS))
        stores.append(s)
    for t in range(N_TASKS):
        ref_words = np.asarray(stores[0].mask_words(t))
        for s in stores[1:]:
            np.testing.assert_array_equal(np.asarray(s.mask_words(t)),
                                          ref_words)
        # packed + coded share the bf16 wire vector -> identical deltas
        np.testing.assert_array_equal(np.asarray(stores[0].delta(t)),
                                      np.asarray(stores[2].delta(t)))
    # masks stay packed in residence whatever the ingest layout
    for s in stores:
        assert all(s.mask_words(t).dtype == jnp.uint32
                   for t in range(N_TASKS))


def test_store_fingerprint_handshake():
    _, _, _, lora0, space, server, _ = _rig()
    store = ModulatorStore(space, lora0)
    bad = server.serving_downlink(fingerprint="0" * 16)
    with pytest.raises(TaskVectorLayoutError):
        store.ingest(bad)
    unstamped = server.serving_downlink()        # fingerprint=None
    with pytest.raises(TaskVectorLayoutError, match="unstamped"):
        store.ingest(unstamped)
    assert store.ingest(unstamped, unchecked=True) == list(range(N_TASKS))


def test_store_lru_eviction_and_rebuild():
    _, _, _, lora0, space, server, _ = _rig()
    store, _ = _store_from(server, space, lora0, packed=True, capacity=2)
    a0 = store.adapter(0)
    store.adapter(1)
    assert store.cached_task_ids() == [0, 1]
    store.adapter(0)                             # touch: 0 now MRU
    assert store.cached_task_ids() == [1, 0]
    store.adapter(2)                             # evicts 1
    assert store.cached_task_ids() == [0, 2]
    assert store.hits == 1 and store.misses == 3
    # eviction loses nothing: rebuild from packed state is bitwise
    store.adapter(0)
    a0_again = store.adapter(1)                  # rebuilt after eviction
    rebuilt = store.adapter(1)
    assert store.misses == 4 and store.hits == 3
    for l1, l2 in zip(jax.tree_util.tree_leaves(a0_again),
                      jax.tree_util.tree_leaves(rebuilt)):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    for l1, l2 in zip(jax.tree_util.tree_leaves(a0),
                      jax.tree_util.tree_leaves(store.adapter(0))):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_store_capacity_validation():
    _, _, _, lora0, space, _, _ = _rig()
    with pytest.raises(ValueError):
        ModulatorStore(space, lora0, capacity=0)


# ---------------------------------------------------------------------------
# routing parity: the acceptance contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("packed", [True, False],
                         ids=["packed-wire", "bool-wire"])
def test_mixed_batch_bitwise_equals_single_tenant(packed):
    """A mixed decode batch over >=4 tasks through the ModulatorStore
    is bit-identical to decoding each request single-tenant with the
    dense unpacked modulator — for both downlink mask layouts."""
    _, model, params, lora0, space, server, prompts = _rig()
    store, dl = _store_from(server, space, lora0, packed=packed)
    dec = MultiTenantDecoder(model, params, store, cfg=GEN_CFG)
    ids = list(range(N_TASKS))
    mixed = dec.generate(prompts, ids)
    assert mixed.shape == (N_TASKS, prompts.shape[1] + GEN_CFG.max_new_tokens)
    for r, t in enumerate(ids):
        lora_t = _oracle_adapter(dl, space, lora0, t)
        single = generate(model, params, lora_t, prompts[r:r + 1], GEN_CFG,
                          max_len=int(prompts.shape[1])
                          + GEN_CFG.max_new_tokens + 8)
        np.testing.assert_array_equal(np.asarray(mixed[r]),
                                      np.asarray(single[0]))


def test_uniform_mix_equals_classic_batch():
    """All-rows-one-task routed decode == the classic (2-D lora)
    uniform batch, bitwise."""
    _, model, params, lora0, space, server, prompts = _rig()
    store, dl = _store_from(server, space, lora0, packed=True)
    dec = MultiTenantDecoder(model, params, store, cfg=GEN_CFG)
    routed = dec.generate(prompts, [2] * N_TASKS)
    classic = generate(model, params, _oracle_adapter(dl, space, lora0, 2),
                       prompts, GEN_CFG,
                       max_len=int(prompts.shape[1])
                       + GEN_CFG.max_new_tokens + 8)
    np.testing.assert_array_equal(np.asarray(routed), np.asarray(classic))


def test_fused_routing_matches_dense_routed():
    """Fused (packed-mask, in-kernel modulation) decode: identical
    tokens to dense-routed; word-aligned sites carry packed words."""
    _, model, params, lora0, space, server, prompts = _rig()
    store, _ = _store_from(server, space, lora0, packed=True)
    ids = [0, 3, 1, 2]
    dense = MultiTenantDecoder(model, params, store, cfg=GEN_CFG)
    fused = MultiTenantDecoder(model, params, store, fused=True, cfg=GEN_CFG)
    np.testing.assert_array_equal(
        np.asarray(dense.generate(prompts, ids)),
        np.asarray(fused.generate(prompts, ids)))

    # the routed tree really is fused where word-aligned: packed uint32
    # words present, no materialised per-request weight
    tree = route_batch(store, ids, fused=True)
    fused_sites = [s for _, s in _iter_sites(tree) if "words" in s.get("a", {})]
    assert fused_sites, "no site took the fused path"
    for site in fused_sites:
        assert site["a"]["words"].dtype == jnp.uint32
        assert site["lam"].shape[-1] == len(ids)


def _iter_sites(node, prefix=""):
    if not isinstance(node, dict):
        return
    if "a" in node and "b" in node:
        yield prefix, node
        return
    for k in node:
        yield from _iter_sites(node[k], f"{prefix}/{k}")


def test_fused_weight_build_within_one_product_rounding():
    """The in-jit ``base + λ·m⊙τ`` build differs from the eagerly
    materialised adapter by at most one rounding of the modulated
    delta per element (XLA fma-contracts the add — the product feeds
    in unrounded — where the adapter rounds it first), and the prefill
    logits of the two routed forms stay within the amplified tolerance
    through the full depth."""
    _, model, params, lora0, space, server, prompts = _rig()
    store, _ = _store_from(server, space, lora0, packed=True)
    ids = [0, 1, 2, 3]
    dense_lora = route_batch(store, ids, fused=False)
    fused_lora = route_batch(store, ids, fused=True)

    # weight level: reconstruct one fused site's effective "a" factor
    # in-jit and ulp-compare against the dense-routed leaf
    site_path, fused_site = next((p, s) for p, s in _iter_sites(fused_lora)
                                 if "words" in s.get("a", {}))
    dense_site = dense_lora
    for k in site_path.strip("/").split("/"):
        dense_site = dense_site[k]

    def build_a(site):
        a = site["a"]
        L, B, W = a["words"].shape
        k, n = a["base"].shape[-2:]
        bits = bitpack.unpack_bits(a["words"].reshape(L * B, W), k * n,
                                   jnp.float32).reshape(L, B, k, n)
        lam = site["lam"][:, :, None, None]
        return a["base"][:, None] + lam * bits * a["tau"][:, None]

    built = np.asarray(jax.jit(build_a)(fused_site))
    want = np.asarray(dense_site["a"])
    base = np.asarray(fused_site["a"]["base"])[:, None]
    delta = want - base                   # the adapter's rounded product
    tol = 2.0 * np.spacing(np.maximum(np.abs(delta), np.abs(want))
                           .astype(np.float32))
    diff = np.abs(built - want)
    assert np.all(diff <= tol), \
        f"weight build off by {np.max(diff / np.maximum(tol, 1e-45)):.1f}x " \
        "the one-product-rounding bound"

    # logits level: the 1-ulp weight wiggle amplifies through L layers
    # to ~1e-4 relative at the head — tokens are identical regardless
    # (test_fused_routing_matches_dense_routed)
    def prefill(lora):
        cache = model.init_cache(N_TASKS, 32)
        logits, _ = model.prefill_step(params, lora, {"tokens": prompts},
                                       cache)
        return logits

    ld = np.asarray(jax.jit(prefill)(dense_lora))
    lf = np.asarray(jax.jit(prefill)(fused_lora))
    np.testing.assert_allclose(lf, ld, rtol=5e-4, atol=1e-5)


def test_one_compiled_program_across_mixes():
    """Task ids are data: one jitted decode program serves every mix."""
    _, model, params, lora0, space, server, prompts = _rig()
    store, _ = _store_from(server, space, lora0, packed=True)
    for fused in (False, True):
        dec = MultiTenantDecoder(model, params, store, fused=fused,
                                 cfg=GEN_CFG)
        for ids in ([0, 1, 2, 3], [3, 2, 1, 0], [1, 1, 2, 2], [0, 0, 0, 0]):
            dec.generate(prompts, ids)
        assert dec.compile_count() == 1, \
            f"fused={fused}: decode recompiled across task mixes"


def test_decoder_validates_batch():
    _, model, params, lora0, space, server, prompts = _rig()
    store, _ = _store_from(server, space, lora0, packed=True)
    dec = MultiTenantDecoder(model, params, store, cfg=GEN_CFG)
    with pytest.raises(ValueError, match="task ids"):
        dec.generate(prompts, [0, 1])
    with pytest.raises(KeyError, match="no resident modulator"):
        dec.generate(prompts, [0, 1, 2, 99])


# ---------------------------------------------------------------------------
# storage accounting: the >=5x headline
# ---------------------------------------------------------------------------

def test_resident_bytes_ratio_at_t30():
    _, _, _, lora0, space, _, _ = _rig()
    T = 30
    rng = np.random.default_rng(0)
    W = bitpack.packed_width(space.d)
    dl = ClientDownlink(
        jnp.asarray(rng.standard_normal(space.d), jnp.float32)
        .astype(jnp.bfloat16),
        jnp.asarray(rng.integers(0, 2**32, (T, W), dtype=np.uint32)),
        jnp.ones((T,), jnp.float32), fingerprint=space.fingerprint)
    store = ModulatorStore(space, lora0)
    store.ingest(dl)
    rep = store.storage_report()
    assert rep["tasks"] == T
    assert rep["checkpoint_bytes"] == T * 4 * space.d
    assert rep["ratio"] >= 5.0, \
        f"resident-bytes win {rep['ratio']:.2f}x < 5x at T={T}"


# ---------------------------------------------------------------------------
# generate() RNG regression
# ---------------------------------------------------------------------------

class _FakeModel:
    """Duck-typed decode stack with constant logits: isolates the
    sampling-loop RNG wiring from any real architecture."""

    def __init__(self, vocab=101):
        self.logits = jax.random.normal(jax.random.PRNGKey(9), (1, vocab))

    def init_cache(self, b, max_len):
        return {"pos": jnp.zeros((b,), jnp.int32)}

    def prefill_step(self, params, lora, batch, cache):
        b = batch["tokens"].shape[0]
        return jnp.broadcast_to(self.logits, (b,) + self.logits.shape[1:]), cache

    def decode_fn(self, params, lora, batch, cache, pos):
        b = batch["tokens"].shape[0]
        return jnp.broadcast_to(self.logits, (b,) + self.logits.shape[1:]), cache


def test_generate_splits_rng_before_first_sample():
    """Regression: the prefill sample must consume a key SPLIT from the
    caller's rng, not the rng itself (which also seeds the scan carry —
    reusing it correlated the first token with step 0)."""
    model = _FakeModel()
    cfg = GenerationConfig(max_new_tokens=8, temperature=1.0)
    rng = jax.random.PRNGKey(42)
    prompt = jnp.ones((1, 4), jnp.int32)
    out = generate(model, {}, {}, prompt, cfg, rng=rng)
    first = int(out[0, 4])

    _, first_key = jax.random.split(rng)
    assert first == int(_sample(model.logits, cfg, first_key)[0])
    # the old behaviour (sampling with the unsplit rng) must NOT match
    assert first != int(_sample(model.logits, cfg, rng)[0])


def test_generate_draws_differ_at_temperature():
    """Two draws from the same (constant-logits) distribution must
    differ at temperature > 0 — any key reuse across steps collapses
    the stream."""
    model = _FakeModel()
    cfg = GenerationConfig(max_new_tokens=12, temperature=1.0)
    out = generate(model, {}, {}, jnp.ones((1, 4), jnp.int32), cfg,
                   rng=jax.random.PRNGKey(0))
    draws = np.asarray(out[0, 4:])
    assert len(set(draws.tolist())) > 1, \
        f"all {len(draws)} draws identical: RNG stream collapsed"
