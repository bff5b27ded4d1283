"""Program spans (``repro.common.trace``).

* the helper adds each block's *self* host microseconds to
  ``phase_us`` (its time less that of the spans opened inside it), so
  nested phases stay exclusive, and keeps its bookkeeping sound when a
  block raises;
* the round and serving paths open the spans the chip benchmark's
  per-layer metrics read, read back here from a ``jax.profiler`` trace
  recorded on the CPU (its ``/host:CPU`` plane carries the ``repro.*``
  events): one ``round`` / ``round.pack`` / ``round.dispatch`` /
  ``round.assemble`` per monolithic round, one ``round.pack`` /
  ``round.wait`` / ``round.assemble`` per chunk of ``round_chunked``,
  and one ``serve.rebuild`` per LRU miss of the modulator store.
"""

import collections
import dataclasses
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.trace import span

N_TASKS = 4


def _traced_spans(fn, trace_dir) -> collections.Counter:
    """Run ``fn`` under a profiler trace; count its ``repro.*`` spans by
    name, prefix dropped."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(trace_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    names = collections.Counter()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        names[e.name[len("repro."):]] += 1
    return names


def _uploads(n, d, *, coded=False, seed=0):
    from repro.core.client import ClientUpload
    from repro.core.unify import unify_with_modulators
    from repro.fed.compression import encode_mask_rows
    from repro.kernels import bitpack
    rng = np.random.default_rng(seed)
    ups = []
    for cid in range(n):
        k = int(rng.integers(1, 3))
        tasks = sorted(rng.choice(N_TASKS, size=k, replace=False).tolist())
        tvs = jnp.asarray(rng.standard_normal((k, d)), jnp.float32)
        uni, masks, lams = unify_with_modulators(tvs)
        words = bitpack.pack_bits_np(np.asarray(masks))
        wire = encode_mask_rows(words, d) if coded else words
        ups.append(ClientUpload(cid, tasks, uni.astype(jnp.bfloat16),
                                jnp.asarray(wire), lams,
                                rng.integers(10, 200, size=k).tolist()))
    return ups


def test_span_adds_self_microseconds_and_nests():
    phase = {}
    t0 = time.perf_counter()
    with span("test.outer", phase, "outer"):
        time.sleep(0.02)
        with span("test.inner", phase, "inner"):
            time.sleep(0.03)
        with span("test.unrecorded"):
            time.sleep(0.01)
    wall_us = (time.perf_counter() - t0) * 1e6
    # the outer block's own time leaves out both children
    assert 20e3 <= phase["outer"] < wall_us - 40e3
    assert phase["inner"] >= 30e3
    assert phase["outer"] + phase["inner"] <= wall_us
    first = phase["inner"]
    with span("test.inner", phase, "inner"):     # accumulates
        time.sleep(0.005)
    assert phase["inner"] >= first + 5e3
    with span("test.default", phase):            # key defaults to name
        pass
    assert set(phase) == {"outer", "inner", "test.default"}


def test_span_stack_survives_a_raise():
    phase = {}
    with pytest.raises(ValueError):
        with span("test.outer"):
            with span("test.inner", phase, "inner"):
                raise ValueError("boom")
    assert "inner" in phase
    with span("test.after", phase, "after"):     # no stale parent left
        time.sleep(0.002)
    assert phase["after"] >= 2e3


@pytest.mark.parametrize("coded", [False, True], ids=["wire", "coded"])
def test_round_spans(tmp_path, coded):
    from repro.core.engine import EngineConfig, RoundEngine
    eng = RoundEngine(EngineConfig(n_tasks=N_TASKS))
    ups = _uploads(5, 300, coded=coded)
    eng.round(ups, code_masks=coded)                 # compile outside
    got = _traced_spans(lambda: eng.round(ups, code_masks=coded), tmp_path)
    want = {"round": 1, "round.pack": 1, "round.h2d": 1,
            "round.dispatch": 1, "round.assemble": 1}
    if coded:
        want.update({"round.decode": 1, "round.encode": 1})
    assert dict(got) == want


def test_round_chunked_spans(tmp_path):
    from repro.core.engine import EngineConfig, RoundEngine
    eng = RoundEngine(EngineConfig(n_tasks=N_TASKS))
    ups = _uploads(6, 300)
    eng.round_chunked(ups, chunk_clients=2)
    got = _traced_spans(lambda: eng.round_chunked(ups, chunk_clients=2),
                        tmp_path)
    assert got["round"] == 1
    assert got["round.pack"] == got["round.h2d"] == 3
    assert got["round.wait"] == got["round.assemble"] == 3
    assert got["round.meta"] == 2                    # pass 0, phase A
    assert got["round.dispatch"] == 3 + 1 + 3        # merge, finish, down


def test_generate_rebuild_spans_count_misses(tmp_path):
    from repro.common.tree import TaskVectorSpace
    from repro.configs.base import SHAPES, load_arch
    from repro.core.client import ClientDownlink
    from repro.kernels import bitpack
    from repro.serve import (GenerationConfig, ModulatorStore,
                             MultiTenantDecoder)
    cfg = dataclasses.replace(load_arch("qwen2-0.5b").reduced(),
                              d_model=64, d_ff=128, vocab=128)
    model = cfg.build(SHAPES["decode_32k"])
    params = model.init(jax.random.PRNGKey(0))
    lora0 = model.lora_init(jax.random.PRNGKey(1))
    space = TaskVectorSpace.from_tree(lora0)
    rng = np.random.default_rng(3)
    store = ModulatorStore(space, lora0, capacity=2)
    store.ingest(ClientDownlink(
        jnp.asarray(0.05 * rng.standard_normal(space.d), jnp.bfloat16),
        jnp.asarray(bitpack.pack_bits_np(rng.random((N_TASKS, space.d))
                                         < 0.5)),
        jnp.ones((N_TASKS,), jnp.float32), fingerprint=space.fingerprint))
    dec = MultiTenantDecoder(model, params, store,
                             cfg=GenerationConfig(max_new_tokens=2,
                                                  temperature=0.0))
    prompts = jnp.ones((4, 6), jnp.int32)
    dec.generate(prompts, [0, 1, 0, 1])              # compile outside
    misses0 = store.misses
    ids = [2, 0, 3, 0]         # LRU [0, 1]: 2, 0 and 3 miss, then 0 hits
    got = _traced_spans(lambda: np.asarray(dec.generate(prompts, ids)),
                        tmp_path)
    assert store.misses - misses0 == 3
    assert got["serve.rebuild"] == store.misses - misses0
    assert got["serve.generate"] == got["serve.route"] == 1
    assert got["serve.decode"] == 1
