"""Downlink assembly (``repro.core.engine._assemble_downlinks``).

Contract under test:

* **bit identity** — every client's downlink is exactly its rows of the
  batched tensors (``down_unified[i]``, ``down_masks[i, :k]``,
  ``down_lams[i, :k]``) for uniform and ragged task counts, rounds
  smaller than ``n_max``, uploads whose masks arrive as uint32 words
  instead of dense bool, and the coded branch;
  padded rows are never handed out;
* **dispatches** — one jitted split per distinct task count: a uniform-K
  round is one call, every field is one of its outputs (no per-client
  eager op), and no host copy of the batched tensors is made;
* **compile bound** — the split's programs stay within
  k_max × (log2 n_max + 1) whatever the round's task-count mix;
* **streaming** — ``round_chunked(..., sink=...)`` hands one dict per
  chunk, in order, with the monolithic round's downlinks;
* **sharding** — under a taskvec mesh each client's unified vector keeps
  the taskvec sharding.
"""

import json
import math
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as eng_mod
from repro.core.client import ClientUpload
from repro.core.engine import EngineConfig, RoundEngine
from repro.core.unify import unify_with_modulators
from repro.fed.compression import decode_mask_rows, quantize_bf16_transport
from repro.kernels import bitpack

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 6


def _uploads(ks, d, seed=0, words=False):
    """Uploads with dense bool masks, or (``words``) the uint32 words."""
    rng = np.random.default_rng(seed)
    ups = []
    for cid, k in enumerate(ks):
        tasks = sorted(rng.choice(T, size=k, replace=False).tolist())
        tvs = jnp.asarray(rng.standard_normal((k, d)), jnp.float32)
        uni, masks, lams = unify_with_modulators(tvs)
        if words:
            masks = bitpack.pack_bits(masks)
        ups.append(ClientUpload(cid, tasks, quantize_bf16_transport(uni),
                                masks, lams,
                                rng.integers(10, 200, size=k).tolist()))
    return ups


class _CountingSplit:
    """Stands in for ``_split_downlinks``: records each call's rows and
    outputs, and runs the real split."""

    def __init__(self, fn):
        self.fn = fn
        self.rows = []
        self.outputs = []

    def __call__(self, du, dm, dl, rows, *, k):
        got = self.fn(du, dm, dl, rows, k=k)
        self.rows.append((k, np.asarray(rows).tolist()))
        self.outputs.extend(x for part in got for x in part if x is not None)
        return got


@pytest.fixture
def counting_split(monkeypatch):
    split = _CountingSplit(eng_mod._split_downlinks)
    monkeypatch.setattr(eng_mod, "_split_downlinks", split)
    return split


CASES = {
    "uniform": dict(ks=[2] * 8, words=False, coded=False),
    "ragged": dict(ks=[1, 3, 2, 4, 1, 4, 2, 3], words=False, coded=False),
    "n_lt_n_max": dict(ks=[2, 1, 2, 2, 1], words=False, coded=False),
    "words": dict(ks=[1, 3, 2, 3, 1], words=True, coded=False),
    "coded": dict(ks=[1, 3, 2, 3, 2, 2], words=False, coded=True),
}


@pytest.mark.parametrize("case", list(CASES))
def test_downlinks_bit_identical_to_row_slices(case, counting_split):
    ks, words, coded = (CASES[case][k] for k in ("ks", "words", "coded"))
    d = 1000
    ups = _uploads(ks, d, seed=len(case), words=words)
    eng = RoundEngine(EngineConfig(n_tasks=T))
    downs, out = eng.round(ups, code_masks=coded)
    n_max = out.down_unified.shape[0]

    assert list(downs) == [u.client_id for u in ups]
    # one split per distinct task count, padded rows only repeat real ones
    assert sorted(k for k, _ in counting_split.rows) == sorted(set(ks))
    for k, rows in counting_split.rows:
        assert len(rows) == 1 << (ks.count(k) - 1).bit_length()
        assert set(rows) == {i for i, ki in enumerate(ks) if ki == k}
    if case == "n_lt_n_max":
        assert n_max > len(ups)

    for i, up in enumerate(ups):
        dl, k = downs[up.client_id], ks[i]
        want_masks = np.asarray(out.down_masks[i, :k])
        for got, want in ((dl.unified, out.down_unified[i]),
                          (dl.lams, out.down_lams[i, :k])):
            assert isinstance(got, jax.Array)
            assert got.dtype == want.dtype and got.shape == want.shape
            assert np.array_equal(np.asarray(got), np.asarray(want))
        if coded:
            assert dl.coded
            rows = decode_mask_rows(np.asarray(dl.masks), d, k)
            assert np.array_equal(rows, want_masks)
        else:
            assert isinstance(dl.masks, jax.Array)
            assert dl.masks.dtype == want_masks.dtype
            assert np.array_equal(np.asarray(dl.masks), want_masks)


def test_uniform_round_is_one_split_call_and_no_host_copy(counting_split):
    ks = [2] * 8
    ups = _uploads(ks, 640, seed=3)
    eng = RoundEngine(EngineConfig(n_tasks=T))
    batch = eng_mod.pack_uploads(ups, T)
    out = eng.run_packed(batch)
    jax.block_until_ready(out)
    with jax.transfer_guard_device_to_host("disallow"):
        downs = eng.downlinks(batch, out)
    assert counting_split.rows == [(2, list(range(8)))]
    # every field handed out is an output of that one call: no
    # per-client eager indexing remains
    made = {id(x) for x in counting_split.outputs}
    for dl in downs.values():
        for x in (dl.unified, dl.masks, dl.lams):
            assert id(x) in made


def test_split_programs_bounded_by_k_and_group_size(monkeypatch):
    """Ten rounds of random task-count mixes over fixed (n_max, k_max)
    tensors compile one program per (k, pow2 group size) seen, never
    one per mix."""
    def fresh(*args, k):     # a function of its own: jit caches by function
        return eng_mod._split_rows_impl(*args, k=k)

    split = jax.jit(fresh, static_argnames="k")
    monkeypatch.setattr(eng_mod, "_split_downlinks", split)
    n_max, k_max, d = 16, 4, 320
    rng = np.random.default_rng(7)
    du = jnp.asarray(rng.standard_normal((n_max, d)), jnp.bfloat16)
    dm = jnp.asarray(rng.integers(0, 2**32, (n_max, k_max, d // 32),
                                  dtype=np.uint32))
    dl = jnp.asarray(rng.random((n_max, k_max)), jnp.float32)
    seen = set()
    for _ in range(10):
        n = int(rng.integers(1, n_max + 1))
        ks = rng.integers(1, k_max + 1, size=n).tolist()
        cids = rng.permutation(1000)[:n].tolist()
        downs = eng_mod._assemble_downlinks(
            cids, [list(range(k)) for k in ks], d, du, dm, dl)
        seen |= {(k, 1 << (ks.count(k) - 1).bit_length()) for k in set(ks)}
        for i, cid in enumerate(cids):
            k = ks[i]
            assert np.array_equal(np.asarray(downs[cid].unified),
                                  np.asarray(du[i]))
            assert np.array_equal(np.asarray(downs[cid].masks),
                                  np.asarray(dm[i, :k]))
            assert np.array_equal(np.asarray(downs[cid].lams),
                                  np.asarray(dl[i, :k]))
    assert split._cache_size() == len(seen)
    assert split._cache_size() <= k_max * (math.log2(n_max) + 1)


def test_round_chunked_sink_gets_one_dict_per_chunk_in_order():
    ks = [1, 2, 2, 1, 2, 2, 1, 1, 2, 2, 1]
    ups = _uploads(ks, 1000, seed=11)
    eng = RoundEngine(EngineConfig(n_tasks=T))
    mono, _ = eng.round(ups)
    chunks = []
    downs, _, stats = eng.round_chunked(ups, chunk_clients=3,
                                        sink=chunks.append)
    assert downs == {}
    assert stats["n_chunks"] == len(chunks) == 4
    ids = [u.client_id for u in ups]
    assert [list(c) for c in chunks] == [ids[i:i + 3]
                                         for i in range(0, len(ids), 3)]
    for c in chunks:
        for cid, dl in c.items():
            for f in ("unified", "masks", "lams"):
                a = np.asarray(getattr(mono[cid], f))
                b = np.asarray(getattr(dl, f))
                assert a.dtype == b.dtype and np.array_equal(a, b), (cid, f)


_SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["REPRO_DISABLE_PALLAS"] = "1"
    import json
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.client import ClientUpload
    from repro.core.engine import EngineConfig, RoundEngine
    from repro.core.unify import unify_with_modulators
    from repro.fed.compression import quantize_bf16_transport
    from repro.launch.mesh import make_debug_mesh
    from repro.nn.sharding import taskvec_axes

    def uploads(rng, ks, T, d):
        ups = []
        for cid, k in enumerate(ks):
            tasks = sorted(rng.choice(T, size=k, replace=False).tolist())
            uni, masks, lams = unify_with_modulators(
                jnp.asarray(rng.standard_normal((k, d)), jnp.float32))
            ups.append(ClientUpload(cid, tasks, quantize_bf16_transport(uni),
                                    masks, lams,
                                    rng.integers(10, 200, size=k).tolist()))
        return ups

    mesh = make_debug_mesh((4, 2))
    ax = tuple(taskvec_axes(mesh))
    T, ks = 6, [1, 2, 2, 1, 2]
    report = {}
    # d = 4096 fills the shards exactly; d = 1000 is padded, and the
    # round's outputs are sliced back to d before the split
    for d in (4096, 1000):
        ups = uploads(np.random.default_rng(d), ks, T, d)
        downs, out = RoundEngine(EngineConfig(n_tasks=T),
                                 mesh=mesh).round(ups)
        equal = same_spec = True
        for i, (cid, dl) in enumerate(downs.items()):
            k = ks[i]
            for got, want in ((dl.unified, out.down_unified[i]),
                              (dl.masks, out.down_masks[i, :k]),
                              (dl.lams, out.down_lams[i, :k])):
                equal &= bool(np.array_equal(np.asarray(got),
                                             np.asarray(want)))
                same_spec &= got.sharding.spec == want.sharding.spec
        spec = downs[0].unified.sharding.spec
        report[f"{d}/equal"] = equal
        report[f"{d}/same_spec_as_row_index"] = same_spec
        if d == 4096:
            lead = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
            report[f"{d}/taskvec_sharded"] = lead == ax
    print(json.dumps(report))
""")


def test_sharded_round_unified_keeps_taskvec_sharding():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SHARDED],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    report = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(report) == 5
    bad = [k for k, v in report.items() if v is not True]
    assert not bad, f"sharded downlinks diverged on: {bad}"
