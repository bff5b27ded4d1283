"""Cross-architecture federated rounds over the reduced model zoo.

The task-vector layout contract end to end (see ``repro.fed.testbed``):

* every zoo family's :class:`TaskVectorSpace` manifest flattens and
  unflattens its LoRA delta pytree losslessly (the d-axis IS the
  manifest);
* a manifest-fingerprint mismatch between client and server aborts
  BEFORE aggregation (both at the strategy and at simulator
  construction);
* a mixed-architecture round over REAL per-task fine-tune deltas,
  each family zero-padded to the common d (the 256-coord word
  boundary), matches the dense reference round — downlink masks bit
  for bit;
* a 30-task round over >= 4 distinct families completes end-to-end
  through ``MaTUStrategy`` with measured wire bits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.tree import (TaskVectorLayoutError, TaskVectorSpace,
                               pad_vector, tree_zeros_like)
from repro.configs.base import ZOO_FAMILIES
from repro.core.client import ClientUpload
from repro.core.engine import EngineConfig, RoundEngine
from repro.core.unify import unify_with_modulators
from repro.data.dirichlet import FedSplit
from repro.data.synthetic import make_constellation, sample_task_batch
from repro.fed.compression import quantize_bf16_transport
from repro.fed.local import make_head, make_local_trainer
from repro.fed.simulator import FedConfig, FedSimulator
from repro.fed.strategies import MaTUStrategy, RoundBatch, Upload
from repro.fed.testbed import (ArchBackbone, make_zoo_backbones, round_up_d,
                               D_BOUNDARY)

jax.config.update("jax_platform_name", "cpu")

FEAT_DIM = 32  # == reduced vit patch_dim: one constellation feeds all


@pytest.fixture(scope="module")
def zoo():
    return make_zoo_backbones(FEAT_DIM, seed=0)


# -- layout manifest ---------------------------------------------------------

def test_flatten_unflatten_roundtrip_per_family(zoo):
    """Every family's manifest is lossless: a random model-space delta
    survives flatten -> unflatten bit-exactly, leaf by leaf."""
    for fam, bb in zoo.items():
        key = jax.random.PRNGKey(hash(fam) % (2**31))
        delta = jax.tree_util.tree_map(
            lambda l, key=key: jax.random.normal(
                jax.random.fold_in(key, l.size % 9973), l.shape, l.dtype),
            bb.lora0)
        flat = bb.space.flatten(delta)
        assert flat.shape == (bb.d,) and flat.dtype == jnp.float32
        back = bb.space.unflatten(flat)
        leaves_a = jax.tree_util.tree_leaves(delta)
        leaves_b = jax.tree_util.tree_leaves(back)
        assert len(leaves_a) == len(leaves_b) == len(bb.space.leaves)
        for a, b in zip(leaves_a, leaves_b):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # zero-padding to the round's common d is invisible on unflatten
        padded = pad_vector(flat, round_up_d(bb.d))
        again = bb.space.unflatten(padded)
        for a, b in zip(leaves_a, jax.tree_util.tree_leaves(again)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_manifest_fingerprints_distinct_and_stable(zoo):
    """Fingerprints identify layouts: distinct across families, stable
    across independent constructions, round-trip through JSON."""
    fps = {fam: bb.fingerprint for fam, bb in zoo.items()}
    assert len(set(fps.values())) == len(fps)
    rebuilt = ArchBackbone(ZOO_FAMILIES["lm"], feat_dim=FEAT_DIM, seed=7)
    assert rebuilt.fingerprint == zoo["lm"].fingerprint  # seed-independent
    space2 = TaskVectorSpace.from_json(zoo["ssm"].space.to_json())
    assert space2.fingerprint == zoo["ssm"].fingerprint


def test_fingerprint_mismatch_aborts_before_aggregation(zoo):
    """The server refuses to aggregate an upload whose manifest
    disagrees with the installed per-task expectation — and the round
    state is untouched afterwards (abort BEFORE, not during)."""
    d = round_up_d(zoo["lm"].d)
    strat = MaTUStrategy(2, d)
    strat.use_layouts({0: zoo["lm"].fingerprint, 1: zoo["lm"].fingerprint})
    tvs = jnp.asarray(np.random.default_rng(0).standard_normal((1, d)),
                      jnp.float32)
    bad = Upload(0, [1], tvs, [64], fingerprint=zoo["vit"].fingerprint)
    with pytest.raises(TaskVectorLayoutError, match="refusing to aggregate"):
        strat.aggregate_batch(RoundBatch.from_uploads([bad], 2))
    assert strat.downlinks == {}  # nothing aggregated
    # matching fingerprint passes the same gate
    ok = Upload(0, [1], tvs, [64], fingerprint=zoo["lm"].fingerprint)
    strat.aggregate_batch(RoundBatch.from_uploads([ok], 2))
    assert 0 in strat.downlinks


def test_simulator_rejects_split_brain_holders(zoo):
    """Holders of one task with different manifests are refused at
    simulator construction (before any training happens)."""
    con = make_constellation(n_tasks=2, n_groups=2, feat_dim=FEAT_DIM,
                             n_classes=4, seed=0)
    split = FedSplit([[0], [0]], {(0, 0): None, (1, 0): None},
                     {(0, 0): 64, (1, 0): 64})
    d = round_up_d(max(zoo["lm"].d, zoo["vit"].d))
    with pytest.raises(TaskVectorLayoutError, match="different"):
        FedSimulator(FedConfig(rounds=1), con, split,
                     {0: zoo["lm"], 1: zoo["vit"]}, MaTUStrategy(2, d))


# -- cross-architecture wire parity ------------------------------------------

def real_finetune_uploads(zoo, families, n_tasks, d):
    """One upload per family, each row a REAL local fine-tune delta
    (3 AdamW steps through the family's actual forward), zero-padded to
    the common d."""
    con = make_constellation(n_tasks=n_tasks, n_groups=2, feat_dim=FEAT_DIM,
                             n_classes=4, seed=3)
    ups = []
    for cid, fam in enumerate(families):
        bb = zoo[fam]
        trainer = make_local_trainer(bb, steps=3, batch_size=8, lr=1e-2)
        rng = jax.random.PRNGKey(100 + cid)
        tasks = [(2 * cid) % n_tasks, (2 * cid + 1) % n_tasks]
        tvs = []
        for t in tasks:
            rng, k1, k2, k3 = jax.random.split(rng, 4)
            x, y = sample_task_batch(con.tasks[t], k1, 32)
            head = make_head(k2, bb.feat_out, con.n_classes)
            tv, _, _ = trainer(jnp.zeros((bb.d,), jnp.float32), head,
                               x, y, k3)
            assert float(jnp.linalg.norm(tv)) > 0  # training moved it
            tvs.append(pad_vector(tv, d))
        unified, masks, lams = unify_with_modulators(jnp.stack(tvs))
        # bf16-quantise ONCE at the wire boundary (as the uplink does)
        # so the engine and the dense reference consume identical values
        ups.append(ClientUpload(cid, tasks, quantize_bf16_transport(unified),
                                masks, lams, [64, 64]))
    return ups


def test_cross_arch_round_packed_bool_bit_parity(zoo, monkeypatch):
    """The engine's round over the packed uint32 wire ≡ the dense
    reference round (``core.aggregation.matu_round`` + ``core.unify``)
    on a round of real fine-tune deltas from different architectures
    padded to one common d: task vectors, similarity, τ̂, m̂ and every
    client's downlink (masks bit for bit).  Pinned to the streaming ref
    round, the CPU path."""
    from test_round_engine import assert_round_equal, dense_oracle

    monkeypatch.setenv("REPRO_DISABLE_PALLAS", "1")
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    families = ["lm", "vit", "ssm", "moe"]
    d = round_up_d(max(zoo[f].d for f in families))
    assert d % D_BOUNDARY == 0
    n_tasks = 4
    ups = real_finetune_uploads(zoo, families, n_tasks, d)
    downs, out = RoundEngine(EngineConfig(n_tasks=n_tasks)).round(ups)
    assert_round_equal(dense_oracle(ups, n_tasks), out, downs, ups)
    # wire accounting is measured off the packed buffers
    bits = sum(u.uplink_bits() for u in ups)
    assert bits > 0


# -- the 30-task reduced-zoo round -------------------------------------------

def test_thirty_task_zoo_round_end_to_end(zoo):
    """30 tasks across 4 distinct families, one full MaTUStrategy round
    through the simulator: per-client manifests flatten into the shared
    slot layout, wire bits are measured, downlinks are packed, and the
    layout expectations are installed per task."""
    families = ["lm", "vit", "ssm", "moe"]
    n_tasks, n_classes = 30, 4
    con = make_constellation(n_tasks=n_tasks, n_groups=4, feat_dim=FEAT_DIM,
                             n_classes=n_classes, seed=5)
    # client c holds task c; family rotates -> holders trivially agree
    tasks = [[t] for t in range(n_tasks)]
    split = FedSplit(tasks,
                     {(c, c): None for c in range(n_tasks)},
                     {(c, c): 64 for c in range(n_tasks)})
    bbs = {c: zoo[families[c % len(families)]] for c in range(n_tasks)}
    d = round_up_d(max(b.d for b in bbs.values()))
    cfg = FedConfig(rounds=1, local_steps=2, batch_size=8, local_data=32,
                    eval_every=1, seed=0)
    strat = MaTUStrategy(n_tasks, d)
    sim = FedSimulator(cfg, con, split, bbs, strat)
    assert sim.d == d and sim.d % D_BOUNDARY == 0
    assert set(strat.expected_layouts) == set(range(n_tasks))
    assert len(set(strat.expected_layouts.values())) == len(families)
    hist = sim.run()
    assert hist.rounds == [1]
    assert hist.uplink_bits_per_round[0] > 0
    assert hist.downlink_bits_per_round[0] > 0
    assert len(hist.task_acc[0]) == n_tasks
    for dl in strat.downlinks.values():
        assert dl.packed and dl.unified.shape == (d,)
