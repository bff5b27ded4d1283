"""Host-pipeline contract: pipelined ≡ sequential, bit for bit.

The two-deep ``RoundEngine.round_stream`` pipeline (pack/decode round
r+1 and encode round r−1's downlinks while round r's jitted step runs)
must be a pure reordering — every downlink byte and every engine output
identical to the ``pipeline=False`` escape hatch, for uploads whose
masks arrive as uint32 words or as dense bool rows, raw and
entropy-coded wires, and the ref / pallas_interpret dispatch modes.
The simulator-level deferred drain (``MaTUStrategy(pipeline=True)``
via ``FedConfig.pipeline``) gets the same multi-round A/B, plus the
per-phase timing plumbing the pipeline makes observable
(History.phase_us).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.client import ClientUpload
from repro.core.engine import EngineConfig, RoundEngine, SlotStage, pack_uploads
from repro.core.unify import unify_with_modulators
from repro.fed.compression import encode_mask_rows
from repro.kernels import bitpack

jax.config.update("jax_platform_name", "cpu")

N_TASKS = 5
D = 512


def _make_rounds(seed, n_rounds, *, coded=False, as_words=True,
                 n_clients=4):
    """n_rounds of ragged uploads (different clients/masks per round):
    masks entropy-coded, as uint32 words with a bf16 vector
    (``as_words``), or as the dense bool rows and fp32 vector a client
    makes, which ``pack_uploads`` packs and rounds."""
    rng = np.random.default_rng(seed)
    rounds = []
    for _ in range(n_rounds):
        ups = []
        for cid in range(n_clients):
            k = int(rng.integers(1, 4))
            tasks = sorted(rng.choice(N_TASKS, size=k, replace=False).tolist())
            tvs = jnp.asarray(rng.standard_normal((k, D)), jnp.float32)
            unified, masks, lams = unify_with_modulators(tvs)
            words = bitpack.pack_bits_np(np.asarray(masks))
            if coded:
                m = jnp.asarray(encode_mask_rows(words, D))
            elif as_words:
                m = jnp.asarray(words)
            else:
                m = masks
            vec = unified.astype(jnp.bfloat16) if as_words else unified
            ups.append(ClientUpload(cid, tasks, vec, m, lams,
                                    rng.integers(32, 256, size=k).tolist()))
        rounds.append(ups)
    return rounds


def _assert_rounds_equal(seq, pipe):
    assert len(seq) == len(pipe)
    for (downs_s, out_s, _), (downs_p, out_p, _) in zip(seq, pipe):
        np.testing.assert_array_equal(np.asarray(out_s.task_vectors),
                                      np.asarray(out_p.task_vectors))
        assert downs_s.keys() == downs_p.keys()
        for cid in downs_s:
            np.testing.assert_array_equal(np.asarray(downs_s[cid].masks),
                                          np.asarray(downs_p[cid].masks))
            np.testing.assert_array_equal(np.asarray(downs_s[cid].unified),
                                          np.asarray(downs_p[cid].unified))
            np.testing.assert_array_equal(np.asarray(downs_s[cid].lams),
                                          np.asarray(downs_p[cid].lams))


@pytest.mark.parametrize("mode", ["ref", "pallas_interpret"])
@pytest.mark.parametrize("layout", ["words", "dense"])
@pytest.mark.parametrize("coded", [False, True])
def test_round_stream_pipelined_matches_sequential(mode, layout, coded):
    rounds = _make_rounds(0, 3, coded=coded, as_words=layout == "words")
    eng = RoundEngine(EngineConfig(n_tasks=N_TASKS))
    seq = list(eng.round_stream(rounds, mode=mode, code_masks=coded,
                                pipeline=False))
    pipe = list(eng.round_stream(rounds, mode=mode, code_masks=coded,
                                 pipeline=True))
    _assert_rounds_equal(seq, pipe)
    for _, _, phase in pipe:
        assert {"pack", "decode", "device"} <= set(phase)
        if coded:
            assert "encode" in phase and phase["encode"] > 0
    # coded downlinks are real uint8 streams in both paths
    if coded:
        for downs, _, _ in pipe:
            assert all(np.asarray(dl.masks).dtype == np.uint8
                       for dl in downs.values())


def test_round_stream_matches_round_api():
    """The streamed rounds equal one-shot ``RoundEngine.round`` calls —
    the pipeline is a scheduling layer, not a different computation."""
    rounds = _make_rounds(1, 3, coded=True)
    eng = RoundEngine(EngineConfig(n_tasks=N_TASKS))
    streamed = list(eng.round_stream(rounds, code_masks=True))
    for ups, (downs_s, out_s, _) in zip(rounds, streamed):
        downs, out = eng.round(ups, code_masks=True)
        np.testing.assert_array_equal(np.asarray(out.task_vectors),
                                      np.asarray(out_s.task_vectors))
        for cid in downs:
            np.testing.assert_array_equal(np.asarray(downs[cid].masks),
                                          np.asarray(downs_s[cid].masks))


def test_slot_stage_reuse_is_clean():
    """A stage refilled with a SMALLER round (fewer clients, fewer
    slots, different masks) must not leak the previous round's bytes
    through the padding — the explicit re-zeroing contract."""
    big = _make_rounds(2, 1, coded=False, n_clients=4)[0]
    small = _make_rounds(3, 1, coded=False, n_clients=3)[0]
    stage = SlotStage()
    pack_uploads(big, N_TASKS, stage=stage)
    reused = pack_uploads(small, N_TASKS, n_max=4, stage=stage)
    fresh = pack_uploads(small, N_TASKS, n_max=4)
    np.testing.assert_array_equal(np.asarray(reused.slot_masks),
                                  np.asarray(fresh.slot_masks))
    np.testing.assert_array_equal(np.asarray(reused.unified),
                                  np.asarray(fresh.unified))


def test_pack_uploads_batched_decode_parity():
    """Mixed coded/raw rounds: the single cross-client batched decode
    in pack_uploads equals packing the raw twins."""
    raw = _make_rounds(4, 1, coded=False)[0]
    coded = [ClientUpload(u.client_id, u.task_ids, u.unified,
                          jnp.asarray(encode_mask_rows(
                              np.asarray(u.masks), D)),
                          u.lams, u.data_sizes)
             for u in raw]
    mixed = [coded[i] if i % 2 else raw[i] for i in range(len(raw))]
    b_raw = pack_uploads(raw, N_TASKS)
    for ups in (coded, mixed):
        b = pack_uploads(ups, N_TASKS)
        np.testing.assert_array_equal(np.asarray(b.slot_masks),
                                      np.asarray(b_raw.slot_masks))


# -- simulator-level pipeline -------------------------------------------------

def _sim_setting():
    from repro.data.dirichlet import dirichlet_split
    from repro.data.synthetic import make_constellation
    from repro.fed.testbed import MLPBackbone
    con = make_constellation(n_tasks=N_TASKS, n_groups=2, feat_dim=16,
                             n_classes=4, seed=0)
    split = dirichlet_split(n_clients=5, n_tasks=N_TASKS, n_classes=4,
                            zeta_t=0.5, tasks_per_client=2, seed=0)
    bb = MLPBackbone(16, hidden=24, lora_rank=4)
    return con, split, bb


@pytest.mark.parametrize("mode_env", ["ref", "pallas_interpret"])
def test_simulator_pipeline_bit_parity(mode_env, monkeypatch):
    """FedConfig.pipeline=True (deferred strategy drain) reproduces the
    sequential run bit for bit: accuracies, measured wire bits, and
    per-client downlink streams — under both dispatch modes."""
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    monkeypatch.setenv("REPRO_DISABLE_PALLAS", "1")
    if mode_env == "pallas_interpret":
        monkeypatch.delenv("REPRO_DISABLE_PALLAS", raising=False)
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    from repro.fed.simulator import FedConfig, FedSimulator
    from repro.fed.strategies import MaTUStrategy
    con, split, bb = _sim_setting()
    hists, strats = {}, {}
    for pipe in (False, True):
        cfg = FedConfig(rounds=3, local_steps=3, eval_every=1, seed=0,
                        pipeline=pipe)
        strat = MaTUStrategy(N_TASKS, bb.d, code_masks=True)
        hists[pipe] = FedSimulator(cfg, con, split, bb, strat).run()
        strats[pipe] = strat
    assert hists[True].mean_acc == hists[False].mean_acc
    assert hists[True].task_acc == hists[False].task_acc
    assert (hists[True].uplink_bits_per_round
            == hists[False].uplink_bits_per_round)
    assert (hists[True].downlink_bits_per_round
            == hists[False].downlink_bits_per_round)
    for cid, dl in strats[False].downlinks.items():
        dl_p = strats[True].downlinks[cid]
        np.testing.assert_array_equal(np.asarray(dl.masks),
                                      np.asarray(dl_p.masks))
        np.testing.assert_array_equal(np.asarray(dl.unified),
                                      np.asarray(dl_p.unified))


def test_simulator_phase_timings_recorded():
    """History.phase_us carries the codec/device split; under
    pipeline=True the first entry is empty (nothing completed yet) and
    later entries hold the previous round's completed phases."""
    from repro.fed.simulator import FedConfig, FedSimulator
    from repro.fed.strategies import MaTUStrategy
    con, split, bb = _sim_setting()
    cfg = FedConfig(rounds=3, local_steps=2, eval_every=3, seed=0)
    strat = MaTUStrategy(N_TASKS, bb.d, code_masks=True)
    hist = FedSimulator(cfg, con, split, bb, strat).run()
    assert len(hist.phase_us) == 3
    for ph in hist.phase_us:     # sequential: every round completed
        assert {"pack", "device", "encode"} <= set(ph)
        assert all(v >= 0 for v in ph.values())
    mean = hist.mean_phase_us
    assert mean["device"] > 0 and mean["pack"] > 0

    cfg_p = FedConfig(rounds=3, local_steps=2, eval_every=3, seed=0,
                      pipeline=True)
    strat_p = MaTUStrategy(N_TASKS, bb.d, code_masks=True)
    hist_p = FedSimulator(cfg_p, con, split, bb, strat_p).run()
    assert hist_p.phase_us[0] == {}          # round 0 still in flight
    assert {"pack", "device"} <= set(hist_p.phase_us[-1])
