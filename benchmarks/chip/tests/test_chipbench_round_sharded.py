"""The sharded round cell on the CPU: its runner at a tiny d on four
host devices (in a subprocess, ``--xla_force_host_platform_device_count
=4``, as the main test process keeps one device), the blocked
reference against ``chipref/matu_round.py``, the configuration file
against the program, and the readers that serve a four-chip cell."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

import chipbench_testlib as lib
from benchlib import load, manifest
from benchlib.context import Observations
from benchlib.trace import TraceSummary

CELL = "qwen2.5-32b.round_sharded"

SCRIPT = textwrap.dedent("""
    import dataclasses, json, sys
    sys.path.insert(0, {tests!r})
    import numpy as np
    import chipbench_testlib as lib
    import jax
    from repro.core.server import MaTUServer

    def tiny():
        conf = lib.tiny_lm("qwen2.5-32b")
        tf = lib.traffic("round_sharded", clients=8, tasks=6)
        return lib.cell({cell!r}, conf, tf)

    out = {{"devices": len(jax.devices())}}
    out["sound"] = lib.run_cell(tiny(), seconds=0.5)
    orig = MaTUServer.round

    def altered(self, uploads, **kw):
        downs = orig(self, uploads, **kw)
        first = min(downs)
        dl = downs[first]
        u = np.asarray(dl.unified, np.float32).copy()
        u[: u.size // 64] *= -1.0
        downs[first] = dataclasses.replace(
            dl, unified=u.astype(dl.unified.dtype))
        return downs

    MaTUServer.round = altered
    out["altered"] = lib.run_cell(tiny(), seconds=0.3)
    MaTUServer.round = lambda self, uploads, **kw: orig(
        self, uploads[: len(uploads) // 2], **kw)
    out["half"] = lib.run_cell(tiny(), seconds=0.3)
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def runs():
    """One subprocess on four host devices: a sound run, a run whose
    first downlink is altered, a run that leaves half the clients out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(lib.ROOT / "src"))
    script = SCRIPT.format(tests=str(lib.CHIP / "tests"), cell=CELL)
    got = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=600)
    assert got.returncode == 0, got.stderr[-3000:]
    return json.loads(got.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(runs):
    assert runs["devices"] == 4
    out = runs["sound"]
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1
    assert out["device"]["count"] == 4
    assert out["metrics"]["round_clients_per_s"]["value"] > 0


def test_altered_downlink_is_caught(runs):
    assert runs["altered"]["correct"] is False


def test_half_the_clients_left_out_is_caught(runs):
    out = runs["half"]
    assert out["correct"] is False
    assert out["checks"]["clients_missing"]["value"] > 0


@pytest.mark.parametrize("d,block", [(3072, 3072), (3072, 1024),
                                     (3072, 1280), (3000, 1024)],
                         ids=["block=d", "block-divides-d",
                              "block-does-not-divide-d", "d-not-word-aligned"])
def test_blocked_reference_equals_matu_round(d, block):
    from chipref import matu_round, matu_round_blocked
    tf = lib.traffic("round_sharded", clients=8, tasks=6, distinct_rounds=1)
    rnd = load("runners", "round").make_rounds(lib.SEED, tf, d)[0]
    args = (rnd["unified"], rnd["words"], rnd["lams"], rnd["sizes"],
            rnd["tasks"], tf["tasks"], d)
    want = matu_round.round_downlinks(*args)
    got = matu_round_blocked.round_downlinks(*args, block=block)
    assert sorted(got) == sorted(want)
    for cid, (wu, wm, wl) in want.items():
        gu, gm, gl = got[cid]
        assert gu.dtype == wu.dtype and gm.shape == wm.shape
        wu, gu = np.asarray(wu, np.float32), np.asarray(gu, np.float32)
        # float32 rounding of the task vectors shows at most as one
        # bf16 step of the unified vector, or one mask bit
        assert np.linalg.norm(gu - wu) <= 1e-3 * np.linalg.norm(wu)
        assert np.mean(gm != wm) <= 1e-3
        np.testing.assert_allclose(gl, wl, rtol=1e-5)


def test_blocked_reference_refuses_a_block_past_exact_dots():
    from chipref import matu_round_blocked
    with pytest.raises(ValueError):
        list(matu_round_blocked._blocks(1 << 25, (1 << 24) + 32))
    with pytest.raises(ValueError):
        list(matu_round_blocked._blocks(4096, 1000))


def test_config_lora_d_is_the_programs_at_published_widths():
    from repro.common.tree import TaskVectorSpace
    from benchlib.lm import build_model
    conf = lib.config("qwen2.5-32b")
    cfg, model = build_model(conf)
    lshape = jax.eval_shape(model.lora_init, jax.random.PRNGKey(0))
    assert TaskVectorSpace.from_tree(lshape).d == conf["lora_d"] == 54_526_144
    pshape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(pshape)) == conf["n_params"]
    assert cfg.tie_embeddings is False and conf["reduced"] == []


def test_manifest_is_sound_with_the_cell():
    bench = manifest.load()
    assert manifest.validate(bench) == []
    c = manifest.cell(bench, CELL)
    assert c.chips == 4 and c.traffic["runner"] == "round_sharded"
    assert "round.collective_ms" in [m["name"] for m in c.per_layer]


def _summary(op_ns, n_devices=4, custom=()):
    events = {k: [v / 2, v / 2] for k, v in op_ns.items()}
    return TraceSummary((0.0, 1e10), n_devices, 5e9, dict(op_ns), events,
                        custom_calls=set(custom))


def test_collective_ms_reads_every_collective_kind_per_chip_per_round():
    reader = load("metrics", "round.collective_ms")
    op_ns = {"all-reduce": 4e6, "all-reduce-start": 2e6,
             "all-reduce-done": 1e6, "collective-permute-done": 8e6,
             "all-gather": 1e6, "reduce-scatter": 1e6, "all-to-all": 1e6,
             "fusion": 9e9, "masked_agg_batched_packed_pallas": 3e9}
    obs = Observations(trace=_summary(op_ns), work={"rounds": 2})
    assert reader.read(obs) == pytest.approx(18e6 * 1e-6 / 4 / 2)
    # no collective on one chip reads 0; untraced or roundless reads None
    one = Observations(trace=_summary({"fusion": 1e9}, 1), work={"rounds": 3})
    assert reader.read(one) == 0.0
    assert reader.read(Observations(work={"rounds": 2})) is None
    assert reader.read(Observations(trace=_summary(op_ns))) is None


def test_aggregate_peaks_keep_a_kernel_roofline_per_chip():
    """Four device planes at the full d against four chips' peaks read
    what one plane at d / 4 reads against one chip's peaks."""
    runner = load("runners", "round_sharded")
    reader = load("metrics", "round.masked_agg_roofline")
    kernel = "masked_agg_batched_packed_pallas"
    d, rounds, per_call_ns = 54_526_144, 3, 0.5e9
    work = {"clients": 32, "tasks": 30, "tasks_per_client": 2,
            "rounds": rounds, "elapsed_s": 10.0}
    four = Observations(
        peaks=runner.aggregate_peaks(lib.PEAKS, 4), work=dict(work, d=d),
        trace=TraceSummary((0.0, 1e10), 4, 5e9,
                           {kernel: 4 * rounds * per_call_ns},
                           {kernel: [per_call_ns] * (4 * rounds)},
                           custom_calls={kernel}))
    one = Observations(
        peaks=lib.PEAKS, work=dict(work, d=d // 4),
        trace=TraceSummary((0.0, 1e10), 1, 5e9, {kernel: rounds * per_call_ns},
                           {kernel: [per_call_ns] * rounds},
                           custom_calls={kernel}))
    # equal but for the rounding of d / 32 up to whole mask words
    assert reader.read(four) == pytest.approx(reader.read(one), rel=1e-6)
    assert runner.aggregate_peaks(lib.PEAKS, 4)["source"] == lib.PEAKS["source"]


def test_runner_refuses_a_chunked_round():
    runner = load("runners", "round_sharded")
    with pytest.raises(ValueError):
        runner.Server(lib.traffic("round_sharded", chunk_clients=4), 1)
