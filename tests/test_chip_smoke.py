"""``chip_smoke.py`` on the CPU: its phases at tiny sizes with the
Pallas kernels interpreted, and its refusal to run without a TPU.

The script's ``main()`` runs only on a chip; these tests call the
phase functions directly on a reduced qwen2 config in bf16 with rank-16
LoRA (the dtype and rank of the published config), so every check the
chip run makes is exercised here on every PR."""

import dataclasses
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_phases_tiny_bf16(monkeypatch):
    from repro.configs.base import load_arch
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("REPRO_DISABLE_PALLAS", raising=False)
    assert ops.resolve_mode() == "pallas_interpret"
    cfg = dataclasses.replace(load_arch("qwen2-0.5b").reduced(),
                              dtype=jnp.bfloat16, lora_rank=16)
    model, params, lora0, space, uploads = chip_smoke.phase_clients(
        cfg, seed=0, batch=2, seq=16)
    assert len(uploads) == 4 and uploads[3].task_ids == [0, 2]
    assert all(u.fingerprint == space.fingerprint for u in uploads)
    server = chip_smoke.phase_server(uploads)
    chip_smoke.phase_serving(cfg, model, params, lora0, space, server,
                             seed=0, n_requests=4, prompt_len=8,
                             new_tokens=4, mixes=([0, 1, 2, 0], [2, 2, 1, 0]))


def test_sharded_phase_on_four_host_devices():
    script = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["REPRO_PALLAS_INTERPRET"] = "1"
        sys.path.insert(0, {REPO!r})
        import chip_smoke
        chip_smoke.phase_sharded(seed=0, n_devices=4, d=5000, n_clients=6,
                                 n_tasks=5, k=2)
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_DISABLE_PALLAS", None)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "sharded_vs_single=match" in out.stdout
    assert "per_device=bf16[8,2048]" in out.stdout


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_without_tpu(tmp_path, where):
    """No TPU: non-zero exit and no result line — whether run from the
    checkout or from a directory holding nothing but the script."""
    script = SCRIPT
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, env=env, timeout=300,
                         cwd=os.path.dirname(script))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_lora_d_matches_published_config():
    """The d the chip run asserts is the published config's."""
    from repro.common.tree import TaskVectorSpace
    from repro.configs.base import load_arch
    import numpy as np
    model = load_arch(chip_smoke.ARCH).build()
    tree = jax.eval_shape(lambda: model.lora_init(jax.random.PRNGKey(0)))
    space = TaskVectorSpace.from_tree(jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, s.dtype), tree))
    assert space.d == chip_smoke.LORA_D
