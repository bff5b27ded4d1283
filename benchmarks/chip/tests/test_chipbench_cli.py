"""The command refuses to run without a TPU: non-zero exit, no result."""

import os
import subprocess
import sys

import chipbench_testlib as lib


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(lib.CHIP / "run.py")] + args,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def test_no_tpu_exits_nonzero_without_result():
    p = _run(["--workload", "qwen2-0.5b.round_sync", "--seed", str(lib.SEED),
              "--seconds", "1", "--trace", "0"], lib.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_unknown_workload_exits_nonzero():
    p = _run(["--workload", "no.such_cell", "--seed", "1", "--seconds", "1",
              "--trace", "0"], lib.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
