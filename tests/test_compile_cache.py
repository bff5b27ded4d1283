"""Where the persistent compilation cache lives
(``repro.launch.compile_cache``): in ``JAX_COMPILATION_CACHE_DIR`` when
it is set, else in the checkout's fixed ``.jax_cache`` directory.

Each case runs in a subprocess, so the test process's JAX config is
never touched, and caches compiles of every duration."""

import os
import subprocess
import sys
import textwrap

from repro.launch.compile_cache import CHECKOUT_CACHE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    print(enable_compile_cache())
    def {name}(x):
        return jnp.sin(x) * 3.0 + 1.0
    jax.jit({name})(jnp.arange(7.0)).block_until_ready()
""")


def _run(name: str, cache_env=None) -> str:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run([sys.executable, "-c", _SCRIPT.format(name=name)],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def _entries(path: str, name: str):
    if not os.path.isdir(path):
        return []
    return [f for f in os.listdir(path) if f.startswith(f"jit_{name}")]


def test_cache_goes_to_env_dir_only(tmp_path):
    name = "env_cache_probe"
    assert _run(name, str(tmp_path)) == str(tmp_path)
    assert _entries(str(tmp_path), name)
    assert not _entries(CHECKOUT_CACHE, name)


def test_cache_defaults_to_checkout_dir():
    name = "checkout_cache_probe"
    assert _run(name) == CHECKOUT_CACHE
    assert CHECKOUT_CACHE == os.path.join(REPO, ".jax_cache")
    assert _entries(CHECKOUT_CACHE, name)
