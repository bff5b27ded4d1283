"""Sharding/lowering tests on a small host-device mesh (subprocess keeps
the main test process at 1 device).  Verifies that the dry-run machinery
lowers a reduced arch on a real multi-device mesh end to end."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    from repro.configs.base import SHAPES, input_specs, load_arch
    from repro.launch.dryrun import (batch_shardings, collective_bytes,
                                     opt_state_shardings)
    from repro.launch.mesh import arch_rules, make_debug_mesh
    from repro.nn.sharding import logical_to_sharding, mesh_context
    from repro.optim import adamw
    from repro.train.trainer import make_train_step

    mesh = make_debug_mesh((4, 2))
    cfg = load_arch("{arch}").reduced()
    shape = SHAPES["train_4k"]
    with mesh_context(mesh, arch_rules(cfg, mesh)):
        model = cfg.build(shape)
        params_struct = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        lora_struct = jax.eval_shape(lambda: model.lora_init(jax.random.PRNGKey(1)))
        params_sh = logical_to_sharding(model.axes(), params_struct, mesh=mesh)
        lora_sh = logical_to_sharding(model.lora_axes(), lora_struct, mesh=mesh)
        batch_struct = input_specs(cfg, shape, batch_override=8, seq_override=64)
        batch_sh = batch_shardings(batch_struct, mesh)
        train_step, opt = make_train_step(model, adamw(1e-4))
        opt_struct = jax.eval_shape(opt.init, lora_struct)
        opt_sh = opt_state_shardings(opt_struct, lora_sh, mesh)
        fn = jax.jit(train_step, in_shardings=(params_sh, lora_sh, opt_sh, batch_sh))
        with mesh:
            compiled = fn.lower(params_struct, lora_struct, opt_struct,
                                batch_struct).compile()
        cost = compiled.cost_analysis()
        print(json.dumps({{"flops": cost.get("flops", -1),
                          "coll": collective_bytes(compiled.as_text())}}))
""")


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "deepseek-v2-236b", "xlstm-1.3b"])
def test_reduced_arch_lowers_on_mesh(arch):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT.format(arch=arch)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["flops"] > 0
