import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_DRYRUN_XLA_FLAGS")
                           or "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
on the production meshes, without allocating any real buffers.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out results/]

For each combo this:
  1. builds the model at full config (bf16),
  2. eval_shape's params / LoRA / optimizer state / caches,
  3. maps every tensor's logical axes to NamedShardings on the mesh,
  4. jit-lowers the step (train: loss+LoRA-grads+AdamW; prefill; decode),
  5. compiles, and records memory_analysis / cost_analysis / per-kind
     collective bytes parsed from the compiled HLO into a JSON artifact
     consumed by benchmarks/bench_roofline.py (§Roofline).

NOTE: the XLA_FLAGS line above MUST run before any other import — jax
locks the device count on first init.  (The first import of jax happens
transitively below.)
"""

import argparse
import json
import re
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ARCH_IDS, SHAPES, input_specs, load_arch
from repro.launch.mesh import arch_rules, make_production_mesh
from repro.nn.sharding import logical_to_sharding, mesh_context
from repro.optim import adamw
from repro.train.trainer import make_train_step

PyTree = Any


# ---------------------------------------------------------------------------
# collective-byte accounting (parsed from compiled HLO)
# ---------------------------------------------------------------------------

_COLL_RE = re.compile(
    r"%?(\w[\w.\-]*)\s*=\s*((?:[a-z0-9_]+\[[^\]]*\](?:\{[^}]*\})?|\([^)]*\)))"
    r"\s*%?(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(sig: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(sig):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output-shape bytes of every collective op, per kind.

    Shapes in the compiled module are per-device (post-SPMD), so the
    returned numbers are bytes per device per step."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        sig, kind = m.group(2), m.group(3)
        if "-start" in line and "-done" in line:
            continue
        out[kind] = out.get(kind, 0) + _shape_bytes(sig)
    return out


# ---------------------------------------------------------------------------
# sharding assembly
# ---------------------------------------------------------------------------

def batch_shardings(batch_struct, mesh):
    def one(s):
        if s.shape and s.shape[0] > 1:
            spec_axes = ("batch",) + (None,) * (len(s.shape) - 1)
        else:
            spec_axes = (None,) * len(s.shape)
        from repro.nn.sharding import resolve_spec
        return NamedSharding(mesh, resolve_spec(spec_axes, s.shape, mesh=mesh))
    return jax.tree_util.tree_map(one, batch_struct)


def opt_state_shardings(opt_state_struct, lora_sh, mesh):
    """mu/nu mirror the LoRA tree; scalars replicated."""
    def one(path, s):
        return NamedSharding(mesh, P()) if s.ndim == 0 else None
    # structure: {"step": scalar, "mu": lora-tree, "nu": lora-tree}
    return {
        "step": NamedSharding(mesh, P()),
        "mu": lora_sh,
        "nu": lora_sh,
    }


# ---------------------------------------------------------------------------
# per-combo dry run
# ---------------------------------------------------------------------------

def run_combo(arch: str, shape_name: str, mesh, *, verbose: bool = True,
              seq_override: Optional[int] = None,
              batch_override: Optional[int] = None) -> Dict[str, Any]:
    cfg = load_arch(arch)
    shape = SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.supports_long:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": "full-attention enc-dec; see DESIGN.md §4"}

    rules = arch_rules(cfg, mesh)
    t0 = time.time()
    with mesh_context(mesh, rules):
        model = cfg.build(shape)
        params_struct = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        lora_struct = jax.eval_shape(lambda: model.lora_init(jax.random.PRNGKey(1)))
        params_sh = logical_to_sharding(model.axes(), params_struct, mesh=mesh, rules=None)
        lora_sh = logical_to_sharding(model.lora_axes(), lora_struct, mesh=mesh, rules=None)
        batch_struct = input_specs(cfg, shape, batch_override=batch_override,
                                   seq_override=seq_override)
        batch_sh = batch_shardings(batch_struct, mesh)

        if shape.kind == "train":
            train_step, opt = make_train_step(model, adamw(1e-4))
            opt_struct = jax.eval_shape(opt.init, lora_struct)
            opt_sh = opt_state_shardings(opt_struct, lora_sh, mesh)
            fn = jax.jit(train_step,
                         in_shardings=(params_sh, lora_sh, opt_sh, batch_sh),
                         donate_argnums=(1, 2))
            args = (params_struct, lora_struct, opt_struct, batch_struct)
        else:
            b = batch_override or shape.global_batch
            s = seq_override or shape.seq_len
            cache_struct = jax.eval_shape(
                lambda: model.init_cache(b, s))
            cache_sh = logical_to_sharding(model.cache_axes(), cache_struct,
                                           mesh=mesh, rules=None)
            if shape.kind == "prefill":
                def prefill_step(params, lora, batch, cache):
                    return model.prefill_step(params, lora, batch, cache)
                fn = jax.jit(prefill_step,
                             in_shardings=(params_sh, lora_sh, batch_sh, cache_sh),
                             donate_argnums=(3,))
                args = (params_struct, lora_struct, batch_struct, cache_struct)
            else:
                def decode_step(params, lora, batch, cache, pos):
                    return model.decode_fn(params, lora, batch, cache, pos)
                pos_struct = jax.ShapeDtypeStruct((), jnp.int32)
                fn = jax.jit(decode_step,
                             in_shardings=(params_sh, lora_sh, batch_sh, cache_sh,
                                           NamedSharding(mesh, P())),
                             donate_argnums=(3,))
                args = (params_struct, lora_struct, batch_struct, cache_struct,
                        pos_struct)

        with mesh:
            lowered = fn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())

    # memory_analysis numbers are PER DEVICE (verified empirically);
    # cost_analysis flops/bytes are whole-program sums.
    n_dev = mesh.size
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": dict(mesh.shape),
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory_per_device": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "temp_size_in_bytes", 0) or 0)
                          + (getattr(mem, "argument_size_in_bytes", 0) or 0),
        },
        "cost": {
            "flops": cost.get("flops") if cost else None,
            "bytes_accessed": cost.get("bytes accessed") if cost else None,
        },
        "collective_bytes_per_device": coll,
        "devices": n_dev,
    }
    if verbose:
        mb = result["memory_per_device"]
        print(f"[{arch} × {shape_name} × {tuple(mesh.shape.values())}] "
              f"compile={t_compile:.0f}s  "
              f"args/dev={(mb['argument_bytes'] or 0)/2**30:.2f}GiB  "
              f"temp/dev={(mb['temp_bytes'] or 0)/2**30:.2f}GiB  "
              f"peak/dev={(mb['peak_bytes'] or 0)/2**30:.2f}GiB  "
              f"flops={result['cost']['flops'] or 0:.3e}  "
              f"coll={ {k: f'{v/2**20:.0f}MiB' for k, v in coll.items()} }")
    return result


def run_matu_round(mesh, *, n_clients: int = 30, n_tasks: int = 30,
                   d: int = 1 << 27, verbose: bool = True):
    """Lower the paper's server aggregation (Eq. 3-6, matu_round) on the
    production mesh: the d dimension shards over ALL mesh axes
    ('taskvec' rule); Eq. 5's sign-dot reduction over d becomes the only
    cross-shard collective.  d defaults to 2^27 (a 7B-class LoRA space /
    a 134M-param full-fine-tune task vector)."""
    from repro.core.aggregation import matu_round
    from repro.nn.sharding import mesh_context, resolve_spec

    t0 = time.time()
    with mesh_context(mesh):
        dv = NamedSharding(mesh, resolve_spec(("taskvec",), (d,), mesh=mesh))
        ndv = NamedSharding(mesh, resolve_spec((None, "taskvec"), (n_clients, d), mesh=mesh))
        ntdv = NamedSharding(mesh, resolve_spec((None, None, "taskvec"),
                                                (n_clients, n_tasks, d), mesh=mesh))
        rep = NamedSharding(mesh, P())
        unified = jax.ShapeDtypeStruct((n_clients, d), jnp.float32)
        masks = jax.ShapeDtypeStruct((n_clients, n_tasks, d), jnp.bool_)
        lams = jax.ShapeDtypeStruct((n_clients, n_tasks), jnp.float32)
        alloc = jax.ShapeDtypeStruct((n_clients, n_tasks), jnp.bool_)
        sizes = jax.ShapeDtypeStruct((n_clients, n_tasks), jnp.float32)

        fn = jax.jit(lambda u, m, l, a, s: matu_round(u, m, l, a, s).task_vectors,
                     in_shardings=(ndv, ntdv, rep, rep, rep))
        with mesh:
            lowered = fn.lower(unified, masks, lams, alloc, sizes)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())
    res = {
        "arch": "matu-round", "shape": f"N{n_clients}_T{n_tasks}_d{d}",
        "mesh": dict(mesh.shape), "status": "ok",
        "compile_s": round(time.time() - t0, 1),
        "memory_per_device": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.temp_size_in_bytes + mem.argument_size_in_bytes,
        },
        "cost": {"flops": cost.get("flops"), "bytes_accessed": cost.get("bytes accessed")},
        "collective_bytes_per_device": coll,
        "devices": mesh.size,
    }
    if verbose:
        m = res["memory_per_device"]
        print(f"[matu-round N={n_clients} T={n_tasks} d=2^{d.bit_length()-1} x {tuple(mesh.shape.values())}] "
              f"args/dev={m['argument_bytes']/2**30:.2f}GiB temp/dev={m['temp_bytes']/2**30:.2f}GiB "
              f"flops={res['cost']['flops'] or 0:.3e} coll={{{', '.join(f'{k}:{v/2**20:.0f}MiB' for k,v in coll.items())}}}")
    return res


def run_round_engine(mesh, *, n_clients: int = 32, n_tasks: int = 30,
                     d: int = 1 << 27, k_max: int = 4,
                     verbose: bool = True):
    """Lower + compile the taskvec-sharded round ENGINE (shard_map over
    ``ops.matu_round_slots_packed``) on the production mesh with no real
    buffers: ShapeDtypeStructs carry the d-axis NamedShardings the
    engine's pack path would install.  Reports the per-shard slot-buffer
    bytes (the wire tensors each chip actually holds) next to the
    compiled memory/cost/collective numbers the model dry-runs emit —
    the d axis shards over every mesh axis, so the only collectives are
    the two all-reduces of the sharding contract (the (T, T) similarity
    dots + the λ block-tree roots)."""
    from repro.core.engine import (EngineConfig, RoundEngine,
                                   _round_up_pow2, pad_d_for_shards)
    from repro.kernels import bitpack
    from repro.nn.sharding import taskvec_sharding

    t0 = time.time()
    eng = RoundEngine(EngineConfig(n_tasks=n_tasks), mesh=mesh)
    n_max = _round_up_pow2(n_clients)
    k_pad = _round_up_pow2(k_max)
    d_pad = pad_d_for_shards(d, eng.n_shards)
    dw = bitpack.packed_width(d_pad)
    rep = NamedSharding(mesh, P())
    args = (
        jax.ShapeDtypeStruct((n_max, d_pad), jnp.bfloat16,
                             sharding=taskvec_sharding(mesh, 2)),
        jax.ShapeDtypeStruct((n_max, k_pad, dw), jnp.uint32,
                             sharding=taskvec_sharding(mesh, 3)),
        jax.ShapeDtypeStruct((n_max, k_pad), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((n_max, k_pad), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((n_max, k_pad), jnp.bool_, sharding=rep),
        jax.ShapeDtypeStruct((n_max, k_pad), jnp.int32, sharding=rep),
    )
    with mesh:
        lowered = eng._impl("ref", d).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())

    # the wire slot buffers each shard holds (uplink; the downlink
    # mirrors them) — d-axis tensors split n_shards ways, per-slot
    # scalars replicated
    sharded = 2 * n_max * d_pad + 4 * n_max * k_pad * dw
    replicated = (4 + 4 + 1 + 4) * n_max * k_pad
    per_shard = sharded // eng.n_shards + replicated
    res = {
        "arch": "matu-round-engine",
        "shape": f"N{n_clients}_T{n_tasks}_d{d}_k{k_max}",
        "mesh": dict(mesh.shape), "status": "ok",
        "taskvec_shards": eng.n_shards,
        "d_pad": d_pad,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "slot_buffer_bytes_per_shard": per_shard,
        "slot_buffer_bytes_total": sharded + replicated * eng.n_shards,
        "memory_per_device": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.temp_size_in_bytes + mem.argument_size_in_bytes,
        },
        "cost": {"flops": cost.get("flops") if cost else None,
                 "bytes_accessed": cost.get("bytes accessed") if cost else None},
        "collective_bytes_per_device": coll,
        "devices": mesh.size,
    }
    if verbose:
        m = res["memory_per_device"]
        print(f"[matu-round-engine N={n_clients} T={n_tasks} "
              f"d=2^{d.bit_length()-1} x {tuple(mesh.shape.values())}] "
              f"shards={eng.n_shards} "
              f"slot-buf/shard={per_shard/2**20:.1f}MiB "
              f"args/dev={m['argument_bytes']/2**20:.1f}MiB "
              f"temp/dev={m['temp_bytes']/2**20:.1f}MiB "
              f"coll={{{', '.join(f'{k}:{v/2**10:.1f}KiB' for k, v in coll.items())}}}")
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--matu-round", action="store_true",
                    help="lower the MaTU server aggregation itself")
    ap.add_argument("--engine-round", action="store_true",
                    help="lower the taskvec-sharded round ENGINE "
                         "(shard_map + wire-format slot tensors)")
    ap.add_argument("--matu-d", type=int, default=1 << 27)
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    tag = "multipod" if args.multi_pod else "singlepod"
    os.makedirs(args.out, exist_ok=True)

    if args.matu_round:
        r = run_matu_round(mesh, d=args.matu_d)
        with open(os.path.join(args.out, f"matu_round__{tag}.json"), "w") as f:
            json.dump(r, f, indent=2)
        return

    if args.engine_round:
        r = run_round_engine(mesh, d=args.matu_d)
        with open(os.path.join(args.out, f"engine_round__{tag}.json"),
                  "w") as f:
            json.dump(r, f, indent=2)
        return

    combos = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                combos.append((arch, shape))
    else:
        combos.append((args.arch, args.shape))

    results = []
    for arch, shape in combos:
        try:
            r = run_combo(arch, shape, mesh, seq_override=args.seq,
                          batch_override=args.batch)
        except Exception as e:  # noqa: BLE001 — record the failure
            r = {"arch": arch, "shape": shape, "status": "error",
                 "error": f"{type(e).__name__}: {e}"}
            print(f"[{arch} × {shape}] FAILED: {r['error'][:300]}")
        results.append(r)
        path = os.path.join(args.out, f"{arch}__{shape}__{tag}.json")
        with open(path, "w") as f:
            json.dump(r, f, indent=2)

    ok = sum(1 for r in results if r["status"] == "ok")
    sk = sum(1 for r in results if r["status"] == "skipped")
    err = sum(1 for r in results if r["status"] == "error")
    print(f"\n== dry-run [{tag}]: {ok} ok, {sk} skipped, {err} failed "
          f"of {len(results)} ==")
    if err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
