"""Production mesh definitions (TPU v5e pods).

Single-pod: 256 chips as (16, 16) → ("data", "model").
Multi-pod:  2 × 256 chips as (2, 16, 16) → ("pod", "data", "model").

``make_production_mesh`` is a FUNCTION (not a module constant) so that
importing this module never touches jax device state — the dry-run
sets XLA_FLAGS for 512 host devices before any jax import; smoke tests
and benches see the single real CPU device.
"""

from __future__ import annotations

from typing import Mapping, Optional

import jax

# TPU v5e hardware constants (per chip) — used by the roofline report.
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # B/s
ICI_BW = 50e9                  # B/s per link


def _make_mesh(shape, axes, devices=None):
    """jax.make_mesh with Auto axis types, so GSPMD stays in charge."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CI-scale sharding tests (8 host devices)."""
    return _make_mesh(shape, axes)


def make_round_mesh(n_devices: Optional[int] = None):
    """1-D ("data",) mesh over the first ``n_devices`` local devices for
    the taskvec-sharded round engine (benches / single-host serving).
    The "taskvec" rule maps onto ("pod", "data", "model"), so on this
    mesh the d axis splits ``n_devices`` ways; on the production pod
    meshes the same rule spans all 256/512 chips."""
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"make_round_mesh: {n} devices requested, "
                         f"{len(devs)} available")
    return _make_mesh((n,), ("data",), devices=devs[:n])


def make_population_mesh(slots: int = 2, n_devices: Optional[int] = None):
    """2-D ("slots", "data") mesh for the chunked population round: the
    "slots" axis shards a chunk's client/slot rows (ingest + phase-C
    downlink re-unification, see the engine's population-scale
    contract) and "data" carries the taskvec d-sharding — composing
    into the ROADMAP's (slots × taskvec) layout.  ``slots`` must divide
    the device count; power-of-two counts keep the chunk row padding
    aligned."""
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"make_population_mesh: {n} devices requested, "
                         f"{len(devs)} available")
    if slots < 1 or n % slots != 0:
        raise ValueError(f"make_population_mesh: slots={slots} must divide "
                         f"the device count {n}")
    return _make_mesh((slots, n // slots), ("slots", "data"),
                      devices=devs[:n])


def arch_rules(cfg, mesh) -> Mapping[str, object]:
    """Per-arch logical-axis rule overrides (DESIGN.md §5).

    kv_heads shard over ``model`` only when the head count divides the
    axis (codeqwen MHA); otherwise KV stays replicated (standard GQA
    tensor parallelism).
    """
    n_model = mesh.shape.get("model", 1)
    rules = {}
    if cfg.n_kv_heads and cfg.n_kv_heads % n_model == 0 and not cfg.use_mla:
        rules["kv_heads"] = "model"
    return rules
