"""Production mesh definitions.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (smoke tests see 1 device; only dryrun.py forces
512 host devices via XLA_FLAGS before any jax import).

Target hardware (roofline constants): TPU v5e — 197 TFLOP/s bf16/chip,
819 GB/s HBM/chip, ~50 GB/s/link ICI. One pod = 16x16 = 256 chips;
multi-pod = 2 pods = 512 chips with a slower inter-pod axis.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
DCN_BW = 6.25e9               # bytes/s per host inter-pod (25 GbE-ish x2)
HBM_PER_CHIP = 16 * 2**30     # 16 GiB


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    # Auto axes: GSPMD propagates shardings from the constraints the
    # serving dispatch pins (jax.make_mesh defaults to Explicit axes,
    # whose sharding-in-types rejects the unannotated ops of this code).
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *, multi_pod: bool = False):
    """Small mesh for CI tests (run under forced host-device count)."""
    if multi_pod:
        return _make_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _make_mesh((n_data, n_model), ("data", "model"))


def make_serving_mesh(n_model: int, *, n_data: int = 1):
    """Mesh for the sharded serving stack (PlaneStore shards + sharded
    decode): tensor/expert parallelism over ``model``, optional replica
    rows over ``data``. Same axes as the debug/production meshes so
    :func:`repro.launch.sharding.serving_spec_for_param` applies
    unchanged. Call only under an adequate device count (e.g.
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` in CI)."""
    return _make_mesh((n_data, n_model), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    """Axes that shard the batch/FSDP dimension (pod joins data)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis(mesh) -> str:
    return "model"
