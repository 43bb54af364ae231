"""Production meshes (TPU v5e target).

A function, not a module-level constant, so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips ("data", "model").
Multi-pod: 2x16x16 = 512 chips ("pod", "data", "model").

Under PNN (the paper's scheme) the "pod" axis carries *stages*, not replicas:
each pod trains one model partition with zero inter-pod collectives during
training (DESIGN.md §2.2); under the conventional baseline the pod axis is an
outer data-parallel axis.
"""
from __future__ import annotations

import os

import jax


def force_host_device_count(n: int) -> None:
    """Ask XLA's CPU backend for ``n`` host devices — the CI/dev-box
    stand-in for a multi-accelerator host that ``repro.dist`` places stages
    across.  Must run BEFORE the first JAX backend touch (any
    ``jax.devices()`` / array op); a no-op when the flag is already set so
    an outer ``XLA_FLAGS`` export wins."""
    cur = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in cur:
        os.environ["XLA_FLAGS"] = \
            (cur + f" --xla_force_host_platform_device_count={n}").strip()


def stage_devices(n: int) -> tuple:
    """The first ``n`` devices, for a stage placement plan."""
    devs = jax.devices()
    if n > len(devs):
        raise RuntimeError(
            f"need {n} devices, have {len(devs)}; on CPU export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            "python starts (or pass --devices to repro.launch.train, which "
            "sets it pre-init)")
    return tuple(devs[:n])


def make_production_mesh(*, multi_pod: bool = False, shape=None):
    """shape: optional (data, model) override, e.g. (32, 8) for an
    expert-parallel variant (model axis dividing the expert count)."""
    if shape is None:
        shape = (16, 16)
    assert shape[0] * shape[1] == 256, "one pod = 256 chips"
    full = ((2,) + tuple(shape)) if multi_pod else tuple(shape)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(full, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def dp_axes(mesh) -> tuple:
    """Data-parallel axis names for this mesh (batch sharding)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def fsdp_axis(mesh) -> str:
    return "data"


def tp_axis(mesh) -> str:
    return "model"


# TPU v5e hardware constants (per chip) for the roofline model
PEAK_FLOPS_BF16 = 197e12      # FLOP/s (MXU native)
PEAK_FLOPS_FP32 = 98.5e12     # FLOP/s (fp32 via multi-pass MXU, ~half rate)
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link
