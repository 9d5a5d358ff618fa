"""Production mesh construction.

The target is a TPU v5e pod-slice: one pod = a (data=16, model=16) mesh
of 256 chips; the multi-pod configuration adds a leading pod axis
(2 x 16 x 16 = 512 chips). Client cohorts of the federated round shard
over ("pod", "data"); tensor/expert parallelism lives on "model".

This module never touches jax device state at import time — meshes are
built inside functions, and only the dry-run entrypoint forces the
512-device host platform.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType

HW = {
    # TPU v5e per-chip constants used by the roofline (benchmarks/roofline.py)
    "peak_flops_bf16": 197e12,   # FLOP/s
    "hbm_bw": 819e9,             # B/s
    "ici_bw": 50e9,              # B/s per link
    "hbm_bytes": 16 * 1024 ** 3,
}


def _mesh(shape, axes, devices):
    """Every axis Auto: GSPMD propagates shardings from the explicit
    constraints in launch/sharding.py, which only Auto axes accept
    (``jax.make_mesh`` defaults to Explicit axes)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devs)}; run via "
            "launch/dryrun.py which forces 512 host devices")
    return _mesh(shape, axes, devs[:n])


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for tests (requires forced host device count >= prod)."""
    n = math.prod(shape)
    return _mesh(shape, axes, jax.devices()[:n])


def make_single_device_mesh():
    """1x1 mesh so smoke tests exercise the pjit path on one CPU device."""
    return _mesh((1, 1), ("data", "model"), jax.devices()[:1])


def data_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# Named meshes the simulation grid accepts (``GridConfig.mesh``). Debug
# presets exist so the multi-device CI job (8 forced host devices) can
# exercise the sharded code paths without the 256-chip production shape.
MESH_PRESETS = {
    "single": make_single_device_mesh,
    "debug": make_debug_mesh,                            # (data=2, model=2)
    "debug-pod": lambda: make_debug_mesh(
        (2, 2, 2), ("pod", "data", "model")),            # 8 devices
    "production": make_production_mesh,
    "production-multipod": lambda: make_production_mesh(multi_pod=True),
}


def resolve_mesh(spec):
    """``None`` | preset name | mesh object -> mesh object (or ``None``).

    This is the one place grid/spec configs turn a *description* of a
    mesh into device state, so configs stay picklable and importing a
    config never touches jax devices."""
    if spec is None:
        return None
    if isinstance(spec, str):
        try:
            factory = MESH_PRESETS[spec]
        except KeyError:
            raise ValueError(f"unknown mesh preset {spec!r}; options: "
                             f"{sorted(MESH_PRESETS)}") from None
        return factory()
    return spec


def axis_size(mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)
