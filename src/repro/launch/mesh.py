"""Production mesh builders (functions, never module-level constants, so
importing this module never touches jax device state).

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — `pod` carries
data parallelism across the slower inter-pod links (one gradient all-reduce
per step, optionally int8-compressed), `model` stays intra-pod on ICI.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes, devices=None):
    """Every mesh here has Auto axes: the sharding rules place arrays with
    NamedSharding/with_sharding_constraint and leave the rest to GSPMD.
    (jax.make_mesh defaults to Explicit axes, under which an un-annotated
    gather such as the embedding lookup is a ShardingTypeError.)"""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2, *, multi_pod: bool = False):
    """Small mesh for CPU tests (requires XLA_FLAGS host device override)."""
    if multi_pod:
        return _make_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _make_mesh((n_data, n_model), ("data", "model"))


def parse_mesh_shape(spec) -> tuple:
    """"2x2" / "1,4" / (2, 2) -> (n_data, n_model)."""
    if isinstance(spec, (tuple, list)):
        shape = tuple(int(x) for x in spec)
    else:
        shape = tuple(int(x) for x in str(spec).replace(",", "x").split("x"))
    if len(shape) != 2 or min(shape) < 1:
        raise ValueError(f"mesh shape must be (n_data, n_model), got {spec!r}")
    return shape


def make_serving_mesh(shape=(1, 2)):
    """Serving mesh with axes (data, model) — `data` carries engine-replica /
    slot batch parallelism, `model` tensor parallelism (DESIGN.md §15).
    Works on CPU meshes for CI; fails with the XLA_FLAGS recipe when the
    process has fewer devices than the shape needs (the flag must be set
    before jax initializes, so it cannot be applied retroactively here)."""
    n_data, n_model = parse_mesh_shape(shape)
    need = n_data * n_model
    have = len(jax.devices())
    if have < need:
        raise RuntimeError(
            f"mesh shape {(n_data, n_model)} needs {need} devices, found "
            f"{have}; on CPU launch with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} (must be set "
            f"before jax initializes)")
    return _make_mesh((n_data, n_model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.shape[name]
