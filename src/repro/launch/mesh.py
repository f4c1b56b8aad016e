"""Production meshes. Defined as FUNCTIONS so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first jax init).

Single pod:  (16, 16)      axes ("data", "model")   = 256 chips (v5e pod)
Multi-pod:   (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

Batch-parallel axes are ("pod","data"); tensor/expert-parallel is "model".
All PartitionSpecs in configs/ refer to these logical names, so the same
rules instantiate any mesh built here (elastic re-mesh reuses this).

Every mesh here has Auto axes: the compiler propagates shardings from the
inputs, and a sharded result stays an ordinary array outside the mesh
(jax.make_mesh defaults to Explicit axes, whose arrays carry the mesh in
their type and refuse eager ops outside it).
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(n_devices: int | None = None, model_axis: int = 1):
    """Small mesh over the locally visible devices (tests / CPU runs)."""
    n = n_devices or len(jax.devices())
    assert n % model_axis == 0
    return _mesh((n // model_axis, model_axis), ("data", "model"))


def make_fit_mesh(n_devices: int | None = None, rep_axis: int = 1):
    """(data × rep) mesh for the IRLI FitEngine (docs/fit.md): batch rows
    split over "data" (psum'd grads), the R independent repetitions —
    params, adam moments, affinity, k-choice, assign — split over "rep".
    ``rep_axis`` must divide both the device count and the config's
    n_reps."""
    n = n_devices or len(jax.devices())
    assert n % rep_axis == 0
    return _mesh((n // rep_axis, rep_axis), ("data", "rep"))


def batch_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh made above."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def batch_size_divisor(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in batch_axes(mesh):
        n *= sizes[a]
    return n
