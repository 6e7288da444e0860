"""Mesh construction — the one place this repo builds a `jax.sharding.Mesh`.

FUNCTIONS, not module-level constants — importing this module never
touches jax device state.  Single-pod: (data=16, model=16) = one v5e-256.
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the `pod` axis carries
data parallelism across pods (gradient sync only, optionally RP-compressed
— repro.dist.compress), `data` carries FSDP, `model` carries TP/EP/SP.

Every axis is `AxisType.Auto`: the models and serving steps place arrays
with explicit `in_shardings`/`out_shardings` and leave the propagation to
the partitioner.  `jax.make_mesh` defaults to `Explicit` axes, under which
gathers such as the embedding lookup need a per-op `out_sharding` and fail
with `ShardingTypeError`; the factories that build a program over a
caller's mesh (`dr_serve.make_dr_transform`, `serve_step.make_prefill` /
`make_decode`) call `require_auto_axes`, so such a mesh is refused before
anything is traced.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """`jax.make_mesh` with every axis `Auto` (see the module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def require_auto_axes(mesh: Mesh) -> None:
    """Raise for a mesh with `Explicit` (or `Manual`) axes."""
    bad = [name for name, t in zip(mesh.axis_names, mesh.axis_types)
           if t != AxisType.Auto]
    if bad:
        raise ValueError(
            f"mesh axes {bad} are not AxisType.Auto; the serving and model "
            f"code places arrays through in/out shardings and needs Auto "
            f"axes — build the mesh with repro.launch.mesh.make_mesh")


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(n_devices: int = 1) -> Mesh:
    """Tiny mesh over whatever devices exist (tests)."""
    n = min(n_devices, len(jax.devices()))
    return make_mesh((1, n), ("data", "model"), devices=jax.devices()[:n])
