"""Mesh sharding rules for the production meshes in `repro.launch.mesh`.

Axis semantics (see launch/mesh.py):

  pod    — cross-pod data parallelism (gradient sync only)
  data   — in-pod data parallelism / FSDP
  model  — tensor / expert / sequence parallelism

Everything here degrades gracefully: an axis that is absent from the mesh,
or a dimension that is not divisible by the axis size, simply stays
replicated.  That is what lets the same rules drive a 512-chip multi-pod
mesh and the single-device smoke mesh the tests run on.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any
AxisName = Union[str, Tuple[str, ...], None]


# ---------------------------------------------------------------------------
# mesh introspection
# ---------------------------------------------------------------------------

def _ambient_mesh() -> Optional[Mesh]:
    """The mesh of the enclosing `with mesh:` block, or None outside one."""
    from jax._src import mesh as mesh_lib

    m = mesh_lib.thread_resources.env.physical_mesh
    return None if m.empty else m


def batch_axes(mesh: Optional[Mesh]) -> AxisName:
    """The data-parallel axis (or axes) of `mesh`.

    Multi-pod meshes carry DP on ("pod", "data"); single-pod on "data".
    Returned as a str when a single axis so it can be used directly as a
    collective axis name; a tuple when several.
    """
    if mesh is None:
        return "data"
    names = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
    if not names:
        return ()
    return names[0] if len(names) == 1 else names


def axis_size(mesh: Optional[Mesh], axes: AxisName) -> int:
    """Product of the sizes of `axes` (str, tuple, or None) in `mesh`."""
    if mesh is None or axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for ax in axes:
        size *= dict(zip(mesh.axis_names, mesh.devices.shape)).get(ax, 1)
    return size


def _divisible(dim: int, mesh: Mesh, axes: AxisName) -> bool:
    s = axis_size(mesh, axes)
    return s >= 1 and dim % s == 0


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

def param_spec(name: str, shape: Sequence[int], mesh: Mesh) -> P:
    """PartitionSpec for one parameter.

    Rules:
      * last dim        → "model"  (TP; the contraction/output feature dim)
      * second-to-last  → "data"   (FSDP shard of the other feature dim)
      * a stacked `layers` leading dim is never sharded (models lax.scan
        over it; sharding it would reshard every layer step)
      * any dim not divisible by its axis size stays replicated
    """
    shape = tuple(shape)
    ndim = len(shape)
    if ndim == 0:
        return P()
    spec: list = [None] * ndim
    if ndim >= 2:
        if "model" in mesh.axis_names and _divisible(shape[-1], mesh, "model"):
            spec[-1] = "model"
        cand = ndim - 2
        stacked = "layers" in name and cand == 0
        if (not stacked and "data" in mesh.axis_names
                and _divisible(shape[cand], mesh, "data")):
            spec[cand] = "data"
    return P(*spec)


def param_specs(params: PyTree, mesh: Mesh) -> PyTree:
    """Tree of PartitionSpecs matching `params` (named by tree path)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = [param_spec(jax.tree_util.keystr(kp), leaf.shape, mesh)
             for kp, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# batch / cache rules
# ---------------------------------------------------------------------------

def train_batch_specs(batch: PyTree, mesh: Mesh) -> PyTree:
    """Shard every batch leaf's leading (batch) dim over the DP axes."""
    dax = batch_axes(mesh)

    def leaf_spec(leaf) -> P:
        if leaf.ndim == 0:
            return P()
        if dax and _divisible(leaf.shape[0], mesh, dax):
            return P(dax, *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree.map(leaf_spec, batch)


def cache_specs(cache: PyTree, mesh: Mesh) -> PyTree:
    """KV/recurrence-cache layout: (layers, batch, seq?, ...).

    dim 1 (batch) shards over the DP axes; for attention K/V caches dim 2
    (sequence) shards over "model" — sequence parallelism, so a long
    context's cache splits across the TP group instead of replicating.
    A latent cache (`c_kv`, `k_pe`: (layers, batch, seq, width)) shards
    its batch only: the absorbed decode reads every position of it for
    every head, so it stays whole on each chip of a TP group.  Counters
    riding with the cache (`pairs_*`) are replicated.
    """
    dax = batch_axes(mesh)
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)

    def leaf_spec(kp, leaf) -> P:
        name = jax.tree_util.keystr(kp)
        if leaf.ndim < 2:
            return P(*([None] * leaf.ndim))
        spec: list = [None] * leaf.ndim
        if dax and _divisible(leaf.shape[1], mesh, dax):
            spec[1] = dax
        is_kv = name.endswith("['k']") or name.endswith("['v']")
        if (is_kv and leaf.ndim >= 4 and "model" in mesh.axis_names
                and _divisible(leaf.shape[2], mesh, "model")):
            spec[2] = "model"
        return P(*spec)

    specs = [leaf_spec(kp, leaf) for kp, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# in-graph constraint helper
# ---------------------------------------------------------------------------

def _resolve_axis(ax: Optional[str], mesh: Mesh) -> AxisName:
    if ax is None:
        return None
    if ax == "batch":
        return batch_axes(mesh)
    return ax if ax in mesh.axis_names else None


def constrain(x: jax.Array, *axes: Optional[str], mesh: Optional[Mesh] = None) -> jax.Array:
    """`with_sharding_constraint` by logical axis name, one per dim.

    `axes` entries: "batch" (→ the mesh's DP axes), a literal mesh axis
    name, or None.  A no-op outside a mesh context, for axes the mesh does
    not have, and for dims the axis size does not divide — so model code
    can pin layouts unconditionally and still run on one device.
    """
    m = mesh if mesh is not None else _ambient_mesh()
    if m is None:
        return x
    spec: list = []
    for dim, ax in zip(x.shape, axes):
        phys = _resolve_axis(ax, m)
        if phys in (None, ()) or not _divisible(dim, m, phys) \
                or axis_size(m, phys) == 1:
            spec.append(None)
        else:
            spec.append(phys)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(m, P(*spec)))
