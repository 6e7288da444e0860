"""Sharded DR inference endpoint — the LM serving treatment for DR models.

`make_dr_transform` compiles one jitted `transform` for a `DRModel` on a
mesh: stage states are replicated per the model's `shard_specs` (R/B are
tiny), the feature batch shards its leading dim over the data-parallel
axes, and the output comes back with the same layout — so a fleet-scale
feature stream (millions of rows) fans out across the mesh with zero
resharding inside the step.  The transform is row-independent, so it runs
under `shard_map`: each device transforms its own rows.  That is also what
lets a Pallas-backed model serve on a mesh — the partitioner cannot split
a Mosaic kernel, and refuses one in a multi-device program outside a
`shard_map`.

    mesh = make_production_mesh()
    step = dr_serve.make_dr_transform(model, mesh)
    y = step(state, x)        # x (B, m) sharded over ("pod","data")

Ensembles serve through the same factory (`ensemble=k`): the vmapped
transform maps one replicated state-stack over the sharded batch.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist import sharding as shard_rules
from repro.launch.mesh import require_auto_axes
from repro.serve.batching import BoundedCompileCache


def _to_sh(spec, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec,
                        is_leaf=lambda x: isinstance(x, P))


def make_dr_transform(model, mesh: Mesh, *, batch_size: Optional[int] = None,
                      ensemble: Optional[int] = None):
    """Returns jit(transform) with explicit in/out shardings on `mesh`.

    `batch_size`: if given, the batch spec degrades to replicated when the
    DP axes do not divide it (ragged client batches still serve).
    `ensemble`: compile for a k-member ensemble state instead (states carry
    a leading (k,) axis; output gains a leading k dim).
    """
    require_auto_axes(mesh)
    dax = shard_rules.batch_axes(mesh)
    n_dp = shard_rules.axis_size(mesh, dax)
    shard_batch = bool(dax) and n_dp > 1 and \
        (batch_size is None or batch_size % n_dp == 0)
    bspec = P(dax) if shard_batch else P()

    sspec = model.shard_specs(mesh)
    if ensemble is not None:
        # ensemble axis is a leading replicated dim on every stage state
        sspec = sspec._replace(stages=jax.tree.map(
            lambda s: P(None, *s), sspec.stages,
            is_leaf=lambda x: isinstance(x, P)))
        fn = model.ensemble(ensemble).transform
    else:
        fn = model.transform

    ospec = P(None, dax) if ensemble and shard_batch else bspec
    per_shard = jax.shard_map(fn, mesh=mesh, in_specs=(sspec, bspec),
                              out_specs=ospec, check_vma=False)
    return jax.jit(
        per_shard,
        in_shardings=(_to_sh(sspec, mesh), NamedSharding(mesh, bspec)),
        out_shardings=NamedSharding(mesh, ospec),
    )


# Bounded LRU over compiled steps (an unbounded cache here pins every mesh
# a step was ever compiled for — see repro.serve.batching).  `DRService`
# keeps its own instance; this one backs the module-level convenience call.
_CACHE = BoundedCompileCache(maxsize=64)


def _cached_transform(model, mesh: Mesh, shard_batch: bool):
    # batch_size=None → shard the batch axis; 1 → force replicated layout
    # (n_dp never divides 1 on a multi-device mesh, and on a 1-device mesh
    # the spec degrades to replicated anyway)
    return _CACHE.get_or_build(
        (model, mesh, shard_batch),
        lambda: make_dr_transform(model, mesh,
                                  batch_size=None if shard_batch else 1))


def dr_transform(model, state, x, *, mesh: Optional[Mesh] = None):
    """One-shot convenience: run the sharded step (compiled once per
    (model, mesh, layout) — cached, so per-batch calls don't re-jit).

    Without a mesh this is just `model.transform` — same math, no layout
    constraints — so callers can share one code path across laptop and pod.
    """
    if mesh is None:
        return model.transform(state, x)
    n_dp = shard_rules.axis_size(mesh, shard_rules.batch_axes(mesh))
    return _cached_transform(model, mesh, x.shape[0] % n_dp == 0)(state, x)
