"""Sharded serving steps: prefill + decode with explicit cache shardings.

Decode donates the cache (in-place KV update on device); batch shards over
(pod, data), cache sequence over `model` (SP) per repro.dist.sharding rules.

Both factories are thin adapters over the serving engine's bounded compile
cache (`repro.serve.batching.BoundedCompileCache`): per (config, mesh,
shape-signature) the jit is built once and LRU-evicted under pressure, so
a long-lived server cycling through configs/meshes doesn't pin every
executable it ever compiled.

The programs take the params as they compute with them
(`compute_params`): a tree stored in float32 is cast to the compute dtype
once (`ComputeParamsCache`), not inside every step.
"""

from __future__ import annotations

import collections
import threading
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import config_hash
from repro.dist import sharding as shard_rules
from repro.launch.mesh import require_auto_axes
from repro.models import api, transformer
from repro.models.config import ArchConfig
from repro.serve.batching import BoundedCompileCache

PyTree = Any

_CACHE = BoundedCompileCache(maxsize=32)


def _to_sh(spec, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec,
                        is_leaf=lambda x: isinstance(x, P))


def _tree_sig(tree: PyTree):
    """Hashable (path, shape, dtype) signature of an abstract pytree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return tuple((jax.tree_util.keystr(kp), tuple(leaf.shape), str(leaf.dtype))
                 for kp, leaf in flat)


def _casts_any(cfg: ArchConfig, params: PyTree) -> bool:
    return cfg.family == "transformer" and transformer.casts_any(
        params, jnp.dtype(cfg.compute_dtype))


def compute_params(cfg: ArchConfig, params: PyTree) -> PyTree:
    """`params` with each float32 matrix the steps would cast in the compute
    dtype (`transformer.compute_params`: the router and vectors keep their
    dtype), so prefill and decode compute on the same operands without a
    cast of their own.  `params` itself where no leaf would change dtype:
    a tree stored in the compute dtype, or a family that casts in its own
    steps."""
    if not _casts_any(cfg, params):
        return params
    return transformer.compute_params(params, jnp.dtype(cfg.compute_dtype))


class ComputeParamsCache:
    """`compute_params`, made once per params tree: an LRU of `MAXSIZE`
    copies keyed on the identities of the tree's leaves.  Each entry holds
    its source leaves, so no other array can take their ids while it
    lives; a new params version has new leaves and gets its own copy, and
    old versions age out.  A tree that needs no cast passes through
    uncached.  Each copy is timed as the `tracker`'s (an `slo.SLOTracker`)
    `lm.params_cast` stage."""

    MAXSIZE = 2

    def __init__(self, tracker: Any):
        self._tracker = tracker
        self._lock = threading.Lock()
        self._d: "collections.OrderedDict" = collections.OrderedDict()  # guarded-by: _lock

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)

    def get(self, cfg: ArchConfig, params: PyTree) -> PyTree:
        leaves, treedef = jax.tree.flatten(params)
        key = (cfg.family, cfg.compute_dtype, treedef, tuple(map(id, leaves)))
        with self._lock:
            hit = self._d.get(key)
            if hit is not None:
                self._d.move_to_end(key)
                return hit[1]
        if not _casts_any(cfg, params):
            return params
        with self._tracker.span("lm.params_cast"):
            copy = jax.block_until_ready(compute_params(cfg, params))
        with self._lock:
            # a racing thread's copy of the same tree wins; ours is dropped
            copy = self._d.setdefault(key, (leaves, copy))[1]
            self._d.move_to_end(key)
            while len(self._d) > self.MAXSIZE:
                self._d.popitem(last=False)
        return copy


def make_prefill(cfg: ArchConfig, mesh: Mesh, params_like: PyTree,
                 batch_like: PyTree, cache_size: int, *,
                 cache: BoundedCompileCache = None):
    """`cache=None` uses the module-level LRU; a `DRService` passes its own
    so LM steps and DR bucket programs share one bounded cache."""
    require_auto_axes(mesh)
    key = ("prefill", config_hash(cfg), mesh, _tree_sig(params_like),
           _tree_sig(batch_like), cache_size)
    return (cache if cache is not None else _CACHE).get_or_build(
        key, lambda: _build_prefill(cfg, mesh, params_like, batch_like,
                                    cache_size))


def _build_prefill(cfg: ArchConfig, mesh: Mesh, params_like: PyTree,
                   batch_like: PyTree, cache_size: int):
    pspec = shard_rules.param_specs(params_like, mesh)
    bspec = shard_rules.train_batch_specs(batch_like, mesh)
    cache_like = jax.eval_shape(
        lambda: api.init_cache(cfg, jax.tree.leaves(batch_like)[0].shape[0], cache_size))
    cspec = shard_rules.cache_specs(cache_like, mesh)

    def fn(params, batch):
        return api.prefill(params, batch, cfg, cache_size)

    return jax.jit(
        fn,
        in_shardings=(_to_sh(pspec, mesh), _to_sh(bspec, mesh)),
        out_shardings=(NamedSharding(mesh, P(shard_rules.batch_axes(mesh))),
                       _to_sh(cspec, mesh)),
    )


def make_decode(cfg: ArchConfig, mesh: Mesh, params_like: PyTree, cache_like: PyTree,
                *, cache: BoundedCompileCache = None):
    require_auto_axes(mesh)
    key = ("decode", config_hash(cfg), mesh, _tree_sig(params_like),
           _tree_sig(cache_like))
    return (cache if cache is not None else _CACHE).get_or_build(
        key, lambda: _build_decode(cfg, mesh, params_like, cache_like))


def _build_decode(cfg: ArchConfig, mesh: Mesh, params_like: PyTree, cache_like: PyTree):
    pspec = shard_rules.param_specs(params_like, mesh)
    cspec = shard_rules.cache_specs(cache_like, mesh)
    b = None
    for leaf in jax.tree.leaves(cache_like):
        if leaf.ndim >= 2:
            b = leaf.shape[1]
            break
    ax = shard_rules.batch_axes(mesh)
    tok_spec = P(ax) if b is not None and b % shard_rules.axis_size(mesh, ax) == 0 else P()

    def fn(params, token, cache):
        return api.decode_step(params, token, cache, cfg)

    return jax.jit(
        fn,
        in_shardings=(_to_sh(pspec, mesh), NamedSharding(mesh, tok_spec),
                      _to_sh(cspec, mesh)),
        out_shardings=(NamedSharding(mesh, tok_spec), _to_sh(cspec, mesh)),
        donate_argnums=(2,),
    )
