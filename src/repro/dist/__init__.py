"""Distribution layer: mesh sharding rules + RP gradient compression.

  sharding — PartitionSpec rules for params / batches / KV caches, the
             logical-axis `constrain` helper models call mid-graph, and
             mesh introspection (`batch_axes`, `axis_size`).
  compress — cross-pod gradient sync through the paper's own primitive:
             a ternary random-projection sketch, psum'd in sketch space
             and back-projected with error feedback.
"""

from __future__ import annotations

from repro.dist import compress, sharding

__all__ = ["compress", "sharding"]
