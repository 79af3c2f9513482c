"""Distribution context for the contrastive update builders.

``DistCtx`` abstracts over single-device and shard_map execution so that the
four methods (DPR / GradAccum / GradCache / ContAccum) are written once.

Under ``shard_map`` the batch is sharded over the data-parallel axes; each
device encodes its local shard, all-gathers the representations (cross-device
in-batch negatives — the pod-scale reading of the paper's "total batch") and
computes the loss over its *own* rows only. Gradients flow through the
all_gather (transpose = psum_scatter sums the cotangents contributed by every
device's loss), after which a single psum over the DP axes yields exactly the
gradient of the global-batch loss. This is validated against single-device
execution in tests/test_distributed.py.
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

AxisNames = Union[str, Tuple[str, ...]]


class DistCtx:
    """axis=None -> single-device semantics (gather = identity, psum = identity)."""

    def __init__(self, axis: Optional[AxisNames] = None):
        if isinstance(axis, str):
            axis = (axis,)
        self.axis: Optional[Tuple[str, ...]] = tuple(axis) if axis else None

    @property
    def is_distributed(self) -> bool:
        return self.axis is not None

    def device_count(self):
        if not self.axis:
            return 1
        n = 1
        for a in self.axis:
            n = n * jax.lax.psum(1, a)
        return n

    def shard_index(self):
        """Flat index of this device along the combined DP axes (major-to-minor
        in the order given, matching all_gather's concatenation order)."""
        if not self.axis:
            return 0
        idx = 0
        for a in self.axis:
            idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
        return idx

    def gather(self, x: jnp.ndarray) -> jnp.ndarray:
        """Concatenate shards along axis 0 (differentiable)."""
        if not self.axis:
            return x
        return jax.lax.all_gather(x, self.axis, axis=0, tiled=True)

    def ring_perm(self, shift: int = 1):
        """Static ppermute pairs rotating the combined DP ring by ``shift``:
        flat device i (in ``shard_index`` order) sends to (i + shift) mod D —
        one complete cycle covering every device, so no shard's contribution
        is ever dropped (reprolint RPL002 enforces this for literal tables).
        ``device_count``/``psum(1, axis)`` are static under shard_map, so the
        table is a compile-time constant."""
        d = self.device_count()
        return [(i, (i + shift) % d) for i in range(d)]

    def ring_rotate(self, x, shift: int = 1):
        """Rotate every leaf of ``x`` one hop around the flattened DP ring
        (device i receives device (i - shift) mod D's value). Differentiable:
        ppermute's transpose is the inverse rotation, so cotangents written
        against a neighbor's shard ride the ring *back* to the owning device
        and sum there — the streaming-loss backward pass needs no extra
        collective. Identity in single-device mode."""
        if not self.axis:
            return x
        perm = self.ring_perm(shift)
        return jax.tree_util.tree_map(
            lambda t: jax.lax.ppermute(t, self.axis, perm=perm), x
        )

    def psum(self, x):
        if not self.axis:
            return x
        return jax.lax.psum(x, self.axis)

    def psum_tree(self, tree):
        if not self.axis:
            return tree
        return jax.tree_util.tree_map(lambda t: jax.lax.psum(t, self.axis), tree)
