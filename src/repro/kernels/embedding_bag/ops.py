"""Public embedding-bag API with custom VJP.

Backward: d table = scatter-add of bag cotangents back to gathered rows —
expressed with segment_sum over the (static-size) index list; indices and bag
ids carry no gradient.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.embedding_bag.embedding_bag import embedding_bag_fwd


def _zero_empty(out, bag_ids, n_bags):
    """Bags with no lookups are never visited by the grid — their output
    blocks are undefined on real hardware. Zero them explicitly (TBE
    semantics)."""
    counts = jax.ops.segment_sum(
        jnp.ones_like(bag_ids, jnp.int32), bag_ids, num_segments=n_bags
    )
    return jnp.where((counts > 0)[:, None], out, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def embedding_bag(table, indices, bag_ids, n_bags, interpret=None):
    """``interpret=None``: compiled on TPU, interpreter elsewhere."""
    out = embedding_bag_fwd(table, indices, bag_ids, n_bags,
                            interpret=resolve_interpret(interpret))
    return _zero_empty(out, bag_ids, n_bags)


def _fwd(table, indices, bag_ids, n_bags, interpret):
    out = embedding_bag_fwd(table, indices, bag_ids, n_bags,
                            interpret=resolve_interpret(interpret))
    return _zero_empty(out, bag_ids, n_bags), (table.shape, indices, bag_ids)


def _bwd(n_bags, interpret, res, g):
    (v, d), indices, bag_ids = res
    # dL/dtable[r] = sum over lookups i with indices[i]==r of g[bag_ids[i]]
    g_rows = jnp.take(g, bag_ids, axis=0)                      # (L, D)
    dtable = jax.ops.segment_sum(g_rows, indices, num_segments=v)
    return dtable, None, None


embedding_bag.defvjp(_fwd, _bwd)
