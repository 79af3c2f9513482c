"""Weights and index rows made on the device from the run's seed.

The benchmark, not the program, makes every input: tower weights in the
program's parameter layout (stored as the program stores them, fp32
masters), and serving index rows, block by block, in the layout the search
reads. The plain references regenerate the same values from the same seed,
so they take nothing that the program made.

Every tensor is drawn from ``fold_in(key, tag)`` with a fixed tag per leaf
or per index block, so a value never depends on which other values were
drawn, or in which order.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def root_key(seed: int) -> jax.Array:
    """The run's key. Seeds up to 2**64 - 1 are taken whole."""
    return jax.random.key(seed % (1 << 64) if seed >= 0 else seed % (1 << 63),
                          impl="threefry2x32")


def _tag(*parts) -> int:
    return zlib.crc32("/".join(str(p) for p in parts).encode()) & 0x7FFFFFFF


def tower_shapes(model: dict) -> dict:
    """Leaf shapes of one tower, keyed like the program's param dict."""
    d, nl, ff = model["hidden_size"], model["num_hidden_layers"], model["intermediate_size"]
    return {
        "embed": {
            "word": (model["vocab_size"], d),
            "pos": (model["max_position_embeddings"], d),
            "type": (model["type_vocab_size"], d),
            "ln_s": (d,), "ln_b": (d,),
        },
        "layers": {
            "wqkv": (nl, d, 3 * d), "bqkv": (nl, 3 * d),
            "wo": (nl, d, d), "bo": (nl, d),
            "ln1_s": (nl, d), "ln1_b": (nl, d),
            "w1": (nl, d, ff), "b1": (nl, ff),
            "w2": (nl, ff, d), "b2": (nl, d),
            "ln2_s": (nl, d), "ln2_b": (nl, d),
        },
    }


def _leaf(key, tower, group, name, shape, std):
    x = std * jax.random.normal(jax.random.fold_in(key, _tag(tower, group, name)),
                                shape, jnp.float32)
    return 1.0 + x if name.endswith("_s") else x


def tower_params(key, model: dict, towers=("query", "passage")) -> dict:
    """{tower: params} for the named towers, in one jitted call."""
    shapes = tower_shapes(model)
    std = float(model["initializer_range"])

    @jax.jit
    def make(key):
        return {
            t: {g: {n: _leaf(key, t, g, n, s, std) for n, s in leaves.items()}
                for g, leaves in shapes.items()}
            for t in towers
        }

    return make(key)


def index_block(key, block_id, block: int, dim: int, dtype):
    """Rows ``[block_id*block, (block_id+1)*block)`` of the index."""
    k = jax.random.fold_in(jax.random.fold_in(key, _tag("index")), block_id)
    return jax.random.normal(k, (block, dim), jnp.float32).astype(dtype)


def _fill(key, first, n_blocks: int, block: int, dim: int, dtype):
    """Blocks ``first .. first + n_blocks`` of the index, written in place."""
    def body(b, buf):
        blk = index_block(key, first + b, block, dim, dtype)
        return jax.lax.dynamic_update_slice(buf, blk, (b * block, 0))

    buf = jnp.zeros((n_blocks * block, dim), dtype)
    return jax.lax.fori_loop(0, n_blocks, body, buf)


def index_rows(key, n_blocks: int, block: int, dim: int, dtype, mesh=None, axis="data"):
    """The whole index, ``n_blocks * block`` rows, written block by block in
    place on the device, in one program. With ``mesh`` each device writes
    only its own contiguous row block (the sharded layout)."""
    dtype = jnp.dtype(dtype)
    if mesh is None:
        return jax.jit(_fill, static_argnums=(2, 3, 4, 5))(key, 0, n_blocks, block, dim, dtype)
    from jax.sharding import PartitionSpec as P

    shards = mesh.shape[axis]
    if n_blocks % shards:
        raise ValueError(f"{n_blocks} index blocks do not split over {shards} shards")
    per = n_blocks // shards

    def local(key):
        return _fill(key, jax.lax.axis_index(axis) * per, per, block, dim, dtype)

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P(), out_specs=P(axis, None),
                                 check_vma=False))(key)
