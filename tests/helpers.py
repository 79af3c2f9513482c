"""Shared test fixtures: a tiny nonlinear dual encoder over vector 'tokens'.

Two-layer MLPs (separate query/passage towers) are enough to make the
GradCache identity and the gradient-norm analyses non-trivial while keeping
tests fast on CPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import DualEncoder, RetrievalBatch


def make_mlp_encoder(dim_in: int = 16, dim_hidden: int = 32, dim_rep: int = 8) -> DualEncoder:
    def tower_init(rng):
        k1, k2 = jax.random.split(rng)
        return {
            "w1": jax.random.normal(k1, (dim_in, dim_hidden)) * 0.3,
            "b1": jnp.zeros((dim_hidden,)),
            "w2": jax.random.normal(k2, (dim_hidden, dim_rep)) * 0.3,
            "b2": jnp.zeros((dim_rep,)),
        }

    def tower_apply(tp, x):
        h = jnp.tanh(x @ tp["w1"] + tp["b1"])
        return h @ tp["w2"] + tp["b2"]

    def init(rng):
        kq, kp = jax.random.split(rng)
        return {"query": tower_init(kq), "passage": tower_init(kp)}

    return DualEncoder(
        init=init,
        encode_query=lambda params, x: tower_apply(params["query"], x),
        encode_passage=lambda params, x: tower_apply(params["passage"], x),
        rep_dim=dim_rep,
    )


def mixed_precision(encoder: DualEncoder, policy) -> DualEncoder:
    """The MLP towers under a PrecisionPolicy, as the model towers bind one
    (``BertConfig.with_precision``, ``LMConfig.with_precision``): params
    stored in ``param_dtype``, cast with the float inputs to
    ``compute_dtype`` at each application, representations in it."""
    from repro.core.precision import resolve_precision

    policy = resolve_precision(policy)
    ct = policy.compute_dtype

    def cast(tree, dtype):
        return jax.tree_util.tree_map(
            lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    def side(encode):
        return lambda params, batch: encode(cast(params, ct), cast(batch, ct)).astype(ct)

    return DualEncoder(
        init=lambda rng: cast(encoder.init(rng), policy.param_dtype),
        encode_query=side(encoder.encode_query),
        encode_passage=side(encoder.encode_passage),
        rep_dim=encoder.rep_dim,
    )


def make_batch(rng, batch_size: int, dim_in: int = 16, n_hard: int = 0) -> RetrievalBatch:
    kq, kp, kh = jax.random.split(rng, 3)
    # planted structure: positives correlated with queries so accuracy moves
    q = jax.random.normal(kq, (batch_size, dim_in))
    p = q + 0.5 * jax.random.normal(kp, (batch_size, dim_in))
    hard = None
    if n_hard > 0:
        hard = q[:, None, :] + 1.5 * jax.random.normal(kh, (batch_size, n_hard, dim_in))
    return RetrievalBatch(query=q, passage_pos=p, passage_hard=hard)


def op_name_scopes(hlo_text: str, scopes) -> dict:
    """{op_name: [the named scopes among ``scopes`` in its path]} over the
    ``metadata={op_name=...}`` of a compiled program's HLO text. A path
    component names a scope as ``towers`` does, or wrapped by
    differentiation, as ``jvp(towers)`` and ``transpose(jvp(towers))``."""
    import re

    pats = {s: re.compile(r"(?:[\w]+\()*%s\)*" % re.escape(s)) for s in scopes}
    out = {}
    for name in set(re.findall(r'op_name="([^"]*)"', hlo_text)):
        parts = re.split(r"[/;]", name)
        out[name] = [s for s in scopes for c in parts if pats[s].fullmatch(c)]
    return out
