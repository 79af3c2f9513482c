"""Plain float32 references, written from the published descriptions and
independent of the program: nothing here imports it.

* ``bert_cls``: a bert-base encoder (Devlin et al., arXiv:1810.04805) as
  the original implementation computes it: word + learned position + token
  type 0 embeddings, LayerNorm (eps from the config), post-LN blocks of
  multi-head self-attention and a GELU feed-forward, the final [CLS] row as
  the representation (DPR, arXiv:2004.04906). GELU is the tanh form of the
  original BERT code. No attention mask: every query and passage token is
  real.
* ``contaccum_steps``: ContAccum updates (arXiv:2406.12356, Eq. 4-7): the
  batch is cut into K local chunks; each chunk's loss is InfoNCE over its
  queries and the query bank's rows, against its positives, its hard
  negatives and the passage bank's rows, mean over the rows; the chunk's
  query and positive-passage representations then enter the two FIFO banks
  in lockstep. Gradients are averaged over the chunks, clipped to a global
  norm, and applied by AdamW under a linear warm-up and decay.
* ``topk``: exact top-k of fp32 scores over an index, block by block.

``cast`` is applied to both operands of every matrix product: the identity
for the reference; a round trip through a narrower type for the control
that the comparison has to fail. Matrix products run at ``HIGHEST``
precision, so float32 is float32 on a TPU too.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0      # largest finite float8_e4m3fn
F8_GRAD_MAX = 57344.0  # largest finite float8_e5m2


def identity(x):
    return x


def _round(x, dtype, top):
    """Round to an fp8 type under a per-tensor scale that maps the
    tensor's largest magnitude to the type's largest finite value."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, top / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def fp8(x):
    """fp8 matrix-product inputs as fp8 training computes them (Micikevicius
    et al., arXiv:2209.05433): operands rounded to e4m3 and the gradients
    flowing back through them to e5m2, each under its own per-tensor scale;
    the products accumulate in fp32."""
    return _round(x, jnp.float8_e4m3fn, F8_MAX)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, g):
    return (_round(g, jnp.float8_e5m2, F8_GRAD_MAX),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


CASTS = {"fp32": identity, "fp8": fp8}


def frozen(model: dict) -> tuple:
    """The sizes of a configuration as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in model.items() if isinstance(v, (int, float, str))))


def mm(a, b, cast):
    return jnp.matmul(cast(a), cast(b), precision=HIGHEST)


def layer_norm(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def bert_cls(p, tokens, model, cast=identity):
    """tokens (B, S) int -> [CLS] representation (B, d), float32."""
    b, s = tokens.shape
    d, h = model["hidden_size"], model["num_attention_heads"]
    dh = d // h
    eps = model["layer_norm_eps"]
    e, L = p["embed"], p["layers"]
    x = e["word"][tokens] + e["pos"][:s][None] + e["type"][0][None, None]
    x = layer_norm(x, e["ln_s"], e["ln_b"], eps)
    for i in range(model["num_hidden_layers"]):
        qkv = mm(x, L["wqkv"][i], cast) + L["bqkv"][i]
        q, k, v = (t.reshape(b, s, h, dh).transpose(0, 2, 1, 3) for t in jnp.split(qkv, 3, -1))
        att = jax.nn.softmax(mm(q, k.transpose(0, 1, 3, 2), cast) / math.sqrt(dh), axis=-1)
        o = mm(att, v, cast).transpose(0, 2, 1, 3).reshape(b, s, d)
        x = layer_norm(x + mm(o, L["wo"][i], cast) + L["bo"][i], L["ln1_s"][i], L["ln1_b"][i], eps)
        f = mm(gelu(mm(x, L["w1"][i], cast) + L["b1"][i]), L["w2"][i], cast) + L["b2"][i]
        x = layer_norm(x + f, L["ln2_s"][i], L["ln2_b"][i], eps)
    return x[:, 0]


# ---------------------------------------------------------------- training
@partial(jax.jit, static_argnames=("model", "cast"))
def _chunk(params, q_tok, p_tok, h_tok, bank_q, bank_p, bank_valid, *, model, cast):
    """One chunk: (loss, rows in the mean, grads, q reps, positive reps)."""
    model = dict(model)

    def loss_fn(params):
        q = bert_cls(params["query"], q_tok, model, cast)
        pp = bert_cls(params["passage"], p_tok, model, cast)
        ph = bert_cls(params["passage"], h_tok, model, cast)
        n, nh = q.shape[0], ph.shape[0]
        cols = jnp.concatenate([pp, ph, bank_p])
        col_ok = jnp.concatenate([jnp.ones(n + nh, bool), bank_valid])
        rows = jnp.concatenate([q, bank_q])
        labels = jnp.concatenate([jnp.arange(n), n + nh + jnp.arange(bank_q.shape[0])])
        weight = jnp.concatenate([jnp.ones(n), bank_valid.astype(jnp.float32)])
        logits = jnp.where(col_ok[None], mm(rows, cols.T, cast), -jnp.inf)
        per_row = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(logits, labels[:, None], 1)[:, 0]
        per_row = jnp.where(weight > 0, per_row, 0.0)
        return (per_row * weight).sum() / weight.sum(), (weight.sum(), q, pp)

    (loss, (n_rows, q, pp)), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
    return loss, n_rows, g, q, pp


@jax.jit
def _leaf_norms(tree):
    return jax.tree_util.tree_map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def schedule(count: int, peak: float, warmup: int, total: int) -> float:
    """Linear warm-up from 0 to ``peak`` over ``warmup`` updates, then
    linear decay to 0 at ``total`` (``count`` is 1 for the first update)."""
    warmup = max(warmup, 1)
    total = max(total, warmup + 1)
    frac = count / warmup if count < warmup else (total - count) / (total - warmup)
    return peak * min(max(frac, 0.0), 1.0)


@partial(jax.jit, static_argnames=("b1", "b2", "eps"))
def _adamw(params, g, m, v, count, lr, *, b1, b2, eps):
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps), params, m, v)
    return params, m, v


def _norms(g) -> list:
    """[global, query tower, passage tower] L2 norms of a gradient tree."""
    sq = {t: sum(float(jnp.sum(x * x)) for x in jax.tree_util.tree_leaves(g[t])) for t in g}
    return [math.sqrt(sum(sq.values())), math.sqrt(sq["query"]), math.sqrt(sq["passage"])]


def contaccum_steps(params, batches, model: dict, opt: dict, wl: dict, cast=identity,
                    chunks_used=None, grad_chunks=None):
    """Run ``len(batches)`` ContAccum updates from ``params``.

    Returns a dict: ``losses``, the loss of each update (mean over every
    row of its chunks); ``grad_norms``, each update's gradient norms before
    clipping, [global, query tower, passage tower]; ``grad1`` and
    ``grad1_norms``, the per-leaf and those three norms of the first
    update's gradient as AdamW receives it (after clipping); ``change``, the
    per-leaf norms of the parameters' change over all the updates.

    Two faults for the control runs: ``chunks_used`` keeps only the first
    chunks of every batch, the mean taken over them (a program that leaves
    part of its batch out); ``grad_chunks`` computes every chunk's loss and
    bank push but accumulates the gradients of the first ``grad_chunks``
    alone, still divided by K (an accumulation that drops chunks).
    """
    k = wl["total_batch"] // wl["local_batch"]
    used = chunks_used or k
    d, cap = model["hidden_size"], wl["bank"]
    sizes = frozen(model)
    p0 = params
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    bank_q = jnp.zeros((cap, d), jnp.float32)
    bank_p = jnp.zeros((cap, d), jnp.float32)
    bank_valid = jnp.zeros((cap,), bool)
    head = 0
    losses, grad_norms, grad1 = [], [], None
    for step, batch in enumerate(batches):
        q_all, p_all, h_all = (np.asarray(x) for x in batch)
        n = wl["local_batch"]
        g_sum, loss_sum, rows_sum = None, 0.0, 0.0
        for c in range(used):
            sl = slice(c * n, (c + 1) * n)
            h = h_all[sl].reshape(-1, h_all.shape[-1])
            loss, n_rows, g, q, pp = _chunk(params, q_all[sl], p_all[sl], h, bank_q, bank_p,
                                            bank_valid, model=sizes, cast=cast)
            if grad_chunks is None or c < grad_chunks:
                g_sum = g if g_sum is None else jax.tree_util.tree_map(jnp.add, g_sum, g)
            loss_sum += float(loss) * float(n_rows)
            rows_sum += float(n_rows)
            idx = (head + jnp.arange(n)) % cap
            bank_q = bank_q.at[idx].set(q)
            bank_p = bank_p.at[idx].set(pp)
            bank_valid = bank_valid.at[idx].set(True)
            head = (head + n) % cap
        g = jax.tree_util.tree_map(lambda x: x / used, g_sum)
        norms = _norms(g)
        grad_norms.append(norms)
        g = jax.tree_util.tree_map(lambda x: x * min(1.0, opt["clip"] / max(norms[0], 1e-12)), g)
        if grad1 is None:
            grad1, grad1_norms = jax.device_get(_leaf_norms(g)), _norms(g)
        lr = schedule(step + 1, opt["lr"], opt["warmup"], opt["total"])
        params, m, v = _adamw(params, g, m, v, float(step + 1), lr,
                              b1=opt["b1"], b2=opt["b2"], eps=opt["eps"])
        losses.append(loss_sum / rows_sum)
    change = jax.device_get(_leaf_norms(jax.tree_util.tree_map(jnp.subtract, params, p0)))
    return {"losses": losses, "grad_norms": grad_norms, "grad1": grad1,
            "grad1_norms": grad1_norms, "change": change}


# ---------------------------------------------------------------- search
@partial(jax.jit, static_argnames=("model", "cast"))
def encode_queries(params, tokens, *, model, cast):
    return bert_cls(params, tokens, dict(model), cast)


@partial(jax.jit, static_argnames=("make_block", "block", "cast"))
def _topk_block(b, carry, q, key, served_ids, n_valid, *, make_block, block, cast):
    best_s, best_i, served_s = carry
    rows = make_block(key, b).astype(jnp.float32)
    s = mm(q, rows.T, cast)
    ids = b * block + jnp.arange(block, dtype=jnp.int32)
    s = jnp.where(ids[None] < n_valid, s, -jnp.inf)
    k = best_s.shape[1]
    top_s, pos = jax.lax.top_k(jnp.concatenate([best_s, s], 1), k)
    top_i = jnp.take_along_axis(
        jnp.concatenate([best_i, jnp.broadcast_to(ids[None], s.shape)], 1), pos, 1)
    mine = (served_ids >= 0) & (served_ids // block == b)
    got = jnp.take_along_axis(s, jnp.clip(served_ids - b * block, 0, block - 1), 1)
    return top_s, top_i, jnp.where(mine, got, served_s)


def topk(q, key, make_block, n_blocks: int, block: int, n_valid: int, k: int, served_ids,
         cast=identity):
    """Exact top-k of ``q @ index.T`` in float32, the index made block by
    block by ``make_block(key, b) -> (block, d)``; rows at or past
    ``n_valid`` are padding. Also gives the fp32 score of every id in
    ``served_ids`` (-1, an empty slot, gets -inf). Returns (scores, ids,
    served_scores)."""
    served_ids = jnp.asarray(served_ids, jnp.int32)
    qn = q.shape[0]
    carry = (jnp.full((qn, k), -jnp.inf), jnp.full((qn, k), -1, jnp.int32),
             jnp.full(served_ids.shape, -jnp.inf))
    for b in range(n_blocks):
        carry = _topk_block(jnp.int32(b), carry, q, key, served_ids, jnp.int32(n_valid),
                            make_block=make_block, block=block, cast=cast)
    return tuple(np.asarray(x) for x in carry)
