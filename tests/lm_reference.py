"""Plain float32 reference of the Moonlight-16B-A3B retriever tower
(DeepSeek-V3's block, arXiv:2412.19437 section 2.1, at DeepSeek-V2-Lite's
sizes), written from the published equations and independent of the
program: nothing here imports it. Every matrix product runs at HIGHEST
precision.

``model`` uses the names of the published config.json
(``hidden_size``, ``kv_lora_rank``, ``n_routed_experts`` ...), plus
``experts_first`` (the first expert id held here) and ``router_experts``
(the router's width, every expert of the layer). ``params`` is laid out as
the program stores one tower (``embed``, ``dense_layers``, ``layers``,
``final_norm``), a layer group's leaves stacked over its layers.

The tower: token embedding; ``first_k_dense_replace`` dense layers and then
the expert layers, each ``x + MLA(RMSNorm(x))`` then ``x + FFN(RMSNorm(x))``;
a final RMSNorm; the mean over positions (the retriever's pooling).

* MLA without a query LoRA: q = x W_q split into (nope, rope) per head;
  [c, k_pe] = x W_kv_a, c RMS-normed, [k_nope, v] = c W_kv_b per head; RoPE
  on q_pe and on the one k_pe every head shares; causal softmax of
  (q_nope k_nope + q_pe k_pe) / sqrt(nope + rope) over v; W_o.
* Expert layer: sigmoid scores of the router over all ``router_experts``;
  the top ``num_experts_per_tok`` of score plus bias choose the experts, the
  unbiased scores weight them, normalised over the chosen and multiplied
  by ``routed_scaling_factor``. Every held expert (a SwiGLU) is evaluated
  densely over every token and weighted by its gate, zero where the token
  did not choose it; the shared experts (one SwiGLU) are added.

Departures from the published model: RoPE in the half-split layout where
the published code interleaves pairs (a fixed permutation of the rope
columns of W_q and W_kv_a, so with random weights the same model); only
the held experts' part of the routed sum (the chip's share of an
expert-parallel deployment; the absent experts add nothing here, as in the
program); the bias is not trained and no auxiliary loss is taken (training
policy, not the layer's equations).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x (B, S, H, D), half-split pairs (i, i + D/2)."""
    s, dim = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla(p, x, model):
    b, s, _ = x.shape
    h = model["num_attention_heads"]
    nope, rdim = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    r, vd = model["kv_lora_rank"], model["v_head_dim"]
    q = mm(x, p["wq"]).reshape(b, s, h, nope + rdim)
    kv_a = mm(x, p["wkv_a"])
    c = rms_norm(kv_a[..., :r], p["kv_norm"], model["rms_norm_eps"])
    k_pe = rope(kv_a[..., r:][:, :, None, :], model["rope_theta"])
    kv = mm(c, p["wkv_b"]).reshape(b, s, h, nope + vd)
    q_pe = rope(q[..., nope:], model["rope_theta"])
    logits = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope], precision=HIGHEST)
              + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe[:, :, 0], precision=HIGHEST))
    logits = logits / math.sqrt(nope + rdim)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", att, kv[..., nope:], precision=HIGHEST)
    return mm(o.reshape(b, s, h * vd), p["wo"])


def swiglu(x, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def gates(p, x, model):
    """(T, router_experts) gate of every expert for every token: zero
    unless chosen."""
    scores = jax.nn.sigmoid(mm(x, p["router"]))
    _, idx = jax.lax.top_k(scores + p["select_bias"], model["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, -1)
    if model["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * model["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(w)


def moe(p, x, model, shared: bool = True):
    """x (T, d) -> the held experts' gated sum (+ the shared experts)."""
    g = gates(p, x, model)
    first = model["experts_first"]
    y = swiglu(x, **p["shared"]) if shared else jnp.zeros_like(x)
    for e in range(model["n_routed_experts"]):
        ye = swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
        y = y + g[:, first + e][:, None] * ye
    return y


def _layer(lp, x, model, dense):
    b, s, d = x.shape
    eps = model["rms_norm_eps"]
    x = x + mla(lp["attn"], rms_norm(x, lp["ln1"], eps), model)
    y = rms_norm(x, lp["ln2"], eps)
    f = swiglu(y, **lp["ffn"]) if dense else moe(lp["ffn"], y.reshape(b * s, d), model)
    return x + f.reshape(b, s, d)


def tower(params, tokens, model):
    """tokens (B, S) -> mean-pooled final hidden states (B, d), float32.
    Each layer is rematerialized, so that the gradient holds one layer's
    intermediates at a time."""
    x = params["embed"][tokens]
    for group, dense in (("dense_layers", True), ("layers", False)):
        stack = params[group]
        n = jax.tree_util.tree_leaves(stack)[0].shape[0]
        for i in range(n):
            lp = jax.tree_util.tree_map(lambda a: a[i], stack)
            x = jax.checkpoint(lambda lp, x: _layer(lp, x, model, dense))(lp, x)
    x = rms_norm(x, params["final_norm"], model["rms_norm_eps"])
    return x.mean(1)


# ---------------------------------------------------------------- training
def schedule(count: int, peak: float, warmup: int, total: int) -> float:
    """Linear warm-up from 0 to ``peak`` over ``warmup`` updates, then
    linear decay to 0 at ``total`` (``count`` is 1 for the first update)."""
    warmup = max(warmup, 1)
    total = max(total, warmup + 1)
    frac = count / warmup if count < warmup else (total - count) / (total - warmup)
    return peak * min(max(frac, 0.0), 1.0)


def chunk_loss(params, q_tok, p_tok, h_tok, bank_q, bank_p, bank_valid, model):
    """ContAccum's loss of one chunk (arXiv:2406.12356, Eq. 4-7) for one
    shared tower: InfoNCE of the chunk's queries and the query bank's rows
    against the chunk's positives, its hard negatives and the passage
    bank's rows; mean over the rows. Returns (loss, (rows, q, pp))."""
    q = tower(params, q_tok, model)
    pp = tower(params, p_tok, model)
    ph = tower(params, h_tok, model)
    n, nh = q.shape[0], ph.shape[0]
    cols = jnp.concatenate([pp, ph, bank_p])
    col_ok = jnp.concatenate([jnp.ones(n + nh, bool), bank_valid])
    rows = jnp.concatenate([q, bank_q])
    labels = jnp.concatenate([jnp.arange(n), n + nh + jnp.arange(bank_q.shape[0])])
    weight = jnp.concatenate([jnp.ones(n), bank_valid.astype(jnp.float32)])
    logits = jnp.where(col_ok[None], mm(rows, cols.T), -jnp.inf)
    per_row = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(logits, labels[:, None], 1)[:, 0]
    per_row = jnp.where(weight > 0, per_row, 0.0)
    return (per_row * weight).sum() / weight.sum(), (weight.sum(), q, pp)


def contaccum_steps(params, batches, model, opt: dict, local_batch: int, bank: int):
    """ContAccum updates of one shared tower from ``params``, one per batch
    of (queries (B, q_len), positives (B, p_len), hard negatives (B, H,
    p_len)): K = B / local_batch chunks, each chunk's loss and gradient,
    its query and positive representations pushed into the two FIFO banks
    after it; the chunks' gradients averaged, clipped to ``opt["clip"]``,
    applied by AdamW (``opt``: lr, warmup, total, clip, b1, b2, eps).
    Returns (losses, params after the last update)."""
    import numpy as np

    d = model["hidden_size"]
    grad = jax.jit(jax.value_and_grad(lambda *a: chunk_loss(*a, model), has_aux=True))
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    bank_q = jnp.zeros((bank, d))
    bank_p = jnp.zeros((bank, d))
    bank_valid = jnp.zeros((bank,), bool)
    head, losses = 0, []
    for step, (q_all, p_all, h_all) in enumerate(batches):
        k = q_all.shape[0] // local_batch
        g_sum, loss_sum, rows_sum = None, 0.0, 0.0
        for c in range(k):
            sl = slice(c * local_batch, (c + 1) * local_batch)
            h = h_all[sl].reshape(-1, h_all.shape[-1])
            (loss, (n_rows, q, pp)), g = grad(params, q_all[sl], p_all[sl], h, bank_q,
                                              bank_p, bank_valid)
            g_sum = g if g_sum is None else jax.tree_util.tree_map(jnp.add, g_sum, g)
            loss_sum += float(loss) * float(n_rows)
            rows_sum += float(n_rows)
            idx = (head + jnp.arange(local_batch)) % bank
            bank_q = bank_q.at[idx].set(q)
            bank_p = bank_p.at[idx].set(pp)
            bank_valid = bank_valid.at[idx].set(True)
            head = (head + local_batch) % bank
        g = jax.tree_util.tree_map(lambda x: x / k, g_sum)
        norm = math.sqrt(sum(float(jnp.sum(x * x)) for x in jax.tree_util.tree_leaves(g)))
        g = jax.tree_util.tree_map(lambda x: x * min(1.0, opt["clip"] / max(norm, 1e-12)), g)
        lr = schedule(step + 1, opt["lr"], opt["warmup"], opt["total"])
        b1, b2, count = opt["b1"], opt["b2"], float(step + 1)
        m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count
        params = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + opt["eps"]), params, m, v)
        losses.append(loss_sum / rows_sum)
    return np.asarray(losses), params

