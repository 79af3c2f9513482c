"""Plain float32 reference of the Moonlight-16B-A3B retriever tower and of
ContAccum's updates on it, with its weights made from the run's seed;
written from the published equations and independent of the program:
nothing here imports it.

* ``tower``: DeepSeek-V3's block (arXiv:2412.19437 section 2.1) at the
  configuration's sizes, as one chip's share of an expert-parallel layer:
  token embedding, ``first_k_dense_replace`` dense layers, then the expert
  layers, each ``x + MLA(RMSNorm(x))`` then ``x + FFN(RMSNorm(x))``; a
  final RMSNorm; the mean over positions (the retriever's pooling).
  MLA without a query LoRA: q = x W_q split per head into (nope, rope);
  [c, k_pe] = x W_kv_a, c RMS-normed, [k_nope, v] = c W_kv_b per head;
  RoPE on q_pe and on the one k_pe all heads share; causal softmax of
  (q_nope k_nope + q_pe k_pe) / sqrt(nope + rope) over v; W_o. Expert
  layer: sigmoid scores of the router over every expert of the layer; the
  top ``num_experts_per_tok`` of score plus bias choose, the unbiased
  scores weigh, normalised over the chosen, times
  ``routed_scaling_factor``; every held expert (a SwiGLU) evaluated
  densely over every token and weighted by its gate, zero where the token
  did not choose it; the shared experts (one SwiGLU) added.
* ``contaccum_steps``: ContAccum updates (arXiv:2406.12356, Eq. 4-7) of the
  one shared tower, as ``bench/harness/reference.py`` computes them for
  two: K chunks, each chunk's InfoNCE over its queries and the query
  bank's rows against its positives, hard negatives and the passage
  bank's rows, mean over the rows, its representations then pushed into
  the FIFO banks; gradients averaged over the chunks, clipped, AdamW.

Departures from the published model: RoPE in the half-split layout where
the published code interleaves pairs (a fixed permutation of the rope
columns of W_q and W_kv_a: with random weights, the same model); only the
held experts' part of the routed sum (the absent experts lie on other
chips, and the program leaves them out alike); the selection bias is held
at its seeded value and no auxiliary loss is taken (training policy, not
the layer's equations). Each layer is rematerialized and the experts are
evaluated one at a time, so that the reference fits one chip beside its
optimizer state.

``cast`` rounds both operands of every matrix product (the fp8 control);
``capacity`` and ``biased_gates`` plant the faults of the expert layer
that the comparison must catch: one that drops the tokens past
``capacity`` times an expert's even share (in token order, per encode
call), and one that weighs the experts by the biased scores that chose
them. ``chunks_used`` and ``grad_chunks`` plant the accumulation's faults
of ``bench/harness/reference.py``: half of every batch left out, the mean
over the rest; every chunk's loss and bank push kept but only the first
chunks' gradients accumulated, still divided by K.
"""

from __future__ import annotations

import functools
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import reference, weights

HIGHEST = jax.lax.Precision.HIGHEST


def mm(a, b, cast):
    return jnp.matmul(cast(a), cast(b), precision=HIGHEST)


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


def mla(p, x, model, cast):
    b, s, _ = x.shape
    h = model["num_attention_heads"]
    nope, rdim = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    r, vd = model["kv_lora_rank"], model["v_head_dim"]
    q = mm(x, p["wq"], cast).reshape(b, s, h, nope + rdim)
    kv_a = mm(x, p["wkv_a"], cast)
    c = rms_norm(kv_a[..., :r], p["kv_norm"], model["rms_norm_eps"])
    k_pe = rope(kv_a[..., r:][:, :, None, :], model["rope_theta"])[:, :, 0]
    kv = mm(c, p["wkv_b"], cast).reshape(b, s, h, nope + vd)
    q_pe = rope(q[..., nope:], model["rope_theta"])
    logits = (jnp.einsum("bqhd,bkhd->bhqk", cast(q[..., :nope]), cast(kv[..., :nope]),
                         precision=HIGHEST)
              + jnp.einsum("bqhd,bkd->bhqk", cast(q_pe), cast(k_pe), precision=HIGHEST))
    logits = logits / math.sqrt(nope + rdim)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", cast(att), cast(kv[..., nope:]), precision=HIGHEST)
    return mm(o.reshape(b, s, h * vd), p["wo"], cast)


def swiglu(x, w_gate, w_up, w_down, cast):
    return mm(jax.nn.silu(mm(x, w_gate, cast)) * mm(x, w_up, cast), w_down, cast)


def gates(p, x, model, cast, fault=None):
    """(T, experts of the layer): each token's gate of each expert, zero
    unless the token chose it. ``fault`` (traced, so that one compiled
    program serves the sound reference and both faults): (capacity, biased);
    a token past ``capacity`` times its expert's even share is dropped
    (inf: none), and ``biased`` 1 weighs by the biased scores."""
    capacity, biased_gates = (jnp.inf, 0.0) if fault is None else fault
    scores = jax.nn.sigmoid(mm(x, p["router"], cast))
    _, idx = jax.lax.top_k(scores + p["select_bias"], model["num_experts_per_tok"])
    w = jnp.take_along_axis(scores + biased_gates * p["select_bias"], idx, -1)
    if model["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * model["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    g = jnp.zeros_like(scores).at[rows, idx].set(w)
    t, e = scores.shape
    cap = jnp.floor(capacity * t * model["num_experts_per_tok"] / e)
    return jnp.where(jnp.cumsum(g > 0, axis=0) <= cap, g, 0.0)


def moe(p, x, model, cast, fault=None):
    """x (T, d) -> the shared experts plus the held experts' gated sum."""
    first, held = model["experts_first"], model["n_routed_experts"]
    g = gates(p, x, model, cast, fault)[:, first:first + held].T

    def expert(y, w):
        w_gate, w_up, w_down, g_e = w
        return y + g_e[:, None] * swiglu(x, w_gate, w_up, w_down, cast), None

    y = swiglu(x, p["shared"]["w_gate"], p["shared"]["w_up"], p["shared"]["w_down"], cast)
    y, _ = jax.lax.scan(jax.checkpoint(expert), y, (p["w_gate"], p["w_up"], p["w_down"], g))
    return y


def _layer(lp, x, model, dense, cast, fault):
    b, s, d = x.shape
    eps = model["rms_norm_eps"]
    x = x + mla(lp["attn"], rms_norm(x, lp["ln1"], eps), model, cast)
    y = rms_norm(x, lp["ln2"], eps)
    if dense:
        f = swiglu(y, lp["ffn"]["w_gate"], lp["ffn"]["w_up"], lp["ffn"]["w_down"], cast)
    else:
        f = moe(lp["ffn"], y.reshape(b * s, d), model, cast, fault).reshape(b, s, d)
    return x + f


def tower(params, tokens, model, cast=reference.identity, fault=None):
    """tokens (B, S) -> mean-pooled final hidden states (B, d), float32."""
    x = params["embed"][tokens]
    for group, dense in (("dense_layers", True), ("layers", False)):
        layer = jax.checkpoint(partial(_layer, model=model, dense=dense, cast=cast, fault=fault))
        x, _ = jax.lax.scan(lambda x, lp: (layer(lp, x), None), x, params[group])
    return rms_norm(x, params["final_norm"], model["rms_norm_eps"]).mean(1)


# ---------------------------------------------------------------- weights
def tower_shapes(model: dict) -> dict:
    """Leaf shapes of the tower, keyed like the program's params."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    nope, rdim = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    r, vd = model["kv_lora_rank"], model["v_head_dim"]
    nd = model["first_k_dense_replace"]
    nm = model["num_hidden_layers"] - nd
    e, held, f = model["router_experts"], model["n_routed_experts"], model["moe_intermediate_size"]
    fs = model["n_shared_experts"] * f

    def attn(n):
        return {"wq": (n, d, h * (nope + rdim)), "wkv_a": (n, d, r + rdim), "kv_norm": (n, r),
                "wkv_b": (n, r, h * (nope + vd)), "wo": (n, h * vd, d)}

    def swi(lead, width):
        return {"w_gate": lead + (d, width), "w_up": lead + (d, width),
                "w_down": lead + (width, d)}

    return {
        "embed": (model["vocab_size"], d),
        "dense_layers": {"ln1": (nd, d), "ln2": (nd, d), "attn": attn(nd),
                         "ffn": swi((nd,), model["intermediate_size"])},
        "layers": {"ln1": (nm, d), "ln2": (nm, d), "attn": attn(nm),
                   "ffn": dict(swi((nm, held), f), router=(nm, d, e), select_bias=(nm, e),
                               shared=swi((nm,), fs))},
        "final_norm": (d,),
    }


NORMS = ("ln1", "ln2", "kv_norm", "final_norm")


def tower_params(key, model: dict, std_bias: float) -> dict:
    """{"shared": tower}: every matrix N(0, initializer_range), norm scales
    1 + N(0, initializer_range), the selection bias N(0, std_bias); each
    leaf from ``fold_in(key, tag of its path)``."""
    std = float(model["initializer_range"])

    def leaf(path, shape):
        name = "/".join(str(k.key) for k in path)
        x = jax.random.normal(jax.random.fold_in(key, weights._tag("shared", name)), shape,
                              jnp.float32)
        last = str(path[-1].key)
        if last in NORMS:
            return 1.0 + std * x
        return (std_bias if last == "select_bias" else std) * x

    shapes = tower_shapes(model)

    @jax.jit
    def make(key):
        return {"shared": jax.tree_util.tree_map_with_path(
            leaf, shapes, is_leaf=lambda s: isinstance(s, tuple))}

    return make(key)


# ---------------------------------------------------------------- training
def infonce(q, pp, ph, bank_q, bank_p, bank_valid, cast=reference.identity):
    """ContAccum's loss of one chunk: InfoNCE of its queries and the query
    bank's rows against its positives, its hard negatives and the passage
    bank's rows; mean over the rows. Returns (loss, rows)."""
    n, nh = q.shape[0], ph.shape[0]
    cols = jnp.concatenate([pp, ph, bank_p])
    col_ok = jnp.concatenate([jnp.ones(n + nh, bool), bank_valid])
    rows = jnp.concatenate([q, bank_q])
    labels = jnp.concatenate([jnp.arange(n), n + nh + jnp.arange(bank_q.shape[0])])
    weight = jnp.concatenate([jnp.ones(n), bank_valid.astype(jnp.float32)])
    logits = jnp.where(col_ok[None], mm(rows, cols.T, cast), -jnp.inf)
    per_row = (jax.nn.logsumexp(logits, -1)
               - jnp.take_along_axis(logits, labels[:, None], 1)[:, 0])
    per_row = jnp.where(weight > 0, per_row, 0.0)
    return (per_row * weight).sum() / weight.sum(), weight.sum()


@functools.lru_cache(maxsize=None)
def _programs(model_items: tuple, cast):
    """(encode, pull, loss_grad), jitted once per model and cast; the
    faults are arguments.

    A chunk's gradient is taken one encode call at a time: the loss's
    gradient with respect to the chunk's representations, then each call's
    VJP through the tower, added into the running sum. That is the same
    gradient as one ``value_and_grad`` over the whole chunk, with one
    call's intermediates and one gradient tree held at a time."""
    model = dict(model_items)

    def enc(params, tokens, fault):
        return tower(params["shared"], tokens, model, cast, fault)

    @partial(jax.jit, donate_argnums=(3,))
    def pull(params, tokens, ct, g_sum, fault):
        _, vjp = jax.vjp(lambda p: enc(p, tokens, fault), params)
        (g,) = vjp(ct)
        return jax.tree_util.tree_map(jnp.add, g_sum, g)

    @jax.jit
    def loss_grad(q, pp, ph, bank_q, bank_p, bank_valid):
        return jax.value_and_grad(
            lambda q, pp, ph: infonce(q, pp, ph, bank_q, bank_p, bank_valid, cast),
            argnums=(0, 1, 2), has_aux=True)(q, pp, ph)

    return jax.jit(enc), pull, loss_grad


@partial(jax.jit, static_argnames=("b1", "b2", "eps"), donate_argnums=(0, 2, 3))
def _adamw(params, g, m, v, count, lr, *, b1, b2, eps):
    """``reference._adamw``, updating its state in place."""
    return reference._adamw.__wrapped__(params, g, m, v, count, lr, b1=b1, b2=b2, eps=eps)


def contaccum_steps(params, batches, model: dict, opt: dict, wl: dict,
                    cast=reference.identity, capacity=None, biased_gates=False,
                    chunks_used=None, grad_chunks=None):
    """``len(batches)`` ContAccum updates of the shared tower ``params``
    ({"shared": tower}). Returns what ``bench/harness/reference.py``'s
    ``contaccum_steps`` returns for two towers: ``losses``; ``grad_norms``
    of each update before clipping, [global, query side, passage side],
    here all three the norm of the one gradient; ``grad1`` and
    ``grad1_norms`` of the first update after clipping; ``change``, the
    per-leaf norms of the parameters' change."""
    k = wl["total_batch"] // wl["local_batch"]
    used = chunks_used or k
    n, cap, d = wl["local_batch"], wl["bank"], model["hidden_size"]
    encode, pull, loss_grad = _programs(tuple(sorted(model.items())), cast)
    fault = (jnp.float32(jnp.inf if capacity is None else capacity),
             jnp.float32(1.0 if biased_gates else 0.0))
    # the starting point waits on the host: the chip holds the parameters,
    # Adam's two moments and two gradients
    p0 = jax.device_get(params)
    m = v = None
    bank_q = jnp.zeros((cap, d), jnp.float32)
    bank_p = jnp.zeros((cap, d), jnp.float32)
    bank_valid = jnp.zeros((cap,), bool)
    head = 0
    losses, grad_norms, grad1 = [], [], None
    for step, batch in enumerate(batches):
        q_all, p_all, h_all = (np.asarray(x) for x in batch)
        g = jax.tree_util.tree_map(jnp.zeros_like, params)
        loss_sum = rows_sum = 0.0
        for c in range(used):
            sl = slice(c * n, (c + 1) * n)
            toks = (q_all[sl], p_all[sl], h_all[sl].reshape(-1, h_all.shape[-1]))
            reps = [encode(params, t, fault) for t in toks]
            (loss, n_rows), cts = loss_grad(*reps, bank_q, bank_p, bank_valid)
            if grad_chunks is None or c < grad_chunks:
                for t, ct in zip(toks, cts):
                    g = pull(params, t, ct, g, fault)
            loss_sum += float(loss) * float(n_rows)
            rows_sum += float(n_rows)
            idx = (head + jnp.arange(n)) % cap
            bank_q = bank_q.at[idx].set(reps[0])
            bank_p = bank_p.at[idx].set(reps[1])
            bank_valid = bank_valid.at[idx].set(True)
            head = (head + n) % cap
        norm = float(jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))) / used
        grad_norms.append([norm] * 3)
        scale = min(1.0, opt["clip"] / max(norm, 1e-12)) / used
        g = jax.tree_util.tree_map(lambda x: x * scale, g)
        if grad1 is None:
            grad1 = jax.device_get(reference._leaf_norms(g))
            grad1_norms = [min(norm, opt["clip"])] * 3
        if m is None:
            m = jax.tree_util.tree_map(jnp.zeros_like, params)
            v = jax.tree_util.tree_map(jnp.zeros_like, params)
        lr = reference.schedule(step + 1, opt["lr"], opt["warmup"], opt["total"])
        params, m, v = _adamw(params, g, m, v, float(step + 1), lr,
                              b1=opt["b1"], b2=opt["b2"], eps=opt["eps"])
        del g
        losses.append(loss_sum / rows_sum)
    del m, v
    change = jax.tree_util.tree_map(
        lambda a, b: float(np.linalg.norm((np.asarray(a) - b).ravel().astype(np.float64))),
        params, p0)
    return {"losses": losses, "grad_norms": grad_norms, "grad1": grad1,
            "grad1_norms": grad1_norms, "change": change}
