"""The Moonlight-16B-A3B (DeepSeek-V3 block) retriever tower against its
plain float32 reference (tests/lm_reference.py), on seeded random weights
at CPU sizes: latent attention, the dropless expert layer under uniform
and fully skewed routing, the selection bias, the chip's share of an
expert-parallel layer, and the ContAccum update of a shared tower."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lm_reference as ref
from repro.configs.moonlight_16b_a3b import MOONLIGHT_TINY
from repro.core.methods import init_state
from repro.core.types import RetrievalBatch
from repro.launch import train
from repro.models import lm, mla, moe

CFG = MOONLIGHT_TINY


def model_dict(cfg=CFG, moe_cfg=None) -> dict:
    """The reference's config names for an LMConfig."""
    m = moe_cfg or cfg.moe
    first, n_held = m.held_range
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "kv_lora_rank": cfg.mla.kv_lora_rank, "qk_nope_head_dim": cfg.mla.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.mla.qk_rope_head_dim, "v_head_dim": cfg.mla.v_head_dim,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "num_experts_per_tok": m.top_k, "routed_scaling_factor": m.routed_scale,
        "norm_topk_prob": m.normalize_top_k, "n_routed_experts": n_held,
        "experts_first": first, "router_experts": m.n_experts,
    }


def layer0(tree):
    return jax.tree_util.tree_map(lambda a: a[0], tree)


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-6)
    assert np.abs(a - b).max() <= tol * scale, (np.abs(a - b).max(), scale)


def test_mla_forward_matches_reference():
    rng = jax.random.PRNGKey(0)
    p = layer0(mla.init_mla(rng, CFG.d_model, CFG.n_heads, CFG.mla, 1))
    p["kv_norm"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), p["kv_norm"].shape)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, CFG.d_model))
    rope = CFG.mla.qk_rope_head_dim
    cos, sin = lm.L.rotary_embedding(jnp.arange(12), rope, CFG.rope_theta)
    cos, sin = (jnp.broadcast_to(t, (2, 12, rope // 2)) for t in (cos, sin))
    with jax.default_matmul_precision("highest"):
        got = mla.mla(p, x, CFG.mla, CFG.n_heads, cos, sin, dtype=jnp.float32,
                      eps=CFG.norm_eps)
        want = ref.mla(p, x, model_dict())
    close(got, want)


def moe_layer(seed: int, moe_cfg, d=None):
    d = d or CFG.d_model
    return layer0(moe.init_moe(jax.random.PRNGKey(seed), d, moe_cfg, 1))


@pytest.mark.parametrize("routing", ["uniform", "skewed"])
def test_dropless_moe_matches_reference(routing):
    """Fully skewed: a bias makes every token choose the same top-k experts
    (experts 0-2, all held), so each takes every token, three slots a token
    fill the whole buffer, and still nothing is dropped."""
    m = CFG.moe
    p = moe_layer(3, m)
    t = 40
    x = jax.random.normal(jax.random.PRNGKey(4), (t, CFG.d_model))
    if routing == "skewed":
        p["select_bias"] = p["select_bias"].at[: m.top_k].add(100.0)
    with jax.default_matmul_precision("highest"):
        got, counters = moe.moe_dropless(p, x, m)
        want = ref.moe(p, x, model_dict())
    close(got, want)
    slots = np.asarray(counters["expert_slots"])
    assert int(counters["rows_routed"]) == slots.sum()
    assert int(counters["rows_routed"]) + int(counters["absent_slots"]) == t * m.top_k
    if routing == "skewed":
        assert slots.tolist() == [t] * m.top_k + [0] * (m.held_range[1] - m.top_k)
        assert int(counters["absent_slots"]) == 0


def test_bias_chooses_experts_but_does_not_weight_them():
    m = CFG.moe
    p = moe_layer(5, m)
    x = jax.random.normal(jax.random.PRNGKey(6), (32, CFG.d_model))
    bias = jnp.zeros((m.n_experts,)).at[jnp.array([1, 7, 12])].set(5.0)
    with jax.default_matmul_precision("highest"):
        gates, experts = moe.route(p["router"], bias, x, m)
        scores = jax.nn.sigmoid(x @ p["router"])
    chosen = np.sort(np.asarray(experts), axis=1)
    assert (chosen == np.array([1, 7, 12])).all()        # the bias chose
    want = np.take_along_axis(np.asarray(scores), np.asarray(experts), 1)
    want = want / want.sum(1, keepdims=True) * m.routed_scale
    close(gates, want, tol=1e-6)                           # the scores weigh
    p["select_bias"] = bias
    with jax.default_matmul_precision("highest"):
        close(moe.moe_dropless(p, x, m)[0], ref.moe(p, x, model_dict()))


def test_expert_shares_sum_to_the_uncut_layer():
    """64 experts, top-6, over 8 shares of 8: the routed parts of every
    share, with the shared experts counted once, add up to the whole
    layer."""
    d = 32
    whole = dataclasses.replace(CFG.moe, n_experts=64, top_k=6, d_expert=16, d_shared=32,
                                held=None)
    p = moe_layer(7, whole, d)
    x = jax.random.normal(jax.random.PRNGKey(8), (24, d))
    sp = p["shared"]
    total = jnp.zeros_like(x)
    with jax.default_matmul_precision("highest"):
        for i in range(8):
            share = dataclasses.replace(whole, held=(8 * i, 8), d_shared=0)
            ps = dict(p, **{k: p[k][8 * i: 8 * (i + 1)] for k in ("w_gate", "w_up", "w_down")})
            total = total + moe.moe_dropless(ps, x, share)[0]
        total = total + ref.swiglu(x, **sp)
        uncut = ref.moe(p, x, dict(model_dict(moe_cfg=whole), hidden_size=d))
    close(total, uncut)


def tiny_args(method="contaccum", steps=10):
    return train.parse_args([
        "--arch", "moonlight-tiny", "--method", method, "--total-batch", "8",
        "--local-batch", "4", "--bank", "8", "--q-len", "8", "--p-len", "16",
        "--steps", str(steps), "--lr", "1e-3", "--corpus-size", "32",
    ])


def test_tiny_moonlight_contaccum_update_matches_reference():
    """Two ContAccum updates of the shared tower through the training
    driver's own step (fp32), against the reference's: each update's loss,
    and every parameter after them."""
    args = tiny_args()
    ts = train.build_step(args)
    state = init_state(jax.random.PRNGKey(0), ts.enc, ts.tx, ts.cfg)
    p0 = jax.tree_util.tree_map(np.asarray, state.params["shared"])
    rng = np.random.default_rng(0)
    batches = [(rng.integers(0, CFG.vocab_size, (8, 8)), rng.integers(0, CFG.vocab_size, (8, 16)),
                rng.integers(0, CFG.vocab_size, (8, 1, 16))) for _ in range(2)]
    losses = []
    with jax.default_matmul_precision("highest"):
        for q, p, h in batches:
            state, metrics = ts.update(state, RetrievalBatch(jnp.asarray(q), jnp.asarray(p),
                                                             jnp.asarray(h)))
            losses.append(float(metrics.loss))
        opt = {"lr": args.lr, "warmup": args.steps // 10, "total": args.steps, "clip": 2.0,
               "b1": 0.9, "b2": 0.999, "eps": 1e-8}
        model = dict(model_dict(), first_k_dense_replace=CFG.first_dense)
        want_losses, want = ref.contaccum_steps(p0, batches, model, opt, 4, 8)
    np.testing.assert_allclose(losses, want_losses, rtol=1e-4)
    got = state.params["shared"]
    for path, a in jax.tree_util.tree_leaves_with_path(got):
        b = p0
        for k in path:
            b = b[k.key]
        w = dict(jax.tree_util.tree_leaves_with_path(want))[path]
        change, want_change = np.asarray(a) - b, np.asarray(w) - b
        if not np.abs(want_change).max():
            assert not np.abs(change).max(), jax.tree_util.keystr(path)
            continue
        # Adam moves a leaf's entries by about lr each whatever their
        # gradient, so entries with a near-zero gradient carry rounding:
        # compare each leaf's change as a whole
        gap = np.linalg.norm(change - want_change) / np.linalg.norm(want_change)
        assert gap < 1e-3, (jax.tree_util.keystr(path), gap)
    assert float(metrics.tower["rows_routed"]) > 0


def test_tiny_moonlight_counters_reach_step_metrics():
    """Counters come out of the encoders, summed over the update's chunks
    and encode calls: every token's top-k slots are counted once."""
    args = tiny_args()
    ts = train.build_step(args)
    state = init_state(jax.random.PRNGKey(1), ts.enc, ts.tx, ts.cfg)
    rng = np.random.default_rng(1)
    batch = RetrievalBatch(*(jnp.asarray(rng.integers(0, CFG.vocab_size, s))
                             for s in ((8, 8), (8, 16), (8, 1, 16))))
    _, metrics = ts.update(state, batch)
    tw = {k: float(v) for k, v in metrics.tower.items()}
    tokens = 8 * 8 + 2 * 8 * 16
    n_moe = CFG.n_layers - CFG.first_dense
    assert tw["rows_routed"] + tw["absent_slots"] == tokens * CFG.moe.top_k * n_moe
    assert tw["expert_slots_mean"] * CFG.moe.held_range[1] * n_moe == tw["rows_routed"]
    assert tw["expert_slots_max"] >= tw["expert_slots_mean"]
    # the static buffer holds min(top_k, held) rows a token
    assert tw["rows_buffer"] == tokens * min(CFG.moe.top_k, CFG.moe.held_range[1]) * n_moe


def test_lm_tower_binds_precision_policy():
    """``make_lm_dual_encoder(precision=...)`` binds the policy through
    ``LMConfig.with_precision``: masters stored in float32, both sides'
    representations in the compute dtype, within bf16 rounding of the
    float32 tower's."""
    from repro.models.towers import make_lm_dual_encoder

    fp32, bf16 = make_lm_dual_encoder(CFG), make_lm_dual_encoder(CFG, precision="bf16")
    key = jax.random.PRNGKey(3)
    assert {a.dtype for a in jax.tree_util.tree_leaves(bf16.init(key))} == {jnp.dtype("float32")}
    params = fp32.init(key)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0, CFG.vocab_size)
    for side in ("encode_query", "encode_passage"):
        got = getattr(bf16, side)(params, tokens)
        assert got.dtype == jnp.bfloat16
        close(got, getattr(fp32, side)(params, tokens), tol=5e-2)


def test_dropless_moe_ignores_rows_past_the_groups(monkeypatch):
    """The chip's grouped-product kernel leaves the buffer's rows past the
    last group unwritten, in its output and in its backward products: with
    those rows NaN, the layer's output and gradients stay finite and
    unchanged."""
    m = CFG.moe
    p = moe_layer(9, m)
    x = jax.random.normal(jax.random.PRNGKey(10), (40, CFG.d_model))

    def loss(p, x):
        y, _ = moe.moe_dropless(p, x, m)
        return jnp.sum(y * y)

    want = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    ragged = jax.lax.ragged_dot

    @jax.custom_vjp
    def unwritten(lhs, rhs, gs):
        out = ragged(lhs, rhs, gs)
        past = jnp.arange(out.shape[0])[:, None] >= gs.sum()
        return jnp.where(past, jnp.nan, out)

    def fwd(lhs, rhs, gs):
        return unwritten(lhs, rhs, gs), (lhs, rhs, gs)

    def bwd(res, g):
        lhs, rhs, gs = res
        d_lhs, d_rhs = jax.vjp(lambda a, b: ragged(a, b, gs), lhs, rhs)[1](g)
        past = jnp.arange(lhs.shape[0])[:, None] >= gs.sum()
        return jnp.where(past, jnp.nan, d_lhs), d_rhs, None

    unwritten.defvjp(fwd, bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten)
    got = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
