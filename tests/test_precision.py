"""PrecisionPolicy (core/precision.py) coverage.

  * fp32 policy is *bit-identical* to the legacy implicit-fp32 behavior.
  * bf16_banks trajectories track the fp32 reference within documented
    tolerance across ALL 12 negative-source x backprop-strategy
    compositions, on both loss backends (dense einsum + fused Pallas kernel
    in interpret mode), with replicated AND sharded bank layouts.
  * Bank rings are allocated in the policy's bank_dtype; the explicit
    ``bank_dtype`` override still wins.
  * Softmax statistics / metrics stay fp32 regardless of input dtype
    (spot-checked here; the hypothesis property suite sweeps it).
  * adamw(keep_master_params=True): fp32 masters in the optimizer state
    track the fp32 reference exactly while the stored params are bf16.

Documented tolerance: bf16 inputs perturb each logit by O(2^-8) relative;
over a 3-step trajectory on the tiny MLP towers the loss stays within 5%
relative and the (fp32-master) params within 5e-2 absolute of the fp32
reference. Statistics keep fp32 *dtype* exactly — only values drift.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    PRECISION_PRESETS,
    ContrastiveConfig,
    PrecisionPolicy,
    RetrievalBatch,
    bank_bytes_per_device,
    build_step_program,
    contrastive_loss,
    init_state,
    resolve_precision,
)
from repro.core.loss import DenseLossBackend, FusedLossBackend
from repro.optim import adamw, chain, clip_by_global_norm, sgd
from repro.optim.adamw import apply_updates

from helpers import make_batch, make_mlp_encoder, mixed_precision

SOURCES = ["in_batch", "gathered", "dual_bank", "passage_bank"]
STRATEGIES = ["direct", "scan", "rep_cache"]
BANK_SOURCES = ("dual_bank", "passage_bank")

LOSS_RTOL = 5e-2      # documented bf16-vs-fp32 trajectory tolerance (loss)
PARAM_ATOL = 5e-2     # ... and params (fp32 masters, bf16-perturbed grads)


def _tx():
    return chain(clip_by_global_norm(2.0), sgd(0.1))


def _cfg(neg, bp, *, precision, loss_impl="dense", shard_banks=False):
    needs_mesh = neg == "gathered" or shard_banks
    return ContrastiveConfig(
        negatives=neg,
        backprop=bp,
        accumulation_steps=2 if bp != "direct" else 1,
        bank_size=8 if neg in BANK_SOURCES else 0,
        loss_impl=loss_impl,
        precision=precision,
        dp_axis="dp" if needs_mesh else None,
        shard_banks=shard_banks,
    )


def _run_trajectory(cfg, n_steps=3):
    """3-step trajectory on the MLP towers; returns (losses, fp32 params).
    Mesh-requiring configs run under a 1-device shard_map (same code path,
    CPU-testable)."""
    policy = resolve_precision(cfg.precision)
    enc = make_mlp_encoder()
    if policy.name != "fp32":
        enc = mixed_precision(enc, policy)
    tx = _tx()
    state = init_state(jax.random.PRNGKey(0), enc, tx, cfg)
    update = build_step_program(enc, tx, cfg).update
    if cfg.dp_axis is not None:
        from jax.sharding import Mesh, PartitionSpec as P

        from repro.distribution.sharding import contrastive_state_spec

        mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
        sspec = contrastive_state_spec(("dp",), cfg.shard_banks)
        bspec = RetrievalBatch(query=P("dp"), passage_pos=P("dp"),
                               passage_hard=P("dp"))
        update = jax.shard_map(update, mesh=mesh, in_specs=(sspec, bspec),
                           out_specs=(sspec, P()), check_vma=False)
    update = jax.jit(update)
    losses = []
    for i in range(n_steps):
        state, m = update(state, make_batch(jax.random.PRNGKey(100 + i), 8,
                                            n_hard=1))
        # metric statistics are fp32 whatever the compute dtype
        assert m.loss.dtype == jnp.float32, cfg
        assert m.accuracy.dtype == jnp.float32, cfg
        losses.append(float(m.loss))
    params = [np.asarray(x, np.float32)
              for x in jax.tree_util.tree_leaves(state.params)]
    return losses, params


_REF_CACHE = {}


def _fp32_reference(neg, bp, loss_impl, shard_banks):
    key = (neg, bp, loss_impl, shard_banks)
    if key not in _REF_CACHE:
        _REF_CACHE[key] = _run_trajectory(
            _cfg(neg, bp, precision="fp32", loss_impl=loss_impl,
                 shard_banks=shard_banks)
        )
    return _REF_CACHE[key]


# ------------------------------------------------------------------ presets
def test_presets_resolve_and_unknown_raises():
    assert set(PRECISION_PRESETS) == {"fp32", "bf16", "bf16_banks"}
    for name, policy in PRECISION_PRESETS.items():
        assert resolve_precision(name) is policy
        assert policy.accum_dtype == jnp.float32
        assert policy.param_dtype == jnp.float32  # masters stay fp32
    assert resolve_precision(None).name == "fp32"
    custom = PrecisionPolicy(name="x", bank_dtype=jnp.bfloat16)
    assert resolve_precision(custom) is custom
    with pytest.raises(ValueError, match="unknown precision"):
        resolve_precision("fp8")
    with pytest.raises(ValueError, match="unknown precision"):
        build_step_program(
            make_mlp_encoder(), _tx(), ContrastiveConfig(precision="nope")
        )


def test_fp32_policy_is_bit_identical_to_legacy_default():
    """precision='fp32' must not change a single bit vs the pre-policy
    behavior (the default-constructed config)."""
    enc = make_mlp_encoder()
    batches = [make_batch(jax.random.PRNGKey(100 + i), 8, n_hard=1)
               for i in range(3)]
    states = []
    for cfg in (
        ContrastiveConfig(method="contaccum", accumulation_steps=2, bank_size=8),
        ContrastiveConfig(method="contaccum", accumulation_steps=2, bank_size=8,
                          precision="fp32"),
    ):
        tx = _tx()
        state = init_state(jax.random.PRNGKey(0), enc, tx, cfg)
        update = jax.jit(build_step_program(enc, tx, cfg).update)
        for b in batches:
            state, _ = update(state, b)
        states.append(state)
    for a, b in zip(jax.tree_util.tree_leaves(states[0]),
                    jax.tree_util.tree_leaves(states[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- the full trajectory matrix
@pytest.mark.parametrize("loss_impl", ["dense", "fused"])
@pytest.mark.parametrize("bp", STRATEGIES)
@pytest.mark.parametrize("neg", SOURCES)
def test_bf16_trajectory_tracks_fp32_reference(neg, bp, loss_impl):
    """All 12 source x strategy compositions, both backends: the bf16_banks
    trajectory stays within documented tolerance of the fp32 reference."""
    l_ref, p_ref = _fp32_reference(neg, bp, loss_impl, False)
    l_bf, p_bf = _run_trajectory(
        _cfg(neg, bp, precision="bf16_banks", loss_impl=loss_impl)
    )
    np.testing.assert_allclose(l_bf, l_ref, rtol=LOSS_RTOL,
                               err_msg=f"{neg}x{bp}/{loss_impl}: loss")
    for a, b in zip(p_bf, p_ref):
        np.testing.assert_allclose(a, b, atol=PARAM_ATOL,
                                   err_msg=f"{neg}x{bp}/{loss_impl}: params")


@pytest.mark.parametrize("loss_impl", ["dense", "fused"])
@pytest.mark.parametrize("bp", ["scan", "rep_cache"])
@pytest.mark.parametrize("neg", BANK_SOURCES)
def test_bf16_trajectory_with_sharded_banks(neg, bp, loss_impl):
    """Sharded bank layout (shard_map path): bf16_banks still tracks the
    fp32 sharded reference — the bf16 rings shard/push/gather like fp32."""
    l_ref, p_ref = _fp32_reference(neg, bp, loss_impl, True)
    l_bf, p_bf = _run_trajectory(
        _cfg(neg, bp, precision="bf16_banks", loss_impl=loss_impl,
             shard_banks=True)
    )
    np.testing.assert_allclose(l_bf, l_ref, rtol=LOSS_RTOL,
                               err_msg=f"sharded {neg}x{bp}/{loss_impl}: loss")
    for a, b in zip(p_bf, p_ref):
        np.testing.assert_allclose(
            a, b, atol=PARAM_ATOL, err_msg=f"sharded {neg}x{bp}/{loss_impl}"
        )


# ---------------------------------------------------------------- bank dtype
def test_bank_rings_allocated_in_policy_dtype():
    enc = make_mlp_encoder()
    cfg = _cfg("dual_bank", "scan", precision="bf16_banks")
    state = init_state(jax.random.PRNGKey(0), enc, _tx(), cfg)
    assert state.bank_q.buf.dtype == jnp.bfloat16
    assert state.bank_p.buf.dtype == jnp.bfloat16
    # 'bf16' keeps fp32 banks; explicit bank_dtype override beats the policy
    cfg16 = dataclasses.replace(cfg, precision="bf16")
    assert init_state(jax.random.PRNGKey(0), enc, _tx(), cfg16).bank_p.buf.dtype == jnp.float32
    cfg_ovr = dataclasses.replace(cfg, precision="fp32", bank_dtype=jnp.float16)
    assert init_state(jax.random.PRNGKey(0), enc, _tx(), cfg_ovr).bank_p.buf.dtype == jnp.float16


def test_bank_bytes_per_device_math():
    # the README memory table: (N_q + N_p) * d * itemsize / shards
    assert bank_bytes_per_device(2048, 2048, 768, "fp32") == 2 * 2048 * 768 * 4
    assert bank_bytes_per_device(2048, 2048, 768, "bf16_banks") == 2 * 2048 * 768 * 2
    assert (
        bank_bytes_per_device(2048, 2048, 768, "bf16_banks", shards=8)
        == 2 * 2048 * 768 * 2 // 8
    )
    # the acceptance criterion: bf16_banks cuts >= 40% vs fp32 replicated
    red = 1 - bank_bytes_per_device(2048, 2048, 768, "bf16_banks") / \
        bank_bytes_per_device(2048, 2048, 768, "fp32")
    assert red >= 0.40


# ------------------------------------------------------- fp32-stats contract
@pytest.mark.parametrize("backend", ["dense", "fused"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_loss_statistics_are_fp32_for_any_input_dtype(backend, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    q = jax.random.normal(k1, (6, 8)).astype(dtype)
    p = jax.random.normal(k2, (6, 8)).astype(dtype)
    loss_dev, aux = contrastive_loss(q, p, backend=backend)
    assert loss_dev.dtype == jnp.float32
    assert aux.loss.dtype == jnp.float32
    assert aux.accuracy.dtype == jnp.float32
    assert np.isfinite(float(aux.loss))


def test_backend_row_stats_dtype_and_value():
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    q32 = jax.random.normal(k1, (5, 8))
    p32 = jax.random.normal(k2, (9, 8))
    labels = jnp.arange(5, dtype=jnp.int32)
    mask = jnp.ones((9,), bool)
    dense = DenseLossBackend()
    ref, _ = dense.row_stats(q32, p32, labels, mask, temperature=1.0)
    for be in (dense, FusedLossBackend(interpret=True)):
        out, correct = be.row_stats(
            q32.astype(jnp.bfloat16), p32.astype(jnp.bfloat16), labels, mask,
            temperature=1.0,
        )
        assert out.dtype == jnp.float32 and correct.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=5e-2, atol=5e-2)


def test_fused_kernel_bf16_grads_match_dense_reference():
    """bf16 q/p through the fused kernel: fp32 stats, bf16 gradients, both
    within bf16 tolerance of the dense fp32-input reference."""
    from repro.core.loss import resolve_loss_backend

    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    q = jax.random.normal(k1, (7, 8))
    p = jax.random.normal(k2, (11, 8))
    labels = jnp.arange(7, dtype=jnp.int32)
    mask = jnp.arange(11) < 9  # two masked columns

    def loss_fn(be, dtype):
        def f(q_, p_):
            out, _ = resolve_loss_backend(be).row_stats(
                q_.astype(dtype), p_.astype(dtype), labels, mask,
                temperature=0.7,
            )
            return out.mean()
        return f

    ref, (gq_ref, gp_ref) = jax.value_and_grad(
        loss_fn("dense", jnp.float32), argnums=(0, 1))(q, p)
    val, (gq, gp) = jax.value_and_grad(
        loss_fn("fused", jnp.bfloat16), argnums=(0, 1))(q, p)
    np.testing.assert_allclose(float(val), float(ref), rtol=2e-2)
    np.testing.assert_allclose(np.asarray(gq, np.float32),
                               np.asarray(gq_ref), atol=2e-2)
    np.testing.assert_allclose(np.asarray(gp, np.float32),
                               np.asarray(gp_ref), atol=2e-2)
    # masked columns get exactly zero gradient, bf16 or not
    np.testing.assert_array_equal(np.asarray(gp, np.float32)[9:], 0.0)


# ----------------------------------------------------------- adamw masters
def test_adamw_master_params_track_fp32_exactly():
    """keep_master_params: bf16 stored params + fp32 masters in the
    optimizer state. With identical (fp32) gradients the master trajectory
    is bit-identical to the all-fp32 run; the bf16 params are the rounded
    masters every step (rounding never compounds)."""
    # start from bf16-representable values so both runs share the same start
    p32 = {"w": jnp.linspace(-1.0, 1.0, 64).astype(jnp.bfloat16).astype(jnp.float32)}
    p16 = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), p32)
    g = {"w": jnp.sin(jnp.arange(64, dtype=jnp.float32))}
    tx32, tx16 = adamw(1e-2), adamw(1e-2, keep_master_params=True)
    s32, s16 = tx32.init(p32), tx16.init(p16)
    assert s16.master["w"].dtype == jnp.float32
    a, b = p32, p16
    for _ in range(10):
        u32, s32 = tx32.update(g, s32, a)
        a = apply_updates(a, u32)
        u16, s16 = tx16.update(g, s16, b)
        b = apply_updates(b, u16)
    assert b["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(a["w"]), np.asarray(s16.master["w"]))
    np.testing.assert_allclose(np.asarray(b["w"], np.float32),
                               np.asarray(a["w"]), atol=1e-2)


def test_adamw_without_masters_state_unchanged():
    """Default adamw keeps master=None — no extra optimizer-state memory."""
    p = {"w": jnp.ones((4,), jnp.float32)}
    tx = adamw(1e-3)
    s = tx.init(p)
    assert s.master is None
    _, s = tx.update({"w": jnp.ones((4,))}, s, p)
    assert s.master is None
