"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler is installed with JAX, so it can compile for a ``v5e:2x2``
topology that is described, not attached. These tests compile the main
path's Pallas kernels, the dense search and the dpr-bert-base ContAccum
step at real widths and check what the chip's compiler would refuse, or
how it lowers the search's sorts: a kernel that does not
lower (it must appear as ``tpu_custom_call``, not an XLA fallback) and a
step that does not fit one chip's 16 GB. Nothing runs, so nothing here is
a result or a time.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under several test workers the
others must still collect the same tests.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.methods import init_state
from repro.core.types import RetrievalBatch
from repro.kernels.fused_infonce.ops import fused_infonce_stats
from repro.kernels.fused_topk.ops import fused_topk_scores
from repro.launch import train
from repro.retrieval import DenseSearchBackend

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A TPU compile written to the persistent cache cannot be read back
    without a chip: keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_count(compiled):
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize(
    "m,n,dtype",
    [
        (128, 2176, jnp.bfloat16),   # ContAccum chunk: 8 local rows x K=16 vs in-batch + bank columns
        (8, 2056, jnp.float32),      # one local chunk against a 2048-row bank
    ],
)
def test_fused_infonce_compiles_for_v5e(one_chip, m, n, dtype):
    d = 768
    args = (
        _shape(one_chip, (m, d), dtype),
        _shape(one_chip, (n, d), dtype),
        _shape(one_chip, (m,), jnp.int32),
        _shape(one_chip, (n,), jnp.bool_),
    )

    def loss(q, p, labels, valid):
        lse, pos, _ = fused_infonce_stats(q, p, labels, valid, 1.0, 128, 128, False)
        return jnp.mean(lse - pos)

    fwd = jax.jit(loss).lower(*args).compile()
    assert _kernel_count(fwd) >= 1
    bwd = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*args).compile()
    # forward (residuals) + dQ + dP kernels
    assert _kernel_count(bwd) >= 3


def test_fused_topk_compiles_for_v5e(one_chip):
    q = _shape(one_chip, (32, 768), jnp.bfloat16)
    index = _shape(one_chip, (65536, 768), jnp.bfloat16)
    valid = _shape(one_chip, (65536,), jnp.bool_)
    compiled = jax.jit(
        lambda q_, p_, v_: fused_topk_scores(q_, p_, 100, col_valid=v_, interpret=False)
    ).lower(q, index, valid).compile()
    assert _kernel_count(compiled) >= 1
    scores, ids = compiled.out_info
    assert scores.shape == ids.shape == (32, 100)


@pytest.mark.parametrize("k", [100, 512])
def test_dense_search_two_stage_merge_compiles_for_v5e(one_chip, k):
    """The serving cells' search shape (Q 32, blocks of 65,536 rows, k 100),
    and k 512, take the two-stage block merge: the chip's compiler sorts no
    operand as wide as a block, and every sort it emits is stable, so ties
    still break toward the lowest id. (One ``top_k`` over a whole block at
    k 512 compiles to sorts that compare the score alone and are not
    stable.)"""
    from test_retrieval import _sort_widths

    be = DenseSearchBackend()
    q = _shape(one_chip, (32, 768), jnp.bfloat16)
    index = _shape(one_chip, (2 * be.block, 768), jnp.bfloat16)
    valid = _shape(one_chip, (2 * be.block,), jnp.bool_)
    compiled = jax.jit(
        lambda q_, p_, v_: be.topk(q_, p_, k, col_valid=v_)
    ).lower(q, index, valid).compile()
    text = compiled.as_text()
    widths = _sort_widths(text)
    assert widths and max(widths) < be.block, widths
    sorts = [line for line in text.splitlines() if " sort(" in line]
    assert sorts and all("is_stable=true" in line for line in sorts), sorts
    scores, ids = compiled.out_info
    assert scores.shape == ids.shape == (32, k)


@pytest.mark.parametrize("loss_impl", ["dense", "fused"])
def test_dpr_bert_base_contaccum_step_fits_one_v5e(one_chip, loss_impl, monkeypatch):
    """The paper's geometry through the training driver's own step builder:
    bert-base towers, N_total=128, N_local=8 (K=16), N_mem=2048, q_len 32,
    p_len 256, one hard negative, bf16 compute and banks."""
    # the loss backend picks the kernel mode from the default backend, which
    # is the CPU here: steer it to the compiled kernel the chip would run
    import repro.kernels.fused_infonce.ops as infonce_ops

    monkeypatch.setattr(infonce_ops, "resolve_interpret",
                        lambda interpret: bool(interpret))
    args = train.parse_args([
        "--arch", "dpr-bert-base", "--method", "contaccum",
        "--total-batch", "128", "--local-batch", "8", "--bank", "2048",
        "--q-len", "32", "--p-len", "256", "--precision", "bf16_banks",
        "--loss-impl", loss_impl,
    ])
    ts = train.build_step(args)
    assert ts.cfg.accumulation_steps == 16
    state = jax.eval_shape(
        lambda: init_state(jax.random.PRNGKey(0), ts.enc, ts.tx, ts.cfg)
    )
    state = jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype), state)
    b = args.total_batch
    batch = RetrievalBatch(
        query=_shape(one_chip, (b, args.q_len), jnp.int32),
        passage_pos=_shape(one_chip, (b, args.p_len), jnp.int32),
        passage_hard=_shape(one_chip, (b, 1, args.p_len), jnp.int32),
    )
    compiled = ts.update.lower(state, batch).compile()
    mem = compiled.memory_analysis()
    resident = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    )
    assert resident < V5E_HBM_BYTES, resident
    if loss_impl == "fused":
        assert _kernel_count(compiled) >= 3


def test_moonlight_contaccum_step_fits_one_v5e(one_chip):
    """The train-moonlight-ep8 cell's update through the training driver's
    own step builder: one shared Moonlight-16B-A3B backbone at one chip's
    share (the dense layer and 4 expert layers, 8 of 64 experts held, 20,480
    ids), N_total 128, N_local 32 (K 4), N_mem 2048, q_len 32, p_len 256,
    one hard negative, bf16 compute and banks. The grouped expert products
    lower to the chip's ragged-dot kernels, and the whole update, with its
    fp32 masters, Adam moments and gradient accumulator, fits one chip."""
    args = train.parse_args([
        "--arch", "moonlight-16b-a3b-ep8", "--method", "contaccum",
        "--total-batch", "128", "--local-batch", "32", "--bank", "2048",
        "--q-len", "32", "--p-len", "256", "--precision", "bf16_banks",
    ])
    ts = train.build_step(args)
    assert ts.cfg.accumulation_steps == 4 and ts.enc.shared
    state = jax.eval_shape(
        lambda: init_state(jax.random.PRNGKey(0), ts.enc, ts.tx, ts.cfg)
    )
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    assert abs(n_params - 526.5e6) < 0.1e6, n_params
    state = jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype), state)
    b = args.total_batch
    batch = RetrievalBatch(
        query=_shape(one_chip, (b, args.q_len), jnp.int32),
        passage_pos=_shape(one_chip, (b, args.p_len), jnp.int32),
        passage_hard=_shape(one_chip, (b, 1, args.p_len), jnp.int32),
    )
    compiled = ts.update.lower(state, batch).compile()
    assert "ragged-dot" in compiled.as_text()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 10e9 < peak < V5E_HBM_BYTES, peak
