"""Cross-device (shard_map) contrastive semantics == single-device semantics.

Runs in a subprocess with 8 host platform devices so the main test process
keeps the default 1-device view (per the dry-run isolation rule).
"""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    sys.path.insert(0, "tests")
    from helpers import make_mlp_encoder, make_batch
    from repro.core import (
        ContrastiveConfig, RetrievalBatch, init_state, make_update_fn,
    )
    from repro.optim import chain, clip_by_global_norm, sgd

    assert jax.device_count() == 8, jax.device_count()
    D = 8
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("pod", "data"))

    enc = make_mlp_encoder()
    B = 32

    def to_global_chunk_order(batch, k):
        '''Distributed accumulation chunks are per-device-local microbatches:
        global chunk j == union over devices of their j-th local chunk. The
        equivalent single-device batch is the (D, K, lk) -> (K, D, lk)
        transpose.'''
        if k == 1:
            return batch

        def perm(x):
            lk = x.shape[0] // (D * k)
            y = x.reshape((D, k, lk) + x.shape[1:])
            y = jnp.swapaxes(y, 0, 1)
            return y.reshape((x.shape[0],) + x.shape[1:])

        return RetrievalBatch(
            query=perm(batch.query),
            passage_pos=perm(batch.passage_pos),
            passage_hard=None,
        )

    def run(method, dp_axis, k=1, bank=0, steps=3):
        cfg = ContrastiveConfig(
            method=method, accumulation_steps=k, bank_size=bank, dp_axis=dp_axis
        )
        tx = chain(clip_by_global_norm(2.0), sgd(0.05))
        state = init_state(jax.random.PRNGKey(0), enc, tx, cfg)
        update = make_update_fn(enc, tx, cfg)
        if dp_axis is not None:
            batch_spec = RetrievalBatch(
                query=P(("pod", "data")),
                passage_pos=P(("pod", "data")),
                passage_hard=None,
            )
            update = jax.shard_map(
                update,
                mesh=mesh,
                in_specs=(P(), batch_spec),
                out_specs=(P(), P()),
                check_vma=False,
            )
        update = jax.jit(update)
        losses = []
        for i in range(steps):
            batch = make_batch(jax.random.PRNGKey(100 + i), B)
            if dp_axis is None:
                batch = to_global_chunk_order(batch, k)
            state, m = update(state, batch)
            losses.append(float(m.loss))
        return state, losses

    # bank sizes for the full-batch (rep_cache) compositions are kept larger
    # than steps*B so FIFO eviction order (which differs between the
    # device-major and chunk-major global orders) cannot enter the math
    for method, kw in [
        ("dpr", {}),
        ("grad_accum", dict(k=2)),
        ("grad_cache", dict(k=2)),
        ("contaccum", dict(k=2, bank=16)),
        ("contcache", dict(k=2, bank=128)),
        ("prebatch_cache", dict(k=2, bank=128)),
    ]:
        s1, l1 = run(method, None, **kw)
        s8, l8 = run(method, ("pod", "data"), **kw)
        np.testing.assert_allclose(l1, l8, rtol=2e-4, err_msg=method)
        for a, b in zip(
            jax.tree_util.tree_leaves(s1.params), jax.tree_util.tree_leaves(s8.params)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-6, err_msg=method
            )
        print(f"OK {method}: dist == single-device, losses {l1}")
    print("ALL-OK")
    """
)


@pytest.mark.slow
def test_cross_device_negatives_match_single_device():
    _run_subprocess(SCRIPT)


SHARDED_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    sys.path.insert(0, "tests")
    from helpers import make_mlp_encoder, make_batch
    from repro.core import (
        ContrastiveConfig, RetrievalBatch, init_state, make_update_fn,
    )
    from repro.distribution.sharding import contrastive_state_spec
    from repro.optim import chain, clip_by_global_norm, sgd

    assert jax.device_count() == 8, jax.device_count()
    D = 8
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("pod", "data"))
    DP = ("pod", "data")

    enc = make_mlp_encoder()
    B = 32

    def to_global_chunk_order(batch, k):
        if k == 1:
            return batch

        def perm(x):
            lk = x.shape[0] // (D * k)
            y = x.reshape((D, k, lk) + x.shape[1:])
            y = jnp.swapaxes(y, 0, 1)
            return y.reshape((x.shape[0],) + x.shape[1:])

        return RetrievalBatch(
            query=perm(batch.query),
            passage_pos=perm(batch.passage_pos),
            passage_hard=None,
        )

    def run(method, distributed, k, bank, loss_impl, shard_banks, steps=3):
        cfg = ContrastiveConfig(
            method=method, accumulation_steps=k, bank_size=bank,
            loss_impl=loss_impl,
            dp_axis=DP if distributed else None,
            shard_banks=shard_banks and distributed,
        )
        tx = chain(clip_by_global_norm(2.0), sgd(0.05))
        state = init_state(jax.random.PRNGKey(0), enc, tx, cfg)
        update = make_update_fn(enc, tx, cfg)
        if distributed:
            state_spec = contrastive_state_spec(DP, cfg.shard_banks)
            batch_spec = RetrievalBatch(
                query=P(DP), passage_pos=P(DP), passage_hard=None
            )
            update = jax.shard_map(
                update,
                mesh=mesh,
                in_specs=(state_spec, batch_spec),
                out_specs=(state_spec, P()),
                check_vma=False,
            )
        update = jax.jit(update)
        losses, fills = [], []
        for i in range(steps):
            batch = make_batch(jax.random.PRNGKey(100 + i), B)
            if not distributed:
                batch = to_global_chunk_order(batch, k)
            state, m = update(state, batch)
            losses.append(float(m.loss))
            fills.append((float(m.bank_fill_q), float(m.bank_fill_p)))
        return state, losses, fills

    # bank sizes chosen so the banks WRAP mid-trajectory for contaccum
    # (16 < 3 steps x 32 rows) and stay eviction-order-safe for the
    # full-batch contcache (128 > 3 x 32), on both loss backends
    for method, k, bank in [("contaccum", 2, 16), ("contcache", 2, 128)]:
        for loss_impl in ("dense", "fused"):
            tag = f"{method}/{loss_impl}/sharded"
            s1, l1, f1 = run(method, False, k, bank, loss_impl, False)
            s8, l8, f8 = run(method, True, k, bank, loss_impl, True)
            np.testing.assert_allclose(l1, l8, rtol=2e-4, err_msg=tag)
            np.testing.assert_allclose(f1, f8, rtol=0, err_msg=tag)
            for a, b in zip(
                jax.tree_util.tree_leaves(s1.params),
                jax.tree_util.tree_leaves(s8.params),
            ):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-6,
                    err_msg=tag,
                )
            # the gathered shard-major bank union must equal the replicated
            # single-device ring: slot-exact for the scan path (chunk order
            # is aligned by to_global_chunk_order); as a row-set for the
            # rep_cache path, whose device-major merge is a permutation of
            # the single-device chunk-major push order (the loss is
            # order-invariant given per-slot label alignment)
            for bank_name in ("bank_q", "bank_p"):
                b1, b8 = getattr(s1, bank_name), getattr(s8, bank_name)
                assert int(b1.head) == int(b8.head), tag
                assert int(b1.valid.sum()) == int(b8.valid.sum()), tag
                r1 = np.asarray(b1.buf)[np.asarray(b1.valid)]
                r8 = np.asarray(b8.buf)[np.asarray(b8.valid)]
                if method == "contaccum":
                    np.testing.assert_array_equal(
                        np.asarray(b1.valid), np.asarray(b8.valid), err_msg=tag
                    )
                    np.testing.assert_array_equal(
                        np.asarray(b1.age), np.asarray(b8.age), err_msg=tag
                    )
                else:
                    order1 = np.lexsort(r1.T)
                    order8 = np.lexsort(r8.T)
                    r1, r8 = r1[order1], r8[order8]
                np.testing.assert_allclose(r1, r8, rtol=2e-4, atol=2e-6,
                                           err_msg=tag)
            print(f"OK {tag}: dist == single-device, losses {l1}")
    print("ALL-OK")
    """
)


@pytest.mark.slow
def test_sharded_banks_match_single_device():
    """shard_banks=True: per-device capacity/D bank shards + gathered-column
    loss reproduce the single-device replicated-bank trajectory (params,
    banks, fills, losses) for contaccum and contcache on both backends."""
    _run_subprocess(SHARDED_SCRIPT)


def _run_subprocess(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src:tests"
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "ALL-OK" in proc.stdout
