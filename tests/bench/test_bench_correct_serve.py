"""A serving run at a size the CPU holds, with the chip check skipped:
sound, it is correct; with an answer altered where it is produced, or the
top-1 id served at every rank, it is not. The control is in test_bench_control.py."""

import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny


@pytest.fixture(scope="module")
def sound():
    return bench_tiny.run(bench_tiny.TinyCell("serve", precision="bf16_banks"))


def test_sound_run_is_correct(sound):
    _, res = sound
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 40
    assert res["end_to_end"]["serve_p95_ms"] > 0


def test_answer_altered_is_caught(monkeypatch):
    from repro.retrieval.search import DenseSearchBackend

    topk = DenseSearchBackend.topk

    def altered(self, q_reps, index, k, *, col_valid=None):
        scores, ids = topk(self, q_reps, index, k, col_valid=col_valid)
        return scores, ids.at[:, 0].set((ids[:, 0] + 1) % index.shape[0])

    monkeypatch.setattr(DenseSearchBackend, "topk", altered)
    _, res = bench_tiny.run(bench_tiny.TinyCell("serve", precision="bf16_banks"))
    assert not res["correct"] and res["checks"]["rank_gap"]["value"] > 0.5


def test_top1_served_at_every_rank_is_caught(monkeypatch):
    from repro.retrieval.search import DenseSearchBackend

    topk = DenseSearchBackend.topk

    def repeated(self, q_reps, index, k, *, col_valid=None):
        scores, ids = topk(self, q_reps, index, k, col_valid=col_valid)
        return (jnp.broadcast_to(scores[:, :1], scores.shape),
                jnp.broadcast_to(ids[:, :1], ids.shape))

    monkeypatch.setattr(DenseSearchBackend, "topk", repeated)
    _, res = bench_tiny.run(bench_tiny.TinyCell("serve", precision="bf16_banks"))
    checks = res["checks"]
    assert not res["correct"]
    assert checks["repeated_ids"]["value"] == 16 * 9


def test_repeats_counts_ids_served_twice_in_a_list():
    from bench.harness.compare import repeats

    assert repeats(np.array([[3, 1, 2], [5, 6, -1]])) == 0
    assert repeats(np.array([[3, 3, 3], [5, -1, -1]])) == 2
    assert repeats(np.array([[1, 2, 1], [4, 4, 7]])) == 2


def test_reference_top_k_is_exact(sound):
    r, res = sound
    ex = res["extra"]
    ref = ex["reference"]
    assert np.all(np.diff(ref["top_s"], axis=1) <= 0)
    # its own ids, rescored, give back its scores
    again = r.cell.driver().reference_search(r, ex["tokens"], ref["top_i"], ex["rows"], ex["block"])
    assert np.allclose(again["served_ref_s"], ref["top_s"])


@pytest.mark.parametrize("trace", [False, True])
def test_result_line_has_the_contract_keys(trace, capsys):
    import json

    from bench.harness import session

    r, res = bench_tiny.run(bench_tiny.TinyCell("serve", precision="bf16_banks"), trace=trace)
    session.report(r, res)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[:5] == keys and list(line)[-1] == "checks"
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert err.strip().splitlines()[-1].startswith("check repeated_ids")
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"]) and "breakdown" in line
        assert set(line["metrics"]) <= {m["name"] for m in r.cell.per_layer}
    else:
        assert set(line["metrics"]) == {"serve_p95_ms", "setup_s"}
