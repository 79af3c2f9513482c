"""The control of each kind of cell at the configuration's own widths
(bert-base-uncased) with a batch and an index the CPU holds: the plain
reference put in the program's place, computed one precision below what
the configuration states (fp8 matrix-product inputs where the program
computes in bf16), comes out not correct under the cell's limits."""

import json

import pytest

import bench_tiny
from bench.harness import compare, reference

MODEL = json.loads((bench_tiny.ROOT / "bench/configs/dpr-bert-base-contaccum.json").read_text())["model"]


def full_width(kind, **workload):
    cell = bench_tiny.TinyCell(kind, precision="bf16_banks", **workload)
    cell.config["model"] = MODEL
    cell.config["program"]["arch"] = "dpr-bert-base"
    return cell


@pytest.fixture(scope="module")
def train_batches():
    # the program's own feed at full width; the run itself is not needed
    from bench.drivers import train

    cell = full_width("train", checked_steps=1)
    run = train.program_args(cell.config, cell.workload, 2**31 + 3)
    from repro.launch import train as program

    built = program.build(run)
    b = built.trainer.next_batch(0)
    import numpy as np

    return cell, [tuple(np.asarray(x) for x in (b.query, b.passage_pos, b.passage_hard))]


def test_train_control_is_not_correct(train_batches):
    from bench.drivers import train

    cell, batches = train_batches
    r = type("R", (), {"config": cell.config, "workload": cell.workload, "seed": 2**31 + 3})()
    ref = train.reference_readings(r, batches)
    ctl = train.reference_readings(r, batches, cast=reference.fp8)
    checks = compare.with_limits(compare.train_readings(ctl, ref), bench_tiny.limits("train-paper"))
    assert not compare.all_within(checks), checks


def test_serve_control_is_not_correct():
    cell = full_width("serve", index_rows=5000, q_len=8)
    r, res = bench_tiny.run(cell)
    assert res["correct"], res["checks"]
    ex, drv = res["extra"], r.cell.driver()
    low = drv.reference_search(r, ex["tokens"], ex["served_ids"], ex["rows"], ex["block"],
                               cast=reference.fp8)
    scored = drv.reference_search(r, ex["tokens"], low["top_i"], ex["rows"], ex["block"])
    ref = dict(ex["reference"], served_ref_s=scored["served_ref_s"])
    checks = compare.with_limits(compare.serve_readings(low["top_i"], low["top_s"], ref),
                                 bench_tiny.limits("serve-msmarco"))
    assert not compare.all_within(checks), checks
