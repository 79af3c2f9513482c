"""The sharded serving path at a size the CPU holds, on four host devices
in a child process (a parent that has set up JAX cannot change its device
count): sound, the run is correct; with the merge between chips left out,
each chip answers from its own shard and the run is not correct."""

import json
import os
import subprocess
import sys

import pytest

from bench_tiny import ROOT

CHILD = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/tests/bench"]
import bench_tiny
from repro.retrieval.retriever import Retriever

out = {}
cell = bench_tiny.TinyCell("serve", precision="bf16_banks", chips=4, shards=4,
                          index_rows=250000)
_, res = bench_tiny.run(cell)
out["sound"] = {"correct": res["correct"], "checks": res["checks"]}
Retriever._merge_shards = lambda self, scores, ids, shard_index, ctx: (scores, ids)
_, res = bench_tiny.run(cell)
out["merge_left_out"] = {"correct": res["correct"], "checks": res["checks"]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", CHILD, str(ROOT)], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sharded_sound_run_is_correct(runs):
    assert runs["sound"]["correct"], runs["sound"]["checks"]


def test_merge_left_out_is_caught(runs):
    bad = runs["merge_left_out"]
    assert not bad["correct"] and bad["checks"]["rank_gap"]["value"] > 0.05, bad
