"""`decode_blocks_read_share.serve` (PR 37): the share of a dense grid's
K/V blocks that the serving segments' fused reads list, from the program's
own counters (`decode_blocks_read`, `decode_blocks_dense`) through
`counters:ratio`.  Synthetic runs only: none of this loads the TPU
library."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.reduce import counters as C  # noqa: E402

NAME = "decode_blocks_read_share.serve"
SERVE = "cgpt13b_serve_closed16"


def _run(counters):
    class Run:
        obs = {"t0": 0.0, "t1": 50.0, "counters": counters}
    return Run


def _reduce(counters):
    spec = harness.layer_metric_files()[NAME]
    return harness.reduce_function(spec["reducer"])(
        _run(counters), None, None, **spec["args"])


def test_the_file_reads_the_two_block_counters():
    spec = harness.layer_metric_files()[NAME]
    assert harness.reduce_function(spec["reducer"]) is C.ratio
    assert spec["workloads"] == [SERVE]
    assert (spec["layer"], spec["moves"], spec["better"], spec["unit"]) == (
        "kernels", "serve_tokens_per_s", "lower", "%")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == NAME)
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert entry[key] == spec[key], key


def test_the_share_of_listed_blocks():
    # 6 live rows reading 7 of 9 blocks and 2 released rows reading 2, for
    # 8 steps at window 1,152
    assert _reduce({"decode_blocks_read": (6 * 7 + 2 * 2) * 8,
                    "decode_blocks_dense": 8 * 9 * 8}) == pytest.approx(
        100.0 * 46 / 72)
    assert _reduce({"decode_blocks_read": 576,
                    "decode_blocks_dense": 576}) == pytest.approx(100.0)


def test_nothing_to_read_where_the_program_counts_no_blocks():
    # the parent commit has neither counter; a model whose steps read
    # their windows through XLA counts 0 of 0
    assert _reduce({"decode_keys_read": 10}) is None
    assert _reduce({"decode_blocks_read": 0,
                    "decode_blocks_dense": 0}) is None
