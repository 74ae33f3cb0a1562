"""The float8 control of the architecture-behind-an-interface serve cell
(`benchmark/drivers/serve_arch.py`) on a FIXED set of requests: the first
16 of the seed's plan, whatever the host's speed (the window-based case in
test_bench_correct.py compares whichever requests half a second completed,
and PERF.md section 7 records why that is unsteady).  Also the near-tie
mask on hand-made margins.  In-process, tiny, on the CPU.
"""

import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import calibrate  # noqa: E402
from benchmark import harness  # noqa: E402
from benchmark.drivers import serve_arch  # noqa: E402

CELL = "lfm2moe_serve_closed16"
REQUESTS = 16


def test_the_float8_control_is_not_correct_on_fixed_requests():
    import mmlspark_tpu  # noqa: F401
    cell, config, traffic = harness.cell_files(CELL, rehearse=True)
    run = harness.Run(cell=cell, config=config, traffic=traffic,
                      seed=2147484001, seconds=0.0, trace=False,
                      rehearse=True, t_process=time.perf_counter(),
                      compiles=harness.CompileWatch())
    state = serve_arch.setup(run)
    clients = state["clients"]
    deadline = time.perf_counter() + 300.0
    done = lambda: {r["index"] for r in clients.records}
    while not set(range(REQUESTS)) <= done():
        assert time.perf_counter() < deadline, "the clients stalled"
        time.sleep(0.05)
    assert clients.finish(90.0)
    records = sorted((r for r in clients.records if r["index"] < REQUESTS),
                     key=lambda r: r["index"])
    serve_arch.stop(state)
    state.clear()
    assert len(records) == REQUESTS and all(r["ok"] for r in records)
    limits = traffic["limits"]
    program = serve_arch._compare(run, records, control=False)
    assert harness.is_correct(0, {k: (program[k], limits[k])
                                  for k in program})
    control = serve_arch._compare(run, records, control=True)
    verdicts = calibrate.judged({k + ".fp8": v for k, v in control.items()},
                                limits)
    assert verdicts == {"fp8": False}, (control, limits)
    # by the gap, not by the share left out
    assert control["served_gap"] > 10 * limits["served_gap"]
    assert control["uncompared_share"] <= limits["uncompared_share"]


def test_a_near_tie_rules_out_the_positions_it_reaches():
    margins = np.ones((4, 12))
    margins[0, 2] = 0.001     # reach 6: positions 2..8
    margins[3, 10] = 0.001    # reach 0: position 10
    mask = serve_arch.compared_mask(margins, [6, 4, 2, 0], 0.01, 0, 12)
    assert mask.tolist() == [True, True] + [False] * 7 + [True, False, True]
    # a layer under an attention layer reaches every later position
    mask = serve_arch.compared_mask(margins, [None, 4, 2, 0], 0.01, 4, 6)
    assert not mask.any()
    # a band of 0 leaves nothing out, and only served positions count
    assert serve_arch.compared_mask(margins, [6, 4, 2, 0], 0.0, 0, 12).all()
    assert serve_arch.compared_mask(margins, [6, 4, 2, 0], 0.01, 9, 3
                                    ).tolist() == [True, False, True]


def test_the_reference_states_reach_and_active_operations():
    from benchmark.reference import lfm2_moe as ref
    c = harness.read_json("benchmark", "configs",
                          "lfm2-8b-a1b-stage.json")["constructor"]
    # attention sits below every expert layer but its own
    assert ref.reach(c) == [6, 4, 2, 0]
    whole = dict(c, layer_types=["conv", "conv", "full_attention", "conv"],
                 n_dense_layers=1)
    assert ref.reach(whole) == [None, 2, 0]
    d, v = 2048, 65536
    conv, attn = 4 * d * d + 3 * d, 2 * d * d + 2 * d * 512
    experts = d * 32 + 4 * 3 * d * 1792
    weights = d * v + 4 * conv + attn + 3 * d * 7168 + 4 * experts
    assert weights == pytest.approx(432e6, rel=0.01)    # active, a token
    assert ref.forward_flops(c, 0, 1) == 2 * weights + 4 * d
    assert ref.forward_flops(c, 0, 1024) == (
        1024 * 2 * weights + 4 * d * (1024 * 1025 // 2))
    assert ref.forward_flops(c, 7, 7) == 0
    held = sum(int(np.prod(leaf.shape)) for leaf in __import__(
        "jax").tree_util.tree_leaves(ref.shapes_for(c)))
    assert held == pytest.approx(1665.4e6, rel=1e-4)


def test_the_new_configuration_states_the_published_sizes():
    data = harness.read_json("benchmark", "configs",
                             "lfm2-8b-a1b-stage.json")
    c = data["constructor"]
    assert (data["hidden_size"], data["num_attention_heads"],
            data["num_key_value_heads"], data["intermediate_size"],
            data["num_experts"], data["moe_intermediate_size"],
            data["num_experts_per_tok"], data["conv_L_cache"],
            data["vocab_size"]) == (2048, 32, 8, 7168, 32, 1792, 4, 3,
                                    65536)
    assert (c["d_model"], c["n_heads"], c["n_kv_heads"], c["mlp_width"],
            c["n_experts"], c["expert_width"], c["experts_per_token"],
            c["conv_kernel"], c["vocab_size"]) == (2048, 32, 8, 7168, 32,
                                                   1792, 4, 3, 65536)
    assert sorted(data["reduced"]) == ["num_dense_layers",
                                       "num_hidden_layers"]
    assert data["num_hidden_layers"] == len(c["layer_types"]) == 5
    assert c["layer_types"] == data["layer_types"][1:6]
    assert data["num_dense_layers"] == c["n_dense_layers"] == 1
