"""The looped-model serve cell (`ouro_serve_reason16`, through
`benchmark/drivers/serve_arch.py`) on a FIXED set of requests, the first 10
of the seed's plan whatever the host's speed: the program comes out correct;
the float8 control and two planted faults put in the program's place (one
pass fewer; pass t reading pass t-1's windows) do not, through the harness's
own comparison.  And the configuration's published sizes and the
reference's operation count at them.  In-process, tiny, on the CPU.
"""

import os
import sys
import time

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import calibrate  # noqa: E402
from benchmark import harness  # noqa: E402
from benchmark.drivers import serve_arch  # noqa: E402
from benchmark.reference import ouro as ref  # noqa: E402

CELL = "ouro_serve_reason16"
REQUESTS = 10


def _served(seed: int = 2147484001) -> tuple:
    """(run, the first `REQUESTS` requests of the plan, served)."""
    import mmlspark_tpu  # noqa: F401
    cell, config, traffic = harness.cell_files(CELL, rehearse=True)
    run = harness.Run(cell=cell, config=config, traffic=traffic, seed=seed,
                      seconds=0.0, trace=False, rehearse=True,
                      t_process=time.perf_counter(),
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
    return run, records


def _judged(run, got: dict) -> bool:
    limits = run.traffic["limits"]
    return harness.is_correct(0, {k: (got[k], limits[k]) for k in got})


def test_the_program_is_correct_and_the_float8_control_is_not():
    run, records = _served()
    limits = run.traffic["limits"]
    program = serve_arch._compare(run, records, control=False)
    assert _judged(run, program)
    # no router, no band: every served position is compared
    assert program["uncompared_share"] == 0.0 == limits["uncompared_share"]
    assert limits["tie_band"] == 0
    control = serve_arch._compare(run, records, control=True)
    verdicts = calibrate.judged({k + ".fp8": v for k, v in control.items()},
                                limits)
    assert verdicts == {"fp8": False}, (control, limits)
    assert control["served_gap"] > 10 * limits["served_gap"]


def _one_pass_fewer(mp, hybrid_lm):
    real = hybrid_lm.looped_stack

    class Fewer:
        def __init__(self, module):
            self._module = module
            self.n_passes = module.n_passes - 1

        def __getattr__(self, name):
            return getattr(self._module, name)
    mp.setattr(hybrid_lm, "looped_stack",
               lambda module, *a, **k: real(Fewer(module), *a, **k))


def _reads_the_pass_before(mp, hybrid_lm):
    import jax.numpy as jnp
    real = hybrid_lm._pass_heads
    mp.setattr(hybrid_lm, "_pass_heads", lambda cache, lane, n: real(
        cache, jnp.maximum(lane - n, 0), n))


@pytest.mark.parametrize("plant", [_one_pass_fewer, _reads_the_pass_before],
                         ids=["one_pass_fewer", "reads_the_pass_before"])
def test_a_planted_fault_in_the_programs_place_is_not_correct(plant,
                                                              monkeypatch):
    from mmlspark_tpu.models import hybrid_lm
    plant(monkeypatch, hybrid_lm)
    run, records = _served()
    got = serve_arch._compare(run, records, control=False)
    assert not _judged(run, got)
    assert got["served_gap"] > 10 * run.traffic["limits"]["served_gap"]


def test_the_configuration_states_the_published_sizes():
    data = harness.read_json("benchmark", "configs", "ouro-2.6b-stage.json")
    c = data["constructor"]
    assert (data["hidden_size"], data["num_attention_heads"],
            data["num_key_value_heads"], data["head_dim"],
            data["intermediate_size"], data["vocab_size"],
            data["total_ut_steps"], data["early_exit_threshold"],
            data["rms_norm_eps"], data["rope_theta"],
            data["max_position_embeddings"], data["tie_word_embeddings"],
            data["hidden_act"]) == (
        2048, 16, 16, 128, 5632, 49152, 4, 1, 1e-6, 1000000, 65536, False,
        "silu")
    assert (c["d_model"], c["n_heads"], c["n_kv_heads"], c["mlp_width"],
            c["vocab_size"], c["tie_embeddings"], c["n_passes"],
            c["exit_threshold"], c["norm_eps"], c["rope_theta"]) == (
        2048, 16, 16, 5632, 49152, False, 4, 1.0, 1e-6, 1e6)
    assert (c["sandwich_norm"], c["qk_norm"], c["exit_gate"]) == (
        True, False, True)
    assert c["d_model"] // c["n_heads"] == data["head_dim"]
    assert data["reduced"] == ["num_hidden_layers"]
    assert data["published"] == {"num_hidden_layers": 48}
    assert data["layer_types"] == ["full_attention"] * 48
    assert data["num_hidden_layers"] == len(c["layer_types"]) == len(
        data["layer_types_held"]) == c["n_dense_layers"]
    assert c["layer_types"] == data["layer_types_held"] == data[
        "layer_types"][:len(c["layer_types"])]
    assert {"sandwich_norm", "norm_between_passes", "exit_gate",
            "one_window_a_pass", "max_len", "weights"} <= set(data["assumed"])
    # a row's window holds the mix's longest prompt and answer
    traffic = harness.read_json("benchmark", "traffic",
                                "closed16_reason.json")
    assert traffic["prompt_len"][1] + traffic["new_tokens"][1] <= c["max_len"]
    assert traffic["engine"]["max_new_tokens"] == traffic["new_tokens"][1]
    assert traffic["new_tokens"][0] >= traffic["prompt_len"][0] - 1
    # 12 of 48 layers held (or 8, by ISSUE 34's rule on the peak)
    layers = len(c["layer_types"])
    assert layers in (8, 12)
    sizes = lambda con: sum(int(np.prod(leaf.shape)) for leaf in
                            jax.tree_util.tree_leaves(ref.shapes_for(con)))
    a_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert a_layer == pytest.approx(51.39e6, rel=1e-4)
    held = sizes(c)
    assert held == layers * a_layer + 2 * 49152 * 2048 + 2048 + 2049
    if layers == 12:
        assert held == pytest.approx(818.0e6, rel=1e-4)
    whole = sizes(dict(c, layer_types=data["layer_types"]))
    assert whole == pytest.approx(2668e6, rel=1e-4)
    # a token's state: a K and a V window for every (pass, layer)
    assert layers * 4 * 2 * 2048 * 2 == {12: 393216, 8: 262144}[layers]


def test_the_reference_counts_required_operations_at_the_published_sizes():
    c = harness.read_json("benchmark", "configs",
                          "ouro-2.6b-stage.json")["constructor"]
    d, w, v, layers = 2048, 5632, 49152, len(c["layer_types"])
    a_pass = layers * (4 * d * d + 3 * d * w) + d      # and the gate
    assert ref.reach(c) == []
    # position 0: one key a (pass, layer); the head once
    assert ref.forward_flops(c, 0, 1) == (
        2 * (4 * a_pass + d * v) + 4 * 4 * layers * d)
    # position 1,023: 1,024 visible keys in each of 4 x `layers` windows
    assert ref.forward_flops(c, 1023, 1024) == (
        2 * (4 * a_pass + d * v) + 4 * 4 * layers * d * 1024)
    assert ref.forward_flops(c, 0, 300) == sum(
        ref.forward_flops(c, t, t + 1) for t in range(300))
    assert ref.forward_flops(c, 7, 7) == 0
    # one pass fewer is a quarter less of everything but the head
    three = dict(c, n_passes=3)
    assert (ref.forward_flops(c, 0, 64) - ref.forward_flops(three, 0, 64)
            == 2 * 64 * a_pass + 4 * layers * d * (64 * 65 // 2))
