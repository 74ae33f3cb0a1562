"""The long-document serve cell (`benchmark/drivers/serve_long.py`) on a
FIXED set of requests, the first 12 of the seed's plan whatever the host's
speed: the program comes out correct, the float8 control and a program that
reads every visible key do not, through the harness's own comparison.  And
the configuration's published sizes and the reference's operation count at
them.  In-process, tiny, on the CPU.
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
from benchmark.drivers import serve_long  # noqa: E402
from benchmark.reference import minicpm_sala as ref  # noqa: E402

CELL = "sala_serve_longdoc16"
REQUESTS = 12


def _served(seed: int = 2147484001) -> tuple:
    """(run, the first `REQUESTS` requests of the plan, served)."""
    import mmlspark_tpu  # noqa: F401
    cell, config, traffic = harness.cell_files(CELL, rehearse=True)
    run = harness.Run(cell=cell, config=config, traffic=traffic, seed=seed,
                      seconds=0.0, trace=False, rehearse=True,
                      t_process=time.perf_counter(),
                      compiles=harness.CompileWatch())
    state = serve_long.setup(run)
    clients = state["clients"]
    deadline = time.perf_counter() + 300.0
    done = lambda: {r["index"] for r in clients.records}
    while not set(range(REQUESTS)) <= done():
        assert time.perf_counter() < deadline, "the clients stalled"
        time.sleep(0.05)
    assert clients.finish(90.0)
    records = sorted((r for r in clients.records if r["index"] < REQUESTS),
                     key=lambda r: r["index"])
    serve_long.stop(state)
    state.clear()
    assert len(records) == REQUESTS and all(r["ok"] for r in records)
    return run, records


def test_the_program_is_correct_and_the_float8_control_is_not():
    run, records = _served()
    limits = run.traffic["limits"]
    # every prompt of the rehearsal is past its `dense_len` of 16
    assert min(len(r["prompt"]) for r in records) >= 17
    program = serve_long._compare(run, records, control=False)
    assert harness.is_correct(0, {k: (program[k], limits[k])
                                  for k in program})
    control = serve_long._compare(run, records, control=True)
    verdicts = calibrate.judged({k + ".fp8": v for k, v in control.items()},
                                limits)
    assert verdicts == {"fp8": False}, (control, limits)
    assert control["served_gap"] > 10 * limits["served_gap"]
    assert control["served_gap_mean"] > 10 * limits["served_gap_mean"]


def test_a_program_without_selection_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    from mmlspark_tpu.ops import sparse_attention as sa

    def every_visible_block(scores, q_pos, cfg):
        own = (q_pos // cfg.block)[:, None, :, None]
        return jnp.broadcast_to(jnp.arange(scores.shape[-1]) <= own,
                                scores.shape)
    monkeypatch.setattr(sa, "read_blocks", every_visible_block)
    monkeypatch.setattr(sa, "capacity", lambda cfg, n_blocks: n_blocks)
    run, records = _served()
    limits = run.traffic["limits"]
    got = serve_long._compare(run, records, control=False)
    assert not harness.is_correct(0, {k: (got[k], limits[k]) for k in got})
    assert got["served_gap"] > 10 * limits["served_gap"]
    assert got["served_gap_mean"] > 10 * limits["served_gap_mean"]


@pytest.mark.parametrize("rehearse", [False, True])
def test_the_cell_has_a_limit_for_each_number_it_compares(rehearse):
    _, _, traffic = harness.cell_files(CELL, rehearse=rehearse)
    assert set(traffic["limits"]) == set(serve_long.NUMBERS)
    assert 0 < traffic["limits"]["served_gap_mean"] < traffic[
        "limits"]["served_gap"]


def test_the_mean_gap_is_over_every_served_position(monkeypatch):
    """Four of ten positions a tenth apart: the widest is a tenth, the mean
    four hundredths, whichever request they lie in."""
    from types import SimpleNamespace as Of
    gaps = iter([np.asarray([0.0, 0.1, 0.0, 0.1]),
                 np.asarray([0.1, 0.0, 0.0, 0.0, 0.0, 0.1])])
    monkeypatch.setattr(serve_long, "reference_of", lambda run: (
        Of(reach=lambda c: [], shapes_for=lambda c: {},
           spec_for=lambda c: None),
        Of(make_variables=lambda shapes, seed: {"params": {}})))
    monkeypatch.setattr(serve_long, "served_gaps",
                        lambda *a, **k: next(gaps))
    run = Of(config={"constructor": {}}, seed=0,
             traffic={"new_tokens": [1, 6], "prompt_len": [1, 4]})
    got = serve_long._compare(run, [{"prompt": [1]}, {"prompt": [1]}],
                              control=False)
    assert got["served_gap"] == pytest.approx(0.1)
    assert got["served_gap_mean"] == pytest.approx(0.04)
    assert got["uncompared_share"] == 0.0


def test_the_head_sees_the_served_positions_only():
    cell, config, traffic = harness.cell_files(CELL, rehearse=True)
    c = config["constructor"]
    from benchmark.reference import minicpm_sala_weights as weights
    params = harness.host_tree(
        weights.make_variables(ref.shapes_for(c), 5))["params"]
    row = np.random.default_rng(0).integers(
        0, c["vocab_size"], (1, 76)).astype(np.int32)
    spec = ref.spec_for(c, positions=16)
    whole = np.asarray(jax.jit(ref.forward, static_argnames=("spec",))(
        params, row, spec=spec)[0])
    part = np.asarray(serve_long.logits_at(ref, params, row, spec, 40, 12))
    assert part.shape == (1, 12, c["vocab_size"])
    assert np.abs(part[0] - whole[0, 40:52]).max() < 1e-5


def test_the_configuration_states_the_published_sizes():
    data = harness.read_json("benchmark", "configs",
                             "minicpm-sala-stage.json")
    c = data["constructor"]
    assert (data["hidden_size"], data["num_attention_heads"],
            data["num_key_value_heads"], data["head_dim"],
            data["intermediate_size"], data["vocab_size"],
            data["lightning_nh"], data["lightning_head_dim"],
            data["scale_emb"], data["scale_depth"], data["dim_model_base"],
            data["max_position_embeddings"]) == (
        4096, 32, 2, 128, 16384, 73448, 32, 128, 12, 1.4, 256, 524288)
    assert (c["d_model"], c["n_heads"], c["n_kv_heads"], c["mlp_width"],
            c["vocab_size"], c["tie_embeddings"]) == (
        4096, 32, 2, 16384, 73448, False)
    assert c["embed_scale"] == data["scale_emb"]
    assert c["residual_scale"] == pytest.approx(1.4 / 32 ** 0.5)
    assert c["logit_scale"] == data["dim_model_base"] / data["hidden_size"]
    assert data["reduced"] == ["num_hidden_layers"]
    assert data["published"] == {"num_hidden_layers": 32}
    assert len(data["mixer_types"]) == 32
    assert data["mixer_types"].count("minicpm4") == 8
    assert data["num_hidden_layers"] == len(c["layer_types"]) == 8
    assert c["layer_types"] == data["mixer_types"][9:17] \
        == data["mixer_types_held"]
    assert c["n_dense_layers"] == 8
    assert (c["sparse_block"], c["sparse_kernel"], c["sparse_stride"],
            c["sparse_window"], c["sparse_init_blocks"], c["sparse_topk"],
            c["sparse_dense_len"]) == (64, 32, 16, 2048, 1, 64, 8192)
    # the mix's longest prompt and answer fill a row's window exactly
    traffic = harness.read_json("benchmark", "traffic",
                                "closed16_longdoc.json")
    assert c["max_len"] == traffic["prompt_len"][1] + traffic[
        "new_tokens"][1] == 33280
    assert traffic["prompt_len"][0] > c["sparse_dense_len"]
    assert traffic["engine"]["cache_chunk"] % c["sparse_block"] == 0
    held = sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(ref.shapes_for(c)))
    assert held == pytest.approx(2820.6e6, rel=1e-4)


def test_the_reference_counts_required_operations_at_the_published_sizes():
    c = harness.read_json("benchmark", "configs",
                          "minicpm-sala-stage.json")["constructor"]
    d, w, v = 4096, 16384, 73448
    weights = (d * v + 8 * 3 * d * w + 6 * 5 * d * d
               + 2 * (3 * d * d + 2 * d * 256))
    assert weights == pytest.approx(2519.7e6, rel=1e-3)   # multiplied, a token
    assert ref.reach(c) == []
    # position 0: one key, no compressed key; six states updated and read
    assert ref.forward_flops(c, 0, 1) == (
        2 * weights + 6 * 4 * d * 128 + 2 * 4 * d)
    # at 32,767: 97 blocks less the own block's tail, 2,047 compressed keys
    assert int(ref.keys_read(c, np.asarray(32767))) == 97 * 64
    assert ref.forward_flops(c, 32767, 32768) == (
        2 * weights + 6 * 4 * d * 128
        + 2 * (4 * d * 97 * 64 + 2 * d * 2047))
    # up to `dense_len` a token reads all it sees
    assert int(ref.keys_read(c, np.asarray(8191))) == 8192
    # past it: the initial block, 31 whole local blocks, 64 by score, and
    # the own block up to the query
    assert int(ref.keys_read(c, np.asarray(8192))) == (1 + 31 + 64) * 64 + 1
    assert ref.forward_flops(c, 7, 7) == 0
