"""The looped model of `models/hybrid_lm.py` (`n_passes` > 1: one stack of
full-attention layers applied four times over the same weights, a norm
before and after each sub-layer, the final norm and an exit gate closing
every pass, a K/V window for every (pass, layer)) against its plain
reference (`benchmark/reference/ouro.py`), tiny and on the CPU, in float32:
the flax module's forward and its gates at all 48 published layers and at
the cut of 12, prefill (whole and chunked) and decode through
`DecodeEngine`, late joins and window growth through `ServingEngine` with
the device's counts, and a row's independence of its neighbours.
`test_ouro_faults.py` plants the faults these comparisons must catch and
holds prefix reuse and handoff against the same reference.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import ouro as ref  # noqa: E402
from benchmark.reference import ouro_weights  # noqa: E402
from mmlspark_tpu.models import DecodeEngine, ModelBundle, hybrid_lm  # noqa: E402
from mmlspark_tpu.models.definitions import build_model  # noqa: E402
from mmlspark_tpu.models.generate import (_grow_state,  # noqa: E402
                                          deserialize_cache_row,
                                          serialize_cache_row)
from mmlspark_tpu.serve import ServeConfig, ServingEngine  # noqa: E402

A = "full_attention"
# float32 on the CPU: the program and the reference differ by the order of
# their sums only (products at default precision against `highest` are the
# same float32 on the CPU).  Logits are of order 1.  Rounding grows with the
# layer applications a logit has behind it: against the reference in float64
# the program and the float32 reference BOTH lie 0.00008 off at the cut (4 x
# 12) and 0.0014-0.0019 off at the published depth (4 x 48), and 0.00005 and
# 0.0017 from each other; a planted fault moves a logit by a tenth or more.
LOGIT_TOL = 2e-4
LOGIT_TOL_WHOLE = 5e-3
# a served token must be the reference's best, or tie with it to rounding
GAP_TOL = 1e-4
# a gate is a sigmoid of a logit of order 0.5: float32 steps
GATE_TOL = 2e-5
VOCAB = 97
PASSES = 4


def constructor(n_layers, **over):
    c = dict(vocab_size=VOCAB, d_model=32, n_heads=4, n_kv_heads=4,
             layer_types=[A] * n_layers, n_dense_layers=n_layers,
             mlp_width=48, rope_theta=1e6, norm_eps=1e-6,
             tie_embeddings=False, n_passes=PASSES, sandwich_norm=True,
             qk_norm=False, exit_gate=True, exit_threshold=1.0, max_len=128,
             dtype="float32")
    return dict(c, **over)


# 48 layers' worth of shapes scaled down, the benchmark's cut of 12, and a
# model the per-request tests can afford
WHOLE, CUT, SMALL = constructor(48), constructor(12), constructor(3)


def model(c, seed=7):
    module = build_model("HybridLM", dict(c))
    variables = ouro_weights.make_variables(ref.shapes_for(c), seed)
    return module, variables


def reference(c, variables, tokens, mode="f32"):
    """(logits, gates (R, B, S)) of the reference.  Not jitted: its loop
    is written out, 4 x 48 layer applications at the published depth, and
    every row length would compile it anew."""
    logits, _, gates = ref.forward(variables["params"], jnp.asarray(tokens),
                                   spec=ref.spec_for(c), mode=mode,
                                   gates=True)
    return np.asarray(logits), np.asarray(gates)


def program_gates(module, variables, tokens):
    """The gates the program's own pass loop computes, (R, B, S)."""
    params = variables["params"]
    tokens = jnp.asarray(tokens)
    b, s = tokens.shape
    x = params["embed"][tokens].astype(module.dtype)
    return np.asarray(hybrid_lm.looped_stack(
        module, params, x, jnp.broadcast_to(jnp.arange(s), (b, s)))[2])


def padded(rows, bucket):
    out = np.zeros((len(rows), bucket), np.int32)
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
    return out, np.asarray([len(r) for r in rows], np.int32)


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, n).astype(np.int32) for n in lengths]


def served_gap(c, variables, prompt, tokens):
    """The widest gap by which a generated token's logit lies below the
    reference's best, the reference run once over prompt + tokens."""
    row = np.concatenate([prompt, tokens])[None]
    logits = reference(c, variables, row)[0][0]
    at = np.arange(len(prompt) - 1, len(row[0]) - 1)
    return float((logits[at].max(-1) - logits[at, row[0, at + 1]]).max())


# -- (a) the flax module's forward and its gates ------------------------------

@pytest.mark.parametrize("c", [WHOLE, CUT], ids=["all48", "cut12"])
def test_forward_and_gates_match_the_reference(c):
    module, variables = model(c)
    shapes = jax.eval_shape(module.init, jax.random.key(0),
                            jax.ShapeDtypeStruct((1, 8), np.int32))
    assert (jax.tree_util.tree_map(lambda l: l.shape, shapes)
            == jax.tree_util.tree_map(lambda l: l.shape, ref.shapes_for(c)))
    tokens = np.stack(prompts_of([37, 37, 37]))
    got = np.asarray(jax.jit(module.apply)(variables, jnp.asarray(tokens)))
    want, want_gates = reference(c, variables, tokens)
    assert got.shape == want.shape == (3, 37, VOCAB)
    assert np.abs(want).max() > 0.5          # the logits are not trivial
    assert np.abs(got - want).max() < (LOGIT_TOL_WHOLE if c is WHOLE
                                       else LOGIT_TOL)
    # the weights' rule keeps the gates off 0 and 1, and the passes differ
    assert 0.05 < want_gates.min() and want_gates.max() < 0.95
    assert np.abs(want_gates[1:] - want_gates[:-1]).max() > 0.01
    if c is CUT:        # (the deep stack's second compile is the test's cost)
        gates = program_gates(module, variables, tokens)
        assert gates.shape == want_gates.shape == (PASSES, 3, 37)
        assert np.abs(gates - want_gates).max() < GATE_TOL


def test_the_exit_distribution_sums_to_one_and_leaves_last_at_threshold_one():
    gates = jnp.asarray(np.random.default_rng(0).uniform(0.1, 0.9, (4, 2, 5)),
                        jnp.float32)
    p = np.asarray(hybrid_lm.exit_distribution(gates))
    assert np.allclose(p.sum(0), 1.0, atol=1e-6)
    g = np.asarray(gates)
    assert np.allclose(p[0], g[0]) and np.allclose(
        p[2], g[2] * (1 - g[0]) * (1 - g[1]), atol=1e-6)
    assert np.allclose(p[3], (1 - g[0]) * (1 - g[1]) * (1 - g[2]), atol=1e-6)
    # the running sum reaches 1 at the last pass only
    assert (np.cumsum(p, 0)[:3] < 1.0 - 1e-4).all()
    want = np.asarray(ref.exit_expected(gates))
    assert np.allclose((p * np.arange(1, 5)[:, None, None]).sum(0), want,
                       atol=1e-5)


def test_build_model_takes_the_loop_from_json_and_refuses_what_it_lacks():
    module = build_model("HybridLM", dict(CUT))
    assert (module.n_passes, module.sandwich_norm, module.qk_norm,
            module.exit_gate) == (4, True, False, True)
    with pytest.raises(ValueError, match="exit_threshold"):
        build_model("HybridLM", dict(CUT, exit_threshold=0.9))
    with pytest.raises(ValueError, match="n_passes"):
        build_model("HybridLM", dict(CUT, layer_types=["conv"] + [A] * 11))
    with pytest.raises(ValueError, match="n_passes"):
        build_model("HybridLM", dict(CUT, n_dense_layers=2, n_experts=4))
    with pytest.raises(ValueError, match="n_passes"):
        build_model("HybridLM", dict(CUT, n_passes=0))
    with pytest.raises(ValueError, match="exit_gate"):
        build_model("HybridLM", dict(CUT, n_passes=1))
    with pytest.raises(ValueError, match="qk_norm"):
        build_model("HybridLM", dict(
            CUT, n_passes=1, exit_gate=False, n_heads=4, n_kv_heads=2,
            layer_types=["lightning-attn"] * 12))


def test_one_pass_with_the_defaults_is_the_plain_model():
    """`n_passes` 1 and the new flags at their defaults: the tree and the
    program of a `HybridLM` as it was, with no loop in it; and the pass
    loop run ONCE over the same layers (lane 0 of a one-pass window) gives
    the plain walk's hidden states and state (to float32 rounding: the
    loop's body is compiled as one program, the walk op by op)."""
    c = dict(vocab_size=VOCAB, d_model=32, n_heads=4, n_kv_heads=2,
             layer_types=["conv", A, "conv"], n_dense_layers=1, mlp_width=48,
             n_experts=4, experts_per_token=2, expert_width=16, max_len=128,
             dtype="float32")
    plain = build_model("HybridLM", dict(c))
    spelled = build_model("HybridLM", dict(
        c, n_passes=1, sandwich_norm=False, qk_norm=True, exit_gate=False,
        exit_threshold=1.0))
    tokens = jnp.asarray(np.stack(prompts_of([21, 21])))
    variables = plain.init(jax.random.key(3), tokens)
    assert "exit_w" not in variables["params"]
    assert set(variables["params"]["layer1"]) == {
        "op_norm", "ffn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
        "router", "expert_bias", "w1", "w2", "w3"}
    assert str(jax.make_jaxpr(plain.apply)(variables, tokens)) == str(
        jax.make_jaxpr(spelled.apply)(variables, tokens))
    assert "while" not in str(jax.jit(plain.apply).lower(
        variables, tokens).as_text())
    # the loop of one pass over dense attention layers, against the walk
    one = constructor(3, n_passes=1, exit_gate=False)
    module, variables = model(dict(one, n_passes=PASSES, exit_gate=True))
    module = build_model("HybridLM", dict(one))
    params = variables["params"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    decoding = hybrid_lm.HybridDecoding(module)
    view = lambda: hybrid_lm.StateView(
        write_at=0, visible=None, n_valid=jnp.full((b,), s))
    walked, walked_state, _ = hybrid_lm.hidden_states(
        module, params, tokens, positions, decoding.empty_state(b, 24),
        view())
    x = params["embed"][tokens].astype(module.dtype)
    looped, looped_state, _ = hybrid_lm.looped_stack(
        module, params, x, positions, decoding.empty_state(b, 24), view())
    assert np.abs(np.asarray(walked) - np.asarray(looped)).max() < 1e-5
    for a, bb in zip(jax.tree_util.tree_leaves(walked_state),
                     jax.tree_util.tree_leaves(looped_state)):
        assert np.abs(np.asarray(a) - np.asarray(bb)).max() < 1e-5


# -- (b) prefill of one bucket's rows and decode, through DecodeEngine ------

LENGTHS = (9, 18, 25, 32)


def generate(c, variables, lengths=LENGTHS, new=14, **engine_args):
    module = build_model("HybridLM", dict(c))
    rows = prompts_of(lengths)
    prompts, true_len = padded(rows, 32)
    engine = DecodeEngine(module, new, chunk=8, **engine_args)
    return rows, engine.generate(variables, prompts, true_len)


@pytest.mark.parametrize("engine_args", [{}, {"prefill_chunk": 8}],
                         ids=["whole_prompt", "chunked_prefill"])
def test_decode_engine_matches_the_reference(engine_args):
    # four true lengths in one bucket of 32; 14 new tokens cross the
    # window's growth from 40 to 48 slots: every pass's windows grow
    _, variables = model(CUT)
    rows, got = generate(CUT, variables, **engine_args)
    assert got.shape == (4, 14)
    for prompt, tokens in zip(rows, got):
        assert served_gap(CUT, variables, prompt, tokens) < GAP_TOL


def test_a_long_prompt_takes_the_flash_kernel_and_matches(monkeypatch):
    # the cell's 512-token prompts take the flash path (interpreted here);
    # lowered to 32 tokens for the test
    monkeypatch.setattr(hybrid_lm, "PREFILL_FLASH_MIN", 32)
    _, variables = model(SMALL)
    rows, got = generate(SMALL, variables, new=6)
    for prompt, tokens in zip(rows, got):
        assert served_gap(SMALL, variables, prompt, tokens) < GAP_TOL


def test_the_state_keeps_a_window_for_every_pass_of_every_layer():
    module = build_model("HybridLM", dict(CUT, dtype="bfloat16"))
    engine = DecodeEngine(module, 8, chunk=8)
    assert engine.state_kinds == ("window",) * 12
    assert engine.count_names[-4:] == hybrid_lm.LOOP_COUNT_NAMES
    state = engine.empty_state(3, 32)
    assert len(state) == 12 and all(len(layer) == 2 for layer in state)
    k, v = state[5]
    # rows on axis 0, slots on axis 1; the passes' heads side by side
    assert k.shape == v.shape == (3, 40, PASSES * 4, 8)
    assert k.dtype == jnp.bfloat16
    held = engine.state_bytes(state)
    assert held == {"window": 12 * PASSES * 2 * 3 * 40 * 4 * 8 * 2,
                    "fixed": 0}
    assert engine.state_window(state) == 40
    grown = _grow_state(state, 48, engine.state_kinds)
    assert all(leaf.shape == (3, 48, 16, 8) for layer in grown
               for leaf in layer)
    # a row's pages carry every pass's window and come back bit for bit
    marked = [tuple(jnp.asarray(np.random.default_rng(i).normal(
        size=leaf.shape), leaf.dtype) for leaf in layer)
        for i, layer in enumerate(state)]
    pages = serialize_cache_row(marked, 1, 8)
    assert len(pages) == 5
    back = deserialize_cache_row(pages)
    for layer, got in zip(marked, back):
        for leaf, g in zip(layer, got):
            assert g.shape == (1,) + leaf.shape[1:]
            assert (np.asarray(g[0]) == np.asarray(leaf[1])).all()
    merged = DecodeEngine.merge_cache_rows(
        grown, back, [2], [0], kinds=engine.state_kinds)
    assert (np.asarray(merged[3][0][2, :40]) == np.asarray(
        marked[3][0][1])).all()
    assert (np.asarray(merged[3][0][2, 40:]) == 0).all()


def test_resident_weights_keep_every_gain_and_the_gate_in_float32():
    from mmlspark_tpu.models.generate import resident_variables
    module, variables = model(dict(SMALL, dtype="bfloat16"))
    resident = resident_variables(module, variables)["params"]
    for name in ("wq", "wk", "wv", "wo", "w1", "w2", "w3"):
        assert resident["layer1"][name].dtype == jnp.bfloat16
    for name in ("op_norm", "op_post_norm", "ffn_norm", "ffn_post_norm"):
        assert resident["layer1"][name].dtype == jnp.float32
    assert resident["head"].dtype == resident["embed"].dtype == jnp.bfloat16
    assert (resident["out_norm"].dtype == resident["exit_w"].dtype
            == resident["exit_b"].dtype == jnp.float32)


# -- (c) late joins and a window growth, through ServingEngine --------------

def serve(c, variables, lengths=(18, 30, 9, 32, 21), new=14, **config):
    module = build_model("HybridLM", dict(c))
    host = jax.tree_util.tree_map(np.asarray, variables)
    engine = ServingEngine(
        ModelBundle.from_module(module, host),
        ServeConfig(**dict(dict(
            max_batch=2, max_new_tokens=new, cache_chunk=8, segment_steps=4,
            warmup_buckets=(32,), warmup_joins=True), **config)))
    engine.warmup()
    requests = []
    for prompt in prompts_of(lengths):
        # each joins a batch that is already running (the third and later
        # wait for a slot, then join rows that are mid-generation)
        requests.append(engine.submit(prompt, new))
        engine._tick()
    while not all(r.finished for r in requests):
        engine._tick()
    stats = engine.stats()
    engine.stop()
    return requests, stats


@pytest.mark.parametrize("config", [{}, {"prefill_chunk": 8}],
                         ids=["whole_prompt", "chunked_prefill"])
def test_serving_engine_matches_the_reference_and_counts_the_passes(config):
    _, variables = model(SMALL)
    lengths = (18, 30, 9, 32, 21)
    requests, stats = serve(SMALL, variables, lengths, **config)
    expected = 0.0
    for r in requests:
        assert r.status == "ok" and len(r.tokens) == 14
        prompt, tokens = np.asarray(r.prompt), np.asarray(r.tokens, np.int32)
        assert served_gap(SMALL, variables, prompt, tokens) < GAP_TOL
        # a row is live for as many steps as its budget has tokens: the
        # steps that take in its 14 served tokens, at positions n..n+13
        gates = reference(SMALL, variables,
                          np.concatenate([prompt, tokens])[None])[1]
        expected += float(np.asarray(ref.exit_expected(jnp.asarray(
            gates)))[0, len(prompt):].sum())
    steps = 14 * len(lengths)
    assert stats["loop_tokens"] == steps
    assert stats["loop_passes"] == PASSES * steps
    assert stats["loop_prompt_passes"] == PASSES * sum(lengths)
    assert 1.0 < stats["loop_exit_expected"] / steps < PASSES
    assert stats["loop_exit_expected"] == pytest.approx(expected, rel=1e-4)
    assert stats["moe_assignments"] == 0
    assert stats["state_bytes_window"] > 0 and stats["state_bytes_fixed"] == 0


# -- (d) a row does not depend on its batch neighbours -----------------------

def test_a_row_does_not_change_with_its_neighbours():
    module, variables = model(SMALL)
    rows = prompts_of((25, 9, 18, 32))
    others = prompts_of((25, 32, 32, 5), seed=5)
    alone = generate(SMALL, variables, lengths=(25,))[1][0]
    prompts_a, len_a = padded(rows, 32)
    prompts_b, len_b = padded([rows[0]] + others[1:], 32)
    engine = DecodeEngine(module, 14, chunk=8)
    in_a = engine.generate(variables, prompts_a, len_a)[0]
    in_b = engine.generate(variables, prompts_b, len_b)[0]
    assert (alone == in_a).all() and (alone == in_b).all()
    tokens = np.stack(prompts_of((40, 40, 40)))
    one = np.asarray(module.apply(variables, jnp.asarray(tokens[:1])))
    three = np.asarray(module.apply(variables, jnp.asarray(tokens)))
    # to a few float32 steps of logits of order 1 after 4 x 3 layer
    # applications (the products tile otherwise)
    assert np.abs(one[0] - three[0]).max() < 2e-5
