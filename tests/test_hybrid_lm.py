"""The hybrid decoder (`models/hybrid_lm.py`: short-convolution and
grouped-attention mixers over dense or routed-expert MLPs) against its
plain reference (`benchmark/reference/lfm2_moe.py`), tiny and on the CPU,
in float32: the flax module's forward, prefill and decode through
`DecodeEngine`, late joins and window growth through `ServingEngine` and
`TextGenerator.transform`, and a row's independence of its neighbours.
`test_hybrid_lm_faults.py` plants the faults these comparisons must catch.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import lfm2_moe as ref  # noqa: E402
from benchmark.reference import lfm2_moe_weights  # noqa: E402
from mmlspark_tpu.models import (DecodeEngine, ModelBundle,  # noqa: E402
                                 TextGenerator, hybrid_lm)
from mmlspark_tpu.models.definitions import build_model  # noqa: E402
from mmlspark_tpu.ops import moe  # noqa: E402
from mmlspark_tpu.serve import ServeConfig, ServingEngine  # noqa: E402

PUBLISHED = ("c c a c c c a c c c a c c c a c c c a c c a c c").split()
KIND = {"c": "conv", "a": "full_attention"}
# float32 on the CPU: the program and the reference differ by the order of
# their sums only.  Logits are of order 1.
LOGIT_TOL = 2e-4
# a served token must be the reference's best, or tie with it to rounding
GAP_TOL = 1e-4


def constructor(layer_types, n_dense):
    return dict(vocab_size=97, d_model=32, n_heads=4, n_kv_heads=2,
                layer_types=list(layer_types), n_dense_layers=n_dense,
                mlp_width=48, n_experts=8, experts_per_token=4,
                expert_width=24, conv_kernel=3, rope_theta=1e6,
                norm_eps=1e-5, tie_embeddings=True, max_len=128,
                dtype="float32")


CUT = constructor(["conv", "full_attention", "conv", "conv", "conv"], 1)
WHOLE = constructor([KIND[k] for k in PUBLISHED], 2)


def model(c, seed=7):
    module = build_model("HybridLM", dict(c))
    variables = lfm2_moe_weights.make_variables(ref.shapes_for(c), seed)
    return module, variables


_reference = jax.jit(ref.forward, static_argnames=("spec", "mode"))


def reference_logits(c, variables, tokens):
    return np.asarray(_reference(variables["params"], jnp.asarray(tokens),
                                 spec=ref.spec_for(c))[0])


def padded(rows, bucket):
    out = np.zeros((len(rows), bucket), np.int32)
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
    return out, np.asarray([len(r) for r in rows], np.int32)


def prompts_of(lengths, seed=0, vocab=97):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]


def served_gap(c, variables, prompt, tokens):
    """The widest gap by which a generated token's logit lies below the
    reference's best, the reference run once over prompt + tokens."""
    row = np.concatenate([prompt, tokens])[None]
    logits = reference_logits(c, variables, row)[0]
    at = np.arange(len(prompt) - 1, len(row[0]) - 1)
    return float((logits[at].max(-1) - logits[at, row[0, at + 1]]).max())


# -- (a) the flax module's forward ------------------------------------------

@pytest.mark.parametrize("c", [WHOLE, CUT], ids=["all24", "cut5"])
def test_forward_matches_the_reference(c):
    module, variables = model(c)
    harness_shapes = jax.eval_shape(module.init, jax.random.key(0),
                                    jax.ShapeDtypeStruct((1, 8), np.int32))
    assert (jax.tree_util.tree_map(lambda l: l.shape, harness_shapes)
            == jax.tree_util.tree_map(lambda l: l.shape, ref.shapes_for(c)))
    tokens = np.stack(prompts_of([24, 24, 24]))
    got = np.asarray(jax.jit(module.apply)(variables, jnp.asarray(tokens)))
    want = reference_logits(c, variables, tokens)
    assert got.shape == want.shape == (3, 24, 97)
    assert np.abs(want).max() > 0.5          # the logits are not trivial
    assert np.abs(got - want).max() < LOGIT_TOL


def test_build_model_takes_layer_types_from_json():
    module = build_model("HybridLM", dict(CUT))
    assert module.layer_types == tuple(CUT["layer_types"])
    assert module.n_layers == 5
    with pytest.raises(ValueError, match="layer types"):
        build_model("HybridLM", dict(CUT, layer_types=["conv", "window"]))


# -- (b) prefill of one bucket's rows and decode, through DecodeEngine ------

LENGTHS = (5, 9, 13, 16)


def generate(c, variables, lengths=LENGTHS, new=14, **engine_args):
    module = build_model("HybridLM", dict(c))
    rows = prompts_of(lengths)
    prompts, true_len = padded(rows, 16)
    engine = DecodeEngine(module, new, chunk=8, **engine_args)
    return rows, engine.generate(variables, prompts, true_len)


@pytest.mark.parametrize("engine_args", [{}, {"prefill_chunk": 4}],
                         ids=["whole_prompt", "chunked_prefill"])
def test_decode_engine_matches_the_reference(engine_args):
    # four true lengths in one bucket of 16; 14 new tokens cross the
    # window's growth from 24 to 32 slots
    _, variables = model(CUT)
    rows, got = generate(CUT, variables, **engine_args)
    assert got.shape == (4, 14)
    for prompt, tokens in zip(rows, got):
        assert served_gap(CUT, variables, prompt, tokens) < GAP_TOL


# -- (c) a late join and a window growth, through ServingEngine -------------

def serve(c, variables, lengths=(5, 13, 9, 16, 7), new=14):
    module = build_model("HybridLM", dict(c))
    host = jax.tree_util.tree_map(np.asarray, variables)
    engine = ServingEngine(
        ModelBundle.from_module(module, host),
        ServeConfig(max_batch=2, max_new_tokens=new, cache_chunk=8,
                    segment_steps=4, warmup_buckets=(16,),
                    warmup_joins=True))
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


def test_serving_engine_matches_the_reference():
    _, variables = model(CUT)
    requests, stats = serve(CUT, variables)
    for r in requests:
        assert r.status == "ok" and len(r.tokens) == 14
        assert served_gap(CUT, variables, np.asarray(r.prompt),
                          np.asarray(r.tokens, np.int32)) < GAP_TOL
    # the device's counts came back with the tokens
    assert stats["moe_assignments"] > 0
    assert 0 < stats["moe_experts_touched"] <= stats["moe_expert_slots"]
    assert stats["moe_load_max"] >= stats["moe_load_mean"] > 0
    # four expert layers of 8 experts: a decode step can touch 32
    assert stats["moe_expert_slots"] % 32 == 0
    assert stats["state_bytes_window"] > 0 and stats["state_bytes_fixed"] > 0


def test_text_generator_transform():
    from mmlspark_tpu import DataTable
    module, variables = model(CUT)
    rows = prompts_of((6, 11, 16))
    col = np.empty(3, object)
    for i, r in enumerate(rows):
        col[i] = r
    stage = TextGenerator(
        ModelBundle.from_module(module, jax.tree_util.tree_map(
            np.asarray, variables)),
        inputCol="prompt", maxNewTokens=6, cacheChunk=8)
    out = stage.transform(DataTable({"prompt": col}))["generated"]
    for prompt, full in zip(rows, out):
        assert (full[:len(prompt)] == prompt).all()
        assert served_gap(CUT, variables, prompt,
                          np.asarray(full[len(prompt):])) < GAP_TOL


# -- (d) a row does not depend on its batch neighbours -----------------------

def test_a_row_does_not_change_with_its_neighbours():
    module, variables = model(CUT)
    rows = prompts_of((9, 5, 13, 16))
    others = prompts_of((9, 16, 16, 3), seed=5)
    alone = generate(CUT, variables, lengths=(9,))[1][0]
    prompts_a, len_a = padded(rows, 16)
    prompts_b, len_b = padded([rows[0]] + others[1:], 16)
    engine = DecodeEngine(module, 14, chunk=8)
    in_a = engine.generate(variables, prompts_a, len_a)[0]
    in_b = engine.generate(variables, prompts_b, len_b)[0]
    assert (alone == in_a).all() and (alone == in_b).all()
    # and its logits: the plain forward, alone and beside two others
    tokens = np.stack(prompts_of((16, 16, 16)))
    one = np.asarray(module.apply(variables, jnp.asarray(tokens[:1])))
    three = np.asarray(module.apply(variables, jnp.asarray(tokens)))
    assert np.abs(one[0] - three[0]).max() < 1e-6
