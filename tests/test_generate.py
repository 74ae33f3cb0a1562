"""Autoregressive generation (models/generate.py): the KV-cache decode
program must reproduce recompute-everything decoding exactly, sample
reproducibly, and ride the pipeline-stage contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu import DataTable
from mmlspark_tpu.models import ModelBundle, TextGenerator, naive_generate
from mmlspark_tpu.models.definitions import build_model
from mmlspark_tpu.models.generate import generate, make_generate_fn

CFG = {"vocab_size": 32, "d_model": 32, "n_heads": 4, "n_layers": 2,
       "max_len": 24, "dtype": "float32"}


@pytest.fixture(scope="module")
def lm_bundle():
    lm = build_model("TransformerLM", CFG)
    toks = np.zeros((1, 4), np.int32)
    variables = lm.init(jax.random.key(3), toks)
    return ModelBundle.from_module(lm, variables)


@pytest.mark.slow
def test_greedy_matches_naive_recompute(lm_bundle):
    """The whole point of the cache: same tokens as the O(N*S^2) oracle."""
    module = lm_bundle.module()
    prompts = np.asarray([[1, 2, 3, 4], [9, 8, 7, 6], [0, 0, 5, 5]],
                         np.int32)
    got = generate(module, lm_bundle.variables, prompts, max_new_tokens=12)
    ref = naive_generate(module, lm_bundle.variables, prompts,
                         max_new_tokens=12)
    assert got.shape == (3, 16)
    np.testing.assert_array_equal(got, ref)


def test_single_new_token(lm_bundle):
    module = lm_bundle.module()
    prompts = np.asarray([[4, 5]], np.int32)
    got = generate(module, lm_bundle.variables, prompts, max_new_tokens=1)
    ref = naive_generate(module, lm_bundle.variables, prompts, 1)
    np.testing.assert_array_equal(got, ref)


@pytest.mark.slow
def test_temperature_sampling_reproducible_and_varied(lm_bundle):
    module = lm_bundle.module()
    fn = make_generate_fn(module, prompt_len=4, max_new_tokens=16,
                          temperature=1.0)
    prompts = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    a = np.asarray(fn(lm_bundle.variables, prompts, jax.random.key(0)))
    b = np.asarray(fn(lm_bundle.variables, prompts, jax.random.key(0)))
    c = np.asarray(fn(lm_bundle.variables, prompts, jax.random.key(1)))
    np.testing.assert_array_equal(a, b)          # same key, same tokens
    assert not np.array_equal(a, c)              # different key differs
    assert a.min() >= 0 and a.max() < CFG["vocab_size"]


def test_budget_validation(lm_bundle):
    module = lm_bundle.module()
    with pytest.raises(ValueError, match="max_len"):
        make_generate_fn(module, prompt_len=20, max_new_tokens=8)
    with pytest.raises(ValueError, match="max_new_tokens"):
        make_generate_fn(module, prompt_len=4, max_new_tokens=0)
    fn = make_generate_fn(module, prompt_len=6, max_new_tokens=2)
    with pytest.raises(ValueError, match="prompt_len=6"):
        fn(lm_bundle.variables, jnp.zeros((1, 4), jnp.int32),
           jax.random.key(0))


@pytest.mark.slow
def test_bf16_decode_logits_match_module_forward():
    """The shipped default dtype: the decode path's prefill logits must
    agree with module.apply to bfloat16 rounding (decode accumulates
    attention in f32 — see module docstring — so exact bit parity is not
    the contract; closeness at bf16 resolution is)."""
    from mmlspark_tpu.models.transformer_decoding import forward_with_cache

    lm = build_model("TransformerLM", dict(CFG, dtype="bfloat16"))
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 32, (2, 8)),
                       jnp.int32)
    variables = lm.init(jax.random.key(0), toks)
    ref = np.asarray(lm.apply(variables, toks), np.float32)
    caches = [(jnp.zeros((2, CFG["max_len"], 4, 8), jnp.bfloat16),
               jnp.zeros((2, CFG["max_len"], 4, 8), jnp.bfloat16))
              for _ in range(CFG["n_layers"])]
    got, _ = forward_with_cache(variables["params"], toks, caches, 0, lm)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=0.05, atol=0.05)


@pytest.mark.slow
def test_text_generator_stage(lm_bundle, tmp_path):
    """Ragged prompt lengths, row alignment, and the persistence fuzz
    contract (save -> load -> identical transform)."""
    gen = TextGenerator(lm_bundle, inputCol="prompt", outputCol="out",
                        maxNewTokens=6)
    rows = np.empty(4, object)
    rows[0] = np.asarray([1, 2, 3], np.int32)
    rows[1] = np.asarray([4, 5], np.int32)
    rows[2] = np.asarray([6, 7, 8], np.int32)
    rows[3] = np.asarray([9], np.int32)
    table = DataTable({"prompt": rows})
    out = gen.transform(table)["out"]
    assert [len(r) for r in out] == [9, 8, 9, 7]
    for prompt, full in zip(rows, out):
        np.testing.assert_array_equal(np.asarray(full[:len(prompt)]), prompt)

    path = str(tmp_path / "gen_stage")
    gen.save(path)
    loaded = TextGenerator.load(path)
    out2 = loaded.transform(table)["out"]
    for a, b in zip(out, out2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_moe_decode_prefill_matches_module_forward():
    """MoE blocks decode: the prefill forward re-applies the REAL MoEMLP
    per layer, so its logits equal module.apply exactly (same token group,
    same capacity arithmetic)."""
    from mmlspark_tpu.models.transformer_decoding import forward_with_cache

    moe = build_model("TransformerLM", dict(
        CFG, mlp_impl="moe", n_experts=4, moe_router_k=2))
    toks = jnp.asarray(np.random.default_rng(6).integers(0, 32, (3, 8)),
                       jnp.int32)
    variables = moe.init(jax.random.key(1), toks)
    ref = np.asarray(moe.apply(variables, toks))
    caches = [(jnp.zeros((3, CFG["max_len"], 4, 8), jnp.float32),
               jnp.zeros((3, CFG["max_len"], 4, 8), jnp.float32))
              for _ in range(CFG["n_layers"])]
    got, _ = forward_with_cache(variables["params"], toks, caches, 0, moe)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5, atol=2e-5)


def test_moe_greedy_decode_matches_naive():
    """Greedy generation through a Switch-MoE LM matches the recompute
    oracle in the drop-free regime: moe_group_size=1 routes every token
    alone (capacity 1, always kept), so stepwise decode routing equals
    full-sequence routing exactly.  With larger groups the two can
    legitimately diverge under capacity pressure — the capacity drop is a
    BATCH-level training construct a stepwise decoder cannot reproduce
    (documented in models/transformer_decoding.py::_mlp)."""
    moe = build_model("TransformerLM", dict(CFG, mlp_impl="moe",
                                            n_experts=2, moe_group_size=1))
    toks = np.asarray([[3, 1, 4, 1]], np.int32)
    variables = moe.init(jax.random.key(2), jnp.asarray(toks))
    # 4 steps: every naive-oracle step is its own XLA compile, and the
    # routing-equivalence property is per-token — longer horizons only
    # re-prove it at higher compile cost
    got = generate(moe, variables, toks, max_new_tokens=4)
    ref = naive_generate(moe, variables, toks, 4)
    np.testing.assert_array_equal(got, ref)


@pytest.mark.slow
def test_generates_from_pipeline_trained_bundle():
    """A bundle that came out of pipeline-parallel training (stacked tree
    unstacked back to TransformerLM) must decode like any other — the
    PP-train -> generate product loop."""
    from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
    from mmlspark_tpu.train import Trainer, TrainerConfig

    mesh = make_mesh(MeshSpec(data=4, model=2))
    cfg = TrainerConfig(
        architecture="TransformerLM",
        model_config=dict(CFG, n_layers=2),
        optimizer="adam", learning_rate=1e-2, epochs=1, batch_size=8,
        pipeline_stages=2, pipeline_microbatches=2)
    trainer = Trainer(cfg, mesh=mesh)
    toks = np.random.default_rng(0).integers(0, 32, (8, 12)).astype(np.int32)
    bundle = trainer.fit_arrays(toks, np.roll(toks, -1, 1))
    module = bundle.module()
    prompts = toks[:2, :6]
    got = generate(module, bundle.variables, prompts, max_new_tokens=8)
    ref = naive_generate(module, bundle.variables, prompts, 8)
    np.testing.assert_array_equal(got, ref)


@pytest.mark.slow
def test_long_prompt_prefill_uses_flash_and_matches_dense():
    """Prefill at >= PREFILL_FLASH_MIN tokens routes through the flash
    kernel (no O(P^2) score tensor); its logits match the module's dense
    forward to online-softmax rounding, the public jit-once generation
    program runs end to end at that prompt length, and no dense fallback
    fires (which would silently re-materialize the scores)."""
    import mmlspark_tpu.ops.flash_attention as fa
    from mmlspark_tpu.models.hybrid_lm import PREFILL_FLASH_MIN
    from mmlspark_tpu.models.transformer_decoding import forward_with_cache

    P = PREFILL_FLASH_MIN
    cfg = {"vocab_size": 32, "d_model": 16, "n_heads": 2, "n_layers": 1,
           "max_len": P + 8, "dtype": "float32"}
    lm = build_model("TransformerLM", cfg)
    toks = jnp.asarray(np.random.default_rng(7).integers(0, 32, (1, P)),
                       jnp.int32)
    variables = lm.init(jax.random.key(0), toks)
    ref = np.asarray(lm.apply(variables, toks))
    caches = [(jnp.zeros((1, P + 8, 2, 8), jnp.float32),
               jnp.zeros((1, P + 8, 2, 8), jnp.float32))]
    got, new_caches = forward_with_cache(variables["params"], toks,
                                         caches, 0, lm)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4, atol=2e-4)
    # the cache was still written for the decode steps that follow
    assert float(jnp.abs(new_caches[0][0][0, :P]).sum()) > 0
    assert float(jnp.abs(new_caches[0][0][0, P:]).sum()) == 0

    # the PUBLIC path: the compiled prefill+scan program at a long prompt,
    # with the dense-fallback warning set untouched (flash really ran)
    before = set(fa._warned_fallbacks)
    fn = make_generate_fn(lm, P, 8)
    out = np.asarray(fn(variables, toks, jax.random.key(0)))
    assert out.shape == (1, P + 8)
    np.testing.assert_array_equal(out[:, :P], np.asarray(toks))
    assert (out >= 0).all() and (out < 32).all()
    assert set(fa._warned_fallbacks) == before, (
        "flash prefill silently fell back to dense")


@pytest.mark.slow
def test_text_generator_over_mesh_matches_single_device(lm_bundle):
    """Mesh-sharded generation (batch over 'data', zero-padded to whole
    shards) must produce exactly the single-device tokens for dense
    models — batch parallelism cannot change any row's decode."""
    from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(data=8))
    rows = np.empty(5, object)  # 5 rows of length 4: pads to 8 shards
    for i in range(5):
        rows[i] = (np.arange(4, dtype=np.int32) + i) % 32
    table = DataTable({"prompt": rows})
    single = TextGenerator(lm_bundle, inputCol="prompt", outputCol="out",
                           maxNewTokens=5).transform(table)["out"]
    meshed = TextGenerator(lm_bundle, inputCol="prompt", outputCol="out",
                           maxNewTokens=5).set_mesh(mesh).transform(
        table)["out"]
    for a, b in zip(single, meshed):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_filter_logits_top_k_and_top_p():
    from mmlspark_tpu.models.generate import NEG_INF, filter_logits

    logits = jnp.asarray([[3.0, 1.0, 2.0, 0.0, -1.0]])
    k2 = np.asarray(filter_logits(logits, top_k=2))
    assert (k2[0, [0, 2]] > NEG_INF / 2).all()        # two best kept
    assert (k2[0, [1, 3, 4]] <= NEG_INF / 2).all()    # rest masked
    # nucleus: probs ~ [.66, .09, .24, .03, .01]; p=.7 keeps {0} then
    # needs 2 to reach .7 -> keeps the smallest prefix covering p
    p7 = np.asarray(filter_logits(logits, top_p=0.7))
    assert p7[0, 0] > NEG_INF / 2 and p7[0, 2] > NEG_INF / 2
    assert (p7[0, [1, 3, 4]] <= NEG_INF / 2).all()
    # a tiny p still keeps the argmax (never an empty distribution)
    p_tiny = np.asarray(filter_logits(logits, top_p=1e-6))
    assert p_tiny[0, 0] > NEG_INF / 2
    assert (p_tiny[0, 1:] <= NEG_INF / 2).all()
    # off switches are identity
    np.testing.assert_array_equal(
        np.asarray(filter_logits(logits, top_k=None, top_p=None)),
        np.asarray(logits, np.float32))


def test_filter_logits_edge_cases():
    """The corners sampling only exercises by accident: filters that cover
    the whole vocabulary are identities, exact ties at the nucleus cutoff
    never split, and fully-masked rows stay finite (no NaN from the
    internal softmax) so a downstream categorical cannot crash."""
    from mmlspark_tpu.models.generate import NEG_INF, filter_logits

    logits = jnp.asarray([[3.0, 1.0, 2.0, 0.0, -1.0]])
    ref = np.asarray(logits, np.float32)
    # top_k covering the vocab (k == V and k > V) is an identity
    np.testing.assert_array_equal(np.asarray(filter_logits(logits, top_k=5)),
                                  ref)
    np.testing.assert_array_equal(np.asarray(filter_logits(logits, top_k=9)),
                                  ref)
    # top_p = 1.0 is the documented off switch — identity, not "keep all
    # but the last"
    np.testing.assert_array_equal(
        np.asarray(filter_logits(logits, top_p=1.0)), ref)
    # exact ties AT the nucleus cutoff are all kept: the cutoff is a logit
    # VALUE, so two tokens with identical logits stand or fall together
    # even when the nucleus mass is reached inside the tie
    tied = jnp.asarray([[2.0, 2.0, 0.0, -8.0, -8.0]])
    for p in (0.3, 0.5):  # mass reached at the 1st and 2nd tie member
        kept = np.asarray(filter_logits(tied, top_p=p))[0]
        assert kept[0] > NEG_INF / 2 and kept[1] > NEG_INF / 2, p
        assert (kept[2:] <= NEG_INF / 2).all(), p
    # an all-NEG_INF row (every token already masked upstream) must come
    # through finite and fully masked under both filters, alone and
    # stacked beside a healthy row
    dead = jnp.full((1, 5), NEG_INF)
    both = jnp.concatenate([logits, dead])
    for out in (filter_logits(dead, top_k=2), filter_logits(dead, top_p=0.5),
                filter_logits(both, top_k=2, top_p=0.5)[1:]):
        arr = np.asarray(out)
        assert not np.isnan(arr).any()
        assert (arr <= NEG_INF / 2).all()


@pytest.mark.slow
def test_top_k_one_equals_greedy(lm_bundle):
    """top_k=1 collapses temperature sampling to greedy exactly — the
    end-to-end pin that the filter really gates the sampler."""
    module = lm_bundle.module()
    prompts = jnp.asarray([[1, 2, 3, 4], [7, 7, 2, 9]], jnp.int32)
    greedy_fn = make_generate_fn(module, 4, 10, temperature=0.0)
    k1_fn = make_generate_fn(module, 4, 10, temperature=1.7, top_k=1)
    a = np.asarray(greedy_fn(lm_bundle.variables, prompts, jax.random.key(0)))
    b = np.asarray(k1_fn(lm_bundle.variables, prompts, jax.random.key(5)))
    np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_top_p_sampling_valid_and_validated(lm_bundle):
    module = lm_bundle.module()
    fn = make_generate_fn(module, 4, 8, temperature=1.0, top_p=0.8)
    out = np.asarray(fn(lm_bundle.variables,
                        jnp.asarray([[1, 2, 3, 4]], jnp.int32),
                        jax.random.key(0)))
    assert out.shape == (1, 12)
    assert (out >= 0).all() and (out < 32).all()
    with pytest.raises(ValueError, match="top_k"):
        make_generate_fn(module, 4, 2, temperature=1.0, top_k=0)
    with pytest.raises(ValueError, match="top_p"):
        make_generate_fn(module, 4, 2, temperature=1.0, top_p=0.0)


def test_text_generator_sampling_params_end_to_end(lm_bundle):
    """topK/topP flow through the stage: defaults (0 / 1.0) normalize to
    off, active values produce valid sampled rows, and greedy ignores
    the filters without recompiling per filter value."""
    rows = np.stack([np.asarray([1, 2, 3, 4], np.int32)] * 2)
    table = DataTable({"prompt": rows})
    sampled = TextGenerator(lm_bundle, inputCol="prompt", outputCol="out",
                            maxNewTokens=6, temperature=0.9, topK=5,
                            topP=0.9).transform(table)["out"]
    assert sampled.shape == (2, 10)
    assert (sampled >= 0).all() and (sampled < 32).all()
    greedy = TextGenerator(lm_bundle, inputCol="prompt", outputCol="out",
                           maxNewTokens=6, topK=7)  # filters ignored
    a = greedy.transform(table)["out"]
    assert len(greedy._compiled) == 1
    greedy.set_params(topK=3)
    b = greedy.transform(table)["out"]
    assert len(greedy._compiled) == 1  # same normalized cache key
    np.testing.assert_array_equal(a, b)


def test_beam_width_one_equals_greedy(lm_bundle):
    """W=1 beam search is exactly greedy decoding — the degenerate-case
    pin that the expand/select/reindex bookkeeping is sound."""
    from mmlspark_tpu.models import beam_search

    module = lm_bundle.module()
    prompts = np.asarray([[1, 2, 3, 4], [8, 6, 4, 2]], np.int32)
    beams, scores = beam_search(module, lm_bundle.variables, prompts,
                                max_new_tokens=9, beam_width=1)
    ref = naive_generate(module, lm_bundle.variables, prompts, 9)
    assert beams.shape == (2, 1, 13) and scores.shape == (2, 1)
    np.testing.assert_array_equal(beams[:, 0], ref)


@pytest.mark.slow
def test_beam_scores_match_recomputed_logprobs(lm_bundle):
    """Every returned beam's score must equal the sum of its generated
    tokens' log-probabilities under a recompute-everything forward — the
    bookkeeping oracle (a reindexing bug in cache/history ancestry breaks
    this immediately).  Scores come back best-first, and the best beam
    never scores below the greedy sequence."""
    from mmlspark_tpu.models import beam_search

    module = lm_bundle.module()
    prompts = np.asarray([[5, 3, 1, 7]], np.int32)
    P, N, W = 4, 6, 3
    beams, scores = beam_search(module, lm_bundle.variables, prompts,
                                max_new_tokens=N, beam_width=W)
    assert (np.diff(scores[0]) <= 1e-6).all()        # best-first
    for wi in range(W):
        seq = jnp.asarray(beams[:, wi])
        logits = module.apply(lm_bundle.variables, seq)
        lp = jax.nn.log_softmax(np.asarray(logits, np.float32), axis=-1)
        recomputed = sum(float(lp[0, P - 1 + t, beams[0, wi, P + t]])
                         for t in range(N))
        np.testing.assert_allclose(scores[0, wi], recomputed,
                                   rtol=1e-4, atol=1e-4)
    # greedy is one length-N candidate; the best beam is at least as good
    greedy = naive_generate(module, lm_bundle.variables, prompts, N)
    logits = module.apply(lm_bundle.variables, jnp.asarray(greedy))
    lp = jax.nn.log_softmax(np.asarray(logits, np.float32), axis=-1)
    greedy_score = sum(float(lp[0, P - 1 + t, greedy[0, P + t]])
                       for t in range(N))
    assert scores[0, 0] >= greedy_score - 1e-4


def test_text_generator_beam_param(lm_bundle):
    """beamWidth > 0 routes the stage through beam search and emits each
    row's best beam."""
    from mmlspark_tpu.models import beam_search

    rows = np.stack([np.asarray([2, 4, 6, 8], np.int32),
                     np.asarray([1, 3, 5, 7], np.int32)])
    table = DataTable({"prompt": rows})
    out = TextGenerator(lm_bundle, inputCol="prompt", outputCol="out",
                        maxNewTokens=5, beamWidth=3).transform(table)["out"]
    ref, _ = beam_search(lm_bundle.module(), lm_bundle.variables, rows,
                         max_new_tokens=5, beam_width=3)
    np.testing.assert_array_equal(out, ref[:, 0])
