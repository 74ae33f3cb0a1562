"""The `minicpm4` and `lightning-attn` mixers of `models/hybrid_lm.py`
(`ops/sparse_attention.py`, `ops/linear_attention.py`) against their plain
reference (`benchmark/reference/minicpm_sala.py`), tiny and on the CPU, in
float32: the flax module's forward at all 32 published layers and at the
cut, prefill (whole and chunked) and decode through `DecodeEngine`, late
joins and window growth through `ServingEngine` with the device's counts,
and a row's independence of its neighbours.  `test_sala_faults.py` plants
the faults these comparisons must catch.

The sizes: blocks of 4 tokens, kernels of 4 with stride 2, a local window
of 8, 2 blocks by score, `dense_len` 16: a prompt of 17 tokens or more is
on the sparse path, and a bucket of 32 holds rows on both sides of it.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import minicpm_sala as ref  # noqa: E402
from benchmark.reference import minicpm_sala_weights  # noqa: E402
from mmlspark_tpu.models import DecodeEngine, ModelBundle  # noqa: E402
from mmlspark_tpu.models.definitions import build_model  # noqa: E402
from mmlspark_tpu.ops import linear_attention as la  # noqa: E402
from mmlspark_tpu.ops import sparse_attention as sa  # noqa: E402
from mmlspark_tpu.serve import ServeConfig, ServingEngine  # noqa: E402

S, L = "minicpm4", "lightning-attn"
# openbmb/MiniCPM-SALA `mixer_types`: 8 sparse layers among 24 linear
PUBLISHED = [S if i in (0, 9, 16, 17, 22, 29, 30, 31) else L
             for i in range(32)]
# float32 on the CPU: the program and the reference differ by the order of
# their sums only (a chunked scan against a scan over positions; a running
# softmax against a whole one).  Logits are of order 1.
LOGIT_TOL = 2e-4
# a served token must be the reference's best, or tie with it to rounding
GAP_TOL = 1e-4
VOCAB = 97


def constructor(layer_types, **over):
    c = dict(vocab_size=VOCAB, d_model=32, n_heads=4, n_kv_heads=2,
             layer_types=list(layer_types), n_dense_layers=len(layer_types),
             mlp_width=48, rope_theta=10000.0, norm_eps=1e-6,
             tie_embeddings=False, embed_scale=12.0,
             residual_scale=1.4 / 32 ** 0.5, logit_scale=1 / 16,
             sparse_block=4, sparse_kernel=4, sparse_stride=2,
             sparse_window=8, sparse_init_blocks=1, sparse_topk=2,
             sparse_dense_len=16, max_len=128, dtype="float32")
    return dict(c, **over)


# the benchmark's cut: published layers 9-16
CUT = constructor(PUBLISHED[9:17])
WHOLE = constructor(PUBLISHED)
# a model the per-request tests can afford: both kinds, sparse at the top
SMALL = constructor([S, L, L, S])


def model(c, seed=7):
    module = build_model("HybridLM", dict(c))
    variables = minicpm_sala_weights.make_variables(ref.shapes_for(c), seed)
    return module, variables


_reference = jax.jit(ref.forward, static_argnames=("spec", "mode"))


def reference_logits(c, variables, tokens, mode="f32"):
    return np.asarray(_reference(variables["params"], jnp.asarray(tokens),
                                 spec=ref.spec_for(c, positions=16),
                                 mode=mode)[0])


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
    logits = reference_logits(c, variables, row)[0]
    at = np.arange(len(prompt) - 1, len(row[0]) - 1)
    return float((logits[at].max(-1) - logits[at, row[0, at + 1]]).max())


# -- (a) the flax module's forward ------------------------------------------

def test_the_published_list_is_the_sources():
    assert PUBLISHED.count(S) == 8 and PUBLISHED.count(L) == 24
    assert CUT["layer_types"] == [S, L, L, L, L, L, L, S]


@pytest.mark.parametrize("c", [WHOLE, CUT], ids=["all32", "cut8"])
def test_forward_matches_the_reference(c):
    module, variables = model(c)
    shapes = jax.eval_shape(module.init, jax.random.key(0),
                            jax.ShapeDtypeStruct((1, 8), np.int32))
    assert (jax.tree_util.tree_map(lambda l: l.shape, shapes)
            == jax.tree_util.tree_map(lambda l: l.shape, ref.shapes_for(c)))
    # 61 positions: past `dense_len`, and no whole number of blocks
    tokens = np.stack(prompts_of([61, 61, 61]))
    got = np.asarray(jax.jit(module.apply)(variables, jnp.asarray(tokens)))
    want = reference_logits(c, variables, tokens)
    assert got.shape == want.shape == (3, 61, VOCAB)
    assert np.abs(want).max() > 0.5          # the logits are not trivial
    assert np.abs(got - want).max() < LOGIT_TOL


def test_build_model_takes_the_mixers_and_the_sparse_numbers_from_json():
    module = build_model("HybridLM", dict(CUT))
    assert module.layer_types == tuple(CUT["layer_types"])
    assert module.sparse_cfg == sa.Sparse(4, 4, 2, 8, 1, 2, 16)
    with pytest.raises(ValueError, match="layer types"):
        build_model("HybridLM", dict(CUT, layer_types=[S, "mamba"]))
    with pytest.raises(ValueError, match="multiple of stride"):
        build_model("HybridLM", dict(CUT, sparse_stride=3))


# -- (b) the two operations alone ---------------------------------------------

def test_the_chunked_scan_is_the_recurrence():
    b, s, h, d = 2, 37, 4, 8
    keys = jax.random.split(jax.random.key(1), 4)
    q, k, v = (jax.random.normal(key, (b, s, h, d)) for key in keys[:3])
    carried = jax.random.normal(keys[3], (b, h, d, d))
    slopes = la.decay_slopes(h)
    assert np.allclose(slopes, [2.0 ** -2, 2.0 ** -4, 2.0 ** -6, 2.0 ** -8])
    n_valid = jnp.asarray([37, 21])
    state, outs = np.asarray(carried), []
    at_21 = None
    for t in range(s):
        state = (np.exp(-np.asarray(slopes))[None, :, None, None] * state
                 + np.einsum("bhd,bhe->bhde", k[:, t], v[:, t]))
        outs.append(np.einsum("bhd,bhde->bhe", q[:, t], state))
        if t == 20:
            at_21 = state[1].copy()
    want = np.stack(outs, 1)
    for block in (64, 8, 5):        # one block; whole blocks; a ragged last
        o, new = la.linear_attention(q, k, v, slopes, carried, n_valid,
                                     block=block)
        assert np.abs(np.asarray(o)[0] - want[0]).max() < 1e-4
        assert np.abs(np.asarray(o)[1, :21] - want[1, :21]).max() < 1e-4
        assert np.abs(np.asarray(new)[0] - state[0]).max() < 1e-4
        # the shorter row's state is the state at ITS length
        assert np.abs(np.asarray(new)[1] - at_21).max() < 1e-4


def test_compressed_keys_are_appended_and_never_recomputed():
    cfg = sa.Sparse(4, 4, 2, 8, 1, 2, 16)
    keys = np.random.default_rng(0).normal(size=(24, 2, 8)).astype(np.float32)
    want = np.stack([keys[2 * j:2 * j + 4].mean(0) for j in range(11)])
    # a prompt of 9, then one key at a time: each window is written when
    # its last key arrives
    cache = np.zeros((24, 2, 8), np.float32)
    cache[:9] = keys[:9]
    kc = sa.compress_row(jnp.zeros((12, 2, 8)), jnp.asarray(cache), 0, 9,
                         cfg)
    assert np.allclose(kc[:3], want[:3], atol=1e-6)
    assert (np.asarray(kc[3:]) == 0).all()
    for t in range(9, 24):
        cache[t] = keys[t]
        before = np.asarray(kc)
        kc = sa.compress_row(kc, jnp.asarray(cache), t, 1, cfg)
        j = (t + 1 - 4) // 2
        changed = np.nonzero(np.abs(np.asarray(kc) - before).sum((1, 2)))[0]
        assert changed.tolist() == ([j] if (t + 1) % 2 == 0 else [])
    assert np.allclose(kc[:11], want, atol=1e-6)
    # a chunk from a traced start, as a later prompt chunk writes it
    kc2 = jax.jit(lambda c, k, at: sa.compress_row(c, k, at, 8, cfg))(
        jnp.zeros((12, 2, 8)), jnp.asarray(keys), 8)
    assert np.allclose(kc2[3:7], want[3:7], atol=1e-6)
    assert (np.asarray(kc2[:3]) == 0).all()


def test_the_selection_reads_init_local_and_the_best_of_the_rest():
    cfg = sa.Sparse(4, 4, 2, 8, 1, 2, 16)
    scores = jnp.asarray([0.0, .1, .5, .2, .5, .3, .9, .9, .9, .9])
    scores = jnp.broadcast_to(scores, (1, 1, 2, 10))
    # position 38: block 9; local 8, 9; init 0; of 1..7 the best two, the
    # earlier of the tied.  Position 12: dense, every visible block
    read = sa.read_blocks(scores, jnp.asarray([[38, 12]]), cfg)
    assert np.nonzero(read[0, 0, 0])[0].tolist() == [0, 6, 7, 8, 9]
    assert np.nonzero(read[0, 0, 1])[0].tolist() == [0, 1, 2, 3]
    tied = sa.read_blocks(scores.at[..., 6].set(0.5).at[..., 7].set(0.5),
                          jnp.asarray([[38, 38]]), cfg)
    assert np.nonzero(tied[0, 0, 0])[0].tolist() == [0, 2, 4, 8, 9]
    assert sa.keys_read(read, jnp.asarray([[38, 12]]), cfg).tolist() == [
        [4 * 4 + 3, 13]]
    assert sa.capacity(cfg, 32) == 5 and sa.capacity(cfg, 3) == 3


def test_chunks_and_tiles_do_not_change_the_masked_attention():
    cfg = sa.Sparse(4, 4, 2, 8, 1, 2, 16)
    b, s, w, h, g, d = 2, 23, 40, 4, 2, 8
    keys = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(keys[0], (b, s, h, d))
    k, v = (jax.random.normal(key, (b, w, g, d)) for key in keys[1:])
    kc = jax.vmap(lambda c, kk: sa.compress_row(c, kk, 0, w, cfg))(
        jnp.zeros((b, w // 2, g, d)), k)
    pos = 13 + jnp.arange(s)
    whole, n_whole = sa.attend_masked(q, k, v, kc, pos, cfg, d ** -0.5,
                                      q_chunk=64, k_tile=64)
    for q_chunk, k_tile in ((4, 8), (5, 12), (23, 16)):
        got, n_read = sa.attend_masked(q, k, v, kc, pos, cfg, d ** -0.5,
                                       q_chunk=q_chunk, k_tile=k_tile)
        assert np.abs(got - whole).max() < 1e-5
        assert (np.asarray(n_read) == np.asarray(n_whole)).all()
    # and a decode step's gather reads what the mask reads
    rows = jnp.broadcast_to(pos[-1:], (b, 1))
    read = sa.read_blocks(sa.block_scores(q[:, -1:], kc, rows, cfg,
                                          d ** -0.5), rows, cfg)
    step = sa.attend_gathered(q[:, -1:], k, v, read, rows, cfg, d ** -0.5)
    assert np.abs(step[:, 0] - whole[:, -1]).max() < 1e-5


# -- (c) prefill of one bucket's rows and decode, through DecodeEngine ------

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
    # four true lengths in one bucket of 32, one under `dense_len`; 14 new
    # tokens cross the window's growth from 40 to 48 slots (the compressed
    # keys grow with it) and, for the shortest row, `dense_len`
    _, variables = model(CUT)
    rows, got = generate(CUT, variables, **engine_args)
    assert got.shape == (4, 14)
    for prompt, tokens in zip(rows, got):
        assert served_gap(CUT, variables, prompt, tokens) < GAP_TOL


def test_the_state_has_three_window_leaves_and_a_float32_matrix():
    module = build_model("HybridLM", dict(CUT, dtype="bfloat16"))
    engine = DecodeEngine(module, 8, chunk=8)
    state = engine.empty_state(3, 32)
    k, v, kc = state[0]
    assert k.shape == v.shape == (3, 40, 2, 8) and k.dtype == jnp.bfloat16
    assert kc.shape == (3, 20, 2, 8) and kc.dtype == jnp.float32
    assert state[1][0].shape == (3, 4, 8, 8)
    assert state[1][0].dtype == jnp.float32
    held = engine.state_bytes(state)
    assert held["window"] == 2 * (k.nbytes + v.nbytes + kc.nbytes)
    assert held["fixed"] == 6 * state[1][0].nbytes
    from mmlspark_tpu.models.generate import _grow_state
    grown = _grow_state(state, 48, engine.state_kinds)
    assert grown[0][0].shape[1] == 48 and grown[0][2].shape[1] == 24
    assert grown[1][0].shape == (3, 4, 8, 8)
    with pytest.raises(ValueError, match="whole blocks"):
        DecodeEngine(module, 8, chunk=7).empty_state(1, 32)


def test_resident_weights_keep_gains_and_norms_in_float32():
    from mmlspark_tpu.models.generate import resident_variables
    module, variables = model(dict(SMALL, dtype="bfloat16"))
    resident = resident_variables(module, variables)["params"]
    for name in ("wq", "wk", "wv", "wo", "wg", "w1", "w2", "w3"):
        assert resident["layer1"][name].dtype == jnp.bfloat16
    for name in ("q_norm", "k_norm", "o_norm", "op_norm", "ffn_norm"):
        assert resident["layer1"][name].dtype == jnp.float32
    assert resident["head"].dtype == resident["embed"].dtype == jnp.bfloat16


# -- (d) late joins and a window growth, through ServingEngine --------------

def serve(c, variables, lengths=(18, 30, 9, 32, 21), new=14):
    module = build_model("HybridLM", dict(c))
    host = jax.tree_util.tree_map(np.asarray, variables)
    engine = ServingEngine(
        ModelBundle.from_module(module, host),
        ServeConfig(max_batch=2, max_new_tokens=new, cache_chunk=8,
                    segment_steps=4, warmup_buckets=(32,),
                    warmup_joins=True, prefill_chunk=8))
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
    _, variables = model(SMALL)
    lengths = (18, 30, 9, 32, 21)
    requests, stats = serve(SMALL, variables, lengths)
    for r in requests:
        assert r.status == "ok" and len(r.tokens) == 14
        assert served_gap(SMALL, variables, np.asarray(r.prompt),
                          np.asarray(r.tokens, np.int32)) < GAP_TOL
    # the device's counts came back with the tokens: two sparse layers, and
    # a row is live for as many steps as its budget has tokens (its first
    # token is the prefill's; the step after its last one still runs)
    steps = 2 * 14 * len(lengths)
    visible = 2 * sum(n + t + 1 for n in lengths for t in range(14))
    assert stats["sparse_keys_visible"] == visible
    c = SMALL
    read = 2 * sum(int(ref.keys_read(c, np.asarray(n + t)))
                   for n in lengths for t in range(14))
    assert stats["sparse_keys_read"] == read < visible
    assert stats["sparse_prompt_keys_visible"] == 2 * sum(
        n * (n + 1) // 2 for n in lengths)
    assert stats["sparse_prompt_keys_read"] == 2 * sum(
        int(ref.keys_read(c, np.arange(n)).sum()) for n in lengths)
    # the row of 9 decodes up to position 21: its first 7 steps are dense
    assert stats["sparse_dense_steps"] == 2 * 7
    # a linear state takes a step a token, a prompt's and a decoded one
    assert stats["linear_state_steps"] == steps + 2 * sum(lengths)
    assert stats["moe_assignments"] == 0
    assert stats["state_bytes_window"] > 0 and stats["state_bytes_fixed"] > 0


# -- (e) a row does not depend on its batch neighbours -----------------------

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
    # to a few float32 steps of logits up to 4 (the products tile otherwise)
    assert np.abs(one[0] - three[0]).max() < 5e-6


# -- (f) the reference's own numbers -----------------------------------------

def test_forward_flops_count_the_keys_read():
    c = dict(CUT)
    d, w, v = 32, 48, VOCAB
    weights = (d * v + 8 * 3 * d * w + 6 * 5 * d * d
               + 2 * (3 * d * d + 2 * d * 16))
    # position 0: one key, no compressed key; a state's update and read
    assert ref.forward_flops(c, 0, 1) == (
        2 * weights + 6 * 4 * d * 8 + 2 * 4 * d * 1)
    # position 38 reads blocks 0, two of the rest, 8 and 3 keys of block 9
    assert int(ref.keys_read(c, np.asarray(38))) == 4 + 8 + 4 + 3
    one = ref.forward_flops(c, 38, 39)
    assert one == (2 * weights + 6 * 4 * d * 8
                   + 2 * (4 * d * 19 + 2 * d * 18))
    assert ref.forward_flops(c, 0, 39) == sum(
        ref.forward_flops(c, t, t + 1) for t in range(39))
    assert ref.forward_flops(c, 5, 5) == 0
    assert ref.reach(c) == []


def test_the_swapped_block_and_the_dense_read_move_the_reference():
    _, variables = model(CUT)
    tokens = np.stack(prompts_of([61]))
    want = reference_logits(CUT, variables, tokens)
    for mode in ("swap", "dense"):
        moved = np.abs(reference_logits(CUT, variables, tokens, mode)
                       - want).max(-1)[0]
        # nothing to swap or to leave out up to `dense_len`
        assert moved[:16].max() < 1e-6 and moved[16:].max() > 1e-3, mode
