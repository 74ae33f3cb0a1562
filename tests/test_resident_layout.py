"""The RESIDENT layout of a TransformerLM's K/V windows
(`TransformerDecoding.folds`): off-mesh, with a model-dtype cache and no
speculation, the state a finished prompt hands on is head-folded, (B, W,
H*D) a leaf, and stays so between calls: through `merge_cache_rows`,
window growth, every segment, the prefix pool's slices and handoff pages.
No program that steps re-tiles a window.  An int8, a speculating and a
meshed engine keep (B, W, H, D).  The fold is a re-tiling, no arithmetic:
token ids are the offline engine's and the parent commit's, recorded
below (`python tests/test_resident_layout.py` prints them from whichever
tree is on the path).  Tiny presets on the CPU; nothing here is a timing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.models.bundle import ModelBundle
from mmlspark_tpu.models.definitions import build_model
from mmlspark_tpu.models.generate import (DecodeEngine,
                                          deserialize_cache_row,
                                          serialize_cache_row)
from mmlspark_tpu.resilience.clock import VirtualClock
from mmlspark_tpu.serve import ServeConfig, ServingEngine

LM = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, max_len=96,
          dtype="float32")
HYBRID = dict(vocab_size=97, d_model=32, n_heads=4, n_kv_heads=2,
              layer_types=["conv", "full_attention"], n_dense_layers=1,
              mlp_width=48, n_experts=8, experts_per_token=4,
              expert_width=24, max_len=96, dtype="float32")
NEW, SEG, CHUNK = 24, 4, 16


def _bundle(arch: str, cfg: dict) -> ModelBundle:
    module = build_model(arch, dict(cfg))
    variables = jax.jit(module.init)(jax.random.key(0),
                                     np.zeros((1, 8), np.int32))
    return ModelBundle.from_module(module, variables)


def _prompts(vocab: int = 64) -> list:
    """Seven prompts over two buckets (8 and 16), so late joins, two
    groups, and answers long enough that both windows grow."""
    rng = np.random.default_rng(33)
    return [rng.integers(1, vocab, n).astype(np.int32)
            for n in (5, 12, 7, 16, 3, 9, 8)]


def offline_tokens(bundle, **how) -> list:
    """Each prompt decoded alone by the offline engine."""
    eng = DecodeEngine(bundle.module(), NEW, chunk=CHUNK, **how)
    out = []
    for p in _prompts(bundle.module().vocab_size):
        padded = np.zeros((1, eng.bucket_for(len(p))), np.int32)
        padded[0, :len(p)] = p
        out.append(eng.generate(bundle.variables, padded, np.asarray(
            [len(p)], np.int32))[0].tolist())
    return out


def _engine(bundle, **overrides) -> ServingEngine:
    kw = dict(max_new_tokens=NEW, max_batch=2, queue_capacity=32,
              segment_steps=SEG, default_deadline_s=1000.0,
              drain_timeout_s=50.0, cache_chunk=CHUNK)
    kw.update(overrides)
    return ServingEngine(bundle, ServeConfig(**kw),
                         clock=VirtualClock()).warmup()


def served_tokens(bundle, between=None, **overrides) -> list:
    """The prompts through `ServingEngine` on the VirtualClock, two slots
    a bucket: every request but the first two of a bucket is a late join;
    answers of 24, 9 and 17 tokens; the third request is cancelled after
    its third pass.  `between(engine)` runs after every pass.  Returns
    each request's tokens (the cancelled one's: what it had)."""
    engine = _engine(bundle, **overrides)
    reqs = [engine.submit(p, (NEW, 9, 17)[i % 3])
            for i, p in enumerate(_prompts(bundle.module().vocab_size))]
    for n in range(400):
        if all(r.finished for r in reqs):
            break
        engine._tick()
        if n == 2:
            engine.cancel_request(reqs[2])
        if between is not None:
            between(engine)
    assert all(r.finished for r in reqs), [r.status for r in reqs]
    assert [r.status for r in reqs].count("cancelled") == 1
    return [list(r.tokens) for r in reqs]


# What the parent commit (bd31242, before the resident layout) gave for
# the scenarios above: `python tests/test_resident_layout.py` on its tree.
PARENT = {
    "offline":
        [[56, 58, 20, 2, 33, 56, 20, 63, 56, 56, 4, 53, 56, 37, 56, 56, 4,
        56, 4, 57, 56, 4, 53, 56], [26, 62, 7, 56, 62, 26, 27, 62, 56, 62,
        27, 62, 26, 62, 62, 26, 62, 62, 26, 62, 26, 62, 26, 62], [25, 41,
        62, 27, 27, 20, 4, 20, 27, 28, 62, 56, 27, 56, 62, 12, 12, 12, 27,
        28, 62, 3, 27, 27], [62, 62, 56, 62, 56, 62, 27, 27, 27, 2, 62,
        56, 12, 62, 56, 40, 56, 20, 12, 20, 56, 62, 56, 28], [62, 12, 62,
        12, 12, 54, 27, 53, 12, 27, 27, 12, 12, 7, 4, 20, 53, 56, 62, 12,
        15, 57, 62, 62], [41, 56, 25, 25, 56, 42, 2, 56, 62, 56, 56, 56,
        4, 25, 62, 56, 56, 56, 56, 56, 28, 56, 42, 56], [27, 54, 56, 20,
        62, 56, 55, 20, 62, 62, 56, 62, 56, 62, 27, 62, 62, 56, 62, 56,
        56, 62, 56, 28]],
    "served":
        [[56, 58, 20, 2, 33, 56, 20, 63, 56, 56, 4, 53, 56, 37, 56, 56, 4,
        56, 4, 57, 56, 4, 53, 56], [26, 62, 7, 56, 62, 26, 27, 62, 56],
        [25, 41, 62, 27, 27, 20, 4, 20, 27, 28, 62, 56, 27], [62, 62, 56,
        62, 56, 62, 27, 27, 27, 2, 62, 56, 12, 62, 56, 40, 56, 20, 12, 20,
        56, 62, 56, 28], [62, 12, 62, 12, 12, 54, 27, 53, 12], [41, 56,
        25, 25, 56, 42, 2, 56, 62, 56, 56, 56, 4, 25, 62, 56, 56], [27,
        54, 56, 20, 62, 56, 55, 20, 62, 62, 56, 62, 56, 62, 27, 62, 62,
        56, 62, 56, 56, 62, 56, 28]],
    "offline_int8":
        [[56, 58, 20, 2, 33, 56, 20, 63, 56, 56, 4, 53, 56, 37, 56, 56, 4,
        56, 4, 57, 56, 4, 53, 56], [26, 62, 7, 56, 62, 26, 27, 62, 56, 62,
        27, 62, 26, 62, 62, 26, 62, 62, 26, 62, 26, 62, 26, 62], [25, 41,
        62, 27, 27, 20, 4, 20, 27, 28, 62, 56, 27, 56, 62, 12, 12, 12, 27,
        28, 62, 3, 27, 27], [62, 62, 56, 62, 56, 62, 27, 27, 27, 2, 62,
        56, 12, 62, 56, 40, 56, 20, 12, 20, 56, 62, 56, 28], [62, 12, 62,
        12, 12, 54, 27, 53, 12, 27, 27, 12, 12, 7, 4, 20, 53, 56, 62, 12,
        15, 57, 62, 62], [41, 56, 25, 25, 56, 42, 2, 56, 62, 56, 56, 56,
        4, 25, 62, 56, 56, 56, 56, 56, 28, 56, 42, 56], [27, 54, 56, 20,
        62, 56, 28, 20, 62, 62, 56, 62, 56, 4, 48, 20, 62, 56, 44, 27, 58,
        2, 54, 12]],
    "served_int8":
        [[56, 58, 20, 2, 33, 56, 20, 63, 56, 56, 4, 53, 56, 37, 56, 56, 4,
        56, 4, 57, 56, 4, 53, 56], [26, 62, 7, 56, 62, 26, 27, 62, 56],
        [25, 41, 62, 27, 27, 20, 4, 20, 27, 28, 62, 56, 27], [62, 62, 56,
        62, 56, 62, 27, 27, 27, 2, 62, 56, 12, 62, 56, 40, 56, 20, 12, 20,
        56, 62, 56, 28], [62, 12, 62, 12, 12, 54, 27, 53, 12], [41, 56,
        25, 25, 56, 42, 2, 56, 62, 56, 56, 56, 4, 25, 62, 56, 56], [27,
        54, 56, 20, 62, 56, 28, 20, 62, 62, 56, 62, 56, 4, 48, 20, 62, 56,
        44, 27, 58, 2, 54, 12]],
    "hybrid_served":
        [[80, 80, 80, 80, 80, 80, 80, 80, 80, 80, 80, 80, 80, 80, 80, 80,
        80, 80, 80, 80, 80, 80, 80, 80], [37, 16, 16, 16, 16, 16, 16, 16,
        16], [5, 5, 5, 5, 5, 5, 5, 5, 5, 5, 5, 5, 5], [10, 10, 10, 10, 10,
        10, 10, 10, 10, 10, 10, 10, 10, 10, 10, 10, 10, 10, 10, 10, 10,
        10, 10, 10], [83, 83, 83, 83, 83, 83, 83, 83, 83], [83, 83, 83,
        83, 83, 83, 83, 83, 83, 83, 83, 83, 83, 83, 83, 83, 83], [78, 78,
        78, 78, 78, 78, 78, 78, 78, 78, 78, 78, 78, 78, 78, 78, 78, 78,
        78, 78, 78, 78, 78, 78]],
}


def _leaf_ranks(caches) -> set:
    return {leaf.ndim for layer in caches for leaf in layer}


@pytest.fixture(scope="module")
def lm():
    return _bundle("TransformerLM", LM)


@pytest.fixture(scope="module")
def hybrid():
    return _bundle("HybridLM", HYBRID)


# -- (a) the same tokens, on rank-3 leaves ----------------------------------

def test_offline_engine_gives_the_parents_tokens_on_folded_windows(lm):
    eng = DecodeEngine(lm.module(), NEW, chunk=CHUNK)
    assert eng._decoding.folds
    assert _leaf_ranks(eng.empty_state(2, 8)) == {3}
    assert offline_tokens(lm) == PARENT["offline"]


def test_serving_gives_the_offline_tokens_with_rank_3_state_between_calls(
        lm):
    seen = []

    def between(engine):
        for g in engine._groups.values():
            if g.caches is not None:
                assert _leaf_ranks(g.caches) == {3}
                seen.append(engine._engines["primary"].state_window(
                    g.caches))
    got = served_tokens(lm, between)
    assert got == PARENT["served"]
    # windows grew under resident rows (16 -> 32 -> 48), late joins came
    assert len(set(seen)) >= 3
    want = offline_tokens(lm)
    for i, (g, w) in enumerate(zip(got, want)):
        n = (NEW, 9, 17)[i % 3]
        assert g == w[:len(g)] and (i == 2 or len(g) == n)


def test_hybrid_lm_serves_the_parents_tokens(hybrid):
    assert served_tokens(hybrid) == PARENT["hybrid_served"]


# -- (e) the engines that keep (B, W, H, D) ----------------------------------

def test_an_int8_cache_keeps_its_layout_and_tokens(lm):
    eng = DecodeEngine(lm.module(), NEW, chunk=CHUNK, cache_dtype="int8")
    assert not eng._decoding.folds
    assert _leaf_ranks(eng.empty_state(2, 8)) == {4, 3}   # payloads, scales
    assert offline_tokens(lm, cache_dtype="int8") == PARENT["offline_int8"]
    assert served_tokens(lm, cache_dtype="int8") == PARENT["served_int8"]


def test_a_speculating_engine_keeps_its_layout_and_tokens(lm):
    from mmlspark_tpu.zoo import truncated_draft_bundle
    draft = truncated_draft_bundle(lm, n_layers=1)
    eng = DecodeEngine(lm.module(), NEW, chunk=CHUNK,
                       draft_module=draft.module(), spec_tokens=3)
    assert not eng._decoding.folds
    assert _leaf_ranks(eng.empty_state(2, 8)) == {4}
    p = _prompts()[1]
    padded = np.zeros((1, 16), np.int32)
    padded[0, :len(p)] = p
    got = eng.generate(lm.variables, padded, np.asarray([len(p)], np.int32),
                       draft_variables=draft.variables)[0].tolist()
    # greedy speculation commits the target's own chain: the parent's too
    assert got == PARENT["offline"][1]


def test_a_meshed_engine_keeps_its_layout_and_tokens(lm):
    from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
    from mmlspark_tpu.parallel.partition import (UNMATCHED_REPLICATE,
                                                 shard_tree)
    mesh = make_mesh(MeshSpec(data=2, model=2), jax.devices()[:4])
    eng = DecodeEngine(lm.module(), NEW, chunk=CHUNK, mesh=mesh)
    assert not eng._decoding.folds
    variables = shard_tree(lm.variables, mesh,
                           on_unmatched=UNMATCHED_REPLICATE)
    p = _prompts()
    rows = [1, 3]                          # both of bucket 16
    padded = np.zeros((2, 16), np.int32)
    for r, i in enumerate(rows):
        padded[r, :len(p[i])] = p[i]
    true_len = np.asarray([len(p[i]) for i in rows], np.int32)
    _, _, caches = eng.serve_prefill(
        variables, padded, true_len, np.ones(2, bool),
        jax.random.split(jax.random.key(0), 2))
    assert _leaf_ranks(caches) == {4}
    got = eng.generate(variables, padded, true_len).tolist()
    assert got == [PARENT["offline"][i] for i in rows]


# -- (b) no program that steps re-tiles a window ------------------------------

_RETILES = {"reshape", "transpose", "copy", "copy_p"}


def _window_retiles(jaxpr, size: int, found: list) -> list:
    """Equations of `jaxpr`, and of every jaxpr inside it, that reshape,
    transpose or copy an operand of a window leaf's size or more."""
    from test_resident_weights import _sub_jaxprs
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _RETILES and any(
                getattr(v.aval, "size", 0) >= size for v in eqn.invars
                if hasattr(v, "aval")):
            found.append((eqn.primitive.name,
                          [tuple(v.aval.shape) for v in eqn.invars
                           if hasattr(v, "aval")]))
        for inner, _ in _sub_jaxprs(eqn):
            _window_retiles(inner, size, found)
    return found


def _stepping_jaxpr(eng, variables, program: str, rows, bucket, w_in):
    """The `serve_segment` or `segment` program of `eng`, traced on a
    resident state of `w_in` slots for a window of 2 x CHUNK."""
    window = 2 * CHUNK
    state = eng._decoding.empty_state(rows, w_in, resident=True)
    zeros = jnp.zeros(rows, jnp.int32)
    keys = jax.random.split(jax.random.key(0), rows)
    if program == "serve_segment":
        return jax.make_jaxpr(eng._serve_segment, static_argnums=(0, 1))(
            SEG, window, variables, state, zeros, jnp.zeros(rows, bool),
            zeros + 5, zeros + NEW, jnp.asarray(bucket, jnp.int32), zeros,
            keys)
    return jax.make_jaxpr(eng._segment, static_argnums=(0, 1))(
        SEG, window, variables, state, zeros, jnp.zeros(rows, bool),
        zeros + 5, jnp.asarray(bucket, jnp.int32),
        jnp.asarray(0, jnp.int32), keys)


@pytest.mark.parametrize("w_in", [2 * CHUNK, CHUNK])      # as is, and grown
@pytest.mark.parametrize("program", ["serve_segment", "segment"])
def test_no_stepping_program_retiles_a_window(lm, program, w_in,
                                              monkeypatch):
    """Traced as the chip compiles it (the fused kernel, not the CPU's
    reference, which unfolds what it reads): the programs of a folding
    decoding hold no reshape, transpose or copy of anything as large as
    a window leaf.  The same walk finds the per-step folds of an engine
    whose windows stay (B, W, H, D) under the kernel (an int8 cache): it
    sees what it is there to see."""
    from mmlspark_tpu.ops import decode_attention
    monkeypatch.setattr(decode_attention, "_auto_interpret", lambda: False)
    bf16 = _bundle("TransformerLM", dict(LM, dtype="bfloat16"))
    rows, bucket = 2, 8
    size = rows * CHUNK * LM["d_model"]                # the smaller leaf
    eng = DecodeEngine(bf16.module(), NEW, chunk=CHUNK)
    closed = _stepping_jaxpr(eng, bf16.variables, program, rows, bucket,
                             w_in)
    assert "decode_sqa" in str(closed)
    assert _window_retiles(closed.jaxpr, size, []) == []
    int8 = DecodeEngine(bf16.module(), NEW, chunk=2 * CHUNK,
                        cache_dtype="int8")
    closed = _stepping_jaxpr(int8, bf16.variables, program, rows, bucket,
                             2 * CHUNK)
    assert {name for name, _ in _window_retiles(
        closed.jaxpr, size, [])} == {"reshape"}


def test_a_serving_segment_steps_on_the_buffers_it_is_handed(lm, hybrid):
    """`serve_step` donates the resident state where the window does not
    grow: the step loop's carry is the caller's own buffers (no copy of
    every window into the carry), and the caller goes on with what is
    returned.  A call that grows the windows keeps its arguments."""
    for bundle in (lm, hybrid):
        eng = DecodeEngine(bundle.module(), NEW, chunk=CHUNK)
        tok, done, cohort = _prefilled(eng, bundle)
        state = DecodeEngine.merge_cache_rows(
            eng.empty_state(2, 16), cohort, [0, 1], [0, 1],
            kinds=eng.state_kinds)
        args = (np.asarray([12, 16], np.int32), np.full(2, NEW, np.int32),
                16)
        keys = jax.random.split(jax.random.key(0), 2)
        leaves = jax.tree_util.tree_leaves
        steady, toks, tok, done = eng.serve_step(
            bundle.variables, state, tok, done, *args,
            np.zeros(2, np.int32), keys, SEG, 32)
        assert all(leaf.is_deleted() for leaf in leaves(state))
        assert eng.state_window(steady) == 32
        grown, *_ = eng.serve_step(
            bundle.variables, steady, tok, done, *args,
            np.full(2, 14, np.int32), keys, SEG, 48)
        assert eng.state_window(grown) == 48
        assert not any(leaf.is_deleted() for leaf in leaves(steady))
        # the consumed state's tokens are the kept state's: one program
        again = DecodeEngine.merge_cache_rows(
            eng.empty_state(2, 16), cohort, [0, 1], [0, 1],
            kinds=eng.state_kinds)
        first = _prefilled(eng, bundle)
        kept = eng._serve_segment_grows(
            SEG, 32, bundle.variables, again, first[0], first[1],
            jnp.asarray(args[0]), jnp.asarray(args[1]),
            jnp.asarray(16, jnp.int32), jnp.zeros(2, jnp.int32), keys)
        np.testing.assert_array_equal(np.asarray(kept[1]),
                                      np.asarray(toks))
        assert not any(leaf.is_deleted() for leaf in leaves(again))


# -- (d) who else reads the resident layout ----------------------------------

def _prefilled(eng, bundle, rows=(1, 3)):
    p = _prompts()
    padded = np.zeros((len(rows), 16), np.int32)
    for r, i in enumerate(rows):
        padded[r, :len(p[i])] = p[i]
    true_len = np.asarray([len(p[i]) for i in rows], np.int32)
    keys = jax.random.split(jax.random.key(0), len(rows))
    return eng.serve_prefill(bundle.variables, padded, true_len,
                             np.ones(len(rows), bool), keys)


def test_a_handoff_page_round_trip_on_folded_state(lm):
    eng = DecodeEngine(lm.module(), NEW, chunk=CHUNK)
    _, _, caches = _prefilled(eng, lm)
    assert _leaf_ranks(caches) == {3}
    back = deserialize_cache_row(serialize_cache_row(caches, 1, CHUNK))
    assert _leaf_ranks(back) == {3}
    resident = eng.empty_state(4, 16)
    merged = DecodeEngine.merge_cache_rows(resident, back, [2], [0],
                                           kinds=eng.state_kinds)
    for layer, src in zip(merged, caches):
        for got, want in zip(layer, src):
            assert got.shape == (4,) + want.shape[1:]
            np.testing.assert_array_equal(np.asarray(got[2]),
                                          np.asarray(want[1]))
            assert not np.asarray(got[:2]).any()
    # the same bytes whichever layout holds them
    flat = DecodeEngine(lm.module(), NEW, chunk=CHUNK, cache_dtype="int8")
    assert (eng.state_bytes(resident)
            == {"window": 4 * 32 * 32 * 4 * 2 * 2, "fixed": 0})
    assert eng.state_window(merged) == 32
    # a page of the other layout is refused by name, not scattered
    other = [tuple(c.reshape(c.shape[:2] + (4, 8)) for c in layer)
             for layer in back]
    with pytest.raises(ValueError, match="state layout"):
        DecodeEngine.merge_cache_rows(resident, other, [2], [0],
                                      kinds=eng.state_kinds)
    with pytest.raises(ValueError, match="state layout"):
        DecodeEngine.merge_cache_rows(flat.empty_state(4, 16), back, [2],
                                      [0], kinds=flat.state_kinds)


def test_reopen_prompt_converts_donor_rows_of_the_other_layout(lm):
    """A prefix row made by an engine of one layout resumes on an engine
    of the other: `reopen_prompt` re-tiles it, and the resumed prompt's
    tokens are the fresh prompt's."""
    folding = DecodeEngine(lm.module(), NEW, chunk=CHUNK)
    from mmlspark_tpu.zoo import truncated_draft_bundle
    draft = truncated_draft_bundle(lm, n_layers=1)
    keeps = DecodeEngine(lm.module(), NEW, chunk=CHUNK,
                         draft_module=draft.module(), spec_tokens=2)
    p = _prompts()[3]                                  # 16 tokens
    padded = p[None].astype(np.int32)
    args = (np.asarray([16], np.int32), np.ones(1, bool),
            jax.random.split(jax.random.key(0), 1))
    for maker, taker in ((folding, keeps), (keeps, folding)):
        tok, _, fresh = taker.serve_prefill(lm.variables, padded, *args)
        _, _, donor = maker.serve_prefill(lm.variables, padded, *args)
        prefix = [tuple(c[:, :8] for c in layer) for layer in donor]
        before = taker.relayout_bytes
        tok2, _, resumed = taker.serve_prefill_resume(
            lm.variables, padded, args[0], 8, prefix, *args[1:])
        assert int(tok2[0]) == int(tok[0])
        assert _leaf_ranks(resumed) == _leaf_ranks(fresh)
        for a, b in zip(jax.tree_util.tree_leaves(resumed),
                        jax.tree_util.tree_leaves(fresh)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)
        # the donor's 8 slots were re-tiled once (and, on folded windows,
        # the suffix chunk's dense read unfolded the whole row)
        donor_bytes = 8 * 32 * 4 * 2 * 2
        row_bytes = 32 * 32 * 4 * 2 * 2
        assert taker.relayout_bytes - before == donor_bytes + (
            row_bytes if taker is folding else 0)


def test_prefix_reuse_on_folded_rows(lm):
    engine = _engine(lm, prefix_cache=True, prefix_max_rows=64)
    prompt = (np.arange(1, 41, dtype=np.int32) % 63) + 1
    tokens = []
    for _ in range(2):
        req = engine.submit(prompt)
        for _ in range(200):
            if req.finished:
                break
            engine._tick()
        assert req.status == "ok"
        tokens.append(list(req.tokens))
    assert tokens[0] == tokens[1]
    stats = engine.prefix_stats()
    assert stats["hits"] >= 1 and stats["leased_rows"] == 0
    hit = engine._prefix.acquire(prompt, 32)
    assert hit.n_tokens == 32 and _leaf_ranks(hit.rows[0]) == {3}
    engine._prefix.release(hit)


# -- (f) the counter ----------------------------------------------------------

def test_state_relayout_bytes_counts_joins_and_not_segments(lm, hybrid):
    engine = _engine(lm)
    row = lambda window: window * LM["d_model"] * 4 * 2 * LM["n_layers"]
    warm = engine.stats()["state_relayout_bytes"]
    req = engine.submit(_prompts()[0], NEW)            # bucket 8: window 16
    engine._tick()
    joined = engine.stats()["state_relayout_bytes"]
    # a short prompt takes the dense read, which unfolds its own row once
    assert joined - warm == row(16)
    while not req.finished:
        engine._tick()
    stats = engine.stats()
    assert stats["segments_dispatched"] >= 5
    assert stats["state_relayout_bytes"] == joined     # 0 a segment
    # a whole prompt of flash length re-tiles nothing at all
    decoding = engine._engines["primary"]._decoding
    state = decoding.empty_state(1, 640)
    assert decoding.relayout_bytes("prompt", state, 512) == 0
    assert decoding.relayout_bytes("prompt", state, 256) == sum(
        leaf.nbytes for layer in state for leaf in layer)
    assert decoding.relayout_bytes("step", state) == 0
    # a model with one layout never re-tiles
    other = _engine(hybrid)
    done = other.submit(_prompts(97)[0], NEW)
    while not done.finished:
        other._tick()
    assert other.stats()["state_relayout_bytes"] == 0


if __name__ == "__main__":
    import json
    lm_, hy_ = _bundle("TransformerLM", LM), _bundle("HybridLM", HYBRID)
    print(json.dumps({
        "offline": offline_tokens(lm_),
        "served": served_tokens(lm_),
        "offline_int8": offline_tokens(lm_, cache_dtype="int8"),
        "served_int8": served_tokens(lm_, cache_dtype="int8"),
        "hybrid_served": served_tokens(hy_),
    }))
