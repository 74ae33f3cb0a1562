"""Decode engine (models/generate.py DecodeEngine): bucketed prefill,
cache-windowed segments, and stop-token early exit must be pure layout —
greedy tokens exactly equal the per-length full-cache decoder's at every
bucket/window configuration — while sampling draws depend only on
(seed, row id, step), never on grouping."""

import jax
import numpy as np
import pytest

from mmlspark_tpu import DataTable
from mmlspark_tpu.models import ModelBundle
from mmlspark_tpu.models.definitions import build_model
from mmlspark_tpu.models.generate import (DecodeEngine, TextGenerator,
                                          bucket_length, decode_segments,
                                          make_generate_fn)

CFG = {"vocab_size": 32, "d_model": 32, "n_heads": 4, "n_layers": 2,
       "max_len": 48, "dtype": "float32"}


@pytest.fixture(scope="module")
def lm():
    module = build_model("TransformerLM", CFG)
    variables = module.init(jax.random.key(3), np.zeros((1, 4), np.int32))
    return module, variables


@pytest.fixture(scope="module")
def lm_bundle(lm):
    module, variables = lm
    return ModelBundle.from_module(module, variables)


# ------------------------------------------------------------- pure plans ---

def test_bucket_length_policy():
    # next power of two, floored at min_bucket
    assert bucket_length(5, 48, 8) == 8
    assert bucket_length(9, 48, 8) == 16
    assert bucket_length(16, 48, 8) == 16
    assert bucket_length(1, 48, 8, min_bucket=8) == 8
    # capped so bucket + budget always decodes: cap = 48 - 8 = 40
    assert bucket_length(33, 48, 8) == 40
    with pytest.raises(ValueError, match="max_len"):
        bucket_length(41, 48, 8)
    with pytest.raises(ValueError, match=">= 1"):
        bucket_length(0, 48, 8)


@pytest.mark.parametrize("bucket,max_new,chunk", [
    (8, 12, 16), (8, 40, 8), (16, 2, 4), (5, 33, 7), (8, 1, 16)])
def test_decode_segments_plan(bucket, max_new, chunk):
    segs = decode_segments(bucket, max_new, chunk)
    if max_new == 1:
        assert segs == []  # the single token comes from prefill
        return
    # segments tile scan steps 0..max_new-2 exactly, in order
    covered = [(t0 + i) for t0, seg_len, _ in segs for i in range(seg_len)]
    assert covered == list(range(max_new - 1))
    prev_w = 0
    for t0, seg_len, w in segs:
        assert seg_len <= chunk  # early-exit check at least once per chunk
        assert w % chunk == 0
        assert w >= prev_w       # windows only grow
        prev_w = w
        # the window covers every slot the segment writes
        assert bucket + (t0 + seg_len - 1) < w


# -------------------------------------------------- greedy parity (the pin) ---

def _ragged_rows(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG["vocab_size"], (n,)).astype(np.int32)
            for n in lengths]


def _engine_generate(engine, variables, rows):
    """Group rows by bucket and decode — the transform grouping, inlined."""
    out = [None] * len(rows)
    by_bucket = {}
    for i, r in enumerate(rows):
        by_bucket.setdefault(engine.bucket_for(len(r)), []).append(i)
    for bucket, idxs in sorted(by_bucket.items()):
        prompts = np.zeros((len(idxs), bucket), np.int32)
        tl = np.asarray([len(rows[i]) for i in idxs], np.int32)
        for j, i in enumerate(idxs):
            prompts[j, :tl[j]] = rows[i]
        got = engine.generate(variables, prompts, tl,
                              row_ids=np.asarray(idxs, np.int32))
        for j, i in enumerate(idxs):
            out[i] = got[j]
    return out


def test_greedy_parity_with_per_length_decoder(lm):
    """THE engine contract: bucketed + windowed greedy tokens are exactly
    the full-cache per-length decoder's, across rows that pad (3, 5 in
    bucket 8), rows that fill their bucket exactly (8), and rows in a
    second bucket (9) — with a chunk small enough that the decode crosses
    several window growths."""
    module, variables = lm
    max_new = 12
    engine = DecodeEngine(module, max_new, chunk=8)
    rows = _ragged_rows([3, 5, 8, 9, 3])
    got = _engine_generate(engine, variables, rows)
    for r, g in zip(rows, got):
        fn = make_generate_fn(module, len(r), max_new)
        ref = np.asarray(fn(variables, r[None], jax.random.key(0)))
        np.testing.assert_array_equal(g, ref[0, len(r):])
    # (program-count consolidation is pinned at the realistic default
    # chunk in test_transform_program_consolidation — a chunk this small
    # deliberately trades programs for window granularity)


@pytest.mark.slow
@pytest.mark.parametrize("chunk,max_new", [(4, 9), (16, 17), (64, 5)])
def test_greedy_parity_across_window_configs(lm, chunk, max_new):
    """The same pin at finer/coarser window growth and generation budgets
    (chunk smaller than, comparable to, and larger than the buckets)."""
    module, variables = lm
    engine = DecodeEngine(module, max_new, chunk=chunk)
    rows = _ragged_rows([1, 4, 7, 8, 13], seed=chunk)
    got = _engine_generate(engine, variables, rows)
    for r, g in zip(rows, got):
        fn = make_generate_fn(module, len(r), max_new)
        ref = np.asarray(fn(variables, r[None], jax.random.key(0)))
        np.testing.assert_array_equal(g, ref[0, len(r):])


def test_engine_validation(lm):
    module, variables = lm
    with pytest.raises(ValueError, match="max_new_tokens"):
        DecodeEngine(module, 0)
    with pytest.raises(ValueError, match="stop token"):
        DecodeEngine(module, 4, stop_tokens=(99,))
    with pytest.raises(ValueError, match="chunk"):
        DecodeEngine(module, 4, chunk=0)
    engine = DecodeEngine(module, 8)
    with pytest.raises(ValueError, match="max_len"):
        engine.generate(variables, np.zeros((1, 48), np.int32),
                        np.asarray([48]))
    with pytest.raises(ValueError, match="bucket width"):
        engine.generate(variables, np.zeros((1, 8), np.int32),
                        np.asarray([9]))


# ------------------------------------------------------- stop-token early exit ---

def test_stop_tokens_freeze_and_early_exit(lm):
    """A row that emits a stop token freezes on it; once every row has
    stopped, the remaining segments are skipped (host check between
    segments) and the skipped tail is filled with the frozen tokens —
    byte-identical output to decoding all max_new steps."""
    module, variables = lm
    max_new = 24
    rows = _ragged_rows([4, 6])
    # the oracle run: find a token every row emits early
    free = DecodeEngine(module, max_new, chunk=4)
    base = _engine_generate(free, variables, rows)
    stop = int(base[0][1])  # row 0's second generated token
    if stop not in base[1][:3].tolist():
        stop_set = (stop, int(base[1][1]))
    else:
        stop_set = (stop,)
    engine = DecodeEngine(module, max_new, chunk=4, stop_tokens=stop_set)
    got = _engine_generate(engine, variables, rows)
    # early exit actually fired: fewer tokens computed than requested
    assert engine.last_new_tokens_computed < max_new
    assert engine.last_segments_run < len(decode_segments(8, max_new, 4))
    for g in got:
        assert g.shape == (max_new,)
        hit = np.nonzero(np.isin(g, np.asarray(stop_set)))[0]
        assert hit.size, "every row should have stopped"
        # frozen after the first stop token: the tail repeats it
        assert (g[hit[0]:] == g[hit[0]]).all()
    # prefix before the stop matches the stop-free decode exactly
    for g, b in zip(got, base):
        hit = np.nonzero(np.isin(g, np.asarray(stop_set)))[0][0]
        np.testing.assert_array_equal(g[:hit + 1], b[:hit + 1])


def test_transform_stop_tokens_trim_rows(lm_bundle):
    """TextGenerator.stopTokens trims each output row after its first stop
    token (kept); rows that never stop keep the full budget."""
    module = lm_bundle.module()
    rows = np.empty(2, object)
    rows[0] = np.asarray([1, 2, 3], np.int32)
    rows[1] = np.asarray([4, 5], np.int32)
    table = DataTable({"prompt": rows})
    base = TextGenerator(lm_bundle, inputCol="prompt", outputCol="out",
                         maxNewTokens=6).transform(table)["out"]
    stop = int(np.asarray(base[0])[3])  # row 0's first generated token
    out = TextGenerator(lm_bundle, inputCol="prompt", outputCol="out",
                        maxNewTokens=6,
                        stopTokens=[stop]).transform(table)["out"]
    row0 = np.asarray(out[0])
    assert row0[-1] == stop and len(row0) <= 3 + 6
    np.testing.assert_array_equal(row0, np.asarray(base[0])[:len(row0)])
    row1 = np.asarray(out[1])
    hits = np.nonzero(np.asarray(base[1])[2:] == stop)[0]
    expected_len = 2 + (hits[0] + 1 if hits.size else 6)
    assert len(row1) == expected_len


# ------------------------------------------------------------ sampling RNG ---

def test_sampling_grouping_independent(lm_bundle):
    """The per-group RNG-reuse fix, pinned: a row's draws depend on its
    table position and the seed, NOT on which length/bucket group it
    lands in or which rows share its batch.  Changing row 1's length
    regroups rows 0 and 2; their samples must not change."""
    r0 = np.asarray([1, 2, 3], np.int32)
    r2 = np.asarray([6, 7, 8], np.int32)

    def run(middle):
        rows = np.empty(3, object)
        rows[0], rows[1], rows[2] = r0, middle, r2
        return TextGenerator(
            lm_bundle, inputCol="prompt", outputCol="out", maxNewTokens=6,
            temperature=1.0, seed=7).transform(
                DataTable({"prompt": rows}))["out"]

    a = run(np.asarray([4, 5], np.int32))           # groups with nothing
    b = run(np.asarray([4, 5, 6, 7, 8, 9, 10, 11, 12], np.int32))  # regroups
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[2]), np.asarray(b[2]))
    # same seed reproduces; a different seed diverges somewhere
    c = run(np.asarray([4, 5], np.int32))
    for x, y in zip(a, c):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    d = TextGenerator(lm_bundle, inputCol="prompt", outputCol="out",
                      maxNewTokens=6, temperature=1.0, seed=8).transform(
        DataTable({"prompt": np.stack([r0, r2])}))["out"]
    a_gen = [np.asarray(a[0])[3:], np.asarray(a[2])[3:]]
    assert not all(np.array_equal(np.asarray(d[i])[3:], a_gen[i])
                   for i in range(2))


def test_sampled_tokens_in_vocab_with_stops(lm):
    """Windowed sampling + stop tokens: tokens stay in-vocab and the run
    is reproducible under the same seed."""
    module, variables = lm
    engine = DecodeEngine(module, 10, temperature=0.9, top_k=8,
                          stop_tokens=(0,), chunk=8)
    rows = _ragged_rows([3, 7])
    a = _engine_generate(engine, variables, rows)
    b = _engine_generate(engine, variables, rows)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
        assert (x >= 0).all() and (x < CFG["vocab_size"]).all()


# ------------------------------------------------------------ observability ---

def test_prefill_decode_spans_recorded(lm_bundle):
    """pipeline_timing around a transform attributes generation's two
    phases (observe/spans.py GENERATE_STAGES)."""
    from mmlspark_tpu import pipeline_timing
    rows = np.stack([np.asarray([1, 2, 3, 4], np.int32)] * 2)
    table = DataTable({"prompt": rows})
    gen = TextGenerator(lm_bundle, inputCol="prompt", outputCol="out",
                        maxNewTokens=6)
    with pipeline_timing() as spans:
        gen.transform(table)
    summary = spans.summary()
    assert summary["stage_prefill_s"] > 0
    assert summary["stage_decode_s"] > 0


def test_transform_program_consolidation(lm_bundle):
    """4 distinct prompt lengths in 2 buckets compile 3 programs (2
    prefill shapes + 1 shared segment — bucket offsets are traced, so
    coinciding windows share one compiled segment), where the per-length
    decoder compiled 4."""
    rows = np.empty(4, object)
    for j, n in enumerate([3, 4, 9, 10]):
        rows[j] = np.arange(n, dtype=np.int32)
    gen = TextGenerator(lm_bundle, inputCol="prompt", outputCol="out",
                        maxNewTokens=6)
    gen.transform(DataTable({"prompt": rows}))
    assert gen._engine_for().compiled_programs == 3


@pytest.mark.slow
def test_engine_over_mesh_matches_single_device(lm_bundle):
    """Bucketed decode over a data mesh (zero-pad rows born done) equals
    single-device decode row-for-row — greedy AND sampled (per-row
    streams make sampling batch-composition-independent too)."""
    from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(data=8))
    rows = np.empty(5, object)
    for i in range(5):
        rows[i] = ((np.arange(3 + i % 3, dtype=np.int32) + i)
                   % CFG["vocab_size"])
    table = DataTable({"prompt": rows})
    for kwargs in ({}, {"temperature": 0.8, "seed": 3},
                   {"stopTokens": [11]}):
        single = TextGenerator(lm_bundle, inputCol="prompt",
                               outputCol="out", maxNewTokens=5,
                               **kwargs).transform(table)["out"]
        meshed = TextGenerator(lm_bundle, inputCol="prompt",
                               outputCol="out", maxNewTokens=5,
                               **kwargs).set_mesh(mesh).transform(
            table)["out"]
        for a, b in zip(single, meshed):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_set_bundle_under_mesh_drops_placed_weights(lm, lm_bundle):
    """Swapping the bundle of a meshed TextGenerator must re-place the
    weights: the per-mesh device copy is keyed by mesh only, so a stale
    entry would keep generating from the previous bundle."""
    from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh

    other = ModelBundle.init(lm[0], (1, 8), seed=1)
    rows = np.empty(2, object)
    rows[0] = np.arange(4, dtype=np.int32)
    rows[1] = np.arange(6, dtype=np.int32) + 2
    table = DataTable({"prompt": rows})
    want = TextGenerator(other, inputCol="prompt", outputCol="out",
                         maxNewTokens=5).transform(table)["out"]
    gen = TextGenerator(lm_bundle, inputCol="prompt", outputCol="out",
                        maxNewTokens=5).set_mesh(make_mesh(MeshSpec(data=8)))
    before = gen.transform(table)["out"]
    after = gen.set_bundle(other).transform(table)["out"]
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(before, after))
    for a, b in zip(want, after):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
