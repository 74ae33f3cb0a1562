"""The serving engine's count of the K/V blocks a decode step's fused read
lists (`decode_blocks_read`) beside those a dense grid reads
(`decode_blocks_dense`): `DecodeEngine.serve_blocks` over the segment's
own masks, by the kernel's own rule (`ops/decode_attention.block_table`),
against a count written out block by block here; summed by
`ServingEngine._harvest` over every dispatched segment.  Tiny shapes on
the CPU, where the read itself takes the reference: the count is what the
chip's kernel lists."""

import jax
import numpy as np
import pytest

from mmlspark_tpu.models.bundle import ModelBundle
from mmlspark_tpu.models.definitions import build_model
from mmlspark_tpu.models.generate import DecodeEngine
from mmlspark_tpu.resilience.clock import VirtualClock
from mmlspark_tpu.serve import ServeConfig, ServingEngine

LM = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, max_len=384,
          dtype="float32")
HYBRID = dict(vocab_size=97, d_model=32, n_heads=4, n_kv_heads=2,
              layer_types=["conv", "full_attention"], n_dense_layers=2,
              mlp_width=48, max_len=384, dtype="float32")


def _blocks_by_hand(true_len, bucket, t_row, steps, window, block):
    """A step's blocks that hold a visible slot of each row: its prompt
    [0, true_len) or its decode slots [bucket, bucket + t_row + s]."""
    listed = 0
    for n, t in zip(true_len, t_row):
        for s in range(steps):
            last = min(bucket + t + s, window - 1)
            for j in range(window // block):
                lo, hi = j * block, (j + 1) * block - 1
                listed += lo < n or (hi >= bucket and lo <= last)
    return listed, len(true_len) * steps * (window // block)


def _module(arch, cfg):
    return build_model(arch, dict(cfg))


@pytest.mark.parametrize("window,chunk", [(272, 16), (384, 128), (640, 128)])
def test_serve_blocks_counts_the_listed_blocks_of_every_row_and_step(
        window, chunk):
    eng = DecodeEngine(_module("TransformerLM", LM), 24, chunk=chunk)
    block = eng._decoding.read_block(window)
    # float32's sublane tile is 8: 272 = 17 blocks of 16, else 128
    assert block == (16 if window == 272 else 128)
    bucket = 256
    cases = {
        # live rows with bucket holes, a released row (true_len 1, t_row 0)
        "mixed": ([130, 200, 1, 256], [0, 5, 0, 9]),
        # every row sees every block of the window
        "all_visible": ([256] * 3, [window - bucket - 4] * 3),
    }
    for true_len, t_row in cases.values():
        want = _blocks_by_hand(true_len, bucket, t_row, 4, window, block)
        got = eng.serve_blocks(np.asarray(true_len, np.int32), bucket,
                               np.asarray(t_row, np.int32), 4, window)
        assert got == want
    assert eng.serve_blocks(np.asarray([256] * 3), bucket,
                            np.asarray([window - bucket - 4] * 3), 4,
                            window) == (3 * 4 * (window // block),) * 2


def test_serve_blocks_are_none_where_steps_take_no_fused_read():
    hybrid = DecodeEngine(_module("HybridLM", HYBRID), 24, chunk=16)
    assert hybrid._decoding.read_block(272) is None
    assert hybrid.serve_blocks(np.asarray([130]), 256, np.asarray([0]), 4,
                               272) == (0, 0)
    # an int8 cache's block is int8's: 32 sublanes, so 272 has none
    int8 = DecodeEngine(_module("TransformerLM", LM), 24, chunk=16,
                        cache_dtype="int8")
    assert int8._decoding.read_block(272) is None
    assert int8._decoding.read_block(384) == 128


def _serving(arch, cfg, chunk):
    module = _module(arch, cfg)
    variables = jax.jit(module.init)(jax.random.key(0),
                                     np.zeros((1, 8), np.int32))
    return ServingEngine(
        ModelBundle.from_module(module, variables),
        ServeConfig(max_new_tokens=24, max_batch=3, queue_capacity=8,
                    segment_steps=4, default_deadline_s=100.0,
                    cache_chunk=chunk), clock=VirtualClock()).warmup()


def _drive(engine, lengths, new):
    """Serve prompts of `lengths`, recording every segment's dispatch:
    `(stats gained, [(true_len, bucket, t_row, steps, window), ...])`."""
    eng = engine._engines["primary"]
    seen = []
    step = eng.serve_step

    def recording(variables, caches, tok, done, true_len, budget, bucket,
                  t_row, keys, seg_len, window):
        window = max(int(window), eng.state_window(caches))
        seen.append((np.array(true_len), int(bucket), np.array(t_row),
                     seg_len, window))
        return step(variables, caches, tok, done, true_len, budget, bucket,
                    t_row, keys, seg_len, window)
    eng.serve_step = recording
    before = engine.stats()
    rng = np.random.default_rng(7)
    reqs = [engine.submit(rng.integers(1, 64, n).astype(np.int32), new)
            for n in lengths]
    for _ in range(200):
        if all(r.finished for r in reqs):
            break
        engine._tick()
    assert all(r.status == "ok" for r in reqs)
    after = engine.stats()
    return {k: after[k] - before.get(k, 0) for k in (
        "decode_blocks_read", "decode_blocks_dense",
        "segments_dispatched")}, seen


def test_the_engine_sums_the_rule_over_every_dispatched_segment():
    engine = _serving("TransformerLM", LM, 16)
    # one bucket (256): holes between prompts and the bucket; the row that
    # finishes first is released and read as slot 0 plus its tail
    gained, seen = _drive(engine, [130, 150, 200], 20)
    assert gained["segments_dispatched"] == len(seen) >= 5
    eng = engine._engines["primary"]
    want = np.sum([_blocks_by_hand(true_len, bucket, t_row, steps, window,
                                   eng._decoding.read_block(window))
                   for true_len, bucket, t_row, steps, window in seen],
                  axis=0)
    assert (gained["decode_blocks_read"],
            gained["decode_blocks_dense"]) == tuple(want)
    assert 0 < gained["decode_blocks_read"] < gained["decode_blocks_dense"]


def test_a_batch_that_sees_every_block_reads_a_share_of_100():
    # bucket 128 in a window of 256 at block 128: the prompt's block and
    # the decode tail's, for live and released rows alike
    engine = _serving("TransformerLM", LM, 128)
    gained, seen = _drive(engine, [90, 100, 128], 12)
    assert {window for *_, window in seen} == {256}
    assert gained["decode_blocks_read"] == gained["decode_blocks_dense"] > 0


def test_a_model_that_reads_its_windows_through_xla_counts_nothing():
    engine = _serving("HybridLM", HYBRID, 16)
    gained, seen = _drive(engine, [30, 40], 8)
    assert seen and gained["decode_blocks_read"] == 0
    assert gained["decode_blocks_dense"] == 0
