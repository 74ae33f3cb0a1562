"""A decode step's per-row write into a resident window leaf
(`hybrid_lm._row_write`): one slot a row is ONE `lax.scatter` that names
every leading dimension of the leaf (the row as a batching dimension, the
slot and the head as indices) and whose window is the trailing dimension
alone, the form the TPU compiler keeps as a single in-place op; `vmap` of `dynamic_update_slice`, the parent's
form, it expands into a `while` over the rows (PERF.md section 6, PR 35).
The write copies values, so everything here is bit for bit: against the
parent's form kept below, for every kind of leaf the engines hold; the
counter that says which form a dispatched segment took; and the compiled
segments of 2-layer cuts of the two benchmark models for a described v5e.
Tiny shapes on the CPU; nothing here is a timing.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mmlspark_tpu.models import hybrid_lm
from mmlspark_tpu.models.bundle import ModelBundle
from mmlspark_tpu.models.definitions import build_model
from mmlspark_tpu.models.generate import DecodeEngine
from mmlspark_tpu.models.hybrid_lm import _row_write, _row_write_is_flat
from mmlspark_tpu.resilience.clock import VirtualClock
from mmlspark_tpu.serve import ServeConfig, ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parent_row_write(cache, update, slots, lane=0):
    """`_row_write` as the parent commit (d438463) had it."""
    zeros = (0,) * (cache.ndim - 3)
    return jax.vmap(lambda c, u, s: lax.dynamic_update_slice(
        c, u, (s, lane) + zeros))(cache, update, slots)


ROWS, WINDOW = 4, 16
# leaf shape, update shape, dtype, lanes: cgpt's head-folded window, an
# int8 cache's scale leaf and its payload, a HybridLM's window, a looped
# model's (the passes' heads side by side: a pass writes 2 of 8)
LEAVES = {
    "folded_bf16": ((ROWS, WINDOW, 32), (ROWS, 1, 32), jnp.bfloat16, (0,)),
    "scale_f32": ((ROWS, WINDOW, 8), (ROWS, 1, 8), jnp.float32, (0,)),
    "payload_int8": ((ROWS, WINDOW, 8, 4), (ROWS, 1, 8, 4), jnp.int8, (0,)),
    "heads_bf16": ((ROWS, WINDOW, 2, 8), (ROWS, 1, 2, 8), jnp.bfloat16,
                   (0,)),
    "heads_f32": ((ROWS, WINDOW, 8, 4), (ROWS, 1, 8, 4), jnp.float32, (0,)),
    "looped_bf16": ((ROWS, WINDOW, 8, 4), (ROWS, 1, 2, 4), jnp.bfloat16,
                    (0, 2, 6)),
}
SLOTS = {
    "in_range": [0, 5, 15, 7],
    "equal": [3, 3, 3, 3],
    # a frozen row past the window writes into its own last slot
    "past_the_window": [16, 40, 15, 0],
}


def _filled(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(-100, 100, shape)).astype(dtype)


def _bits(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("slots", list(SLOTS))
@pytest.mark.parametrize("leaf", list(LEAVES))
def test_one_slot_a_row_equals_the_parents_write_bit_for_bit(leaf, slots):
    shape, ushape, dtype, lanes = LEAVES[leaf]
    cache, update = _filled(shape, dtype, 1), _filled(ushape, dtype, 2)
    at = jnp.asarray(SLOTS[slots], jnp.int32)
    assert _row_write_is_flat(cache.ndim, 1)
    for lane in lanes:
        lane = jnp.asarray(lane, jnp.int32)     # traced, as a pass's is
        new = jax.jit(_row_write)(cache, update, at, lane)
        old = jax.jit(parent_row_write)(cache, update, at, lane)
        assert new.dtype == old.dtype == cache.dtype
        np.testing.assert_array_equal(_bits(new), _bits(old))
        # and something was written: the leaf changed in one slot a row
        changed = (_bits(new) != _bits(cache)).reshape(ROWS, WINDOW, -1)
        assert (changed.any(-1).sum(-1) <= 1).all()


@pytest.mark.parametrize("leaf", ["folded_bf16", "looped_bf16"])
def test_one_slot_a_row_is_one_scatter_with_the_trailing_window(leaf):
    shape, ushape, dtype, _ = LEAVES[leaf]
    text = str(jax.make_jaxpr(_row_write)(
        jnp.zeros(shape, dtype), jnp.zeros(ushape, dtype),
        jnp.zeros(ROWS, jnp.int32), jnp.int32(0)))
    inner = tuple(range(1, len(shape) - 1))
    assert text.count("scatter[") == 1
    # the row is a batching dimension, every other leading one is indexed
    # and inserted: the window is the trailing dimension alone
    assert f"inserted_window_dims={inner}" in text
    assert "operand_batching_dims=(0,)" in text
    assert f"update_window_dims=({len(shape) - 2},)" in text
    assert "indices_are_sorted=True" in text and "unique_indices=True" in text
    assert "mode=GatherScatterMode.CLIP" in text


@pytest.mark.parametrize("leaf", ["folded_bf16", "heads_f32", "looped_bf16"])
def test_more_slots_than_one_keep_the_parents_form(leaf):
    """`run_verify` writes a drafted segment of S > 1 slots a row."""
    shape, ushape, dtype, lanes = LEAVES[leaf]
    ushape = (ROWS, 3) + ushape[2:]
    cache, update = _filled(shape, dtype, 1), _filled(ushape, dtype, 2)
    at = jnp.asarray([0, 13, 20, 6], jnp.int32)
    assert not _row_write_is_flat(cache.ndim, 3)
    args = (cache, update, at, jnp.int32(lanes[-1]))
    assert (str(jax.make_jaxpr(_row_write)(*args))
            == str(jax.make_jaxpr(parent_row_write)(*args)))
    np.testing.assert_array_equal(_bits(_row_write(*args)),
                                  _bits(parent_row_write(*args)))


# -- the decodings' steps, through the engine's own programs ------------------

LM = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, max_len=96,
          dtype="float32")
HYBRID = dict(vocab_size=97, d_model=32, n_heads=4, n_kv_heads=2,
              layer_types=["conv", "full_attention"], n_dense_layers=1,
              mlp_width=48, n_experts=8, experts_per_token=4,
              expert_width=24, max_len=96, dtype="float32")
LOOPED = dict(vocab_size=97, d_model=32, n_heads=4, n_kv_heads=4,
              layer_types=["full_attention"] * 2, n_dense_layers=2,
              mlp_width=48, tie_embeddings=False, n_passes=3,
              sandwich_norm=True, qk_norm=False, exit_gate=True,
              max_len=96, dtype="float32")
SPARSE = dict(vocab_size=97, d_model=64, n_heads=4, n_kv_heads=2,
              layer_types=["minicpm4", "lightning-attn"], n_dense_layers=2,
              mlp_width=48, max_len=256, dtype="float32", sparse_block=8,
              sparse_kernel=4, sparse_stride=2, sparse_window=16,
              sparse_init_blocks=1, sparse_topk=2, sparse_dense_len=16)
MODELS = {"lm": ("TransformerLM", LM), "hybrid": ("HybridLM", HYBRID),
          "looped": ("HybridLM", LOOPED)}
NEW, SEG, CHUNK = 24, 4, 16


def _bundle(name: str) -> ModelBundle:
    arch, cfg = MODELS[name]
    module = build_model(arch, dict(cfg))
    variables = jax.jit(module.init)(jax.random.key(0),
                                     np.zeros((1, 8), np.int32))
    return ModelBundle.from_module(module, variables)


@pytest.fixture(scope="module")
def bundles():
    return {name: _bundle(name) for name in MODELS}


def _segment_tokens(bundle, **how):
    """Three rows at different decode offsets, two segments of SEG steps
    through `DecodeEngine.serve_step` after a whole-prompt prefill."""
    module = bundle.module()
    eng = DecodeEngine(module, NEW, chunk=CHUNK, **how)
    variables = bundle.variables
    rng = np.random.default_rng(5)
    prompts = rng.integers(1, module.vocab_size, (3, 16)).astype(np.int32)
    true_len = np.asarray([16, 9, 12], np.int32)
    keys = jax.random.split(jax.random.key(0), 3)
    tok, done, state = eng.serve_prefill(variables, prompts, true_len,
                                         np.ones(3, bool), keys)
    t_row = np.asarray([2, 0, 1], np.int32)
    budget = np.asarray([NEW, 5, NEW], np.int32)    # row 1 freezes early
    out = []
    for _ in range(2):
        window = eng.serve_window(16, int(t_row.max()), SEG)
        state, toks, tok, done = eng.serve_step(
            variables, state, tok, done, true_len, budget, 16, t_row, keys,
            SEG, window)
        out.append(np.asarray(toks))
        t_row = t_row + SEG
    return np.concatenate(out, axis=1), eng


@pytest.mark.parametrize("how", [
    ("lm", {}), ("lm", {"cache_dtype": "int8"}), ("hybrid", {}),
    ("looped", {})], ids=["lm", "lm_int8", "hybrid", "looped"])
def test_segments_give_the_parents_tokens(bundles, how, monkeypatch):
    """The same segments with the parent's write planted back give the
    same token ids: the write copies values, whichever form it takes."""
    name, kw = how
    new, eng = _segment_tokens(bundles[name], **kw)
    from mmlspark_tpu.models import transformer_decoding
    monkeypatch.setattr(hybrid_lm, "_row_write", parent_row_write)
    monkeypatch.setattr(transformer_decoding, "_row_write", parent_row_write)
    old, _ = _segment_tokens(bundles[name], **kw)
    np.testing.assert_array_equal(new, old)
    assert eng.row_writes > 0 and eng.row_writes_looped == 0


# -- the counter --------------------------------------------------------------

def test_a_decoding_counts_its_steps_writes_from_shapes_alone():
    def decoding(arch, cfg, **how):
        return DecodeEngine(build_model(arch, dict(cfg)), NEW, chunk=CHUNK,
                            **how)._decoding
    lm = decoding("TransformerLM", LM)
    state = lm.empty_state(2, 32, resident=True)
    assert lm.row_writes("step", state, 8) == (8 * 2 * 2, 0)
    # a verify segment writes every leaf once, S slots a row: looped
    assert lm.row_writes("verify", state, 4) == (2 * 2, 2 * 2)
    int8 = decoding("TransformerLM", LM, cache_dtype="int8")
    assert int8.row_writes(
        "step", int8.empty_state(2, 32, resident=True), 8) == (8 * 2 * 4, 0)
    # one attention layer of two: K and V
    hybrid = decoding("HybridLM", HYBRID)
    assert hybrid.row_writes(
        "step", hybrid.empty_state(2, 32, resident=True), 8) == (8 * 2, 0)
    # every pass of every layer writes its own heads of K and V
    looped = decoding("HybridLM", LOOPED)
    assert looped.row_writes(
        "step", looped.empty_state(2, 32, resident=True), 8) == (
            8 * 3 * 2 * 2, 0)
    # a minicpm4 layer: K and V (its compressed keys take compress_row),
    # a linear state none
    sparse = decoding("HybridLM", SPARSE)
    assert sparse.row_writes(
        "step", sparse.empty_state(2, 32, resident=True), 8) == (8 * 2, 0)


def _serving(bundle, **overrides) -> ServingEngine:
    kw = dict(max_new_tokens=NEW, max_batch=2, queue_capacity=32,
              segment_steps=SEG, default_deadline_s=1000.0,
              drain_timeout_s=50.0, cache_chunk=CHUNK)
    kw.update(overrides)
    draft = kw.pop("draft_bundle", None)
    return ServingEngine(bundle, ServeConfig(**kw), clock=VirtualClock(),
                         draft_bundle=draft).warmup()


def _serve(engine, n=3):
    rng = np.random.default_rng(33)
    reqs = [engine.submit(rng.integers(1, 64, 5 + 3 * i).astype(np.int32),
                          NEW) for i in range(n)]
    for _ in range(400):
        if all(r.finished for r in reqs):
            break
        engine._tick()
    assert all(r.status == "ok" for r in reqs)
    return engine.stats()


@pytest.mark.parametrize("name", list(MODELS))
def test_stats_count_a_segments_writes_and_none_looped(bundles, name):
    engine = _serving(bundles[name])
    before = engine.stats()
    stats = _serve(engine)
    per_step = {"lm": 4, "hybrid": 2, "looped": 12}[name]
    segments = (stats["segments_dispatched"]
                - before.get("segments_dispatched", 0))
    assert segments >= 6
    assert (stats["row_writes"] - before["row_writes"]
            == segments * SEG * per_step)
    assert stats["row_writes_looped"] == 0


def test_stats_count_a_speculative_rounds_verify_as_looped(bundles):
    from mmlspark_tpu.zoo import truncated_draft_bundle
    lm = bundles["lm"]
    engine = _serving(lm, spec_tokens=2,
                      draft_bundle=truncated_draft_bundle(lm, n_layers=1))
    before = engine.stats()
    stats = _serve(engine, n=2)
    writes = stats["row_writes"] - before["row_writes"]
    looped = stats["row_writes_looped"] - before["row_writes_looped"]
    # a round: the one-layer draft's 3 steps of 2 leaves, the target's
    # verify of 4 leaves (3 slots a row each: the looped form)
    assert looped > 0 and writes == looped // 4 * (3 * 2 + 4)


# -- the lowering, for a described v5e (nothing runs) -------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _config(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def _two_layers(name: str):
    """(architecture, constructor cut to 2 layers, max_new_tokens, cache
    chunk, window) of a benchmark configuration, at its published widths."""
    cfg = _config(name)
    c = dict(cfg["constructor"])
    if "layer_types" in c:
        c.update(layer_types=c["layer_types"][:2], n_dense_layers=2)
        return cfg["architecture"], c, 512, 256, 1024
    return cfg["architecture"], dict(c, n_layers=2), 96, 128, 1152


def _compiled_segment(name: str, one_chip, window=None):
    """`DecodeEngine._serve_segment`, 8 rows x 8 steps, compiled by the
    TPU's own compiler from shapes on the described chip (at `window`, or
    the configuration's own): `(text, leaf elements, temporaries'
    bytes)`."""
    arch, c, new, chunk, own = _two_layers(name)
    window = window or own
    module = build_model(arch, c)
    eng = DecodeEngine(module, new, chunk=chunk)
    on = lambda tree: jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip),
        tree)
    shapes = jax.eval_shape(module.init, jax.random.key(0),
                            jax.ShapeDtypeStruct((1, 8), np.int32))["params"]
    weights = eng._decoding.resident_params(
        shapes, lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16))
    rows, steps = 8, 8
    state = jax.eval_shape(
        lambda: eng._decoding.empty_state(rows, window, resident=True))
    i32 = jax.ShapeDtypeStruct((rows,), jnp.int32)
    keys = jax.eval_shape(
        lambda: jax.random.split(jax.random.key(0), rows))
    compiled = eng._serve_segment.lower(
        steps, window, on({"params": weights}), on(state), on(i32),
        on(jax.ShapeDtypeStruct((rows,), jnp.bool_)), on(i32), on(i32),
        on(jax.ShapeDtypeStruct((), jnp.int32)), on(i32),
        on(keys)).compile()
    leaf = jax.tree_util.tree_leaves(state)[0]
    return (compiled.as_text(), int(np.prod(leaf.shape)),
            compiled.memory_analysis().temp_size_in_bytes)


def _window_sized_copies(text: str, elements: int) -> list:
    """Instructions that copy a whole window leaf (or a pass's quarter of
    one), outside fused computations, which materialize nothing."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    found, current = [], None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            current = head.group(1)
            continue
        if current in fused:
            continue
        m = re.search(r"= \(?\w+\[([\d,]+)\]\S* (copy|copy-start)\(", line)
        if m and np.prod([int(d) for d in m.group(1).split(",")]) >= (
                elements // 4):
            found.append(line.strip()[:120])
    return found


# ` while(` in the compiled segment: the step scan, and a looped model's
# pass scan; the parent's had 4 more, one a leaf (5 and 6).  Temporaries:
# the parent's, my compiles of its tree (PR 35)
LOWERED = {"cerebras-gpt-1.3b": (1, 6784512),
           "ouro-2.6b-stage": (2, 54654976)}


@pytest.mark.parametrize("name", list(LOWERED))
def test_the_compiled_segment_holds_no_loop_a_leaf(name, one_chip,
                                                   monkeypatch):
    # code that asks the backend takes its CPU branch here: the segment as
    # the chip compiles it reads through the fused kernel
    from mmlspark_tpu.ops import decode_attention
    monkeypatch.setattr(decode_attention, "_auto_interpret", lambda: False)
    text, elements, temporaries = _compiled_segment(name, one_chip)
    whiles, parent_temporaries = LOWERED[name]
    assert len(re.findall(r" while\(", text)) == whiles
    # 2 layers x K and V, each ONE native scatter
    assert len(re.findall(r" scatter\(", text)) == 4
    assert _window_sized_copies(text, elements) == []
    assert temporaries <= parent_temporaries


# -- the fused read of the listed blocks (ops/decode_attention.py) ------------

def _operand(text: str, name: str) -> str:
    """The line that defines instruction `%name`."""
    return next(line for line in text.splitlines()
                if line.lstrip().startswith(f"%{name} = "))


def _source(text: str, name: str) -> str:
    """`name`, or what an asynchronous copy of it copies, followed back."""
    line = _operand(text, name)
    m = re.search(r" copy-(?:done|start)\(%([\w.\-]+)\)", line)
    return _source(text, m.group(1)) if m else name


@pytest.mark.parametrize("window", [640, 1152])
def test_the_compiled_segment_reads_its_windows_from_hbm_through_one_table(
        window, one_chip, monkeypatch):
    """The cgpt segment cut to 2 layers, compiled for a described v5e: one
    `decode_sqa` a layer; the block table and counts it reads are built
    once a step and shared by every layer's call (24 calls, one table, in
    the whole model); no K or V window reaches the kernel through a
    `copy-done` or from the chip's fast memory (`S(1)`), where XLA would
    have streamed it whole whatever the kernel lists."""
    from mmlspark_tpu.ops import decode_attention
    monkeypatch.setattr(decode_attention, "_auto_interpret", lambda: False)
    text, elements, _ = _compiled_segment("cerebras-gpt-1.3b", one_chip,
                                          window)
    calls = [re.search(r"custom-call\(([^)]*)\)", line).group(1)
             for line in text.splitlines()
             if re.match(r"\s*%decode_sqa[.\d]* = ", line)]
    assert len(calls) == 2
    operands = [[o.strip().split("*/")[-1].lstrip("%")
                 for o in c.split(",")] for c in calls]
    # (table, count, q, k, v, visible): the first two are one pair, or
    # copies of it
    assert len({tuple(_source(text, n) for n in o[:2])
                for o in operands}) == 1
    for o in operands:
        for name in o[3:5]:
            line = _operand(text, name)
            assert "copy-done(" not in line and "S(1)" not in line.split(
                " = ")[1].split(" ")[0], line[:160]
    assert _window_sized_copies(text, elements) == []
