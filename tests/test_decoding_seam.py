"""The seam between `DecodeEngine` and a model (`generate._decoding_for`):
what every architecture's decoding answers, and TransformerLM's one block
under each of its views against the flax module that trained the weights
(`module.apply` on the extended sequence).  Tiny presets on the CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.models.definitions import build_model
from mmlspark_tpu.models.generate import _DECODINGS, _decoding_for
from mmlspark_tpu.models.hybrid_lm import FIXED, WINDOW
from mmlspark_tpu.models.transformer_decoding import forward_with_cache
from test_resident_weights import HYBRID, TLM

ROWS, BUCKET, WINDOW_SLOTS = 3, 8, 16
TRUE_LEN = np.asarray([8, 5, 6], np.int32)


def _model(arch: str, cfg: dict, seed: int = 5):
    module = build_model(arch, cfg)
    variables = jax.jit(module.init)(jax.random.key(seed),
                                     np.zeros((1, 8), np.int32))
    return module, variables["params"]


def _prompts(vocab: int, extra: int = 0):
    """Right-padded prompts (ROWS, BUCKET) and `extra` tokens a row to
    continue them with."""
    rng = np.random.default_rng(30)
    prompts = rng.integers(0, vocab, (ROWS, BUCKET)).astype(np.int32)
    prompts *= np.arange(BUCKET)[None, :] < TRUE_LEN[:, None]
    return prompts, rng.integers(0, vocab, (ROWS, extra)).astype(np.int32)


def _prompt_state(decoding, params, prompts):
    """The state a whole prompt leaves, closed as segments carry it."""
    @jax.jit
    def run(params, prompts):
        state = decoding.empty_state(ROWS, WINDOW_SLOTS)
        _, state, _ = decoding.run_prompt(params, prompts, state, 0,
                                          jnp.asarray(TRUE_LEN),
                                          jnp.ones(ROWS, bool))
        return decoding.close_prompt(state)
    return run(params, prompts)


def _layout(state) -> list:
    return [[(leaf.shape, leaf.dtype) for leaf in layer] for layer in state]


def assert_bitwise(got, want) -> None:
    got, want = (jax.tree_util.tree_leaves(t) for t in (got, want))
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(
            np.asarray(g).view(np.uint8), np.asarray(w).view(np.uint8))


SEAM_CASES = {
    "transformer_lm": ("TransformerLM", TLM, {}),
    "transformer_lm_int8_state": ("TransformerLM", TLM,
                                  {"cache_dtype": "int8"}),
    "transformer_lm_folded": ("TransformerLM", TLM, {"fused": True}),
    "hybrid_lm": ("HybridLM", HYBRID, {}),
}


def test_every_engine_architecture_has_a_seam_case():
    assert ({arch.__name__ for arch in _DECODINGS}
            == {arch for arch, _, _ in SEAM_CASES.values()})


@pytest.mark.parametrize("case", list(SEAM_CASES))
def test_decoding_state_and_uniform_step(case):
    """A decoding's `empty_state` has one entry a layer whose leaves are
    what `state_kinds` says, and the step at one shared slot IS the
    per-row step with equal slots: logits and state bit for bit."""
    arch, cfg, how = SEAM_CASES[case]
    module, params = _model(arch, cfg)
    decoding = _decoding_for(module, **how)
    for resident in (False, True):
        state = decoding.empty_state(ROWS, WINDOW_SLOTS, resident=resident)
        wider = decoding.empty_state(ROWS, 2 * WINDOW_SLOTS,
                                     resident=resident)
        assert len(state) == len(decoding.state_kinds) == module.n_layers
        for kind, layer, wide in zip(decoding.state_kinds, state, wider):
            assert kind in (WINDOW, FIXED) and len(layer) == len(wide) > 0
            for leaf, wide_leaf in zip(layer, wide):
                assert leaf.shape[0] == ROWS and not np.asarray(leaf).any()
                if kind == WINDOW:
                    assert leaf.shape[1] == WINDOW_SLOTS
                    assert wide_leaf.shape[1] == 2 * WINDOW_SLOTS
                else:
                    assert wide_leaf.shape == leaf.shape
    prompts, new = _prompts(module.vocab_size, 1)
    closed = _prompt_state(decoding, params, prompts)
    assert _layout(closed) == _layout(
        decoding.empty_state(ROWS, WINDOW_SLOTS, resident=True))
    # a segment steps on the state `close_prompt` returned, as it is:
    # head-folded where the decoding folds, (B, W, H*D) a leaf
    state = closed
    if case == "transformer_lm_folded":
        assert decoding.folds and {
            leaf.shape for layer in state for leaf in layer} == {
                (ROWS, WINDOW_SLOTS, module.d_model)}
    slots = jnp.arange(WINDOW_SLOTS)
    visible = ((slots[None, :] < TRUE_LEN[:, None])
               | (slots[None, :] == BUCKET))
    args = (params, jnp.asarray(new[:, 0]), jnp.asarray(TRUE_LEN))
    live = jnp.ones(ROWS, bool)
    one = jax.jit(decoding.run_step)(
        *args, jnp.asarray(BUCKET, jnp.int32), state, visible, live)
    rows = jax.jit(decoding.run_step_rows)(
        *args, jnp.full(ROWS, BUCKET, jnp.int32), state, visible, live)
    assert one[0].shape == (ROWS, module.vocab_size)
    assert len(one[2]) == len(rows[2]) == (1 if decoding.count_names else 0)
    assert_bitwise(rows, one)
    assert _layout(one[1]) == _layout(rows[1]) == _layout(closed)


FOREIGN = {"transformer_lm": "transformer_lm_folded",
           "transformer_lm_folded": "transformer_lm",
           "transformer_lm_int8_state": "transformer_lm_folded"}


@pytest.mark.parametrize("case", list(SEAM_CASES))
def test_reopen_prompt_undoes_close_prompt(case):
    """`reopen_prompt(close_prompt(s))` is `s` bit for bit (an int8
    cache rounds once: closing what it reopened stores the same bytes),
    and rows that a decoding of ANOTHER layout closed reopen as this
    decoding's own prompt state."""
    arch, cfg, how = SEAM_CASES[case]
    module, params = _model(arch, cfg)
    decoding = _decoding_for(module, **how)
    prompts, _ = _prompts(module.vocab_size)

    @jax.jit
    def opened(params, prompts):
        state = decoding.empty_state(ROWS, WINDOW_SLOTS)
        return decoding.run_prompt(params, prompts, state, 0,
                                   jnp.asarray(TRUE_LEN),
                                   jnp.ones(ROWS, bool))[1]
    state = opened(params, prompts)
    closed = jax.jit(decoding.close_prompt)(state)
    again = jax.jit(decoding.reopen_prompt)(closed)
    assert _layout(again) == _layout(state)
    if how.get("cache_dtype") == "int8":
        assert_bitwise(jax.jit(decoding.close_prompt)(again), closed)
    else:
        assert_bitwise(again, state)
    assert decoding.relayout_bytes("reopen", closed) == 0
    if case in FOREIGN:
        other = _decoding_for(module, **SEAM_CASES[FOREIGN[case]][2])
        theirs = _prompt_state(other, params, prompts)
        mine = jax.jit(decoding.reopen_prompt)(theirs)
        assert _layout(mine) == _layout(state)
        if "int8" not in case:
            assert_bitwise(mine, state)
            assert decoding.relayout_bytes("reopen", theirs) == sum(
                leaf.nbytes for layer in theirs for leaf in layer)


# TransformerLM's views at float32: each continues a prompt to the logits
# `module.apply` gives on the row's own tokens plus the new ones.  A row
# writes right behind ITS prompt (slot = its true length), so rows sit at
# different slots.
F32 = dict(TLM, dtype="float32")
MOE = dict(F32, mlp_impl="moe", n_experts=4, moe_group_size=1)
VIEWS = {"segment": (1, 3), "step": (1,), "step_rows": (1,),
         "verify": (1, 3)}
VIEW_CASES = [(view, s, cache, mlp)
              for view, lengths in VIEWS.items() for s in lengths
              for cache, mlp in (("model", "dense"), ("int8", "dense"),
                                 ("model", "moe"))
              if not (view == "segment" and cache == "int8")]


def _continue(decoding, params, view, state, new):
    """Logits (ROWS, S, V) of the `new` tokens through one view."""
    s = new.shape[1]
    slots = jnp.arange(WINDOW_SLOTS)
    start = jnp.asarray(TRUE_LEN)
    if view == "segment":
        # the whole-segment view writes every row from one slot on: a row
        # at a time, each from its own length
        return jnp.concatenate([forward_with_cache(
            params, jnp.asarray(new[r:r + 1]),
            [tuple(c[r:r + 1] for c in layer) for layer in state],
            jnp.asarray(TRUE_LEN[r]), decoding.module)[0]
            for r in range(ROWS)])
    if view == "verify":
        visible = (slots[None, None, :]
                   <= (start[:, None] + jnp.arange(s)[None, :])[:, :, None])
        return decoding.run_verify(params, jnp.asarray(new), start, start,
                                   state, visible)[0]
    visible = slots[None, :] <= start[:, None]
    live = jnp.ones(ROWS, bool)
    tok = jnp.asarray(new[:, 0])
    if view == "step_rows":
        return decoding.run_step_rows(params, tok, start, start, state,
                                      visible, live)[0][:, None]
    # the uniform-slot step: the rows that share a slot, a slot at a time
    logits = jnp.zeros((ROWS, decoding.module.vocab_size), jnp.float32)
    for slot in sorted(set(TRUE_LEN.tolist())):
        got = decoding.run_step(params, tok, start,
                                jnp.asarray(slot, jnp.int32), state,
                                visible, live)[0]
        logits = jnp.where((start == slot)[:, None], got, logits)
    return logits[:, None]


@pytest.mark.parametrize("view,s,cache,mlp", VIEW_CASES)
def test_transformer_view_continues_to_module_apply(view, s, cache, mlp):
    module, params = _model("TransformerLM", MOE if mlp == "moe" else F32)
    decoding = _decoding_for(module, cache_dtype=cache)
    prompts, new = _prompts(module.vocab_size, s)
    state = _prompt_state(decoding, params, prompts)
    assert len(state[0]) == (4 if cache == "int8" else 2)
    got = np.asarray(jax.jit(
        lambda params, state, new: _continue(decoding, params, view, state,
                                             new))(params, state, new))
    assert got.shape == (ROWS, s, module.vocab_size)
    # float32 state: the order of the sums only; int8 state rounds K and V
    # to 1/254 a head (tests/test_generate.py's two tolerances)
    tol = 0.05 if cache == "int8" else 2e-5
    apply = jax.jit(module.apply)
    for r, n in enumerate(TRUE_LEN):
        tokens = np.concatenate([prompts[r, :n], new[r]])[None]
        want = np.asarray(apply({"params": params}, tokens))[0, n:]
        np.testing.assert_allclose(got[r], want, rtol=tol, atol=tol)
