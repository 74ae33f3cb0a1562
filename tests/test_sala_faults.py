"""Planted faults of the `minicpm4` and `lightning-attn` mixers that the
comparisons of `tests/test_sala.py` must catch, and the compositions a model
with such layers refuses by name (a file goes to one test worker).  Tiny,
float32, on the CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_sala import (CUT, GAP_TOL, LOGIT_TOL, generate, model, prompts_of,
                       reference_logits, served_gap)

from mmlspark_tpu.models import DecodeEngine, ModelBundle, hybrid_lm
from mmlspark_tpu.models.definitions import build_model
from mmlspark_tpu.ops import sparse_attention as sa
from mmlspark_tpu.serve import ServeConfig, ServingEngine


def _no_decay(mp):
    mp.setattr(hybrid_lm, "decay_slopes",
               lambda n: jnp.zeros((n,), jnp.float32))


def _padding_enters_the_state(mp):
    real = hybrid_lm.HybridDecoding.run_prompt

    def whole_segment(self, params, tokens, state, start, true_len, live):
        full = jnp.full_like(true_len, start + tokens.shape[1])
        return real(self, params, tokens, state, start, full, live)
    mp.setattr(hybrid_lm.HybridDecoding, "run_prompt", whole_segment)


def _selection(change):
    def plant(mp):
        real = sa.read_blocks
        mp.setattr(sa, "read_blocks",
                   lambda scores, q_pos, cfg: real(scores, q_pos,
                                                   change(cfg)))
    return plant


def _every_key_read(mp):
    def visible(scores, q_pos, cfg):
        own = (q_pos // cfg.block)[:, None, :, None]
        return jnp.broadcast_to(jnp.arange(scores.shape[-1]) <= own,
                                scores.shape)
    mp.setattr(sa, "read_blocks", visible)
    mp.setattr(sa, "capacity", lambda cfg, n_blocks: n_blocks)


def _compressed_keys_late(mp):
    # the window that the newest keys complete is left for a later call
    real = sa.compress_row
    mp.setattr(sa, "compress_row",
               lambda kc, k_cache, start, n_new, cfg: real(
                   kc, k_cache, start, n_new - cfg.stride, cfg))


def _head_reads_the_other_group(mp):
    real = sa.attend_masked

    def swapped(q, *rest, **kw):
        b, s, h, d = q.shape
        out, n = real(q.reshape(b, s, 2, h // 2, d)[:, :, ::-1].reshape(
            q.shape), *rest, **kw)
        return out.reshape(b, s, 2, h // 2, d)[:, :, ::-1].reshape(
            out.shape), n
    mp.setattr(sa, "attend_masked", swapped)


FAULTS = {
    "no_decay": _no_decay,
    "padding_enters_the_linear_state": _padding_enters_the_state,
    "selection_without_the_init_block": _selection(
        lambda cfg: cfg._replace(init_blocks=0)),
    "selection_without_the_local_blocks": _selection(
        lambda cfg: cfg._replace(window=cfg.block)),
    "selection_of_one_block_too_few": _selection(
        lambda cfg: cfg._replace(topk=cfg.topk - 1)),
    "every_key_read": _every_key_read,
    "compressed_keys_one_kernel_late": _compressed_keys_late,
    "query_heads_read_the_other_kv_head": _head_reads_the_other_group,
}
MULTIPLIERS = ("embed_scale", "residual_scale", "logit_scale")


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_caught(fault, monkeypatch):
    module, variables = model(CUT)
    FAULTS[fault](monkeypatch)
    if fault == "padding_enters_the_linear_state":
        # only the rows shorter than their bucket see it, in their decode
        rows, got = generate(CUT, variables)
        gaps = [served_gap(CUT, variables, p, t) for p, t in zip(rows, got)]
        assert min(gaps[:3]) > 100 * GAP_TOL
        assert gaps[3] < GAP_TOL             # the row that fills the bucket
        return
    tokens = np.stack(prompts_of([61, 61, 61]))
    got = np.asarray(jax.jit(module.apply)(variables, jnp.asarray(tokens)))
    want = reference_logits(CUT, variables, tokens)
    assert np.abs(got - want).max() > 100 * LOGIT_TOL
    if fault.startswith(("selection", "every_key")):
        # a query under `dense_len` reads all it sees, fault or none
        assert np.abs(got - want)[:, :16].max() < LOGIT_TOL


def test_compressed_keys_left_late_are_caught_in_decode(monkeypatch):
    # a decode step that never appends: the rows past `dense_len` read
    # other blocks than the reference
    _, variables = model(CUT)
    _compressed_keys_late(monkeypatch)
    rows, got = generate(CUT, variables)
    gaps = [served_gap(CUT, variables, p, t) for p, t in zip(rows, got)]
    assert max(gaps) > 100 * GAP_TOL


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_a_multiplier_left_out_is_caught(name):
    _, variables = model(CUT)
    module = build_model("HybridLM", dict(CUT, **{name: 1.0}))
    tokens = np.stack(prompts_of([24, 24]))
    got = np.asarray(jax.jit(module.apply)(variables, jnp.asarray(tokens)))
    want = reference_logits(CUT, variables, tokens)
    assert np.abs(got - want).max() > 100 * LOGIT_TOL


# -- what this model does not carry over refuses by name ---------------------

def _bundle():
    module, variables = model(CUT)
    return module, ModelBundle.from_module(
        module, jax.tree_util.tree_map(np.asarray, variables))


def _mesh(**axes):
    from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
    return make_mesh(MeshSpec(**axes), jax.devices()[:2])


REFUSALS = {
    "mesh model>1": lambda m, b: DecodeEngine(
        m, 8, chunk=8, mesh=_mesh(data=1, model=2)),
    "speculative decoding": lambda m, b: DecodeEngine(
        m, 8, chunk=8, draft_module=build_model("TransformerLM", dict(
            vocab_size=97, d_model=16, n_heads=2, n_layers=1, max_len=128)),
        spec_tokens=2),
    "cache_dtype='int8'": lambda m, b: DecodeEngine(m, 8, chunk=8,
                                                    cache_dtype="int8"),
    "prefix cache": lambda m, b: ServingEngine(b, ServeConfig(
        max_new_tokens=8, cache_chunk=8, prefix_cache=True)),
    "KV handoff": lambda m, b: ServingEngine(
        b, ServeConfig(max_new_tokens=8, cache_chunk=8, role="decode")),
}


@pytest.mark.parametrize("feature", sorted(REFUSALS))
def test_an_unsupported_composition_refuses_by_name(feature):
    module, bundle = _bundle()
    named = {"mesh model>1": "model>1 or seq>1"}.get(feature, feature)
    with pytest.raises(ValueError, match=named) as raised:
        REFUSALS[feature](module, bundle)
    assert "HybridLM" in str(raised.value)
