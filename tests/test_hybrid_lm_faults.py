"""The hybrid decoder's planted faults, its routed experts against every
expert on every token, and the compositions it refuses by name
(`tests/test_hybrid_lm.py` has the comparisons with the reference that
these faults must fail; a file goes to one test worker).  Tiny, float32,
on the CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_hybrid_lm import (CUT, GAP_TOL, LOGIT_TOL, generate, model,
                            prompts_of, reference_logits, served_gap)

from mmlspark_tpu.models import (DecodeEngine, ModelBundle, TextGenerator,
                                 hybrid_lm)
from mmlspark_tpu.models.definitions import build_model
from mmlspark_tpu.ops import moe
from mmlspark_tpu.serve import ServeConfig, ServingEngine


# -- (e) planted faults the comparison catches -------------------------------

def _top3(mp):
    real = hybrid_lm.routed_experts
    mp.setattr(hybrid_lm, "routed_experts",
               lambda *a, top_k, **kw: real(*a, top_k=top_k - 1, **kw))


def _no_bias(mp):
    real = hybrid_lm.routed_experts
    mp.setattr(hybrid_lm, "routed_experts",
               lambda x, router, bias, *a, **kw: real(
                   x, router, jnp.zeros_like(bias), *a, **kw))


def _not_renormalised(mp):
    real = moe.route_tokens

    def raw(x, kernel, bias, top_k):
        chosen, _, margin = real(x, kernel, bias, top_k)
        scores = jax.nn.sigmoid(x.astype(jnp.float32) @ kernel)
        return chosen, jnp.take_along_axis(scores, chosen, -1), margin
    mp.setattr(moe, "route_tokens", raw)


def _state_at_the_buckets_end(mp):
    real = hybrid_lm.HybridDecoding.run_prompt

    def whole_segment(self, params, tokens, state, start, true_len, live):
        full = jnp.full_like(true_len, start + tokens.shape[1])
        return real(self, params, tokens, state, start, full, live)
    mp.setattr(hybrid_lm.HybridDecoding, "run_prompt", whole_segment)


def _heads_misgrouped(mp):
    real = hybrid_lm._grouped_attention

    def modulo(q, k, v, visible, scale):
        # head i reads KV head i % n_kv instead of i // group
        b, s, h, d = q.shape
        kv = k.shape[2]
        swap = lambda t: t.reshape(b, s, h // kv, kv, d).transpose(
            0, 1, 3, 2, 4).reshape(b, s, h, d)
        out = real(swap(q), k, v, visible, scale)
        return out.reshape(b, s, kv, h // kv, d).transpose(
            0, 1, 3, 2, 4).reshape(b, s, h, d)
    mp.setattr(hybrid_lm, "_grouped_attention", modulo)


def _rotary_before_the_norm(mp):
    real_norm, real_rotary, last = hybrid_lm.rms_norm, hybrid_lm.rotary, {}

    def norm(x, scale, eps, dtype):
        last["scale"] = scale
        return real_norm(x, scale, eps, dtype)

    def rotary(x, positions, theta):
        # x = n(h) * g came in; rotation is linear and the rms a scalar a
        # head, so n(rot(h)) * g = rot(x / g) * g
        g = last["scale"]
        return real_rotary(x / g, positions, theta) * g
    mp.setattr(hybrid_lm, "rms_norm", norm)
    mp.setattr(hybrid_lm, "rotary", rotary)


FAULTS = {"top3_of_top4": _top3, "bias_left_out": _no_bias,
          "not_renormalised": _not_renormalised,
          "conv_state_at_the_buckets_end": _state_at_the_buckets_end,
          "kv_heads_misgrouped": _heads_misgrouped,
          "rotary_before_the_norm": _rotary_before_the_norm}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_caught(fault, monkeypatch):
    module, variables = model(CUT)
    FAULTS[fault](monkeypatch)
    if fault == "conv_state_at_the_buckets_end":
        # only the rows shorter than their bucket see it, in their decode
        rows, got = generate(CUT, variables)
        gaps = [served_gap(CUT, variables, p, t) for p, t in zip(rows, got)]
        assert max(gaps[:3]) > 100 * GAP_TOL
        assert gaps[3] < GAP_TOL             # the row that fills the bucket
        return
    tokens = np.stack(prompts_of([24, 24, 24]))
    got = np.asarray(jax.jit(module.apply)(variables, jnp.asarray(tokens)))
    want = reference_logits(CUT, variables, tokens)
    assert np.abs(got - want).max() > 100 * LOGIT_TOL


# -- (f) routed experts against every expert on every token ------------------

def _every_expert(x, router, bias, w1, w3, w2, k):
    scores = jax.nn.sigmoid(x @ router)
    chosen = jax.lax.top_k(scores + bias, k)[1]
    gate = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(
        jnp.take_along_axis(scores, chosen, -1))
    gate = gate / (gate.sum(-1, keepdims=True) + 1e-6)
    up = (jax.nn.silu(jnp.einsum("td,edw->tew", x, w1))
          * jnp.einsum("td,edw->tew", x, w3))
    return jnp.einsum("tew,ewd,te->td", up, w2, gate)


def test_routed_experts_match_every_expert_on_every_token():
    e, d, w, k, t = 8, 16, 12, 2, 24
    keys = jax.random.split(jax.random.key(3), 6)
    x = jax.random.normal(keys[0], (t, d))
    router = jax.random.normal(keys[1], (d, e)) * d ** -0.5
    # expert 0 is chosen by every token, expert 7 by none
    bias = (0.1 * jax.random.normal(keys[2], (e,))
            ).at[0].set(10.0).at[7].set(-10.0)
    w1 = jax.random.normal(keys[3], (e, d, w)) * d ** -0.5
    w3 = jax.random.normal(keys[4], (e, d, w)) * d ** -0.5
    w2 = jax.random.normal(keys[5], (e, w, d)) * w ** -0.5
    args = (x, router, bias, w1, w3, w2)
    y, load = moe.routed_experts(*args, top_k=k, dtype=jnp.float32)
    assert load[0] == t and load[7] == 0 and load.sum() == t * k
    assert np.abs(y - _every_expert(*args, k)).max() < 1e-5
    # nothing dropped: a call of one token gives that token's row
    alone, _ = moe.routed_experts(x[5:6], *args[1:], top_k=k,
                                  dtype=jnp.float32)
    assert np.abs(alone[0] - y[5]).max() < 1e-6
    # counted over the marked tokens only
    _, half = moe.routed_experts(*args, top_k=k, dtype=jnp.float32,
                                 valid=jnp.arange(t) < t // 2)
    assert half[0] == t // 2 and half.sum() == t // 2 * k
    loss = lambda f: lambda *a: (f(*a) ** 2).sum()
    routed = lambda *a: moe.routed_experts(*a, top_k=k,
                                           dtype=jnp.float32)[0]
    dense = lambda *a: _every_expert(*a, k)
    which = (0, 1, 3, 4, 5)
    got = jax.grad(loss(routed), argnums=which)(*args)
    want = jax.grad(loss(dense), argnums=which)(*args)
    for g, h in zip(got, want):
        assert np.abs(g - h).max() < 1e-4 * max(1.0, float(np.abs(h).max()))
    assert np.abs(got[2][7]).max() == 0      # the expert nobody chose
    assert np.abs(got[2][0]).max() > 0


# -- (g) what this model does not carry over refuses by name -----------------

def _bundle():
    module, variables = model(CUT)
    return module, ModelBundle.from_module(
        module, jax.tree_util.tree_map(np.asarray, variables))


def _mesh(**axes):
    from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
    return make_mesh(MeshSpec(**axes), jax.devices()[:2])


REFUSALS = {
    "mesh model>1": lambda m, b: DecodeEngine(
        m, 8, mesh=_mesh(data=1, model=2)),
    "mesh seq>1": lambda m, b: DecodeEngine(
        m, 8, mesh=_mesh(data=1, seq=2)),
    "speculative decoding": lambda m, b: DecodeEngine(
        m, 8, draft_module=build_model("TransformerLM", dict(
            vocab_size=97, d_model=16, n_heads=2, n_layers=1, max_len=128)),
        spec_tokens=2),
    "cache_dtype='int8'": lambda m, b: DecodeEngine(m, 8,
                                                    cache_dtype="int8"),
    "prefix cache": lambda m, b: ServingEngine(b, ServeConfig(
        max_new_tokens=8, cache_chunk=8, prefix_cache=True)),
    "tiered roles and KV handoff": lambda m, b: ServingEngine(
        b, ServeConfig(max_new_tokens=8, role="prefill")),
    "KV handoff": lambda m, b: ServingEngine(
        b, ServeConfig(max_new_tokens=8, role="decode")),
    "beam search": lambda m, b: TextGenerator(
        b, inputCol="p", maxNewTokens=4, beamWidth=2).transform(
        __import__("mmlspark_tpu").DataTable(
            {"p": np.zeros((1, 4), np.int32)})),
}


@pytest.mark.parametrize("feature", sorted(REFUSALS))
def test_an_unsupported_composition_refuses_by_name(feature):
    module, bundle = _bundle()
    named = {"mesh model>1": "model>1 or seq>1",
             "mesh seq>1": "model>1 or seq>1"}.get(feature, feature)
    with pytest.raises(ValueError, match=named) as raised:
        REFUSALS[feature](module, bundle)
    assert "HybridLM" in str(raised.value)


def test_the_engines_name_the_architectures_they_accept():
    from mmlspark_tpu.models.generate import make_generate_fn
    linear = build_model("LinearModel", {})
    with pytest.raises(ValueError, match="TransformerLM and HybridLM"):
        DecodeEngine(linear, 8)
    with pytest.raises(ValueError, match="decode TransformerLM models"):
        make_generate_fn(build_model("HybridLM", dict(CUT)), 8, 4)
