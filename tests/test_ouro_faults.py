"""Planted faults of the looped model that the comparisons of
`tests/test_ouro.py` must catch, and what a `HybridLM` of WINDOW layers only
does with `DecodeEngine`'s and `ServingEngine`'s options: prefix reuse and
handoff pages work against the reference, the rest refuses by name (a file
goes to one test worker).  Tiny, float32, on the CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_ouro import (CUT, GAP_TOL, GATE_TOL, LOGIT_TOL, SMALL, generate,
                       model, program_gates, prompts_of, reference,
                       served_gap)

from mmlspark_tpu.models import DecodeEngine, ModelBundle, hybrid_lm
from mmlspark_tpu.models.definitions import build_model
from mmlspark_tpu.serve import ServeConfig, ServingEngine


def _a_pass_left_out(mp):
    real = hybrid_lm.looped_stack

    class Fewer:
        n_passes = 3

        def __init__(self, module):
            self._module = module

        def __getattr__(self, name):
            return getattr(self._module, name)
    mp.setattr(hybrid_lm, "looped_stack",
               lambda module, *a, **k: real(Fewer(module), *a, **k))


def _lanes(change):
    """`grouped_attention` with its `lane` changed."""
    def plant(mp):
        real = hybrid_lm.grouped_attention
        mp.setattr(hybrid_lm, "grouped_attention",
                   lambda *a, lane=None, **k: real(*a, lane=change(lane),
                                                   **k))
    return plant


def _a_pass_reads_the_pass_before(mp):
    real = hybrid_lm._pass_heads
    mp.setattr(hybrid_lm, "_pass_heads", lambda cache, lane, n: real(
        cache, jnp.maximum(lane - n, 0), n))


def _closing(close):
    def plant(mp):
        mp.setattr(hybrid_lm, "close_pass", close)
    return plant


def _no_norm_between_passes(module, params, x):
    gate = jax.nn.sigmoid(x.astype(jnp.float32) @ params["exit_w"]
                          + params["exit_b"])
    return x, gate


def _gate_before_the_norm(module, params, x):
    gate = jax.nn.sigmoid(x.astype(jnp.float32) @ params["exit_w"]
                          + params["exit_b"])
    return hybrid_lm.rms_norm(x, params["out_norm"], module.norm_eps,
                              module.dtype), gate


def _a_post_norm_left_out(mp):
    """Layer 1's norm after the MLP is skipped: the one call of `rms_norm`
    whose gain is that leaf."""
    real_states, real_norm = hybrid_lm.hidden_states, hybrid_lm.rms_norm

    def states(module, params, *a, **k):
        skipped = params["layer1"]["ffn_post_norm"]
        mp.setattr(hybrid_lm, "rms_norm", lambda x, scale, eps, dtype: (
            x if scale is skipped else real_norm(x, scale, eps, dtype)))
        try:
            return real_states(module, params, *a, **k)
        finally:
            mp.setattr(hybrid_lm, "rms_norm", real_norm)
    mp.setattr(hybrid_lm, "hidden_states", states)


def _keys_rotary_advanced_by_the_pass(mp):
    """Pass t turns its KEYS at position + t, its queries at the position.
    (Advancing both alike is no fault: rotary scores depend on the
    distance of two positions alone, and the program's logits then stay
    the reference's: `test_rotary_advanced_alike_is_no_fault`.)"""
    real_attention, real_rotary = (hybrid_lm.grouped_attention,
                                   hybrid_lm.rotary)

    def attention(*a, lane=None, n_kv_heads, **k):
        calls = []

        def rotary(x, positions, theta):      # q's call, then k's
            calls.append(x)
            ahead = lane // n_kv_heads if len(calls) % 2 == 0 else 0
            return real_rotary(x, positions + ahead, theta)
        mp.setattr(hybrid_lm, "rotary", rotary)
        try:
            return real_attention(*a, lane=lane, n_kv_heads=n_kv_heads, **k)
        finally:
            mp.setattr(hybrid_lm, "rotary", real_rotary)
    mp.setattr(hybrid_lm, "grouped_attention", attention)


# name -> (how it is planted, where it must show: the forward pass, decode
# through the windows, or the gates)
FAULTS = {
    "a_pass_left_out": (_a_pass_left_out, "forward"),
    "the_norm_between_passes_left_out": (
        _closing(_no_norm_between_passes), "forward"),
    "a_post_norm_left_out": (_a_post_norm_left_out, "forward"),
    "keys_rotary_position_advanced_by_the_pass": (
        _keys_rotary_advanced_by_the_pass, "forward"),
    "passes_share_one_window": (_lanes(lambda lane: 0 * lane), "decode"),
    "a_pass_reads_the_pass_before": (_a_pass_reads_the_pass_before,
                                     "decode"),
    "the_gate_read_before_the_norm": (_closing(_gate_before_the_norm),
                                      "gates"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_caught(fault, monkeypatch):
    module, variables = model(CUT)
    plant, where = FAULTS[fault]
    plant(monkeypatch)
    tokens = np.stack(prompts_of([37, 37]))
    want, want_gates = reference(CUT, variables, tokens)
    if where == "decode":
        # a plain forward keeps no window: the fault shows once decode
        # reads what the prompt wrote, in every row
        rows, got = generate(CUT, variables)
        gaps = [served_gap(CUT, variables, p, t) for p, t in zip(rows, got)]
        # (a row whose every served token happens to stay the reference's
        # best reads 0: with 97 ids one of four may)
        assert sorted(gaps)[1] > 100 * GAP_TOL
        return
    got = np.asarray(module.apply(variables, jnp.asarray(tokens)))
    if where == "gates":
        # the logits do not see the gate; the gates do
        assert np.abs(got - want).max() < LOGIT_TOL
        gates = program_gates(module, variables, tokens)
        assert np.abs(gates - want_gates).max() > 100 * GATE_TOL
        return
    assert np.abs(got - want).max() > 100 * LOGIT_TOL


def test_rotary_advanced_alike_is_no_fault(monkeypatch):
    """ISSUE 34 lists "rotary position advanced by the pass" among the
    faults: with queries and keys advanced alike it is none."""
    module, variables = model(CUT)
    real = hybrid_lm.grouped_attention
    monkeypatch.setattr(
        hybrid_lm, "grouped_attention",
        lambda p, h, positions, *a, lane=None, n_kv_heads, **k: real(
            p, h, positions + lane // n_kv_heads, *a, lane=lane,
            n_kv_heads=n_kv_heads, **k))
    tokens = np.stack(prompts_of([37, 37]))
    got = np.asarray(module.apply(variables, jnp.asarray(tokens)))
    assert np.abs(got - reference(CUT, variables, tokens)[0]).max() < (
        LOGIT_TOL)


def test_norms_over_q_and_k_are_caught():
    # a model WITH the per-head norms (gains of 1), fed the same weights
    _, variables = model(CUT)
    module = build_model("HybridLM", dict(CUT, qk_norm=True))
    params = dict(variables["params"])
    for i in range(12):
        params[f"layer{i}"] = dict(params[f"layer{i}"],
                                   q_norm=jnp.ones(8), k_norm=jnp.ones(8))
    tokens = np.stack(prompts_of([37, 37]))
    got = np.asarray(module.apply({"params": params}, jnp.asarray(tokens)))
    want = reference(CUT, variables, tokens)[0]
    assert np.abs(got - want).max() > 100 * LOGIT_TOL


def test_the_shared_window_fault_also_fails_the_counted_gates(monkeypatch):
    """Through `ServingEngine`: passes that share one window serve other
    tokens AND count other gates than the reference's."""
    from test_ouro import serve
    _, variables = model(SMALL)
    _lanes(lambda lane: 0 * lane)(monkeypatch)
    requests, stats = serve(SMALL, variables, (18, 30, 9))
    gaps = [served_gap(SMALL, variables, np.asarray(r.prompt),
                       np.asarray(r.tokens, np.int32)) for r in requests]
    assert max(gaps) > 100 * GAP_TOL
    assert stats["loop_passes"] == 4 * stats["loop_tokens"]


# -- what a HybridLM of WINDOW layers only composes with ---------------------

def _bundle(c=SMALL):
    module, variables = model(c)
    return module, variables, ModelBundle.from_module(
        module, jax.tree_util.tree_map(np.asarray, variables))


def _drain(engine, requests, max_ticks=400):
    for _ in range(max_ticks):
        if all(r.finished for r in requests):
            return
        engine._tick()
    raise AssertionError([r.status for r in requests])


@pytest.mark.parametrize("prefill_chunk", [0, 8], ids=["whole", "chunked"])
def test_prefix_reuse_serves_the_reference(prefill_chunk):
    """A prompt that shares its first chunks with a resident donor resumes
    from the donor's windows, every pass's, and serves what a fresh
    prefill serves: the reference's best token at every position."""
    _, variables, bundle = _bundle()
    engine = ServingEngine(bundle, ServeConfig(
        max_new_tokens=8, max_batch=2, segment_steps=4, cache_chunk=8,
        prefix_cache=True, prefix_max_rows=16, prefill_chunk=prefill_chunk))
    engine.warmup()
    donor = prompts_of([30])[0]
    shared = donor.copy()
    shared[26:] = (shared[26:] + 1) % 97        # diverge in the tail
    served = []
    for prompt in (donor, donor, shared):
        served.append(engine.submit(prompt, 8))
        _drain(engine, served)
    stats = engine.prefix_stats()
    engine.stop()
    assert stats["hits"] >= 2 and stats["leased_rows"] == 0
    assert served[0].tokens == served[1].tokens
    for r in served:
        assert r.status == "ok" and len(r.tokens) == 8
        assert served_gap(SMALL, variables, np.asarray(r.prompt),
                          np.asarray(r.tokens, np.int32)) < GAP_TOL


def test_a_tiered_fleet_hands_every_pass_window_over():
    """Prefill replicas ship a row's pages (R windows a layer in each) to
    a decode replica, which serves the reference's tokens."""
    from mmlspark_tpu.resilience.clock import VirtualClock
    from mmlspark_tpu.serve.router import RouterConfig, build_fleet
    _, variables, bundle = _bundle()
    clock = VirtualClock()
    router = build_fleet(
        bundle, cfg=RouterConfig(
            replicas=2, prefill_replicas=1, decode_replicas=1,
            queue_capacity=16, default_deadline_s=100.0,
            drain_timeout_s=50.0),
        serve_cfg=ServeConfig(
            max_new_tokens=8, max_batch=2, queue_capacity=8,
            segment_steps=4, default_deadline_s=100.0, drain_timeout_s=50.0,
            cache_chunk=8), clock=clock)
    router.warmup()
    requests = [router.submit(p, max_new_tokens=8)
                for p in prompts_of((18, 30, 9))]
    for _ in range(600):
        if all(r.finished for r in requests):
            break
        if not router._tick():
            clock.advance(0.05)
    handoff = router.stats()["handoff"]
    router.stop()
    assert handoff["spliced"] == 3 and handoff["retries"] == 0
    for r in requests:
        assert r.status == "ok" and len(r.tokens) == 8
        assert served_gap(SMALL, variables, np.asarray(r.prompt),
                          np.asarray(r.tokens, np.int32)) < GAP_TOL


def _mesh(**axes):
    from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
    return make_mesh(MeshSpec(**axes), jax.devices()[:2])


REFUSALS = {
    "mesh model>1": lambda m: DecodeEngine(m, 8,
                                           mesh=_mesh(data=1, model=2)),
    "mesh seq>1": lambda m: DecodeEngine(m, 8, mesh=_mesh(data=1, seq=2)),
    "speculative decoding": lambda m: DecodeEngine(
        m, 8, draft_module=build_model("TransformerLM", dict(
            vocab_size=97, d_model=16, n_heads=2, n_layers=1, max_len=128)),
        spec_tokens=2),
    "cache_dtype='int8'": lambda m: DecodeEngine(m, 8, cache_dtype="int8"),
}


@pytest.mark.parametrize("c", [SMALL, dict(SMALL, n_passes=1,
                                           exit_gate=False)],
                         ids=["looped", "one_pass"])
@pytest.mark.parametrize("feature", sorted(REFUSALS))
def test_a_window_only_hybrid_refuses_by_name_what_it_lacks(feature, c):
    """Nothing but WINDOW layers: the engine's refusals hang on what the
    decoding says it carries, not on a FIXED layer being there."""
    module = build_model("HybridLM", dict(c))
    assert set(DecodeEngine(module, 8).state_kinds) == {"window"}
    named = {"mesh model>1": "model>1 or seq>1",
             "mesh seq>1": "model>1 or seq>1"}.get(feature, feature)
    with pytest.raises(ValueError, match=named) as raised:
        REFUSALS[feature](module)
    assert "HybridLM" in str(raised.value)
