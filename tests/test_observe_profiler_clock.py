"""The program's spans on the profiler's clock, and the scheduler's counts.

The three span helpers of the hot paths (`spans.span_on`,
`trace.span_on_tracer`, `trace.trace_span`) enter a
`jax.profiler.TraceAnnotation` named `mmlspark_tpu.<name>` besides what
they record for their collector or tracer, and also when handed None: a
profiler session being on is the switch.  The benchmark's trace reduction
(`benchmark/reduce/trace.py`) finds them by that prefix.  The serving
engine counts its scheduler's work where it happens; the kernels carry
names.  Nothing here is timed: these are CPU runs.
"""

import http.client
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reduce import trace as T  # noqa: E402
from mmlspark_tpu.models.bundle import ModelBundle  # noqa: E402
from mmlspark_tpu.models.definitions import build_model  # noqa: E402
from mmlspark_tpu.observe.spans import PipelineTimings, span_on  # noqa: E402
from mmlspark_tpu.observe.trace import (Tracer, span_on_tracer,  # noqa: E402
                                        trace_span, tracing)
from mmlspark_tpu.resilience.clock import VirtualClock  # noqa: E402
from mmlspark_tpu.serve import ServeConfig, ServingEngine  # noqa: E402

# helper -> (how a hot path enters it with no collector, the span's name)
HELPERS = {
    "span_on": (lambda: span_on(None, "host"), "mmlspark_tpu.host"),
    "span_on_tracer": (lambda: span_on_tracer(None, "serve.segment",
                                              cat="serve", bucket=8),
                       "mmlspark_tpu.serve.segment"),
    "trace_span": (lambda: trace_span("x", cat="phase", rows=1),
                   "mmlspark_tpu.x"),
}


@pytest.fixture(scope="module")
def traced_host_spans(tmp_path_factory):
    """One profiler session (the benchmark's own options) round each
    helper entered once with no collector; the host spans that
    `benchmark.reduce.trace.load` keeps."""
    log_dir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        for enter, _ in HELPERS.values():
            with enter():
                jnp.zeros(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    return T.load(log_dir)["host"]


@pytest.mark.parametrize("helper", sorted(HELPERS))
def test_helper_leaves_a_span_on_the_profilers_clock(traced_host_spans,
                                                     helper):
    _, name = HELPERS[helper]
    found = [s for s in traced_host_spans if s[0] == name]
    assert len(found) == 1, [s[0] for s in traced_host_spans]
    _, start_ns, duration_ns = found[0]
    assert start_ns > 0 and duration_ns > 0
    assert all(s[0].startswith(T.HOST_PREFIXES) for s in traced_host_spans)


@pytest.mark.parametrize("helper", sorted(HELPERS))
def test_helper_with_no_session_and_no_collector_is_inert(helper):
    enter, _ = HELPERS[helper]
    with enter() as handle:
        assert handle is None
    # an exception inside the block passes through untouched
    with pytest.raises(KeyError):
        with enter():
            raise KeyError("from the block")


def test_span_on_still_fills_its_collector():
    timings = PipelineTimings()
    with span_on(timings, "drain"):
        pass
    with span_on(timings, "drain"):
        pass
    assert timings.counts == {"drain": 2}
    assert timings.seconds["drain"] >= 0.0


def test_span_on_tracer_still_fills_its_tracer():
    tracer = Tracer()
    with span_on_tracer(tracer, "serve.prefill", parent=7, cat="serve",
                        bucket=16) as sp:
        sp.attrs["joins"] = 2
    [rec] = tracer.records()
    assert (rec["name"], rec["parent"], rec["cat"]) \
        == ("serve.prefill", 7, "serve")
    assert rec["attrs"] == {"bucket": 16, "joins": 2}


def test_trace_span_still_nests_under_the_ambient_tracer():
    tracer = Tracer()
    with tracing(tracer):
        with trace_span("outer") as outer:
            with trace_span("inner", cat="phase") as inner:
                assert inner.parent_id == outer.span_id
    assert [r["name"] for r in tracer.records()] == ["inner", "outer"]


# -- the scheduler's counters, under a virtual clock -------------------------

LM = {"vocab_size": 64, "d_model": 32, "n_heads": 4, "n_layers": 2,
      "max_len": 64}
NEW_COUNTERS = (
    "joined", "queue_wait_s", "slot_steps_live", "slot_steps_capacity",
    "prefill_tokens_true", "prefill_tokens_padded", "decode_keys_live",
    "decode_keys_read", "tick_s", "prefill_s", "fetch_wait_s")


@pytest.fixture(scope="module")
def driven_engine():
    """An engine driven by `_tick` alone: three requests of two prompt
    lengths, each queued for two virtual seconds before its join."""
    model = build_model("TransformerLM", LM)
    variables = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    clock = VirtualClock()
    engine = ServingEngine(
        ModelBundle.from_module(model, variables),
        ServeConfig(max_new_tokens=12, max_batch=4, queue_capacity=8,
                    segment_steps=4, default_deadline_s=100.0,
                    cache_chunk=16), clock=clock)
    engine.warmup()
    before = engine.stats()
    reqs = [engine.submit(np.arange(1, n + 1), max_new_tokens=9)
            for n in (5, 7, 3)]
    clock.advance(2.0)
    for _ in range(50):
        if all(r.finished for r in reqs):
            break
        engine._tick()
    assert all(r.status == "ok" for r in reqs)
    return engine, before, engine.stats()


def test_every_new_counter_rises(driven_engine):
    _, before, after = driven_engine
    for name in NEW_COUNTERS:
        assert after[name] > before.get(name, 0), name
    assert after["joined"] == 3
    # on the engine's clock: each request waited the two virtual seconds
    assert after["queue_wait_s"] == pytest.approx(6.0)
    assert after["prefill_tokens_true"] == 5 + 7 + 3


def test_counters_keep_their_order(driven_engine):
    _, _, c = driven_engine
    assert c["slot_steps_live"] <= c["slot_steps_capacity"]
    assert c["prefill_tokens_true"] <= c["prefill_tokens_padded"]
    assert c["decode_keys_live"] <= c["decode_keys_read"]
    # the waits for the device lie inside the passes; so do the prefills
    assert c["fetch_wait_s"] <= c["tick_s"]
    assert c["prefill_s"] <= c["tick_s"]
    # three rows of four slots, nine tokens each: one from the prefill,
    # then two full segments of four steps
    assert c["slot_steps_live"] == 3 * 2 * 4
    assert c["slot_steps_capacity"] == 4 * 2 * 4


def test_decode_keys_count_the_visible_lengths(driven_engine):
    _, _, c = driven_engine
    # a row's visible keys at step s of a segment: its prompt and the
    # t_row + s + 1 slots generated so far
    want = sum(n + t + s + 1 for n in (5, 7, 3) for t in (0, 4)
               for s in range(4))
    assert c["decode_keys_live"] == want
    # what the read streams whatever the masks: every slot, the whole
    # cache width (bucket 8 + 1 rounded up to the chunk of 16, grown as
    # the rows advance)
    assert c["decode_keys_read"] % (4 * 4 * 16) == 0
    # no row ever sees more than is read (nothing clips the count): three
    # of the four slots were live
    assert c["decode_keys_live"] * 4 <= c["decode_keys_read"] * 3


def test_statz_holds_time_to_first_token(driven_engine):
    from mmlspark_tpu.serve.lifecycle import start_http, stop_http
    engine, _, _ = driven_engine
    server = start_http(engine, port=0)
    conn = http.client.HTTPConnection("127.0.0.1", server.server_address[1],
                                      timeout=30.0)
    try:
        conn.request("GET", "/statz")
        statz = json.loads(conn.getresponse().read())
    finally:
        conn.close()
        stop_http(server)
    assert statz["ttft_p50_s"] == pytest.approx(2.0)
    assert statz["ttft_p95_s"] == pytest.approx(2.0)
    assert statz["latency_p95_s"] >= statz["ttft_p95_s"]


def test_percentile_samples_are_bounded(driven_engine):
    engine, _, _ = driven_engine
    assert engine._latencies.maxlen == engine._ttfts.maxlen
    assert 0 < len(engine._ttfts) <= engine._ttfts.maxlen


def test_a_row_is_live_only_until_it_finishes(driven_engine):
    # seven tokens: one from the prefill, a whole segment of four, then
    # two of the next segment's four steps, the rest frozen
    engine, _, _ = driven_engine
    before = engine.stats()
    req = engine.submit(np.arange(1, 7), max_new_tokens=7)
    for _ in range(50):
        if req.finished:
            break
        engine._tick()
    assert req.status == "ok" and len(req.tokens) == 7
    after = engine.stats()
    gained = {k: after[k] - before[k] for k in NEW_COUNTERS}
    assert gained["slot_steps_live"] == 4 + 2
    assert gained["slot_steps_capacity"] == 4 * 2 * 4
    assert gained["decode_keys_live"] == sum(6 + s for s in range(1, 7))
    assert gained["decode_keys_live"] * 4 <= gained["decode_keys_read"]


def test_idle_passes_leave_the_tracers_ring_alone(driven_engine):
    # an idle engine passes a hundred times a second: a record a pass
    # would scroll every request's records out of the run's ring
    engine, _, _ = driven_engine
    assert engine.in_flight() == 0 and engine.admission.pending() == 0
    tracer = Tracer()
    engine._tracer = tracer
    try:
        ticks = engine.stats()["tick_s"]
        for _ in range(100):
            assert engine._tick() is False
        assert tracer.records() == []
        assert engine.stats()["tick_s"] > ticks
        # a request's pass does write: the seam itself is on
        req = engine.submit(np.arange(1, 4), max_new_tokens=2)
        while not req.finished:
            engine._tick()
        assert {"serve.admit", "serve.prefill", "serve.fetch",
                "serve.splice"} <= {r["name"] for r in tracer.records()}
        assert "serve.tick" not in {r["name"] for r in tracer.records()}
    finally:
        engine._tracer = None


# -- the kernels' names, in the text lowered for the TPU ---------------------

def _lowered_for_tpu(fn, *shapes) -> str:
    return jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.fixture(scope="module")
def flash_text():
    from mmlspark_tpu.ops.flash_attention import flash_attention
    qkv = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()
    return _lowered_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv"])
def test_flash_kernels_carry_their_names(flash_text, kernel):
    assert flash_text.count("tpu_custom_call") == 3
    assert kernel in flash_text


def test_decode_kernel_carries_its_name():
    from mmlspark_tpu.ops.decode_attention import \
        fused_single_query_attention
    q = jax.ShapeDtypeStruct((2, 4, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 256, 4, 64), jnp.bfloat16)
    visible = jax.ShapeDtypeStruct((2, 256), jnp.bool_)
    text = _lowered_for_tpu(
        lambda q, k, v, vis: fused_single_query_attention(
            q, k, v, vis, 0.125, interpret=False), q, kv, kv, visible)
    assert "tpu_custom_call" in text
    assert "decode_sqa" in text
