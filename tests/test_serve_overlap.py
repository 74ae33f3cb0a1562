"""A group's segment stays in flight while the scheduler works on the
other group (serve/engine.py `_Flight`, `_turn`, `_harvest`).

The loop thread's pass is `_tick(carry=True)`: what it dispatches is
harvested at the group's next turn.  `_tick()` ends with everything
harvested.  Both must serve the same tokens, count the same, and keep the
row state of a group in flight untouched.  The device's queue is played
by `Device`, a fake clock on which programs run one after another in
dispatch order, so order and times are exact and nothing sleeps.
"""

import jax
import numpy as np
import pytest

from mmlspark_tpu.models.bundle import ModelBundle
from mmlspark_tpu.models.definitions import build_model
from mmlspark_tpu.models.generate import DecodeEngine
from mmlspark_tpu.resilience.clock import VirtualClock
from mmlspark_tpu.serve import ServeConfig, ServingEngine
from mmlspark_tpu.serve import engine as engine_mod
from mmlspark_tpu.zoo import truncated_draft_bundle

LM = {"vocab_size": 64, "d_model": 32, "n_heads": 4, "n_layers": 2,
      "max_len": 64}
HYBRID = dict(vocab_size=97, d_model=32, n_heads=4, n_kv_heads=2,
              layer_types=["conv", "full_attention", "conv"],
              n_dense_layers=1, mlp_width=48, n_experts=8,
              experts_per_token=4, expert_width=24, conv_kernel=3,
              rope_theta=1e6, norm_eps=1e-5, tie_embeddings=True,
              max_len=64, dtype="float32")
NEW, SEG = 12, 4
SHORT, LONG = 8, 16            # the two buckets, so two groups


def _bundle(arch, cfg):
    module = build_model(arch, dict(cfg))
    variables = module.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    return ModelBundle.from_module(module, variables)


@pytest.fixture(scope="module")
def lm():
    return _bundle("TransformerLM", LM)


@pytest.fixture(scope="module")
def hybrid():
    return _bundle("HybridLM", HYBRID)


@pytest.fixture(scope="module")
def oracle(lm):
    """The offline decode of one prompt: the tokens serving must give."""
    eng = DecodeEngine(lm.module(), NEW, chunk=16)

    def decode(prompt):
        padded = np.zeros((1, eng.bucket_for(len(prompt))), np.int32)
        padded[0, :len(prompt)] = prompt
        return eng.generate(lm.variables, padded,
                            np.asarray([len(prompt)], np.int32)
                            )[0].tolist()
    return decode


def make_engine(bundle, clock=None, **overrides):
    kw = dict(max_new_tokens=NEW, max_batch=2, queue_capacity=32,
              segment_steps=SEG, default_deadline_s=100.0,
              drain_timeout_s=50.0, cache_chunk=16)
    kw.update(overrides)
    return ServingEngine(bundle, ServeConfig(**kw),
                         clock=clock or VirtualClock()).warmup()


def prompts(n, vocab=64, seed=3):
    """`n` prompts that alternate between the two buckets."""
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, (5, 12)[i % 2] + i % 3).astype(np.int32)
            for i in range(n)]


def carried(engine) -> bool:
    """One pass as the loop thread makes it."""
    return engine._tick(carry=True)


def in_flight(engine) -> list:
    return [f.group.bucket for f in engine._flights]


def serve_all(engine, reqs, passes, limit=400) -> None:
    for _ in range(limit):
        if all(r.finished for r in reqs):
            return
        passes(engine)
    raise AssertionError([r.status for r in reqs])


# -- the device, played on a fake clock --------------------------------------

class Device:
    """One queue: a program starts when it is dispatched or when the one
    before it ends, whichever is later.  Waiting for a result moves the
    host's clock to that program's end."""

    def __init__(self):
        self.now = self.free = 0.0
        self.log = []

    def monotonic(self) -> float:
        return self.now

    def run(self, tag, seconds: float) -> float:
        self.log.append(("dispatch", tag))
        self.free = max(self.now, self.free) + seconds
        return self.free


class Handle:
    """A result still on the device."""

    def __init__(self, device, value, tag, end):
        self.device, self.value, self.tag, self.end = device, value, tag, end

    def block_until_ready(self):
        self.device.now = max(self.device.now, self.end)
        return self

    def __array__(self, dtype=None, copy=None):
        self.block_until_ready()
        self.device.log.append(("fetch", self.tag))
        return np.asarray(self.value)


class Recorder:
    """The lane's `DecodeEngine`, its prefills and segments run on a
    `Device`: every result (the device counts too) is a `Handle`."""

    def __init__(self, eng, device, segment_s=0.0, prefill_s=0.0):
        self.__dict__.update(eng=eng, device=device, segment_s=segment_s,
                             prefill_s=prefill_s)

    def __getattr__(self, name):
        return getattr(self.eng, name)

    def __setattr__(self, name, value):
        setattr(self.eng, name, value)

    def _handles(self, tag, seconds, *values):
        end = self.device.run(tag, seconds)
        self.eng.counts_out = [Handle(self.device, c, tag, end)
                               for c in self.eng.counts_out]
        return [Handle(self.device, v, tag, end) for v in values]

    def serve_prefill(self, variables, prompts, *args):
        tok, done, caches = self.eng.serve_prefill(variables, prompts,
                                                   *args)
        [tok] = self._handles(("prefill", prompts.shape[1]),
                              self.prefill_s, tok)
        return tok, done, caches

    def serve_seat(self, tok, done, cohort_tok, cohort_done, dst_rows):
        # a queued join's first tokens stay on the device: no fetch
        self.device.run(("seat", len(dst_rows)), 0.0)
        return self.eng.serve_seat(tok, done, cohort_tok.value, cohort_done,
                                   dst_rows)

    def serve_step(self, variables, caches, tok, done, true_len, budget,
                   bucket, *args):
        caches, *out = self.eng.serve_step(variables, caches, tok, done,
                                           true_len, budget, bucket, *args)
        return (caches, *self._handles(("segment", bucket),
                                       self.segment_s, *out))


def on_device(engine, monkeypatch, **seconds) -> Device:
    device = Device()
    monkeypatch.setattr(engine_mod, "monotonic", device.monotonic)
    engine._engines["primary"] = Recorder(engine._engines["primary"],
                                          device, **seconds)
    return device


# -- (a) the same service, carried or not -------------------------------------

COUNTERS = ("slot_steps_live", "slot_steps_capacity", "decode_keys_live",
            "decode_keys_read", "joined", "segments_dispatched",
            "tokens_served", "prefill_tokens_true")


def _served(bundle, passes, vocab=64, n=9):
    engine = make_engine(bundle)
    order, complete = [], engine._complete

    def completed(req, *args):
        order.append(req.id)
        complete(req, *args)
    engine._complete = completed
    reqs = [engine.submit(p, (NEW, 7, 10)[i % 3])
            for i, p in enumerate(prompts(n, vocab))]
    serve_all(engine, reqs, passes)
    engine._tick()              # whatever a carried pass left in flight
    assert not engine._flights
    by_group = {b: [i for i in order if reqs[i - 1].bucket == b]
                for b in (SHORT, LONG)}
    return reqs, by_group, engine.stats()


def test_carried_passes_serve_what_ticks_serve(lm, oracle):
    ticked, order_t, stats_t = _served(lm, lambda e: e._tick())
    carry, order_c, stats_c = _served(lm, carried)
    for a, b in zip(ticked, carry):
        assert a.status == b.status == "ok"
        assert a.tokens == b.tokens == oracle(a.prompt)[:a.max_new_tokens]
    assert order_t == order_c and all(order_t.values())
    assert {k: stats_t[k] for k in COUNTERS} == {
        k: stats_c[k] for k in COUNTERS}
    # nothing to overlap with inside a `_tick()` but the other group's
    # segment of the same pass; the loop's passes overlap all but the first
    assert stats_c["segments_overlapped"] >= stats_t["segments_overlapped"]


# -- (b) who is dispatched and fetched when ----------------------------------

def test_with_two_groups_a_fetch_follows_the_other_groups_dispatch(
        lm, monkeypatch):
    engine = make_engine(lm)
    device = on_device(engine, monkeypatch)
    reqs = [engine.submit(p, NEW) for p in prompts(4)]   # 2 rows a group
    assert carried(engine)                     # joins, first dispatches
    assert in_flight(engine) == [SHORT, LONG]
    first = engine.stats()
    assert (first["segments_dispatched"], first["segments_overlapped"]) \
        == (2, 1)
    carried(engine)
    carried(engine)
    seg = lambda what, b: (what, ("segment", b))
    # each turn: the group's three results fetched, its next dispatched;
    # the other group's segment is in flight all the while
    turn = lambda b: [seg("fetch", b)] * 3 + [seg("dispatch", b)]
    assert [e for e in device.log if e[1][0] == "segment"] == (
        [seg("dispatch", SHORT), seg("dispatch", LONG)]
        + (turn(SHORT) + turn(LONG)) * 2)
    after = engine.stats()
    n = after["segments_dispatched"] - first["segments_dispatched"]
    assert n >= 2
    assert after["segments_overlapped"] - first["segments_overlapped"] == n
    serve_all(engine, reqs, carried)
    assert all(r.status == "ok" for r in reqs)


def test_one_group_has_nothing_to_overlap_with(lm, oracle, monkeypatch):
    engine = make_engine(lm)
    device = on_device(engine, monkeypatch)
    reqs = [engine.submit(p, NEW) for p in prompts(6)[::2]]   # one bucket
    serve_all(engine, reqs, carried)
    stats = engine.stats()
    assert stats["segments_dispatched"] >= 3
    assert stats["segments_overlapped"] == 0
    assert [r.tokens for r in reqs] == [oracle(r.prompt) for r in reqs]
    # and the group's own next segment is never dispatched before its
    # harvest: a row that finished would sit dead for a whole segment
    kinds = [e[0] for e in device.log if e[1][0] == "segment"]
    assert "dispatch dispatch" not in " ".join(kinds)


def test_tick_leaves_nothing_in_flight(lm):
    engine = make_engine(lm)
    reqs = [engine.submit(p, NEW) for p in prompts(4)]
    engine._tick()
    assert not engine._flights and all(len(r.tokens) == 1 + SEG
                                       for r in reqs)
    carried(engine)
    assert in_flight(engine) == [SHORT, LONG]
    engine._tick()              # a direct caller after the loop's pass
    assert not engine._flights
    assert all(r.status == "ok" and len(r.tokens) == NEW for r in reqs)


# -- (c) what arrives while a segment is in flight ----------------------------

def _flying(lm, clock=None, n=4, **overrides):
    """An engine after one carried pass: a segment of each group in
    flight, `n` requests seated (2 a group) or queued behind them."""
    engine = make_engine(lm, clock, **overrides)
    reqs = [engine.submit(p, NEW) for p in prompts(n)]
    carried(engine)
    assert in_flight(engine) == [SHORT, LONG]
    return engine, reqs


def test_cancel_in_flight_frees_the_slot_at_the_harvest(lm, oracle):
    engine, reqs = _flying(lm, n=6)
    victim, waiting = reqs[0], reqs[4]          # both of the short bucket
    g = engine._groups[(SHORT, "primary")]
    slot = g.rows.index(victim)
    assert engine.cancel_request(victim)
    assert victim.status == "cancelled" and len(victim.tokens) == 1
    # the row state is the segment's until its harvest: nobody is seated
    assert g.rows[slot] is victim and not waiting.tokens
    carried(engine)
    assert g.rows[slot] is waiting and len(victim.tokens) == 1
    serve_all(engine, reqs, carried)
    for r in reqs[1:]:
        assert r.status == "ok" and r.tokens == oracle(r.prompt)
    assert engine.stats()["cancelled_external"] == 1


def test_a_deadline_that_passes_in_flight_keeps_the_segments_tokens(
        lm, oracle):
    clock = VirtualClock()
    engine = make_engine(lm, clock)
    late = engine.submit(prompts(1)[0], NEW, deadline_s=5.0)
    rest = [engine.submit(p, NEW) for p in prompts(4)[1:]]
    carried(engine)
    clock.advance(6.0)
    carried(engine)             # harvest, then the boundary's cancel
    assert late.status == "timeout"
    assert late.tokens == oracle(late.prompt)[:1 + SEG]
    serve_all(engine, rest, carried)
    assert all(r.tokens == oracle(r.prompt) for r in rest)


def test_drain_and_stop_in_flight_lose_no_token(lm, oracle):
    engine, reqs = _flying(lm, n=6)
    engine.begin_drain("test")
    assert in_flight(engine) == [SHORT, LONG]   # a drain touches no row
    carried(engine)
    engine.stop()               # threadless: ticks to the end
    assert engine.state == "stopped" and not engine._flights
    for r in reqs:
        assert r.status == "ok" and r.tokens == oracle(r.prompt)


def test_the_drain_deadline_harvests_before_it_cancels(lm, oracle):
    clock = VirtualClock()
    engine, reqs = _flying(lm, clock, drain_timeout_s=2.0)
    engine.begin_drain("test")
    clock.advance(3.0)
    carried(engine)
    assert not engine._flights and not engine._groups
    for r in reqs:
        assert r.status == "cancelled"
        assert r.tokens == oracle(r.prompt)[:1 + SEG]


def test_a_remote_splice_waits_for_the_harvest(lm, oracle):
    engine, reqs = _flying(lm)
    g = engine._groups[(SHORT, "primary")]
    donor = make_engine(lm)
    prompt = prompts(1, seed=11)[0]
    eng = donor._engines["primary"]
    padded = np.zeros((1, SHORT), np.int32)
    padded[0, :len(prompt)] = prompt
    tok, _, caches = eng.serve_prefill(
        donor._variables["primary"], padded,
        np.asarray([len(prompt)], np.int32), np.ones(1, bool),
        donor._row_keys(np.zeros(1, np.int32)))
    # both rows of the group are taken: no slot, but the segment in
    # flight was harvested before the rows were looked at
    assert engine.splice_remote(prompt, NEW, 100.0, int(tok[0]),
                                caches) is None
    assert in_flight(engine) == [LONG]
    assert all(len(r.tokens) == 1 + SEG for r in g.rows)
    serve_all(engine, reqs, carried)
    assert all(r.tokens == oracle(r.prompt) for r in reqs)


# -- (d) a model that counts on the device ------------------------------------

MOE = ("moe_assignments", "moe_experts_touched", "moe_expert_slots",
       "moe_load_max", "moe_load_mean")


def test_device_counts_are_booked_once(hybrid):
    _, _, ticked = _served(hybrid, lambda e: e._tick(), vocab=97)
    _, _, carry = _served(hybrid, carried, vocab=97)
    assert ticked["moe_assignments"] > 0
    assert {k: ticked[k] for k in MOE} == {k: carry[k] for k in MOE}


def test_a_harvest_fetches_its_own_programs_counts(hybrid, monkeypatch):
    engine = make_engine(hybrid)
    device = on_device(engine, monkeypatch)
    reqs = [engine.submit(p, NEW) for p in prompts(4, vocab=97)]
    carried(engine)
    assert all(len(f.counts) == 1 for f in engine._flights)
    del device.log[:]
    engine._harvest(engine._groups[(SHORT, "primary")])
    # toks, tok, done and the segment's counts: nothing of the long
    # group's program, which is still in flight behind it
    assert device.log == [("fetch", ("segment", SHORT))] * 4
    assert in_flight(engine) == [LONG]
    serve_all(engine, reqs, carried)


# -- (e) a program's time is its own ------------------------------------------

def test_the_estimator_sees_one_segment_not_the_round(lm, monkeypatch):
    engine = make_engine(lm)
    device = on_device(engine, monkeypatch, segment_s=2.0, prefill_s=0.5)
    steps, prefills = [], []
    monkeypatch.setattr(engine.estimator, "observe_step",
                        lambda b, s: steps.append((b, s)))
    monkeypatch.setattr(engine.estimator, "observe_prefill",
                        lambda b, s: prefills.append((b, s)))
    # six requests on 2 x 2 slots: the last two join while the other
    # group's segment is in flight, and their prefill's fetch waits
    # behind it
    reqs = [engine.submit(p, (7, 7, NEW, NEW, 7, 7)[i])
            for i, p in enumerate(prompts(6))]
    serve_all(engine, reqs, carried)
    assert len(steps) == engine.stats()["segments_dispatched"] >= 8
    assert {s for _, s in steps} == {2.0 / SEG}
    assert len(prefills) == 4 and {s for _, s in prefills} == {0.5}
    # the scheduler thread's clock: the device never waited for it
    assert device.now == device.free == 2.0 * len(steps) + 0.5 * 4


# -- the loop thread itself, with other threads on its rows -------------------

def test_the_loop_thread_serves_while_other_threads_submit_and_cancel(
        lm, oracle):
    import sys
    import threading
    from mmlspark_tpu.serve.lifecycle import start_engine
    engine = ServingEngine(lm, ServeConfig(
        max_new_tokens=NEW, max_batch=2, queue_capacity=64,
        segment_steps=SEG, default_deadline_s=120.0, drain_timeout_s=30.0,
        cache_chunk=16))
    start_engine(engine, install_sigterm=False)
    served, errors = [], []

    def client(k: int) -> None:
        try:
            for j, prompt in enumerate(prompts(4, seed=20 + k)):
                req = engine.submit(prompt, NEW)
                cancel = (k + j) % 3 == 0
                if cancel:
                    # while it is queued, prefilled or in a segment in flight
                    req.wait(0.002 * (k + 1))
                    engine.cancel_request(req)
                assert req.wait(60.0), "a request never finished"
                served.append((req, cancel))
        except Exception as e:      # reported below, on the test's thread
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        threads = [threading.Thread(target=client, args=(k,), daemon=True)
                   for k in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
        engine.stop(timeout=60.0)
    assert not errors, errors
    assert engine.state == "stopped" and not engine._thread.is_alive()
    assert not engine._flights and engine.in_flight() == 0
    assert len(served) == 24
    for req, cancelled in served:
        want = oracle(req.prompt)
        if req.status == "ok":
            assert req.tokens == want
        else:
            assert cancelled and req.status == "cancelled"
            assert req.tokens == want[:len(req.tokens)]
    stats = engine.stats()
    assert stats["ok"] >= 16 and stats["segments_overlapped"] > 0


# -- (f) a join turn queues its merge and segment behind its prefill ---------

def _merges_on(device, monkeypatch):
    """Log each merge's dispatch on the device's queue."""
    merge = DecodeEngine.merge_cache_rows

    def logged(dst, src, dst_rows, *args, **kw):
        device.run(("merge", len(dst_rows)), 0.0)
        return merge(dst, src, dst_rows, *args, **kw)
    monkeypatch.setattr(DecodeEngine, "merge_cache_rows",
                        staticmethod(logged))


def _joined(stats) -> tuple:
    return stats["join_cohorts"], stats["join_cohorts_queued"]


def test_a_join_turn_dispatches_merge_and_segment_before_its_fetch(
        lm, oracle, monkeypatch):
    engine = make_engine(lm)
    device = on_device(engine, monkeypatch)
    _merges_on(device, monkeypatch)
    reqs = [engine.submit(p, (3, NEW, NEW)[i])
            for i, p in enumerate(prompts(6)[::2])]   # one bucket, 2 slots
    carried(engine)
    turn = [("dispatch", ("prefill", SHORT)), ("dispatch", ("merge", 2)),
            ("dispatch", ("seat", 2)), ("dispatch", ("segment", SHORT)),
            ("fetch", ("prefill", SHORT))]
    assert device.log == turn
    assert [len(r.tokens) for r in reqs] == [1, 1, 0]
    # the first request spends its budget in that segment: the next turn
    # harvests, then the third joins its slot the same way
    del device.log[:]
    carried(engine)
    assert device.log == [("fetch", ("segment", SHORT))] * 3 + [
        ("dispatch", ("prefill", SHORT)), ("dispatch", ("merge", 1)),
        ("dispatch", ("seat", 1)), ("dispatch", ("segment", SHORT)),
        ("fetch", ("prefill", SHORT))]
    assert reqs[0].status == "ok" and len(reqs[2].tokens) == 1
    serve_all(engine, reqs, carried)
    assert [r.tokens for r in reqs] == [
        oracle(r.prompt)[:r.max_new_tokens] for r in reqs]
    assert _joined(engine.stats()) == (2, 2)


@pytest.mark.parametrize("passes", ["ticked", "carried"])
def test_greedy_streams_with_many_late_joins_equal_the_offline_tokens(
        lm, oracle, passes):
    engine = make_engine(lm)
    reqs = [engine.submit(p, (2, 5, NEW, 3)[i % 4])
            for i, p in enumerate(prompts(14, seed=5))]
    serve_all(engine, reqs,
              carried if passes == "carried" else lambda e: e._tick())
    engine._tick()
    for r in reqs:
        assert r.status == "ok"
        assert r.tokens == oracle(r.prompt)[:r.max_new_tokens]
    cohorts, queued = _joined(engine.stats())
    assert cohorts == queued >= 7       # every slot refilled at least once
    assert not engine._flights and engine.in_flight() == 0


def test_the_prefill_fetch_waits_for_what_is_ahead_of_it_only(
        lm, monkeypatch):
    engine = make_engine(lm)
    device = on_device(engine, monkeypatch, segment_s=2.0, prefill_s=0.5)
    prefills = []
    monkeypatch.setattr(engine.estimator, "observe_prefill",
                        lambda b, s: prefills.append((b, s)))
    fetched_at = []
    fetch = engine._fetch

    def timed(*args, **kw):
        out = fetch(*args, **kw)
        fetched_at.append(device.now)
        return out
    engine._fetch = timed
    [engine.submit(p, NEW) for p in prompts(4)]       # 2 rows a group
    carried(engine)
    short, long_ = engine._flights
    # the short group's prefill ends at 0.5 and its segment at 2.5: its
    # fetch came back at 0.5.  The long group's prefill queues behind
    # that segment (2.5 -> 3.0): its fetch stamped the segment first,
    # and did not wait for its own group's segment, queued after it
    assert fetched_at == [0.5, 3.0] and device.free == 5.0
    assert short.own_s == 2.0 and long_.own_s is None
    assert prefills == [(SHORT, 0.5), (LONG, 0.5)]
    assert engine._device_free == 3.0


def test_a_row_that_ends_at_its_first_token_runs_one_frozen_segment(
        lm, oracle, monkeypatch):
    ps = prompts(6)[::2]
    stop = oracle(ps[1])[0]
    engine = make_engine(lm, stop_tokens=(stop,))
    g_rows = lambda: engine._groups[(SHORT, "primary")].rows
    one = engine.submit(ps[0], 1)               # its budget is one token
    ends = engine.submit(ps[1], NEW)            # its first token stops it
    waiting = engine.submit(ps[2], NEW)
    carried(engine)
    # both finish at the fetch, their rows stay seated for the segment
    # in flight, and the harvest frees them for the request that waits
    assert (one.status, ends.status) == ("ok", "ok")
    assert one.tokens == oracle(ps[0])[:1] and ends.tokens == [stop]
    assert g_rows() == [one, ends] and not waiting.tokens
    carried(engine)
    assert g_rows()[0] is waiting and g_rows()[1] is None
    serve_all(engine, [waiting], carried)
    want = oracle(ps[2])
    cut = want.index(stop) + 1 if stop in want else len(want)
    assert waiting.tokens == want[:cut]


def test_a_cancel_between_the_dispatch_and_the_fetch(lm, oracle,
                                                     monkeypatch):
    engine = make_engine(lm)
    ps = prompts(6)[::2]
    victim, kept, waiting = (engine.submit(p, NEW) for p in ps)
    fetch = engine._fetch

    def cancelling(*args, after=None, **kw):
        if after is not None:                   # a queued join's prefill
            engine.cancel_request(victim)
        return fetch(*args, after=after, **kw)
    monkeypatch.setattr(engine, "_fetch", cancelling)
    carried(engine)
    g = engine._groups[(SHORT, "primary")]
    assert victim.status == "cancelled" and victim.tokens == []
    assert g.rows[0] is victim and len(kept.tokens) == 1
    monkeypatch.setattr(engine, "_fetch", fetch)
    carried(engine)                             # the harvest frees it
    assert g.rows[0] is waiting
    serve_all(engine, [kept, waiting], carried)
    assert kept.tokens == oracle(kept.prompt)
    assert waiting.tokens == oracle(waiting.prompt)
    assert engine.stats()["cancelled_external"] == 1


def test_joins_after_the_warmup_compile_nothing(lm):
    engine = make_engine(lm, warmup_buckets=(SHORT, LONG),
                         warmup_joins=True)
    before = engine.stats()
    reqs = [engine.submit(p, (3, NEW, 5)[i % 3])
            for i, p in enumerate(prompts(12, seed=9))]
    serve_all(engine, reqs, carried)
    engine._tick()
    after = engine.stats()
    assert after["join_cohorts_queued"] >= 6
    assert after["compile_programs"] == before["compile_programs"]
    assert after["compiles_after_ready"] == 0


def _old_order_engine(lm, kind):
    if kind == "speculative":
        return ServingEngine(lm, ServeConfig(
            max_new_tokens=NEW, max_batch=2, queue_capacity=8,
            segment_steps=SEG, default_deadline_s=100.0, cache_chunk=16,
            spec_tokens=2), draft_bundle=truncated_draft_bundle(
                lm, n_layers=1), clock=VirtualClock()).warmup()
    if kind == "chunked":       # bucket 16 in chunks of 8; bucket 8 whole
        return make_engine(lm, prefill_chunk=8)
    return make_engine(lm, prefix_cache=True, prefix_max_rows=8)


@pytest.mark.parametrize("kind", ["chunked", "prefix_hit", "speculative"])
def test_joins_that_keep_the_old_order_count_as_not_queued(lm, oracle,
                                                           kind):
    engine = _old_order_engine(lm, kind)
    if kind == "prefix_hit":
        donor = np.arange(1, 21, dtype=np.int32)
        first = engine.submit(donor, NEW)
        serve_all(engine, [first], carried)
        reqs = [first, engine.submit(donor.copy(), NEW)]
        serve_all(engine, reqs, carried)
        assert engine.stats()["prefix_hits"] == 1
        want = (2, 1)           # the donor's whole-prompt join is queued
    else:
        reqs = [engine.submit(p, NEW) for p in prompts(4)]
        serve_all(engine, reqs, carried)
        # the chunked engine's bucket-8 cohort joins whole, and queued
        want = {"chunked": (2, 1), "speculative": (2, 0)}[kind]
    assert _joined(engine.stats()) == want
    assert [r.tokens for r in reqs] == [oracle(r.prompt) for r in reqs]
