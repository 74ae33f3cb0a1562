"""The compile ledger (observe/compiles.py): rows from `jax.monitoring`
(a miss, then a hit with retrieval seconds, against a temporary cache
directory), their scope and thread, the readers' clock cuts, and what the
serving engine shows of it on `stats()` / `/statz` / `prometheus_text()`.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from mmlspark_tpu.models.bundle import ModelBundle
from mmlspark_tpu.models.definitions import build_model
from mmlspark_tpu.observe import compiles, prometheus_text, run_telemetry
from mmlspark_tpu.observe.compiles import setup_phase
from mmlspark_tpu.serve import ServeConfig, ServingEngine

CFG = {"vocab_size": 64, "d_model": 32, "n_heads": 4, "n_layers": 2,
       "max_len": 64}
SERVE = dict(max_new_tokens=12, max_batch=2, queue_capacity=8,
             segment_steps=4, default_deadline_s=100.0, cache_chunk=16,
             warmup_buckets=(8,), warmup_joins=True)


@pytest.fixture
def cache_dir(tmp_path):
    """The persistent compile cache, which tier-1 runs without
    (conftest.py), on a directory of this test's own."""
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    yield tmp_path
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def _fresh(name: str, scale: float):
    """A jitted function no other test compiled: its name is the row's."""
    def f(x):
        return jnp.sin(x * scale) @ x
    f.__name__ = name
    return jax.jit(f)


def _rows(fun_name: str) -> list:
    with compiles._lock:
        return [r for r in compiles._rows if r["fun_name"] == fun_name]


def test_a_miss_then_a_hit_with_retrieval_seconds(cache_dir):
    f = _fresh("ledger_miss_hit", 1.25)
    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    jax.clear_caches()
    f(x).block_until_ready()
    miss, hit = _rows("jit(ledger_miss_hit)")
    assert (miss["cache"], hit["cache"]) == ("miss", "hit")
    assert miss["retrieval_s"] == 0.0 and 0.0 < hit["retrieval_s"] \
        <= hit["backend_s"]
    for row in (miss, hit):
        # the trace is the function's own, not its inner `sin` or `matmul`
        assert row["trace_s"] > 0.0 and row["lower_s"] > 0.0
        assert row["thread"] == threading.current_thread().name
    table = {r["fun_name"]: r for r in compiles.by_function()}
    mine = table["jit(ledger_miss_hit)"]
    assert (mine["programs"], mine["cache_hits"], mine["cache_misses"]) \
        == (2, 1, 1)
    assert mine["backend_s"] == miss["backend_s"]
    assert mine["cache_load_s"] == hit["retrieval_s"]


def test_without_the_cache_a_row_reads_off():
    f = _fresh("ledger_cache_off", 1.5)
    f(jnp.ones((4, 4))).block_until_ready()
    [row] = _rows("jit(ledger_cache_off)")
    assert row["cache"] == "off" and row["scope"] is None
    assert row["backend_s"] > 0.0 and row["retrieval_s"] == 0.0


def test_rows_carry_the_open_phases_scope():
    x = jnp.ones((4, 4))
    with setup_phase("warmup") as outer:
        with setup_phase("warm_program", kind="prefill", bucket=4) as inner:
            _fresh("ledger_scoped", 1.75)(x).block_until_ready()
        _fresh("ledger_scoped_outer", 2.0)(x).block_until_ready()
    assert _rows("jit(ledger_scoped)")[0]["scope"] == "warm_program/prefill"
    assert _rows("jit(ledger_scoped_outer)")[0]["scope"] == "warmup"
    # a closed phase holds its seconds and its thread's rows
    assert inner.programs == 1 and outer.programs == 2
    assert 0.0 < inner.seconds <= outer.seconds
    assert inner.backend_s > 0.0 and inner.cache_hits == 0


def test_two_threads_compiling_at_once_do_not_swap_hits(cache_dir):
    x = jnp.ones((8, 8))
    warm, cold = _fresh("ledger_thread_a", 2.25), _fresh("ledger_thread_b",
                                                         2.5)
    warm(x).block_until_ready()     # thread a's program is in the cache
    jax.clear_caches()
    gate = threading.Barrier(2)

    def work(f, scope):
        gate.wait()
        with setup_phase(scope):
            f(x).block_until_ready()

    threads = [threading.Thread(target=work, args=(warm, "thread_a"),
                                name="ledger-a"),
               threading.Thread(target=work, args=(cold, "thread_b"),
                                name="ledger-b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    a = _rows("jit(ledger_thread_a)")[-1]
    [b] = _rows("jit(ledger_thread_b)")
    assert (a["cache"], a["scope"], a["thread"]) \
        == ("hit", "thread_a", "ledger-a")
    assert (b["cache"], b["scope"], b["thread"]) \
        == ("miss", "thread_b", "ledger-b")
    assert a["retrieval_s"] > 0.0 and b["retrieval_s"] == 0.0


def test_the_readers_cut_by_the_clock():
    x = jnp.ones((4, 4))
    t0 = time.perf_counter()
    _fresh("ledger_first", 2.75)(x).block_until_ready()
    t1 = time.perf_counter()
    with setup_phase("cut"):
        _fresh("ledger_second", 3.0)(x).block_until_ready()
    t2 = time.perf_counter()
    names = lambda **cut: {r["fun_name"] for r in compiles.by_function(**cut)}
    assert "jit(ledger_first)" in names(since=t0, until=t1)
    assert "jit(ledger_second)" not in names(since=t0, until=t1)
    assert "jit(ledger_second)" in names(since=t1, until=t2)
    assert "jit(ledger_first)" not in names(since=t1)
    assert compiles.totals(since=t0, until=t2)["programs"] \
        == compiles.totals(since=t0, until=t1)["programs"] \
        + compiles.totals(since=t1, until=t2)["programs"]
    assert compiles.totals(since=t2)["programs"] == 0
    # with no cut, the process's own sums
    assert compiles.totals()["programs"] >= \
        compiles.totals(since=t0)["programs"] >= 2
    scopes = compiles.by_scope(since=t1, until=t2)
    assert scopes["cut"]["programs"] >= 1
    assert scopes["cut"]["phases"] == 1
    assert scopes["cut"]["top_s"] == scopes["cut"]["phase_s"] > 0.0
    assert "cut" not in compiles.by_scope(since=t0, until=t1)


def test_registering_twice_counts_once():
    compiles.register()
    compiles.register()
    x = jnp.ones((4, 4))
    before = compiles.totals()["programs"]
    _fresh("ledger_once", 3.25)(x).block_until_ready()
    assert len(_rows("jit(ledger_once)")) == 1
    assert compiles.totals()["programs"] == before + 1


def _synthetic_program(name: str, seconds: float = 0.001) -> None:
    """The events of one compiled program, as JAX sends them."""
    compiles._on_duration(compiles._COMPILE + "jaxpr_trace_duration",
                          seconds, fun_name=name)
    compiles._on_duration(
        compiles._COMPILE + "jaxpr_to_mlir_module_duration", seconds,
        fun_name=f"jit({name})")
    compiles._on_event(compiles._CACHE + "compile_requests_use_cache")
    compiles._on_duration(compiles._COMPILE + "backend_compile_duration",
                          seconds, fun_name=f"jit({name})")


@pytest.fixture
def empty_ledger(monkeypatch):
    """Rows and sums of this test's own: the process's are capped."""
    monkeypatch.setattr(compiles, "_rows", [])
    monkeypatch.setattr(compiles, "_phases", [])
    monkeypatch.setattr(compiles, "_sums", dict.fromkeys(compiles.SUMS, 0.0))


def test_many_threads_lose_no_row(empty_ledger):
    import sys
    threads, each = 16, 100
    seen: dict = {}

    def work(k: int) -> None:
        with setup_phase(f"stress_{k}"):
            for _ in range(each):
                _synthetic_program(f"stress_{k}")
        seen[k] = compiles.since_mark()["programs"]

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=work, args=(k,))
                for k in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(60.0)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in pool)
    assert seen == {k: each for k in range(threads)}
    totals = compiles.totals()
    assert totals["programs"] == totals["cache_misses"] == threads * each
    assert totals["trace_s"] == pytest.approx(threads * each * 0.001)
    scopes = compiles.by_scope()
    assert all(scopes[f"stress_{k}"]["programs"] == each
               and scopes[f"stress_{k}"]["phases"] == 1
               for k in range(threads))


def test_past_the_cap_the_sums_only(empty_ledger, monkeypatch):
    monkeypatch.setattr(compiles, "MAX_ROWS", 3)
    for _ in range(5):
        _synthetic_program("capped")
    assert len(compiles._rows) == 3
    assert compiles.totals()["programs"] == 5
    assert compiles.totals(since=0.0)["programs"] == 3


def test_the_sums_are_process_counters_for_prometheus():
    from mmlspark_tpu.observe import get_counter
    x = jnp.ones((4, 4))        # may compile a program of its own
    before = get_counter("compile.programs")
    with setup_phase("warmup"):
        _fresh("ledger_prom", 3.5)(x).block_until_ready()
    assert get_counter("compile.programs") == before + 1
    text = prometheus_text()
    for name in ("compile_programs", "compile_trace_s", "compile_lower_s",
                 "compile_backend_s", "setup_warmup_s"):
        assert f"mmlspark_tpu_{name}_total " in text, name
    assert f"mmlspark_tpu_compile_programs_total {int(before) + 1}\n" in text


def test_the_package_import_is_the_first_phase():
    entry = compiles.by_scope()["import"]
    assert entry["phases"] == 1 and entry["top_s"] == compiles.import_s > 0.0


# -- the serving engine ------------------------------------------------------

@pytest.fixture(scope="module")
def bundle():
    model = build_model("TransformerLM", CFG)
    variables = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    return ModelBundle.from_module(model, variables)


def _events(run, kind: str) -> list:
    return [e for e in run.summary()["serve"] if e.get("event") == kind]


def test_a_warmed_engines_stats_and_events(bundle):
    with run_telemetry() as run:
        engine = ServingEngine(bundle, ServeConfig(**SERVE)).warmup()
        stats = engine.stats()
        programs = _events(run, "warmup_program")
        [done] = _events(run, "warmup_done")
        [placed] = _events(run, "weights_placed")
    for key in ("compile_programs", "compile_trace_s", "compile_lower_s",
                "compile_backend_s", "compile_cache_load_s",
                "compile_cache_hits", "compile_cache_misses",
                "compiles_after_ready", "warmup_s", "warmup_programs",
                "weights_place_s", "import_s"):
        assert key in stats, key
    # numbers only: the table by jitted function is `/statz`'s
    assert all(not isinstance(v, list) for v in stats.values())
    assert stats["warmup_programs"] == len(programs) > 0
    assert {e["kind"] for e in programs} == {"prefill", "merge", "segment"}
    assert all(e["bucket"] == 8 and e["seconds"] >= 0.0 for e in programs)
    # one clock: the event's seconds are the gauge's, the phase's own
    assert done["seconds"] == round(stats["warmup_s"], 3) > 0.0
    assert done["programs"] >= sum(e["compiled"] for e in programs) > 0
    assert (done["cache_hits"], done["cache_misses"]) == (0, 0)
    assert placed["seconds"] == round(stats["weights_place_s"], 3)
    assert stats["compiles_after_ready"] == 0
    assert stats["import_s"] == compiles.import_s
    table = {r["fun_name"]: r for r in compiles.by_function()}
    assert table["jit(prefill_meshed)"]["programs"] >= 2
    assert table["jit(serve_segment_meshed)"]["trace_s"] > 0.0
    # the spans are the tracing's own: `setup.*`, cat "setup"
    spans = {r["name"] for r in run.tracer.records()
             if r.get("type") == "span" and r.get("cat") == "setup"}
    assert {"setup.place_weights", "setup.warmup",
            "setup.warm_program"} <= spans


def test_a_second_engine_takes_its_merges_from_memory(bundle):
    ServingEngine(bundle, ServeConfig(**SERVE)).warmup()
    mark = time.perf_counter()
    second = ServingEngine(bundle, ServeConfig(**SERVE)).warmup()
    rose = {r["fun_name"]: r["programs"]
            for r in compiles.by_function(since=mark)}
    # an engine's prefill and segment are `jax.jit`s of its own closures,
    # so a second engine traces and compiles them again; the merge is a
    # module's function and comes from jit's in-memory cache
    assert "jit(_merge_cache_rows_jit)" not in rose
    assert rose["jit(prefill_meshed)"] >= 2
    assert second.stats()["compile_programs"] \
        == compiles.totals()["programs"]


def test_a_class_first_met_after_ready_is_counted(bundle):
    engine = ServingEngine(bundle, ServeConfig(**SERVE)).warmup()
    assert engine.stats()["compiles_after_ready"] == 0
    # bucket 16 was not warmed: its prefill compiles against the request
    req = engine.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=4)
    for _ in range(200):
        if req.finished:
            break
        engine._tick()
    assert req.status == "ok"
    after = engine.stats()
    assert after["compiles_after_ready"] >= 1
    late = {r["fun_name"] for r in compiles.by_function(
        since=engine._ready_at)}
    assert "jit(prefill_meshed)" in late


def test_statz_of_a_warmed_engine_shows_the_ledger(bundle):
    import http.client
    import json
    from mmlspark_tpu.serve.lifecycle import start_http, stop_http
    engine = ServingEngine(bundle, ServeConfig(**SERVE)).warmup()
    server = start_http(engine, port=0)
    try:
        conn = http.client.HTTPConnection(
            "127.0.0.1", server.server_address[1], timeout=30.0)
        conn.request("GET", "/statz")
        statz = json.loads(conn.getresponse().read())
        conn.close()
    finally:
        stop_http(server)
    assert "programs" not in engine.stats()
    assert statz["warmup_s"] > 0.0 and statz["warmup_programs"] > 0
    assert (statz["compile_cache_hits"], statz["compile_cache_misses"]) \
        == (compiles.totals()["cache_hits"], compiles.totals()["cache_misses"])
    assert {"fun_name", "programs", "trace_s", "lower_s", "backend_s",
            "cache_load_s", "cache_hits", "cache_misses"} \
        <= set(statz["programs"][0])
    text = prometheus_text()
    assert "mmlspark_tpu_compile_programs_total" in text
    assert "mmlspark_tpu_setup_warm_program_s_total" in text


def test_a_new_shape_class_names_itself_with_what_it_cost(bundle):
    from mmlspark_tpu.models.generate import DecodeEngine
    # this thread compiled before the engine was built: none of that is
    # the engine's first class's cost (its mark is set at the build)
    _fresh("ledger_before_engine", 4.5)(jnp.ones((5, 5))).block_until_ready()
    with run_telemetry() as run:
        built = compiles.totals()["programs"]
        eng = DecodeEngine(bundle.module(), 4, chunk=16)
        eng.generate(bundle.variables, np.ones((1, 8), np.int32),
                     np.asarray([5], np.int32))
        events = [r["attrs"] for r in run.tracer.records()
                  if r.get("name") == "recompile"]
    assert events
    first = events[0]
    assert first["where"] == "decode" and first["programs"] >= 1
    assert first["trace_s"] > 0.0 and first["backend_s"] > 0.0
    assert first["cache_hits"] == 0
    assert sum(e["programs"] for e in events) \
        <= compiles.totals()["programs"] - built


def test_a_model_scored_untraced_first_still_reports_under_telemetry():
    """A warm-up call with no tracer, then a run under `run_telemetry`:
    the run's first batch of the class is its `recompile` event and cost
    row (the roofline's), and the phase `setup.score_program` was the
    untraced call's, once."""
    from mmlspark_tpu import DataTable
    from mmlspark_tpu.models import TPUModel
    from mmlspark_tpu.observe import get_counter
    model = build_model("MLPClassifier",
                        {"hidden_sizes": [8], "num_classes": 3})
    scorer = TPUModel(ModelBundle.init(model, (1, 6)), inputCol="x",
                      outputCol="y", miniBatchSize=16)
    table = DataTable({"x": np.ones((32, 6), np.float32)})
    phases = lambda: compiles.by_scope().get(
        "score_program", {"phases": 0, "programs": 0})
    before = phases()
    seconds = get_counter("setup.score_program_s")
    scorer.transform(table)
    warm = phases()
    assert warm["phases"] == before["phases"] + 1
    assert warm["programs"] > before["programs"]
    assert get_counter("setup.score_program_s") > seconds
    with run_telemetry() as run:
        scorer.transform(table)
        names = [r.get("name") for r in run.tracer.records()]
        summary = run.summary()
    assert phases()["phases"] == warm["phases"]
    assert names.count("recompile") == 1
    assert "setup.score_program" not in names
    [row] = [r for r in summary["programs"].values()
             if r["where"] == "tpu_model"]
    assert row["flops"] > 0 and row["executions"] == 2
    # and a model first scored under a tracer enters the phase round the
    # probe, which is what compiles: the rows are the phase's own
    fresh = TPUModel(ModelBundle.init(model, (1, 6)), inputCol="x",
                     outputCol="y", miniBatchSize=8)
    with run_telemetry() as run:
        fresh.transform(table)
        names = [r.get("name") for r in run.tracer.records()]
    assert names.count("setup.score_program") == 1
    assert names.count("recompile") == 1
    last = phases()
    assert last["phases"] == warm["phases"] + 1
    assert last["programs"] > warm["programs"]
