"""Efficiency & health analytics (observe/costmodel.py + numerics.py +
history.py): per-program roofline attribution, numerics monitoring, and
the bench-history regression store — plus their degradation contracts
(cost_analysis-unavailable backends and torn history files are logged
no-ops, never crashes)."""

import json
import os

import numpy as np
import pytest

from mmlspark_tpu.observe.costmodel import roofline
from mmlspark_tpu.observe.history import (append_records, baseline,
                                          direction, judge, load_history)
from mmlspark_tpu.observe.numerics import (LossSpikeDetector,
                                           NonFiniteError, tree_health)
from mmlspark_tpu.observe.telemetry import run_telemetry


# -- costmodel.py: the roofline verdict logic -------------------------------

def test_roofline_compute_bound():
    """High arithmetic intensity, healthy utilization: the ceiling is
    compute and the program is near it."""
    r = roofline(flops=1e12, bytes_accessed=1e9, step_s=0.005,
                 peak_flops=4e14, peak_bw=1e12)
    assert r["bound"] == "compute"
    assert r["verdict"] == "compute-bound"
    assert r["mfu"] == pytest.approx(0.5)
    assert r["arithmetic_intensity"] == pytest.approx(1000.0)
    assert r["ridge"] == pytest.approx(400.0)


def test_roofline_bandwidth_bound():
    """AI below the ridge: bandwidth is the ceiling (the decode steady
    step's regime)."""
    r = roofline(flops=1e9, bytes_accessed=1e9, step_s=0.002,
                 peak_flops=4e14, peak_bw=1e12)
    assert r["bound"] == "bandwidth"
    assert r["verdict"] == "bandwidth-bound"
    assert r["hbm_bw_util"] == pytest.approx(0.5)


def test_roofline_host_bound():
    """Far below BOTH ceilings: the program is not the bottleneck — the
    BENCH_r05 resnet50 end-to-end story (MFU 0.0056 vs 0.46 on-device)."""
    r = roofline(flops=1e12, bytes_accessed=1e9, step_s=5.0,
                 peak_flops=4e14, peak_bw=1e12)
    assert r["bound"] == "compute"
    assert r["verdict"] == "host-bound"
    assert r["mfu"] < 0.01


def test_roofline_unknown_peaks_fabricates_nothing():
    """No device peaks (the CPU mesh): utilizations and verdict are None
    — never fabricated."""
    r = roofline(flops=1e12, bytes_accessed=1e9, step_s=0.005)
    assert r["mfu"] is None and r["hbm_bw_util"] is None
    assert r["bound"] is None and r["verdict"] is None
    assert r["arithmetic_intensity"] == pytest.approx(1000.0)


# -- costmodel.py: capture through the real hot paths -----------------------

def _score_once(tmp_path, n_rows=24, batch=16):
    from mmlspark_tpu import DataTable
    from mmlspark_tpu.models import ConvNetCIFAR10, ModelBundle, TPUModel
    bundle = ModelBundle.init(ConvNetCIFAR10(), (1, 32, 32, 3), seed=0)
    model = TPUModel(bundle, inputCol="image", outputCol="s",
                     miniBatchSize=batch)
    d = str(tmp_path / "run")
    with run_telemetry(d) as rt:
        model.transform(
            DataTable({"image": np.zeros((n_rows, 32, 32, 3), np.uint8)}))
        text = __import__("mmlspark_tpu.observe.export",
                          fromlist=["prometheus_text"]).prometheus_text(rt)
    return d, rt, text


def test_scoring_program_cost_capture(tmp_path):
    """TPUModel under run_telemetry captures each shape class's compiled
    cost once, joins it with execution counts, and the roofline table
    lands in run_summary.json, run.jsonl, and the Prometheus exposition
    with # HELP/# TYPE metadata."""
    import re
    d, rt, text = _score_once(tmp_path)
    summary = json.load(open(os.path.join(d, "run_summary.json")))
    progs = summary["programs"]
    (key,) = [k for k in progs if k.startswith("tpu_model:")]
    row = progs[key]
    assert row["flops"] > 0 and row["bytes_accessed"] > 0
    assert row["executions"] == 2          # 24 rows / batch 16 -> 2 batches
    assert row["step_s"] > 0 and row["step_basis"] == "probe"
    assert row["arithmetic_intensity"] > 0
    # the capture event streamed to run.jsonl (torn-run degradation path)
    events = [json.loads(line) for line in
              open(os.path.join(d, "run.jsonl"))]
    costs = [e for e in events if e.get("name") == "program_cost"]
    assert len(costs) == 1
    assert costs[0]["attrs"]["flops"] == row["flops"]
    # the sealed `programs` event rode the stream too
    assert any(e.get("type") == "programs" for e in events)
    # Prometheus: the new gauges carry metadata and stay grammar-valid
    assert "# TYPE mmlspark_tpu_program_flops gauge" in text
    assert "# HELP mmlspark_tpu_program_step_seconds" in text
    assert 'where="tpu_model"' in text
    sample = re.compile(r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? \S+$')
    for line in text.strip().splitlines():
        assert line.startswith("#") or sample.match(line), line


def test_warm_model_second_run_replays_cost_rows(tmp_path):
    """A model already warm (shape class seen, no recompile) must still
    give LATER runs roofline rows: the hot loop replays its remembered
    capture instead of paying a fresh AOT compile per run — the
    steady-state serving runs are exactly the ones that need verdicts."""
    from mmlspark_tpu import DataTable
    from mmlspark_tpu.models import ConvNetCIFAR10, ModelBundle, TPUModel
    bundle = ModelBundle.init(ConvNetCIFAR10(), (1, 32, 32, 3), seed=0)
    model = TPUModel(bundle, inputCol="image", outputCol="s",
                     miniBatchSize=16)
    table = DataTable({"image": np.zeros((16, 32, 32, 3), np.uint8)})
    with run_telemetry(str(tmp_path / "run1")):
        model.transform(table)
    with run_telemetry(str(tmp_path / "run2")):
        model.transform(table)
    summary = json.load(open(str(tmp_path / "run2" / "run_summary.json")))
    (key,) = [k for k in summary["programs"]
              if k.startswith("tpu_model:")]
    row = summary["programs"][key]
    assert row["flops"] > 0 and row["step_s"] > 0
    # replayed, not re-captured: run2 streamed no capture event
    events = [json.loads(line) for line in
              open(str(tmp_path / "run2" / "run.jsonl"))]
    assert not any(e.get("name") == "program_cost" for e in events)


def test_cost_analysis_unavailable_degrades_to_noop(tmp_path, monkeypatch):
    """A backend without a cost model (or any capture failure) must not
    crash the run: scoring proceeds, the program simply has no cost row,
    and the failure is a logged event."""
    import jax.stages
    monkeypatch.setattr(
        jax.stages.Lowered, "compile",
        lambda self, *a, **k: (_ for _ in ()).throw(
            RuntimeError("no cost model on this backend")))
    d, rt, _ = _score_once(tmp_path)
    summary = json.load(open(os.path.join(d, "run_summary.json")))
    progs = summary["programs"]
    # execution times were still accumulated; the cost side is absent
    (key,) = [k for k in progs if k.startswith("tpu_model:")]
    assert progs[key]["flops"] is None
    assert progs[key]["executions"] == 2
    events = [json.loads(line) for line in
              open(os.path.join(d, "run.jsonl"))]
    assert any(e.get("name") == "program_cost_unavailable"
               for e in events)


def test_costmodel_kill_switch(tmp_path):
    from mmlspark_tpu import config
    config.set("MMLSPARK_TPU_COSTMODEL", "0")
    try:
        d, rt, _ = _score_once(tmp_path)
    finally:
        config.set("MMLSPARK_TPU_COSTMODEL", None)
    events = [json.loads(line) for line in
              open(os.path.join(d, "run.jsonl"))]
    assert not any(e.get("name") == "program_cost" for e in events)


def test_trainer_program_cost_basis_is_span_wall(tmp_path):
    """The trainer's cost row joins the SYNCED step spans (true walls),
    not a probe — its step donates buffers, so it is never re-executed."""
    from mmlspark_tpu.train import TrainerConfig
    from mmlspark_tpu.train.trainer import Trainer
    rng = np.random.default_rng(0)
    x = rng.standard_normal((32, 4)).astype(np.float32)
    y = (x @ np.asarray([1., -2., 0.5, 0.], np.float32)).astype(np.float32)
    cfg = TrainerConfig(architecture="LinearModel",
                        model_config={"num_outputs": 1}, optimizer="sgd",
                        learning_rate=0.1, epochs=1, batch_size=16,
                        loss="mse", seed=0)
    d = str(tmp_path / "run")
    with run_telemetry(d):
        Trainer(cfg).fit_arrays(x, y)
    summary = json.load(open(os.path.join(d, "run_summary.json")))
    (key,) = [k for k in summary["programs"]
              if k.startswith("trainer:")]
    row = summary["programs"][key]
    assert row["step_basis"] == "span_wall"
    assert row["executions"] == 2          # 32 rows / batch 16
    assert row["flops"] > 0
    assert "probe_step_s" not in row


# -- report.py: roofline/numerics sections + --format json ------------------

def _synthetic_run_with_analytics(path: str) -> str:
    events = [
        {"type": "run_start", "ts": 0.0, "wall_time": 1.0, "pid": 1},
        {"type": "span", "name": "train.step", "id": 1, "parent": None,
         "cat": "step", "ts": 0.1, "dur": 0.30, "thread": 0,
         "attrs": {"step": 0, "loss": 2.0}},
        {"type": "event", "name": "numerics.probe", "id": 2,
         "parent": None, "cat": "numerics", "ts": 0.2, "thread": 0,
         "attrs": {"step": 0, "loss": 2.0, "verdict": "ok",
                   "nonfinite_elements": 0.0}},
        {"type": "event", "name": "numerics.loss_spike", "id": 3,
         "parent": None, "cat": "resilience", "ts": 0.4, "thread": 0,
         "attrs": {"step": 7, "loss": 93.0, "threshold": 2.5}},
        {"type": "stage_timings", "ts": 0.9,
         "seconds": {"host": 0.1, "transfer": 0.8, "compute": 0.3},
         "summary": {}},
        {"type": "programs", "ts": 0.9, "programs": {
            "trainer:(16, 4):float32": {
                "where": "trainer", "program": "(16, 4):float32",
                "flops": 1e9, "bytes_accessed": 1e7, "executions": 12,
                "span_s": 0.24, "step_s": 0.02,
                "step_basis": "span_wall",
                "arithmetic_intensity": 100.0, "ridge": 400.0,
                "mfu": 0.42, "hbm_bw_util": 0.1,
                "bound": "bandwidth", "verdict": "bandwidth-bound"}}},
        {"type": "run_end", "ts": 0.9, "wall_s": 0.9},
    ]
    with open(path, "w") as f:
        for ev in events:
            f.write(json.dumps(ev) + "\n")
    return path


def test_report_renders_roofline_and_numerics(tmp_path):
    from mmlspark_tpu.observe.report import (build_report, load_run,
                                             render_report)
    path = _synthetic_run_with_analytics(str(tmp_path / "run.jsonl"))
    report = build_report(load_run(path))
    assert report["programs"]["trainer:(16, 4):float32"]["verdict"] \
        == "bandwidth-bound"
    assert [e["name"] for e in report["numerics"]] \
        == ["numerics.probe", "numerics.loss_spike"]
    # the spike ALSO rides the resilience timeline (its cat)
    assert "numerics.loss_spike" in [e["name"] for e in
                                     report["resilience"]]
    text = render_report(report)
    assert "verdict: bandwidth-bound" in text
    assert "numerics.loss_spike" in text
    assert "MFU 0.42" in text


def test_report_format_json_is_machine_readable(tmp_path, capsys):
    from mmlspark_tpu.observe import report
    _synthetic_run_with_analytics(str(tmp_path / "run.jsonl"))
    assert report.main([str(tmp_path), "--format", "json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["bottleneck"] == "transfer"
    assert doc["programs"]["trainer:(16, 4):float32"]["mfu"] == 0.42
    assert doc["numerics"][1]["name"] == "numerics.loss_spike"
    assert doc["slowest_steps"][0]["attrs"]["step"] == 0


def test_report_torn_run_degrades_to_capture_events(tmp_path):
    """A run killed before finish() has no sealed `programs` event; the
    report rebuilds a degraded cost table from the capture events."""
    from mmlspark_tpu.observe.report import build_report, load_run
    path = str(tmp_path / "run.jsonl")
    events = [
        {"type": "run_start", "ts": 0.0, "wall_time": 1.0, "pid": 1},
        {"type": "event", "name": "program_cost", "id": 1, "parent": None,
         "cat": "cost", "ts": 0.1, "thread": 0,
         "attrs": {"where": "tpu_model", "program": "(8, 4):float32",
                   "flops": 2e6, "bytes_accessed": 1e5,
                   "probe_step_s": 0.001}},
    ]
    with open(path, "w") as f:
        for ev in events:
            f.write(json.dumps(ev) + "\n")
        f.write('{"torn')
    report = build_report(load_run(path))
    row = report["programs"]["tpu_model:(8, 4):float32"]
    assert row["flops"] == 2e6 and row["step_s"] == 0.001
    assert row["verdict"] is None


# -- numerics.py: probes, detector, halt ------------------------------------

def test_tree_health_counts_and_groups():
    import jax.numpy as jnp
    params = {"dense": {"kernel": jnp.asarray([[3.0, 4.0]]),
                        "bias": jnp.asarray([0.0])},
              "head": {"kernel": jnp.asarray([[jnp.inf]])}}
    grads = {"dense": {"kernel": jnp.asarray([[1.0, jnp.nan]]),
                       "bias": jnp.asarray([2.0])},
             "head": {"kernel": jnp.asarray([[0.5]])}}
    updates = {"dense": {"kernel": jnp.asarray([[0.5, 0.0]]),
                         "bias": jnp.asarray([0.0])},
               "head": {"kernel": jnp.asarray([[0.1]])}}
    h = {k: float(v) for k, v in
         tree_health(params, grads, updates,
                     acts=jnp.asarray([1.0, jnp.nan])).items()}
    assert h["nonfinite_params"] == 1.0      # the inf
    assert h["nonfinite_grads"] == 1.0       # the nan
    assert h["nonfinite_acts"] == 1.0
    assert h["param_norm/dense"] == pytest.approx(5.0)
    assert h["grad_norm/head"] == pytest.approx(0.5)
    assert h["update_ratio/dense"] == pytest.approx(0.1, rel=1e-4)


def test_loss_spike_detector_verdicts():
    det = LossSpikeDetector(window=10, spike_sigmas=6.0, warmup=5,
                            div_consecutive=3)
    # warmup + flat history: quiet
    assert [det.update(1.0 + 0.01 * i) for i in range(8)] == ["ok"] * 8
    # a single wild jump is a spike; sustained spikes are a divergence
    assert det.update(50.0) == "spike"
    assert det.update(60.0) == "spike"
    assert det.update(70.0) == "divergence"
    # recovery resets the consecutive-spike run
    assert det.update(1.02) == "ok"
    assert det.update(float("nan")) == "nonfinite"


def test_loss_spike_detector_tolerates_ordinary_noise():
    rng = np.random.default_rng(0)
    det = LossSpikeDetector()
    verdicts = {det.update(float(2.0 + 0.05 * rng.standard_normal()))
                for _ in range(200)}
    assert verdicts == {"ok"}


def _nan_chaos(step: int):
    from mmlspark_tpu import config
    from mmlspark_tpu.resilience.chaos import reset_chaos
    config.set("MMLSPARK_TPU_CHAOS_NAN_AT_STEP", step)
    reset_chaos()


def _train_cfg(ckpt, **kw):
    from mmlspark_tpu.train import TrainerConfig
    return TrainerConfig(architecture="LinearModel",
                         model_config={"num_outputs": 1}, optimizer="sgd",
                         learning_rate=0.1, epochs=3, batch_size=16,
                         loss="mse", seed=0, checkpoint_dir=ckpt, **kw)


def test_chaos_nan_detected_and_halt_preserves_finite_checkpoint(tmp_path):
    """The acceptance drill: a chaos-injected NaN is detected within one
    probe interval, halt_on_nonfinite raises BEFORE the step-boundary
    checkpoint, and the newest valid checkpoint restores finite params."""
    import jax
    from flax import serialization
    from mmlspark_tpu import config
    from mmlspark_tpu.resilience.chaos import reset_chaos
    from mmlspark_tpu.resilience.checkpoints import latest_valid_checkpoint
    from mmlspark_tpu.train.trainer import Trainer

    rng = np.random.default_rng(0)
    x = rng.standard_normal((48, 4)).astype(np.float32)
    y = (x @ np.asarray([1., -2., 0.5, 0.], np.float32)).astype(np.float32)
    ckpt = str(tmp_path / "ckpt")
    cfg = _train_cfg(ckpt, checkpoint_every_steps=1, numerics_cadence=1,
                     halt_on_nonfinite=True)
    poison_step = 4
    _nan_chaos(poison_step)
    d = str(tmp_path / "run")
    try:
        trainer = Trainer(cfg)
        with run_telemetry(d):
            with pytest.raises(NonFiniteError) as err:
                trainer.fit_arrays(x, y)
    finally:
        config.set("MMLSPARK_TPU_CHAOS_NAN_AT_STEP", None)
        reset_chaos()
    # detected within one probe interval (cadence 1: the poisoned step)
    assert err.value.step == poison_step
    # the newest checkpoint predates the poison and restores finite
    path = latest_valid_checkpoint(ckpt)
    assert path is not None
    state = trainer.init_state((1, 4), 1)
    template = jax.tree_util.tree_map(
        lambda a: np.zeros(np.shape(a), a.dtype),
        {"step": state.step, "params": state.params,
         "opt_state": state.opt_state, "batch_stats": state.batch_stats})
    restored = serialization.from_bytes(template, open(path, "rb").read())
    assert int(restored["step"]) <= poison_step
    assert all(np.isfinite(np.asarray(leaf)).all() for leaf in
               jax.tree_util.tree_leaves(restored["params"]))
    # the run record carries the detection + the chaos injection
    events = [json.loads(line) for line in
              open(os.path.join(d, "run.jsonl"))]
    names = [e.get("name") for e in events]
    assert "chaos.nan_injection" in names
    assert "numerics.nonfinite" in names


def test_nan_without_halt_records_and_continues(tmp_path):
    """Default posture (halt off): the poisoned run keeps going, the
    probe events say exactly when health was lost."""
    from mmlspark_tpu import config
    from mmlspark_tpu.resilience.chaos import reset_chaos
    from mmlspark_tpu.train.trainer import Trainer
    rng = np.random.default_rng(0)
    x = rng.standard_normal((48, 4)).astype(np.float32)
    y = (x @ np.asarray([1., -2., 0.5, 0.], np.float32)).astype(np.float32)
    cfg = _train_cfg(None, numerics_cadence=1)
    _nan_chaos(3)
    d = str(tmp_path / "run")
    try:
        trainer = Trainer(cfg)
        with run_telemetry(d):
            trainer.fit_arrays(x, y)    # completes despite the poison
    finally:
        config.set("MMLSPARK_TPU_CHAOS_NAN_AT_STEP", None)
        reset_chaos()
    assert trainer.last_health["nonfinite_params"] > 0
    events = [json.loads(line) for line in
              open(os.path.join(d, "run.jsonl"))]
    nonfinite = [e for e in events if e.get("name") == "numerics.nonfinite"]
    assert nonfinite and nonfinite[0]["attrs"]["step"] == 3
    assert nonfinite[0]["attrs"]["halting"] is False


def test_numerics_cadence_zero_is_off(tmp_path):
    from mmlspark_tpu.train.trainer import Trainer
    rng = np.random.default_rng(0)
    x = rng.standard_normal((32, 4)).astype(np.float32)
    y = (x @ np.asarray([1., -2., 0.5, 0.], np.float32)).astype(np.float32)
    cfg = _train_cfg(None, numerics_cadence=0)
    d = str(tmp_path / "run")
    trainer = Trainer(cfg)
    with run_telemetry(d):
        trainer.fit_arrays(x, y)
    assert trainer.last_health is None
    events = [json.loads(line) for line in
              open(os.path.join(d, "run.jsonl"))]
    assert not any(str(e.get("name", "")).startswith("numerics.")
                   for e in events)


# -- history.py: baselines, verdicts, degradation ---------------------------

_REC = {"metric": "cifar10_convnet_score_images_per_sec_per_chip",
        "value": 10000.0, "unit": "images/sec", "mfu": 0.004,
        "steady_step_ms": 2.0, "stage_host_s": 1.0, "vs_baseline": None}


def _store_with_runs(path, values):
    for v in values:
        append_records(str(path), [{**_REC, "value": v}])
    return str(path)


def test_history_direction_inference():
    assert direction("value") == 1
    assert direction("ragged_tokens_per_sec") == 1
    assert direction("windowed_step_ms") == -1
    assert direction("telemetry_overhead") == -1
    assert direction("int8_device_speedup") == 1
    assert direction("stage_host_s") is None      # attribution, not quality


def test_history_quiet_across_identical_runs(tmp_path):
    store = _store_with_runs(tmp_path / "h.jsonl", [10000.0, 10000.0])
    rows = judge(load_history(store), [dict(_REC)])
    assert {r["verdict"] for r in rows} == {"ok"}


def test_history_flags_20pct_regression_and_improvement(tmp_path):
    store = _store_with_runs(tmp_path / "h.jsonl", [10000.0, 10050.0])
    rows = judge(load_history(store), [{**_REC, "value": 8000.0,
                                        "steady_step_ms": 1.2}])
    by_field = {r["field"]: r["verdict"] for r in rows}
    assert by_field["value"] == "regression"          # -20% on a rate
    assert by_field["steady_step_ms"] == "improvement"  # -40% on a time
    assert by_field["mfu"] == "ok"
    assert "stage_host_s" not in by_field


def test_history_noise_widens_tolerance(tmp_path):
    """A jittery series widens its own band: a swing that a tight 10%
    gate would flag is inside the measured noise envelope."""
    store = _store_with_runs(tmp_path / "h.jsonl",
                             [10000.0, 13000.0, 9000.0, 12500.0, 9500.0])
    hist = load_history(store)
    base = baseline(hist, _REC["metric"], "value")
    assert base["mad"] > 0
    rows = judge(hist, [{**_REC, "value": 8600.0}])
    (value_row,) = [r for r in rows if r["field"] == "value"]
    assert value_row["tol"] > 0.10
    assert value_row["verdict"] == "ok"


def test_history_first_run_is_new_not_flagged(tmp_path):
    rows = judge([], [dict(_REC)])
    assert {r["verdict"] for r in rows} == {"new"}


def test_history_torn_file_degrades(tmp_path):
    """Torn/partial store lines (a killed ingest) are skipped, counted,
    and never raised on — the remaining history still judges."""
    store = _store_with_runs(tmp_path / "h.jsonl", [10000.0, 10000.0])
    with open(store, "a") as f:
        f.write('{"kind": "bench", "run_id": 99, "record": {"met')
        f.write("\nnot json at all\n")
        f.write('{"foreign": "line"}\n')
    hist = load_history(store)
    assert len(hist) == 2                       # torn/foreign all skipped
    rows = judge(hist, [dict(_REC)])
    assert {r["verdict"] for r in rows} == {"ok"}
    # appending after the tear still works and run ids keep rising
    run_id = append_records(store, [dict(_REC)])
    assert run_id == 3


def test_history_cli_ingest_check_strict(tmp_path, capsys):
    from mmlspark_tpu.observe import history
    bench = tmp_path / "bench.json"
    store = str(tmp_path / "store.jsonl")
    bench.write_text("backend warning noise\n"
                     + json.dumps(_REC) + "\n")
    assert history.main(["ingest", str(bench), "--store", store]) == 0
    assert history.main(["ingest", str(bench), "--store", store]) == 0
    out = capsys.readouterr().out
    assert "quiet: every tracked field" in out
    # an identical third pass stays quiet even under --strict
    assert history.main(["check", str(bench), "--store", store,
                         "--strict"]) == 0
    regressed = tmp_path / "regressed.json"
    regressed.write_text(json.dumps({**_REC, "value": 8000.0}) + "\n")
    assert history.main(["check", str(regressed), "--store", store]) == 0
    assert history.main(["check", str(regressed), "--store", store,
                         "--strict"]) == 1
    out = capsys.readouterr().out
    assert "regression" in out
    # check never appended: the store still holds exactly two runs
    assert len({e["run_id"] for e in load_history(store)}) == 2
    # machine-readable verdicts for CI
    assert history.main(["check", str(regressed), "--store", store,
                         "--format", "json"]) == 0
    rows = json.loads(capsys.readouterr().out)
    assert any(r["verdict"] == "regression" and r["field"] == "value"
               for r in rows)
    assert history.main(["show", "--store", store]) == 0
    assert "bench history" in capsys.readouterr().out


def test_history_cli_empty_bench_file(tmp_path, capsys):
    from mmlspark_tpu.observe import history
    empty = tmp_path / "empty.json"
    empty.write_text("no records here\n")
    assert history.main(["check", str(empty),
                         "--store", str(tmp_path / "s.jsonl")]) == 1
    capsys.readouterr()


# -- the analytic-FLOPs satellite (utils/perf.py) ---------------------------

def test_lm_train_flops_causal_halving():
    from mmlspark_tpu.utils.perf import lm_train_flops
    causal = lm_train_flops(8, 8192, 1024, 4, 8192)
    full = lm_train_flops(8, 8192, 1024, 4, 8192, causal=False)
    assert causal["attn"] * 2 == full["attn"] == causal["attn_full"]
    assert causal["dense"] == full["dense"]
    # the dense part matches the hand formula the bench always used
    n_linear = 4 * 12 * 1024 * 1024 + 1024 * 8192
    assert causal["dense"] == 6 * 8 * 8192 * n_linear
    # flash: pallas is opaque to XLA — visible = dense alone; dense impl
    # executes (and XLA sees) the FULL S^2 matmuls, mask or no mask
    assert causal["xla_visible"] == causal["dense"]
    dense_impl = lm_train_flops(8, 8192, 1024, 4, 8192, attn_impl="dense")
    assert dense_impl["xla_visible"] == dense_impl["dense"] \
        + dense_impl["attn_full"]
