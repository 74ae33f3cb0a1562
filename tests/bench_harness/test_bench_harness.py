"""The benchmark's own tests (BENCHMARK.json `paths`): the reduction from a
recorded device trace, the operation and byte counts, the peaks table, the
data files against the manifest, each driver rehearsed tiny on the CPU
through run.py.  The control and the broken timed paths are in
test_bench_correct.py beside this file (a file goes to one test worker).
None of this loads the TPU library.
"""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.reduce import flops as F  # noqa: E402
from benchmark.reduce import kernels as K  # noqa: E402
from benchmark.reduce import peaks as P  # noqa: E402
from benchmark.reduce import readers as R  # noqa: E402
from benchmark.reduce import trace as T  # noqa: E402

MANIFEST = harness.manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
FIXTURE = os.path.join(ROOT, "benchmark", "reduce", "fixtures",
                       "v5e_probe.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- the trace reduction, on the recorded fixture ---------------------------

@pytest.fixture(scope="module")
def recorded():
    trace = T.load_fixture(FIXTURE)
    trace["t0"], trace["t1"] = T.span_named(trace, "bench.window")
    trace["windows"] = [(trace["t0"], trace["t1"])]
    return trace


def test_busy_time_is_a_union(recorded):
    ops = recorded["device"]["/device:TPU:0"]
    t0, t1 = recorded["t0"], recorded["t1"]
    busy = T.busy_seconds(recorded, t0, t1)
    summed = sum(d for _, s, d in ops if t0 <= s < t1) / 1e9
    # the decode loop's `while` encloses its body: the union counts it once
    assert 0 < busy < summed
    assert busy <= (t1 - t0) / 1e9
    # a hand-made case: two overlapping ops and one apart
    toy = {"device": {"d": [["a", 0, 10], ["b", 5, 10], ["c", 30, 5]]},
           "host": []}
    assert T.busy_intervals(toy["device"]["d"], 0, 100) == [[0, 15], [30, 35]]
    assert T.busy_seconds(toy, 0, 100) == pytest.approx(20e-9)
    assert T.busy_seconds(toy, 10, 32) == pytest.approx(7e-9)


def test_a_kernels_summed_time(recorded):
    by_name = T.op_seconds(recorded, recorded["t0"], recorded["t1"])
    fused = {n: s for n, s in by_name.items()
             if "closed_call" in n and "tpu_custom_call" in n}
    # two layers x 15 decode steps of the fused single-query read: each
    # layer's calls are one name, summed
    assert len(fused) == 2
    assert sum(fused.values()) == pytest.approx(30 * 4.47e-6, rel=0.05)
    assert T.short_name(next(iter(fused))).startswith("%closed_call.")


def test_idle_gaps_go_to_the_covering_span(recorded):
    gaps = dict(T.idle_gaps(recorded, recorded["t0"], recorded["t1"]))
    assert set(gaps) <= {"bench.window", "bench.flash", "bench.conv",
                         "bench.generate"}
    # the decode loop's host side is where the device waited most
    assert max(gaps, key=gaps.get) in ("bench.generate", "bench.window")
    toy = {"device": {"d": [["a", 0, 10], ["b", 90, 10]]},
           "host": [["bench.outer", 0, 100], ["bench.inner", 20, 40]]}
    assert T.idle_gaps(toy, 0, 100) == [["bench.inner", pytest.approx(80e-9)]]


def test_kernel_shapes_are_read_from_the_event_name(recorded):
    name = next(n for n, _, _ in recorded["device"]["/device:TPU:0"]
                if "closed_call" in n and "tpu_custom_call" in n)
    operands, results = K.parse_call(name)
    assert operands == [("bf16", (2, 1, 256)), ("bf16", (2, 640, 256)),
                        ("bf16", (2, 640, 256)), ("s32", (2, 640, 1))]
    assert results == [("f32", (2, 1, 256))]
    assert F.kernel_bytes(operands, results) == (
        2 * 2 * 256 + 2 * 2 * 2 * 640 * 256 + 4 * 2 * 640 + 4 * 2 * 256)


def test_kernel_roofline_on_the_fixture(recorded):
    # the fixture's two flash forward calls (`%prefill_meshed.N`, 5
    # operands, 1 result) over (4, 512, 128) blocks, through the very
    # arguments of the `flash_roofline.train` metric's file
    spec = harness.layer_metric_files()["flash_roofline.train"]
    assert harness.reduce_function(spec["reducer"]) is K.kernel_roofline

    class Run:
        obs = {}
    peaks = P.peaks_for("TPU v5 lite")
    share = K.kernel_roofline(Run, recorded, peaks, **spec["args"])
    calls = [(n, d) for n, _, d in recorded["device"]["/device:TPU:0"]
             if n.startswith("%prefill_meshed.")]
    assert len(calls) == 2
    # by hand: 2 x 4 x 512 x 512 x 128 operations a call at 197e12 a
    # second, against 4 blocks of 4 x 512 x 128 bfloat16 at 819e9: the
    # bytes bound it
    by_ops = 2 * 4 * 512 * 512 * 128 / 197e12
    by_bytes = (8 + 4 * 2 * 4 * 512 * 128) / 819e9
    assert by_bytes > by_ops
    assert share == pytest.approx(
        100.0 * 2 * by_bytes / (sum(d for _, d in calls) / 1e9))
    assert 0.0 < share < 100.0
    assert "bound by operations 0.000000" in Run.obs["notes"][-1]
    args = dict(spec["args"], match="no_such_kernel")
    assert K.kernel_roofline(Run, recorded, peaks, **args) is None
    assert "no such call" in Run.obs["notes"][-1]


# -- reducers are found by name, in any module of reduce/ --------------------

def test_a_reducer_from_a_module_of_its_own_is_found(tmp_path, monkeypatch):
    # what a later PR does: a module beside the others, a metric's file
    # that names it; no file that is there is edited
    import benchmark.reduce as package
    (tmp_path / "later_pr.py").write_text(
        "def twice(run, trace, peaks, key):\n"
        "    return 2.0 * run.obs[key]\n")
    monkeypatch.setattr(package, "__path__",
                        list(package.__path__) + [str(tmp_path)])
    monkeypatch.delitem(sys.modules, "benchmark.reduce.later_pr",
                        raising=False)

    class Run:
        obs = {"n": 21}
    assert harness.reduce_function("later_pr:twice")(
        Run, None, None, key="n") == 42.0
    sys.modules.pop("benchmark.reduce.later_pr", None)
    with pytest.raises(ValueError):
        harness.reduce_function("work_mfu")
    with pytest.raises(ModuleNotFoundError):
        harness.reduce_function("no_such_module:f")


def test_observed_reads_a_group_of_the_observations():
    class Run:
        obs = {"ttft_p95_ms": 7.5, "counters": {"shed": 3, "state": "up"},
               "stages": None}
    read = lambda key: R.observed(Run, None, None, key=key)
    assert read("ttft_p95_ms") == 7.5
    assert read("counters.shed") == 3.0
    # nothing to read is nothing, never 0
    assert read("counters.missing") is None
    assert read("stages.host") is None
    assert read("absent") is None


# -- operations and bytes, against hand-worked numbers ----------------------

def test_resnet50_forward_operations():
    # He et al. count 3.8e9 multiply-adds with the stride on the first 1x1;
    # with it on the 3x3 (v1.5, what the program builds) it is 4.09e9
    assert F.resnet_forward_flops(224) == pytest.approx(2 * 4.09e9, rel=0.005)


def test_lm_operations_for_both_configurations():
    d, v = 2048, 50257
    per_layer = 12 * d * d
    assert F.lm_linear_params(d, 24, v) == 24 * per_layer + d * v
    whole = F.lm_train_flops(4, 2048, d, 8, v)
    assert whole["dense"] == 6 * 8192 * (8 * per_layer + d * v)
    assert whole["attn"] == 6 * 8 * 4 * 2048 * 2048 * d
    assert whole["total"] == pytest.approx(26.5e12, rel=0.005)
    # one token at position 0 reads one key; the prompt's tokens 1..n
    one = F.lm_forward_flops(0, 1, d, 24, v)
    assert one == 2 * (24 * per_layer + d * v) + 4 * 24 * d
    assert F.lm_forward_flops(0, 1024, d, 24, v) == (
        1024 * 2 * (24 * per_layer + d * v)
        + 4 * 24 * d * (1024 * 1025 // 2))
    assert F.lm_forward_flops(5, 5, d, 24, v) == 0


def test_flash_kernel_operations():
    qkv = [("s32", (1,)), ("s32", (1,))] + [("bf16", (64, 2048, 128))] * 3
    assert F.flash_pair_causal(qkv, [("bf16", (64, 2048, 128))]) \
        == 2 * 64 * 2048 * 2048 * 128
    assert F.kernel_bytes(qkv, [("bf16", (64, 2048, 128))]) \
        == 8 + 4 * 2 * 64 * 2048 * 128


def test_peaks_raise_for_an_unknown_device():
    assert P.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert P.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        P.peaks_for("TPU v9 imaginary")


# -- the data files against the manifest ------------------------------------

def test_every_data_file_loads_and_matches_the_manifest():
    end_to_end = {m["name"]: m for m in MANIFEST["end_to_end"]}
    reports = {cell: {n for n, m in end_to_end.items()
                      if cell in m.get("workloads", CELLS)}
               for cell in CELLS}
    for config in MANIFEST["configs"]:
        data = harness.read_json(config["file"])
        assert data["name"] == config["name"]
        assert data["source"] == config["source"] and len(data["source"]) < 200
        assert sorted(data["reduced"]) == sorted(config["reduced"])
        assert NAME.match(config["name"])
    files = harness.layer_metric_files()
    listed = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert set(files) == set(listed)
    for name, spec in files.items():
        entry = listed[name]
        for key in ("unit", "better", "source", "layer", "moves",
                    "workloads"):
            assert entry[key] == spec[key], (name, key)
        assert NAME.match(name) and UNIT.match(spec["unit"])
        assert callable(harness.reduce_function(spec["reducer"]))
        for kind in spec.get("args", {}).get("kernels", []):
            assert callable(harness.reduce_function(kind["flops"]))
        assert spec["moves"] in end_to_end
        for cell in spec["workloads"]:
            assert spec["moves"] in reports[cell], (name, cell)
    for metric in MANIFEST["end_to_end"]:
        assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
        assert 0 < metric["bound"] <= 0.1
    for cell in MANIFEST["workloads"]:
        _, config, traffic = harness.cell_files(cell["name"])
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        assert len(cell["why"]) <= 200 and cell["chips"] in (1, 4)
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "drivers", traffic["driver"] + ".py"))
        assert reports[cell["name"]] - {"setup_s"}
        assert any(cell["name"] in m["workloads"]
                   for m in MANIFEST["per_layer"])


def test_the_lm_configurations_state_the_published_sizes():
    for name, layers in (("cerebras-gpt-1.3b", 24),
                         ("cerebras-gpt-1.3b-stage", 8)):
        if name not in {c["name"] for c in MANIFEST["configs"]}:
            continue
        data = harness.read_json("benchmark", "configs", name + ".json")
        c = data["constructor"]
        assert (data["n_embd"], data["n_head"], data["n_inner"],
                data["n_positions"], data["vocab_size"]) \
            == (2048, 16, 8192, 2048, 50257)
        assert (c["d_model"], c["n_heads"], c["mlp_ratio"] * c["d_model"],
                c["max_len"], c["vocab_size"]) == (2048, 16, 8192, 2048, 50257)
        assert data["n_layer"] == c["n_layers"] == layers


# -- each driver, rehearsed through run.py ----------------------------------

def _run_py(*args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
               JAX_ENABLE_COMPILATION_CACHE="false")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *args],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)


def _rehearsed_line(cell: str, trace: int) -> dict:
    done = _run_py("--workload", cell, "--seed", "2147483999", "--seconds",
                   "1", "--trace", str(trace), "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    # a rehearsal prints under no device metric's name
    assert all(k.startswith("rehearsal.") for k in result["metrics"])
    assert "compared" in done.stderr.strip().splitlines()[-1]
    return result


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_through_run_py(cell):
    result = _rehearsed_line(cell, trace=0)
    reported = {k[len("rehearsal."):] for k in result["metrics"]}
    wanted = {m["name"] for m in MANIFEST["end_to_end"]
              if cell in m.get("workloads", CELLS)}
    assert reported == wanted


def test_traced_rehearsal_reports_per_layer_metrics_only():
    result = _rehearsed_line(CELLS[0], trace=1)
    per_layer = {m["name"] for m in MANIFEST["per_layer"]}
    reported = {k[len("rehearsal."):] for k in result["metrics"]}
    # off the chip a reader of a share of a peak finds nothing to read
    assert reported and reported <= per_layer
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_run_py_refuses_the_cpu():
    done = _run_py("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0")
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "TPU" in done.stderr
