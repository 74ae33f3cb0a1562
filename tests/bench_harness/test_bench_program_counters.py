"""The reducers that read what the program itself counts and spans
(`benchmark/reduce/counters.py`, `benchmark/reduce/gaps.py`): on hand-made
inputs, on the recorded v5e fixture, and through a traced rehearsal of the
serve cell.  None of this loads the TPU library.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.reduce import counters as C  # noqa: E402
from benchmark.reduce import gaps as G  # noqa: E402
from benchmark.reduce import trace as T  # noqa: E402

FIXTURE = os.path.join(ROOT, "benchmark", "reduce", "fixtures",
                       "v5e_probe.json")
SERVE = "cgpt13b_serve_closed16"
COUNTER_METRICS = {
    "queue_wait_mean_ms.serve", "batch_occupancy.serve",
    "prefill_share_of_tick.serve", "tick_fetch_wait_share.serve",
    "prefill_token_use.serve", "decode_window_use.serve"}


class Run:
    obs = {"t0": 10.0, "t1": 14.0,
           "counters": {"queue_wait_s": 6.0, "joined": 4, "tick_s": 3.0,
                        "prefill_s": 0.5, "fetch_wait_s": 1.0, "idle": 0,
                        "state": "ready"}}


def _ratio(**args):
    return C.ratio(Run, None, None, **args)


def test_ratio_of_sums_of_counters():
    assert _ratio(num=["queue_wait_s"], den=["joined"], scale=1e3) == 1500.0
    assert _ratio(num=["prefill_s", "fetch_wait_s"], den=["tick_s"],
                  scale=100.0) == pytest.approx(50.0)
    assert _ratio(num=["tick_s"], den="window_s") == pytest.approx(0.75)


def test_ratio_finds_nothing_where_there_is_nothing_to_read():
    # a counter the program lacks (the parent commit), a denominator of 0,
    # a cell whose driver keeps no counters: nothing, never 0
    assert _ratio(num=["no_such"], den=["joined"]) is None
    assert _ratio(num=["joined"], den=["no_such"]) is None
    assert _ratio(num=["joined"], den=["idle"]) is None

    class Score:
        obs = {"t0": 0.0, "t1": 1.0, "stages": {"drain": 0.5}}
    assert C.ratio(Score, None, None, num=["joined"], den="window_s") is None


def test_owned_share_is_the_prefixes_part_of_the_idle_time():
    # one chip busy 0-10 and 90-100: the gap's middle lies in
    # `mmlspark_tpu.drain`; a second window idle throughout, under no span
    trace = {"device": {"/device:TPU:0": [["a", 0, 10], ["b", 90, 10]]},
             "host": [["bench.transform", 0, 100],
                      ["mmlspark_tpu.drain", 20, 60]],
             "windows": [(0, 100), (500, 520)]}
    assert G.owned_share(None, trace, None, prefix="mmlspark_tpu.") \
        == pytest.approx(80.0)
    assert G.owned_share(None, trace, None, prefix="bench.") == 0.0
    assert G.owned_share(None, trace, None, prefix="") == pytest.approx(100.0)
    busy = dict(trace, windows=[(0, 10)])
    assert G.owned_share(None, busy, None, prefix="mmlspark_tpu.") is None
    assert G.owned_share(None, None, None, prefix="mmlspark_tpu.") is None
    assert G.owned_share(None, dict(trace, device={}), None,
                         prefix="mmlspark_tpu.") is None


def test_owned_share_on_the_recorded_fixture():
    # recorded before the program wrote any span of its own: every gap
    # there lies under a `bench.*` span
    trace = T.load_fixture(FIXTURE)
    trace["windows"] = [T.span_named(trace, "bench.window")]
    assert G.owned_share(None, trace, None, prefix="mmlspark_tpu.") == 0.0
    assert G.owned_share(None, trace, None, prefix="bench.") \
        == pytest.approx(100.0)


def test_the_new_metrics_name_reducers_of_their_own_modules():
    files = harness.layer_metric_files()
    for name in COUNTER_METRICS:
        assert files[name]["reducer"] == "counters:ratio"
        assert files[name]["workloads"] == [SERVE]
        assert harness.reduce_function(files[name]["reducer"]) is C.ratio
    spec = files["idle_owned_share.serve"]
    assert harness.reduce_function(spec["reducer"]) is G.owned_share
    assert spec["args"] == {"prefix": "mmlspark_tpu."}
    assert files["score_drain_share"]["args"] == {"stages": ["drain"]}


def test_a_traced_serve_rehearsal_reports_the_counter_metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
               JAX_ENABLE_COMPILATION_CACHE="false")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", SERVE, "--seed", "2147484001", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    metrics = result["metrics"]
    for name in COUNTER_METRICS:
        value = metrics["rehearsal." + name]["value"]
        assert value > 0.0, name
        if metrics["rehearsal." + name]["unit"] == "%":
            assert value <= 100.0, name
    # the CPU has no device plane: no idle time to own
    assert "rehearsal.idle_owned_share.serve" not in metrics
