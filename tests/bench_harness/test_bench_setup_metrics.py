"""The reducers that read the program's compile ledger
(`benchmark/reduce/startup.py`): on a hand-made ledger, with none (a
parent commit), and through a traced rehearsal of a serve cell, the train
cell and the score cell.  None of this loads the TPU library.
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
from benchmark.reduce import readers as R  # noqa: E402
from benchmark.reduce import startup as S  # noqa: E402

MANIFEST = harness.manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
SERVE_CELLS = [c for c in CELLS if "serve" in c]
EVERY_CELL = {"setup_trace_lower_s", "setup_backend_compile_s",
              "setup_cache_load_s", "setup_cache_hit_share",
              "setup_programs", "setup_unattributed_share"}
SERVE_ONLY = {"setup_warmup_share", "program_compiles_in_window"}


class Run:
    """Set-up from 100 to 180 on the host's clock."""
    t_process = 100.0
    obs = {"t0": 180.0, "t1": 230.0,
           "counters": {"compile_programs": 0.0, "joined": 4}}


class Ledger:
    """40 programs before the window: 30 from the cache, 10 compiled."""

    def __init__(self, asked: bool = True):
        self.asked = asked
        self.cuts: list = []

    def totals(self, since=None, until=None):
        self.cuts.append((since, until))
        return {"programs": 40.0, "trace_s": 12.0, "lower_s": 3.0,
                "backend_s": 20.0, "cache_load_s": 5.0,
                "cache_hits": 30.0 if self.asked else 0.0,
                "cache_misses": 10.0 if self.asked else 0.0, "saved_s": 0.0}

    def by_scope(self, since=None, until=None):
        self.cuts.append((since, until))
        row = lambda **over: dict(
            {"programs": 0.0, "trace_s": 0.0, "lower_s": 0.0,
             "backend_s": 0.0, "cache_load_s": 0.0, "cache_hits": 0.0,
             "cache_misses": 0.0, "saved_s": 0.0, "phases": 0,
             "phase_s": 0.0, "top_s": 0.0}, **over)
        return {
            "import": row(phases=1, phase_s=4.0, top_s=4.0),
            "warmup": row(phases=1, phase_s=48.0, top_s=48.0),
            # inside `warmup`: counted there, not again
            "warm_program/prefill": row(programs=8.0, trace_s=10.0,
                                        phases=8, phase_s=30.0),
            # compiled outside every phase (the benchmark's weights)
            None: row(programs=6.0, trace_s=1.0, lower_s=1.0,
                      backend_s=5.0, cache_load_s=1.0)}

    def by_function(self, since=None, until=None):
        return [{"fun_name": "jit(prefill_meshed)", "programs": 8.0}]


@pytest.fixture
def ledger(monkeypatch):
    made = Ledger()
    monkeypatch.setattr(S, "ledger", lambda: made)
    return made


def test_sums_of_the_ledgers_totals_before_the_window(ledger):
    assert S.before_window(Run, None, None, keys=["trace_s", "lower_s"]) \
        == 15.0
    assert S.before_window(Run, None, None, keys=["backend_s"]) == 20.0
    assert S.before_window(Run, None, None, keys=["cache_load_s"]) == 5.0
    assert S.before_window(Run, None, None, keys=["programs"]) == 40.0
    # cut by the clock: the process's start to the window's
    assert set(ledger.cuts) == {(100.0, 180.0)}


def test_the_caches_hit_share(ledger, monkeypatch):
    assert S.cache_hit_share(Run, None, None) == pytest.approx(75.0)
    # with the cache off no program asked it: nothing to read, never 0
    monkeypatch.setattr(S, "ledger", lambda: Ledger(asked=False))
    assert S.cache_hit_share(Run, None, None) is None


def test_a_phases_share_of_setup(ledger):
    assert S.phase_share(Run, None, None, scope="warmup") \
        == pytest.approx(60.0)
    assert S.phase_share(Run, None, None, scope="import") \
        == pytest.approx(5.0)
    assert S.phase_share(Run, None, None, scope="train_step") is None


def test_what_no_span_and_no_row_covers(ledger):
    # 80 s less import 4, warmup 48 (its inner phases not again) and the
    # 8 s of the programs compiled outside every phase
    assert S.unattributed_share(Run, None, None) \
        == pytest.approx(100.0 * (80.0 - 4.0 - 48.0 - 8.0) / 80.0)


def test_the_table_a_cell(ledger):
    table = S.where_setup_goes(Run)
    assert table["setup_s"] == 80.0 and table["totals"]["programs"] == 40.0
    assert set(table["by_scope"]) == {"import", "warmup",
                                      "warm_program/prefill", "None"}
    assert table["by_function"][0]["fun_name"] == "jit(prefill_meshed)"


@pytest.mark.parametrize("reducer,args", [
    (S.before_window, {"keys": ["programs"]}), (S.cache_hit_share, {}),
    (S.phase_share, {"scope": "warmup"}), (S.unattributed_share, {})],
    ids=["before_window", "cache_hit_share", "phase_share",
         "unattributed_share"])
def test_nothing_to_read_without_a_ledger(monkeypatch, reducer, args):
    # the parent commit: `mmlspark_tpu.observe` has no `compiles`
    monkeypatch.setattr(S, "ledger", lambda: None)
    assert reducer(Run, None, None, **args) is None
    assert S.where_setup_goes(Run) == {}


def test_the_ledger_is_looked_up_and_a_missing_one_is_none(monkeypatch):
    from mmlspark_tpu.observe import compiles
    assert S.ledger() is compiles
    monkeypatch.setitem(sys.modules, "mmlspark_tpu.observe.compiles", None)
    monkeypatch.delattr("mmlspark_tpu.observe.compiles", raising=False)
    assert S.ledger() is None


def test_the_programs_own_count_of_compiles_in_the_window():
    spec = harness.layer_metric_files()["program_compiles_in_window"]
    assert spec["reducer"] == "readers:observed"
    assert R.observed(Run, None, None, **spec["args"]) == 0.0

    class Parent:       # its stats() has no such count
        obs = {"counters": {"joined": 4}}
    assert R.observed(Parent, None, None, **spec["args"]) is None


def test_the_new_metrics_files_name_their_cells_and_reducers():
    files = harness.layer_metric_files()
    for name in EVERY_CELL | SERVE_ONLY:
        spec = files[name]
        assert spec["source"] == "program_counter", name
        assert spec["workloads"] == (SERVE_CELLS if name in SERVE_ONLY
                                     else CELLS), name
        assert callable(harness.reduce_function(spec["reducer"]))
    for name in EVERY_CELL | {"setup_warmup_share"}:
        assert files[name]["moves"] == "setup_s", name
        assert files[name]["reducer"].startswith("startup:"), name
    assert files["program_compiles_in_window"]["moves"] \
        == "serve_tokens_per_s"
    assert files["setup_warmup_share"]["layer"] == "HTTP and scheduler"


def _traced_rehearsal(cell: str, cache_dir) -> dict:
    """One `--trace 1 --rehearse` run with the persistent compile cache
    on, in a directory of the test's own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "2147484003", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    return {k[len("rehearsal."):]: v["value"]
            for k, v in result["metrics"].items()}


@pytest.mark.parametrize("cell", ["cgpt13b_serve_closed16",
                                  "cgpt13b_stage_train",
                                  "resnet50_bulk_score"])
def test_a_traced_rehearsal_reports_the_setup_metrics(cell, tmp_path):
    metrics = _traced_rehearsal(cell, tmp_path)
    wanted = EVERY_CELL | (SERVE_ONLY if cell in SERVE_CELLS else set())
    assert wanted <= set(metrics)
    assert not (SERVE_ONLY - wanted) & set(metrics)
    # a first run against an empty directory: every program a miss
    assert metrics["setup_cache_hit_share"] == 0.0
    assert metrics["setup_cache_load_s"] == 0.0
    assert metrics["setup_programs"] >= 3
    assert metrics["setup_trace_lower_s"] > 0.0
    assert metrics["setup_backend_compile_s"] > 0.0
    assert 0.0 < metrics["setup_unattributed_share"] < 100.0
    if cell in SERVE_CELLS:
        assert 0.0 < metrics["setup_warmup_share"] < 100.0
        assert metrics["program_compiles_in_window"] == 0.0
        assert metrics["compiles_in_window.serve"] == 0.0
