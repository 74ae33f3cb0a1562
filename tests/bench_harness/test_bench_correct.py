"""`correct` shown to fail (BENCHMARK.json `paths`): the control (the
reference in float8 put in the program's place) comes out as not correct,
and a rehearsal run with the timed path broken underneath sees `correct`
false, once for each fault a cell can have.  In-process, tiny, on the CPU.
"""

import importlib
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import calibrate  # noqa: E402
from benchmark import harness  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

CELLS = [w["name"] for w in harness.manifest()["workloads"]]


def _rehearse(cell: str, seed: int = 2147484001) -> tuple:
    """(result, run) of one rehearsal in this process."""
    return bench_run.run_cell(cell, seed, 0.5, False, True,
                              time.perf_counter())


@pytest.mark.parametrize("cell", CELLS)
def test_the_float8_control_is_not_correct(cell):
    result, run = _rehearse(cell)
    assert result["correct"] is True
    driver = importlib.import_module(
        "benchmark.drivers." + run.traffic["driver"])
    control = driver.control(run)
    # the harness's own comparison, as calibrate.py applies it on the chip
    verdicts = calibrate.judged(control, run.traffic["limits"])
    assert "fp8" in verdicts
    assert not any(verdicts.values()), (control, run.traffic["limits"])


def _broken_score(monkeypatch):
    from mmlspark_tpu.models import TPUModel
    real = TPUModel.transform

    def shifted(self, table):
        out = real(self, table)
        col = self.outputCol
        return out.with_column(col, np.roll(out[col], 1, axis=0))
    monkeypatch.setattr(TPUModel, "transform", shifted)


def _broken_serve(monkeypatch):
    from mmlspark_tpu.serve.request import Request
    real = Request.note_tokens

    def altered(self):
        if len(self.tokens) >= 3 and not getattr(self, "detail", ""):
            self.tokens[2] = (self.tokens[2] + 1) % 97
            self.detail = "altered"
        real(self)
    monkeypatch.setattr(Request, "note_tokens", altered)


def _broken_train(how: str):
    def plant(monkeypatch):
        from mmlspark_tpu.train import Trainer
        real = Trainer.make_train_step

        def make(self):
            step = real(self)

            def unchanged(state, x, y, mask, *rest):
                keep = jax.tree_util.tree_map(
                    jnp.copy, (state.params, state.opt_state))
                new, loss, metrics = step(state, x, y, mask, *rest)
                return (new.replace(params=keep[0], opt_state=keep[1]),
                        loss, metrics)

            def half(state, x, y, mask, *rest):
                h = x.shape[0] // 2
                twice = lambda a: jnp.concatenate([a[:h], a[:h]])
                return step(state, twice(x), twice(y), mask, *rest)
            return {"unchanged": unchanged, "half": half}[how]
        monkeypatch.setattr(Trainer, "make_train_step", make)
    return plant


FAULTS = [("resnet50_bulk_score", "an answer altered", _broken_score),
          ("cgpt13b_serve_closed16", "a token altered", _broken_serve),
          ("cgpt13b_stage_train", "state unchanged",
           _broken_train("unchanged")),
          ("cgpt13b_stage_train", "half of the batch left out",
           _broken_train("half"))]


@pytest.mark.parametrize("cell,fault,plant",
                         [f for f in FAULTS if f[0] in CELLS],
                         ids=lambda v: v if isinstance(v, str) else "")
def test_a_broken_timed_path_is_not_correct(cell, fault, plant, monkeypatch):
    plant(monkeypatch)
    result, _ = _rehearse(cell)
    assert result["correct"] is False, (fault, result["compared"])
