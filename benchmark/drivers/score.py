"""Bulk scoring: one table of images through `TPUModel.transform`, again
and again for the window (chip_smoke.py's score phase with a timed window
put round it).  Host arrays in, host arrays out.

Traffic parameters: `rows`, `image_hw`, `mini_batch` (a power of two that
does not divide `rows`, so every call ends in a ragged batch),
`check_rows` (rows of every call held against the reference, a third of
them from the ragged batch), `limits`.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from benchmark import harness
from benchmark.reduce import flops as F
from benchmark.reference import resnet, weights


def _module(config: dict):
    from mmlspark_tpu.models.definitions import build_model
    return build_model(config["architecture"], dict(config["constructor"]))


def _shapes(config: dict) -> dict:
    c = config["constructor"]
    return resnet.variable_shapes(c["num_classes"], tuple(c["stage_sizes"]),
                                  tuple(c["widths"]))


def setup(run) -> dict:
    from mmlspark_tpu import DataTable
    from mmlspark_tpu.models import ModelBundle, TPUModel
    t = run.traffic
    rows, hw, mb = t["rows"], t["image_hw"], t["mini_batch"]
    if rows % mb == 0:
        raise ValueError("rows must leave a ragged last batch")
    module = _module(run.config)
    shapes = _shapes(run.config)
    harness.same_tree(jax.eval_shape(
        module.init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, hw, hw, 3), np.float32)), shapes)
    variables = harness.host_tree(weights.make_variables(shapes, run.seed))
    run.lap("imports_and_weights")
    rng = np.random.default_rng(run.seed)
    images = rng.integers(0, 256, (rows, hw, hw, 3), dtype=np.uint8)
    # the rows compared: from the ragged batch and from the full ones
    tail = rows - rows % mb
    n_tail = min(t["check_rows"] // 3, rows - tail)
    picked = np.concatenate([
        rng.choice(np.arange(tail, rows), n_tail, replace=False),
        rng.choice(tail, t["check_rows"] - n_tail, replace=False)])
    model = TPUModel(ModelBundle.from_module(module, variables),
                     inputCol="image", outputCol="scores", miniBatchSize=mb)
    # one full and one ragged batch: both are the one padded shape
    model.transform(DataTable({"image": images[:mb + 1]}))
    return {"model": model, "table": DataTable({"image": images}),
            "images": images, "picked": np.sort(picked), "held": []}


def window(run, state: dict) -> None:
    import contextlib
    from mmlspark_tpu.observe.spans import pipeline_timing
    model, table, picked = state["model"], state["table"], state["picked"]
    rows = run.traffic["rows"]
    timing = pipeline_timing() if run.trace else contextlib.nullcontext()
    mark = run.compiles.mark()
    calls = failed = 0
    run.start_trace()
    with timing as stages:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < run.seconds:
            with run.annotate("transform"):
                scores = model.transform(table)["scores"]
            calls += 1
            if scores.shape[0] != rows or not np.isfinite(scores).all():
                failed += 1
            state["held"].append(np.array(scores[picked]))
        t1 = time.perf_counter()
    run.obs.update(
        t0=t0, t1=t1, attempted=calls, failed=failed,
        end_to_end={"images_per_s": calls * rows / (t1 - t0)},
        work={"score_flops": calls * rows * F.resnet_forward_flops(
            run.traffic["image_hw"])},
        stages=dict(stages.seconds) if run.trace else None,
        compiles_in_window=run.compiles.since(mark)[0])


def rel_rms(got: np.ndarray, want: np.ndarray) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def reference_scores(run, images: np.ndarray, mode: str = "f32"):
    variables = weights.make_variables(_shapes(run.config), run.seed)
    forward = jax.jit(resnet.forward, static_argnames=("mode", "stage_sizes"))
    stages = tuple(run.config["constructor"]["stage_sizes"])
    block = run.traffic.get("reference_block", 32)
    return np.concatenate([
        np.asarray(forward(variables, images[i:i + block], mode=mode,
                           stage_sizes=stages))
        for i in range(0, len(images), block)])


def check(run, state: dict) -> dict:
    """Every call's picked rows against the reference's scores of the same
    images: the worst call's error, root mean square over the picked rows
    and classes, as a share of the reference's own root mean square."""
    images = state["images"][state["picked"]]
    held = state["held"]
    state.clear()           # the program's model, table and device state
    want = reference_scores(run, images)
    run.obs["kept"] = {"images": images, "want": want}
    worst = max(rel_rms(got, want) for got in held)
    return {"score_rel_rms": (worst, run.traffic["limits"]["score_rel_rms"])}


def control(run) -> dict:
    """The reference in float8 put in the program's place (calibrate.py;
    no benchmark run computes this)."""
    kept = run.obs["kept"]
    low = reference_scores(run, kept["images"], mode="fp8")
    return {"score_rel_rms.fp8": rel_rms(low, kept["want"])}
