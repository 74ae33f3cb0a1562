#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip.  It finds the cell's configuration and
traffic by their names in BENCHMARK.json, builds the program's objects and
their weights from the seed, warms up every shape (all of that is
`setup_s`), measures for `--seconds`, reads the device's peak memory,
frees the program's state, and holds what the timed path produced against
the plain reference (`benchmark/reference/`).  The last line of standard
output is the result: `correct`, `attempted`, `failed`, `metrics`,
`device` (and `breakdown` with `--trace 1`), then `memory` (the allocator's
two peaks) and `compared`, each number that decided `correct` beside its
limit.  With `--trace 0` the metrics are
the cell's end-to-end metrics, with `--trace 1` its per-layer metrics.

It exits non-zero and prints no result where JAX finds no TPU or fewer
chips than the cell asks for.  `--rehearse` (the tests' flag) runs the
files' tiny `rehearsal` sizes on whatever JAX finds, and then every
metric's name starts with `rehearsal.`: none is a device number.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import gc
import importlib
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _args():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true")
    return p.parse_args()


def _device(jax, chips: int, rehearse: bool) -> dict:
    devices = jax.devices()
    first = devices[0]
    if not rehearse and (first.platform != "tpu" or len(devices) < chips):
        print(f"benchmark: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} x {first.platform} ({first.device_kind})",
              file=sys.stderr)
        raise SystemExit(3)
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices)}


def _memory_peak(jax, when: str) -> dict:
    """What the fullest chip held.  This runtime keeps two peaks apart:
    `peak_bytes_in_use`, the live buffers, and `peak_bytes_reserved`, what
    it set aside for running programs' temporaries (for ResNet-50 most of
    the memory).  They need not fall together, so their sum can pass the
    chip's memory; `memory_peak_bytes` is the larger of the two, which the
    chip held at once for certain, and the result line gives both under
    `memory`."""
    stats = [d.memory_stats() or {} for d in jax.devices()]
    print(f"benchmark: memory_stats {when} {stats[0]}", file=sys.stderr)
    live, reserved = max(
        ((int(s.get("peak_bytes_in_use", 0)),
          int(s.get("peak_bytes_reserved", 0))) for s in stats), key=max)
    return {"peak_bytes_in_use": live, "peak_bytes_reserved": reserved}


def _per_layer(run, trace, device) -> dict:
    from benchmark import harness
    from benchmark.reduce import peaks as P
    # off the TPU (a rehearsal) there is no peak, and no share of one
    peaks = P.peaks_for(device["kind"]) if device["platform"] == "tpu" \
        else None
    out = {}
    for name, spec in harness.layer_metric_files().items():
        if run.cell["name"] not in spec["workloads"]:
            continue
        value = harness.reduce_function(spec["reducer"])(
            run, trace, peaks, **spec.get("args", {}))
        if value is not None:
            out[name] = {"value": value, "unit": spec["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool, t_process: float) -> dict:
    """One run, as the module's docstring says; returns the result line's
    object and the run (what `calibrate.py`'s controls and the tests read).
    Raises SystemExit(3) off the TPU unless `rehearse`."""
    import jax
    import mmlspark_tpu  # noqa: F401  the system under test
    from benchmark import harness
    from benchmark.reduce import trace as T
    cell, config, traffic = harness.cell_files(workload, rehearse)
    device = _device(jax, cell["chips"], rehearse)
    run = harness.Run(cell=cell, config=config, traffic=traffic, seed=seed,
                      seconds=seconds, trace=trace, rehearse=rehearse,
                      t_process=t_process, compiles=harness.CompileWatch())
    driver = importlib.import_module("benchmark.drivers." + traffic["driver"])

    state = driver.setup(run)
    run.lap("setup")
    _memory_peak(jax, "after set-up")
    driver.window(run, state)
    session_dirs = run.finished_sessions()
    run.lap("window")
    memory = _memory_peak(jax, "after the window")
    device["memory_peak_bytes"] = max(memory.values())
    compared = driver.check(run, state)
    del state
    gc.collect()
    run.lap("check")

    obs = run.obs
    units = {m["name"]: m["unit"] for m in harness.manifest()["end_to_end"]}
    metrics = dict(obs["end_to_end"], setup_s=obs["t0"] - run.t_process)
    result_metrics = {k: {"value": v, "unit": units[k]}
                      for k, v in metrics.items()}
    breakdown = None
    if run.trace:
        loaded = T.load_sessions(session_dirs, harness.TraceWindow.SPAN)
        if not loaded["windows"]:
            raise RuntimeError("no session of the trace holds its span")
        device["busy_s"] = sum(T.over_windows(T.busy_seconds, loaded))
        device["window_s"] = T.window_seconds(loaded)
        result_metrics = _per_layer(run, loaded, device)
        breakdown = {
            "device_ops": [[T.short_name(n), s] for n, s in T.merged(
                T.over_windows(T.top_ops, loaded))],
            "idle_gaps": T.merged(T.over_windows(T.idle_gaps, loaded))}
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    if rehearse:
        result_metrics = {"rehearsal." + k: v
                          for k, v in result_metrics.items()}

    result = {"correct": harness.is_correct(obs["failed"], compared), "attempted": int(obs["attempted"]),
              "failed": int(obs["failed"]), "metrics": result_metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["memory"] = memory
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    run.lap("reduce")
    for note in obs.get("notes", []) + [
            "seconds " + ", ".join(f"{n} {s:.1f}" for n, s in run.laps)]:
        print("benchmark: " + note, file=sys.stderr)
    return result, run


def main() -> int:
    args = _args()
    try:
        import jax  # noqa: F401
        import mmlspark_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the program is not in this directory: {e}",
              file=sys.stderr)
        return 2
    result, _ = run_cell(args.workload, args.seed, args.seconds,
                         bool(args.trace), args.rehearse, T_PROCESS)
    print("benchmark: compared " + json.dumps(result["compared"]),
          file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
