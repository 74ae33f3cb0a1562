#!/usr/bin/env python3
"""Readings for the limits: the program on many seeds, and on the first
few of them the control (the reference in float8 put in the program's
place) and the training faults, all in one process at the cell's own size.

    python3 benchmark/calibrate.py --workload <cell> --seeds 12 --controls 3 --seconds 8

Prints one JSON line a seed: the numbers `run.py` compares and, for each
control, its readings and what `run.py`'s own comparison makes of them
(`control_correct`: every reading at or under the traffic file's limit).
It exits 1 if a seed of the program came out not correct or a control came
out correct.  No benchmark run calls this; PERF.md records what it read.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def judged(readings: dict, limits: dict) -> dict:
    """{control: `correct` as run.py decides it} for the controls in
    `readings` (`<number>.<control>`: value), each number beside the
    limit the cell's traffic file gives it."""
    from benchmark import harness
    compared: dict = {}
    for key, value in readings.items():
        number, _, control = key.rpartition(".")
        if number in limits:
            compared.setdefault(control, {})[number] = (value, limits[number])
    return {control: harness.is_correct(0, numbers)
            for control, numbers in compared.items()}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first-seed", type=int, default=2_147_500_000)
    p.add_argument("--controls", type=int, default=3)
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    import jax
    from benchmark import harness
    cell, config, traffic = harness.cell_files(args.workload, args.rehearse)
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 3
    driver = importlib.import_module("benchmark.drivers." + traffic["driver"])
    watch = harness.CompileWatch()
    sound = True
    for i in range(args.seeds):
        t_start = time.perf_counter()
        run = harness.Run(cell=cell, config=config, traffic=traffic,
                          seed=args.first_seed + 7919 * i,
                          seconds=args.seconds, trace=False,
                          rehearse=args.rehearse, t_process=t_start,
                          compiles=watch)
        state = driver.setup(run)
        driver.window(run, state)
        compared = driver.check(run, state)
        correct = harness.is_correct(run.obs["failed"], compared)
        line = {"seed": run.seed, "correct": correct,
                "failed": run.obs["failed"],
                "attempted": run.obs["attempted"],
                "program": {k: v for k, (v, _) in compared.items()},
                "limits": {k: limit for k, (_, limit) in compared.items()},
                "end_to_end": {("rehearsal." if args.rehearse else "") + k: v
                               for k, v in run.obs["end_to_end"].items()},
                "setup_s": run.obs["t0"] - t_start}
        if i < args.controls:
            line["control"] = driver.control(run)
            line["control_correct"] = judged(line["control"],
                                             traffic["limits"])
            sound = sound and not any(line["control_correct"].values())
        sound = sound and correct
        line["seconds"] = time.perf_counter() - t_start
        print(json.dumps(line), flush=True)
        del run, state
        gc.collect()
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
