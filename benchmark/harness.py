"""What every driver shares: the manifest and its data files, the count of
compilations, the traced part of a window, and a run's observations.

A driver (`benchmark/drivers/<name>.py`) is three functions:

  setup(run)          build the program's objects from the seed, warm up
                      every shape; returns the driver's own state
  window(run, state)  the measured window; fills `run.obs`
  check(run, state)   after the window, the peak read and the program's
                      state freed: run the plain reference, and return
                      {number: (value, limit)}; `correct` is every value
                      at or under its limit

`run.obs` is what the per-layer readers (`benchmark/reduce/*.py`, named by
the metric files as `<module>:<function>`) read; its keys are listed in
`Run`.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
OUT = os.path.join(HERE, "out")


def read_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def manifest() -> dict:
    return read_json("BENCHMARK.json")


def cell_files(cell_name: str, rehearse: bool = False) -> tuple:
    """(cell, configuration file, traffic file) for a cell of the
    manifest, found by name.  `rehearse` lays each file's `rehearsal`
    group over it: the tiny sizes the CPU tests run."""
    man = manifest()
    cells = {w["name"]: w for w in man["workloads"]}
    if cell_name not in cells:
        raise SystemExit(f"no cell {cell_name!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    cell = cells[cell_name]
    config_path = next(c["file"] for c in man["configs"]
                       if c["name"] == cell["config"])
    config = read_json(config_path)
    traffic = read_json("benchmark", "traffic", cell["traffic"] + ".json")
    if rehearse:
        config = _overlay(config, config.get("rehearsal", {}))
        traffic = _overlay(traffic, traffic.get("rehearsal", {}))
    return cell, config, traffic


def _overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = _overlay(out[key], value)
        else:
            out[key] = value
    return out


def layer_metric_files() -> dict:
    """name -> the metric's own file, for every file in layer_metrics/."""
    folder = os.path.join(HERE, "layer_metrics")
    return {name[:-5]: read_json("benchmark", "layer_metrics", name)
            for name in sorted(os.listdir(folder)) if name.endswith(".json")}


def reduce_function(name: str):
    """The function `<module>:<function>` of `benchmark/reduce/`: how a
    metric's file names its reducer, and a kernel its operations count.
    A later PR brings a module of its own and edits none."""
    module, _, function = name.partition(":")
    if not function:
        raise ValueError(f"{name!r} is not <module>:<function> of "
                         "benchmark/reduce/")
    return getattr(importlib.import_module("benchmark.reduce." + module),
                   function)


class CompileWatch:
    """Counts the programs JAX lowers for the backend (a load from the
    persistent cache counts as the compile it replaces), with their
    seconds: `mark()` before a window, `since(mark)` after it."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self._count, self._seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, seconds: float, **_) -> None:
        if event == self.EVENT:
            with self._lock:
                self._count += 1
                self._seconds += seconds

    def mark(self) -> tuple:
        with self._lock:
            return self._count, self._seconds

    def since(self, mark: tuple) -> tuple:
        count, seconds = self.mark()
        return count - mark[0], seconds - mark[1]


class TraceWindow(threading.Thread):
    """Traces parts of the measured window from a thread of its own (the
    window's own thread is inside the program).

    On this installation (jax 0.9, libtpu 0.0.34, v5e) a profiler session
    records the device for about its first half second only, whatever its
    options, and the TPU runtime's own host threads write millions of
    events a second into it, so that stopping a long session takes tens
    of seconds (PERF.md, Findings, PR 25).  So a traced run takes
    `sessions` short ones, `every_s` apart: each is the `bench.traced`
    span, `span_s` long, opened `lead_s` after the profiler started (the
    device's tracer comes up some 50 ms after the host's).  The reduction
    reads device time inside those spans only and adds them up."""

    SPAN = "bench.traced"

    def __init__(self, log_dir: str, sessions: int, span_s: float,
                 every_s: float, lead_s: float = 0.08):
        super().__init__(daemon=True, name="bench-trace")
        self.log_dir, self.sessions = log_dir, sessions
        self.span_s, self.every_s, self.lead_s = span_s, every_s, lead_s
        self.dirs: list = []       # one a finished session
        self.ended: list = []      # host clock at the end of its span
        self.halt = threading.Event()
        self.done = threading.Event()
        self.error = None

    def run(self) -> None:
        import jax
        try:
            for i in range(self.sessions):
                if self.halt.wait(self.every_s):
                    break
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1     # TraceAnnotations need it
                opts.enable_hlo_proto = False
                where = os.path.join(self.log_dir, str(i))
                jax.profiler.start_trace(where, profiler_options=opts)
                try:
                    time.sleep(self.lead_s)
                    with jax.profiler.TraceAnnotation(self.SPAN):
                        time.sleep(self.span_s)
                    ended = time.perf_counter()
                finally:
                    jax.profiler.stop_trace()
                self.dirs.append(where)
                self.ended.append(ended)
        except Exception as e:  # reported by the runner, never swallowed
            self.error = e
        finally:
            self.done.set()


@dataclasses.dataclass
class Run:
    """One run of one cell: its arguments, its files, what it observed."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_process: float                # host clock when the process began
    compiles: CompileWatch = None
    tracer: TraceWindow = None
    # filled by the driver's window():
    #   t0, t1        host clock at the window's two ends
    #   attempted, failed
    #   end_to_end    {metric: value}
    #   work          counts the readers turn into rates (driver's own keys)
    #   stages        pipeline_timing seconds, every stage (score)
    #   counters      the program's own counts, window's end less its
    #                 start (serve: every number of ServingEngine.stats())
    #   compiles_in_window
    obs: dict = dataclasses.field(default_factory=dict)
    laps: list = dataclasses.field(default_factory=list)

    def lap(self, name: str) -> None:
        """Seconds since the last lap (or the process's start), for the
        line of phases that a run prints on standard error."""
        now = time.perf_counter()
        last = self.t_process + sum(s for _, s in self.laps)
        self.laps.append((name, now - last))

    @property
    def trace_dir(self) -> str:
        """One directory a process: two runs may share a checkout."""
        return os.path.join(OUT, f"trace_{self.cell['name']}_{os.getpid()}")

    def start_trace(self) -> None:
        """Called by a driver as its window opens, in a `--trace 1` run."""
        if not self.trace:
            return
        t = self.traffic["trace"]
        self.tracer = TraceWindow(self.trace_dir, int(t["sessions"]),
                                  float(t["span_s"]), float(t["every_s"]))
        self.tracer.start()

    def finished_sessions(self) -> list:
        """Stop tracing, wait for the session in hand, and return the
        directories of the sessions whose span closed inside the window."""
        if self.tracer is None:
            return []
        self.tracer.halt.set()
        if not self.tracer.done.wait(600.0):
            raise RuntimeError("the profiler did not stop")
        if self.tracer.error is not None:
            raise self.tracer.error
        return [d for d, ended in zip(self.tracer.dirs, self.tracer.ended)
                if ended <= self.obs["t1"]]

    def annotate(self, name: str):
        """A host span for the trace (`bench.<name>`); free when off."""
        import contextlib
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)


def is_correct(failed: int, compared: dict) -> bool:
    """`correct`: nothing failed, and every number compared
    ({name: (value, limit)}) is at or under its limit."""
    return bool(failed == 0 and all(
        value <= limit for value, limit in compared.values()))


def percentile(values: list, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def same_tree(program_shapes, reference_shapes) -> None:
    """Raise unless the program's variable tree has the names and shapes
    the reference declares: both are then fed the same weights."""
    import jax
    def flat(tree):
        tree = tree.unfreeze() if hasattr(tree, "unfreeze") else tree
        return {jax.tree_util.keystr(p): tuple(leaf.shape) for p, leaf
                in jax.tree_util.tree_leaves_with_path(tree)}
    a, b = flat(program_shapes), flat(reference_shapes)
    if a != b:
        odd = sorted(set(a.items()) ^ set(b.items()))[:6]
        raise AssertionError(
            f"the program's variables and the reference's differ: {odd}")


def host_tree(tree):
    """A device tree brought to the host as numpy arrays: a `ModelBundle`
    holds host arrays (`ModelBundle.init`, `load_bundle`), and what the
    program does with them is the program's."""
    import jax
    import numpy as np
    return jax.tree_util.tree_map(np.asarray, jax.device_get(tree))
