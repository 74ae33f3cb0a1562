"""The per-layer readers that need no trace arithmetic of their own.  A
metric's file (`layer_metrics/<name>.json`) names its `reducer` as
`<module>:<function>` of this directory (`readers:work_mfu`,
`kernels:kernel_roofline`) and gives its arguments; a new kind of
reduction is a new module beside this one (`harness.reduce_function`).

Every reader takes the run (`harness.Run`: `run.obs`, `run.config`,
`run.traffic`), the trace (`reduce.trace.load_sessions`, with the traced
`windows`) and the chip's peaks, and returns a number, or None when it
finds nothing to read: the runner then leaves the metric out.
"""

from __future__ import annotations

from benchmark.reduce import trace as T


def work_mfu(run, trace, peaks, work: str) -> float | None:
    """Required operations of the window's work (`run.obs["work"][work]`,
    counted by the driver with the functions of reduce/flops.py) over the
    window's seconds and the chip's bf16 peak, in percent."""
    ops = run.obs.get("work", {}).get(work)
    if not ops or peaks is None:
        return None
    seconds = run.obs["t1"] - run.obs["t0"]
    return 100.0 * ops / seconds / (peaks["bf16_flops"] * run.cell["chips"])


def device_idle_share(run, trace, peaks) -> float | None:
    """1 - (time in which an op ran on the device) / traced window, in
    percent, averaged over the chips."""
    if trace is None or not trace["device"] or not trace["windows"]:
        return None
    busy = sum(T.over_windows(T.busy_seconds, trace))
    return 100.0 * (1.0 - busy / T.window_seconds(trace))


def stage_share(run, trace, peaks, stages: list) -> float | None:
    """Thread-seconds the program's pipeline stages `stages` took inside
    the window (`observe/spans.pipeline_timing`), over the window's
    seconds, in percent; may pass 100 where stages run on several
    threads."""
    seconds = run.obs.get("stages")
    if not seconds:
        return None
    total = sum(seconds.get(s, 0.0) for s in stages)
    return 100.0 * total / (run.obs["t1"] - run.obs["t0"])


def observed(run, trace, peaks, key: str) -> float | None:
    """A number the driver itself took on the client's side or counted:
    `run.obs[key]`, or for `a.b` the entry `b` of the group `run.obs[a]`
    (`counters.shed`: a counter of the program's, over the window)."""
    value = run.obs
    for part in key.split("."):
        value = value.get(part) if isinstance(value, dict) else None
    return None if value is None else float(value)
