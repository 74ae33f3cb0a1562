"""Per-program cost capture + roofline attribution.

PR 5 made every hot path *measurable* (spans, counters, run records);
this module makes the measurements *interpretable*.  BENCH_r05 is the
motivating read: resnet50 end-to-end MFU 0.0056 against 0.46 on-device —
two numbers, no verdict.  The missing piece is per-program cost: XLA's
compiled `cost_analysis()` knows exactly how many FLOPs and HBM bytes
each compiled program moves, and the telemetry layer already knows how
long each execution took.  Joining the two yields, for every compiled
program the run paid for:

  * **MFU** — achieved FLOP/s over the chip's bf16 peak
    (`utils/perf.device_peak_flops`);
  * **HBM-bandwidth utilization** — achieved bytes/s over the chip's HBM
    peak (`utils/perf.device_peak_hbm_bw`);
  * a **roofline verdict** — the program's arithmetic intensity against
    the chip's ridge point names its ceiling (compute vs bandwidth), and
    its achieved fraction of that ceiling tells whether the program ever
    gets near it: a program far below BOTH ceilings is not the
    bottleneck — the host is (`host-bound`, exactly BENCH_r05's resnet
    end-to-end story).

Capture rides the recompile detectors PR 5 installed: the moment a hot
loop registers a NEW shape class (TPUModel batch shapes, Trainer's train
step, DecodeEngine prefill/segment programs), `capture_program_cost`
AOT-lowers the same jitted callable at the same arguments and reads
`compiled.cost_analysis()` — once per program per hot-loop lifetime,
never in the steady state.  The hot loops remember each returned cost
row and replay it (`RunTelemetry.record_program_cost`, idempotent) into
every later `run_telemetry` block, so a warm model/engine's steady-state
runs still get roofline rows without paying a fresh capture.  The capture costs one extra XLA compile (plus, when
`probe=True`, one synced execution that yields a clean per-program step
time on paths whose live spans wall only the async dispatch).  Backends
without a cost model (and any capture failure at all) degrade to a
logged no-op: the run proceeds, the program simply has no cost row.

MMLSPARK_TPU_COSTMODEL=0 switches capture off without touching the rest
of telemetry (the mirror of the MMLSPARK_TPU_TELEMETRY kill switch).
"""

from __future__ import annotations

import time
from typing import Any, Optional, Sequence

from mmlspark_tpu import config
from mmlspark_tpu.observe.logging import get_logger

COSTMODEL = config.register(
    "MMLSPARK_TPU_COSTMODEL", default=None,
    doc="Per-program cost capture kill switch: '0'/'off'/'false' skips "
        "the compile-time cost_analysis() capture (and its one-off AOT "
        "compile per program) while the rest of telemetry stays live "
        "(observe/costmodel.py).")

# below this fraction of the binding ceiling, the program is not what
# bounds the run — something outside it (the host pipeline) is
HOST_BOUND_FLOOR = 0.05


def costmodel_enabled() -> bool:
    """False only when MMLSPARK_TPU_COSTMODEL is an explicit off value."""
    raw = COSTMODEL.current()
    return str(raw).strip().lower() not in ("0", "off", "false") \
        if raw is not None else True


def extract_cost(compiled) -> Optional[dict]:
    """{'flops', 'bytes_accessed'} from a Compiled's cost_analysis(), or
    None when the backend provides no cost model (never raises)."""
    try:
        cost = compiled.cost_analysis()
        flops = cost.get("flops")
        byts = cost.get("bytes accessed")
        if not flops and not byts:
            return None
        return {"flops": float(flops) if flops else None,
                "bytes_accessed": float(byts) if byts else None}
    except Exception:
        return None


def capture_program_cost(fn, args: Sequence[Any], *, where: str,
                         program: str, run=None, probe: bool = False,
                         static_argnums: Sequence[int] = ()) -> Optional[dict]:
    """Capture one compiled program's cost row into the active run.

    `fn` is the jitted callable the hot loop is about to execute (or just
    executed) at `args`; `program` is the hot loop's own shape-class key —
    the SAME key its spans and recompile events carry, so the join is by
    construction.  `probe=True` additionally executes the AOT-compiled
    program once, synced, for a clean per-program step time (used by the
    scoring/decode paths, whose live spans wall only the async dispatch;
    never probe a donating function — its buffers would be consumed).

    Every failure — no cost model on this backend, an AOT lowering quirk,
    anything — is a logged no-op: capture is diagnostics, and diagnostics
    never take down a run.
    """
    from mmlspark_tpu.observe.telemetry import active_run
    run = run if run is not None else active_run()
    if run is None or not run.live or not costmodel_enabled():
        return None
    program = str(program)
    try:
        compiled = fn.lower(*args).compile()
        rec = extract_cost(compiled)
        if rec is None:
            raise ValueError("backend reports no cost model")
        if probe:
            call_args = [a for i, a in enumerate(args)
                         if i not in set(static_argnums)]
            out = compiled(*call_args)
            t0 = time.perf_counter()
            out = compiled(*call_args)
            import jax
            jax.block_until_ready(out)
            rec["probe_step_s"] = round(time.perf_counter() - t0, 6)
    except Exception as exc:  # diagnostics must never crash the run
        get_logger("observe.costmodel").info(
            "cost capture unavailable for %s program %s: %s",
            where, program, exc)
        tracer = run.tracer
        tracer.event("program_cost_unavailable", cat="cost", where=where,
                     program=program, error=str(exc))
        return None
    run.record_program_cost(where, program, rec)
    run.tracer.event("program_cost", cat="cost", where=where,
                     program=program, **rec)
    return rec


def roofline(flops: Optional[float], bytes_accessed: Optional[float],
             step_s: Optional[float], peak_flops: Optional[float] = None,
             peak_bw: Optional[float] = None,
             host_floor: float = HOST_BOUND_FLOOR) -> dict:
    """One program's roofline placement.

    The ridge point (peak_flops / peak_bw, FLOP per byte) splits the
    roofline: a program whose arithmetic intensity sits above it has a
    compute ceiling, below it a bandwidth ceiling.  The achieved fraction
    of that ceiling (MFU or bw_util) is the verdict's second axis — a
    program under `host_floor` of its own ceiling is not what bounds the
    run, so the verdict is `host-bound` rather than naming a device
    ceiling it never approaches.  Unknown peaks (CPU, unrecognized
    device kinds) yield None utilizations and no verdict — never
    fabricated numbers.
    """
    ai = (flops / bytes_accessed
          if flops and bytes_accessed else None)
    ridge = (peak_flops / peak_bw
             if peak_flops and peak_bw else None)
    mfu = (flops / step_s / peak_flops
           if flops and step_s and peak_flops else None)
    bw_util = (bytes_accessed / step_s / peak_bw
               if bytes_accessed and step_s and peak_bw else None)
    bound = None
    if ai is not None and ridge is not None:
        bound = "compute" if ai >= ridge else "bandwidth"
    util = {"compute": mfu, "bandwidth": bw_util, None: None}[bound]
    verdict = None
    if util is not None:
        verdict = "host-bound" if util < host_floor else f"{bound}-bound"
    return {
        "arithmetic_intensity": round(ai, 3) if ai is not None else None,
        "ridge": round(ridge, 3) if ridge is not None else None,
        "mfu": round(mfu, 5) if mfu is not None else None,
        "hbm_bw_util": round(bw_util, 5) if bw_util is not None else None,
        "bound": bound,
        "verdict": verdict,
    }


def program_summary(costs: dict, times: dict,
                    peak_flops: Optional[float] = None,
                    peak_bw: Optional[float] = None) -> dict:
    """Join cost rows with execution times into the per-program roofline
    table (run_summary's `programs` section and the report's roofline
    view).

    `costs` and `times` are keyed `(where, program)` — costs from
    `capture_program_cost`, times accumulated by the hot loops
    (`RunTelemetry.add_program_time`).  The per-step time each roofline
    uses is the most honest one available: accumulated span walls when
    the live span brackets the execution (`basis='step_wall'`, the
    trainer's synced step spans), else the capture-time probe
    (`basis='dispatch'` paths, whose live spans wall only the async
    dispatch and would overstate utilization wildly).
    """
    out: dict[str, dict] = {}
    for key in sorted(set(costs) | set(times), key=str):
        where, program = key
        cost = costs.get(key, {})
        t = times.get(key, {})
        count = t.get("count", 0)
        basis = t.get("basis")
        span_step_s = (t["seconds"] / count) if count else None
        probe_s = cost.get("probe_step_s")
        if basis == "step_wall" and span_step_s:
            step_s, step_basis = span_step_s, "span_wall"
        elif probe_s:
            step_s, step_basis = probe_s, "probe"
        else:
            step_s, step_basis = span_step_s, basis
        row = {
            "where": where,
            "program": program,
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes_accessed"),
            "executions": count,
            "span_s": round(t.get("seconds", 0.0), 6),
            "step_s": round(step_s, 6) if step_s else None,
            "step_basis": step_basis,
            **roofline(cost.get("flops"), cost.get("bytes_accessed"),
                       step_s, peak_flops, peak_bw),
        }
        out[f"{where}:{program}"] = row
    return out


def device_peaks() -> tuple:
    """(peak_flops, peak_hbm_bw) of the default device, both None on the
    CPU — one lazy import point for the summary/exposition callers."""
    from mmlspark_tpu.utils.perf import (device_peak_flops,
                                         device_peak_hbm_bw)
    return device_peak_flops(), device_peak_hbm_bw()
