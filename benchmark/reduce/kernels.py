"""Kernels' shares of their roofline, from the device trace.  A Pallas
kernel is a `custom-call` event whose name is its HLO text, operand and
result shapes included; the metric's file tells the kinds of call apart
and names each kind's operations count as `<module>:<function>`
(`flops:flash_pair_causal`).
"""

from __future__ import annotations

import re

from benchmark import harness
from benchmark.reduce import flops as F
from benchmark.reduce import trace as T

SHAPE = re.compile(r"\b(pred|bf16|[suf]\d+)\[([\d,]*)\]")


def parse_call(name: str):
    """(operands, results) of a custom-call trace event, each a list of
    (dtype, dims), from the HLO text that is the event's name."""
    if " custom-call(" not in name:
        return None
    head, tail = name.split(" custom-call(", 1)
    tail = tail.split("), custom_call_target", 1)[0]
    shapes = lambda text: [
        (dt, tuple(int(x) for x in dims.split(",") if x))
        for dt, dims in SHAPE.findall(text)]
    return shapes(tail), shapes(head.split(" = ", 1)[-1])


def kernel_roofline(run, trace, peaks, match: str, kernels: list):
    """Sum over the matching kernel calls in the traced window of the
    least time the chip could take for that call (the larger of its
    required operations over the bf16 peak and its bytes over the HBM
    peak, from the shapes in the event's name), over the sum of their
    device time, in percent.  `kernels` tells the calls apart by their
    number of operands and of results and names each kind's operations
    function (`flops:flash_pair_causal`)."""
    if trace is None or peaks is None:
        return None
    rx = re.compile(match)
    least, spent, bound = 0.0, 0.0, {"flops": 0.0, "bytes": 0.0}
    others: dict = {}       # custom calls of another shape, for the note
    for ops in trace["device"].values():
        for name, start, dur in ops:
            if not any(t0 <= start < t1 for t0, t1 in trace["windows"]) \
                    or not rx.search(name):
                continue
            parsed = parse_call(name)
            if parsed is None:
                continue
            operands, results = parsed
            kind = next((k for k in kernels
                         if k["operands"] == len(operands)
                         and len(results) in k["results"]), None)
            if kind is None:
                key = (T.short_name(name), len(operands), len(results))
                others[key] = others.get(key, 0) + 1
                continue
            t_ops = harness.reduce_function(kind["flops"])(
                operands, results) / peaks["bf16_flops"]
            t_mem = F.kernel_bytes(operands, results) \
                / peaks["hbm_bytes_per_s"]
            least += max(t_ops, t_mem)
            bound["flops" if t_ops >= t_mem else "bytes"] += max(t_ops, t_mem)
            spent += dur / 1e9
    if spent <= 0.0:
        run.obs.setdefault("notes", []).append(
            "roofline of %s: no such call in the traced spans; other custom "
            "calls there (name, operands, results, count): %s" % (
                [k["flops"] for k in kernels],
                [k + (n,) for k, n in sorted(others.items())][:8]))
        return None
    run.obs.setdefault("notes", []).append(
        "roofline of %s: least seconds bound by operations %.6f, by bytes "
        "%.6f" % ([k["flops"] for k in kernels], bound["flops"],
                  bound["bytes"]))
    return 100.0 * least / spent

