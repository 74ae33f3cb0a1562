"""From a profiler trace to device busy time, time by operation, and the
longest idle gaps with the host span that covers each.

The JAX profiler writes `<dir>/plugins/profile/<time>/<host>.xplane.pb`;
`jax.profiler.ProfileData` reads it.  `load()` keeps two things, as plain
lists, so that the arithmetic below runs the same on a recorded fixture
(`fixtures/*.json`: these two keys, cut to one traced span) as on a fresh
trace:

  device: {plane name: [[op name, start_ns, duration_ns], ...]}  one list a
          chip, from the plane's "XLA Ops" line (what ran on the device)
  host:   [[span name, start_ns, duration_ns], ...]  the benchmark's own
          `bench.*` TraceAnnotations and the program's `mmlspark_tpu.*`
          ones (it has none yet), all threads together

Both are on the profiler's one clock.  A traced run is several short
sessions (harness.TraceWindow says why); `load_sessions` lays them one
after another on one time line and lists each session's traced span under
`windows`, and every reduction below adds up over those windows.
"""

from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PREFIXES = ("bench.", "mmlspark_tpu.")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(log_dir: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(log_dir))
    device: dict = {}
    host: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIXES))
    host.sort(key=lambda s: s[1])
    return {"device": device, "host": host}


SESSION_STRIDE = 10 ** 12       # ns between sessions on the joint time line


def load_sessions(log_dirs: list, span: str) -> dict:
    """The sessions of one traced run as one trace; `windows` holds the
    (start_ns, end_ns) of the span called `span` in each session that has
    one and that saw the device at all."""
    joint = {"device": {}, "host": [], "windows": []}
    for i, log_dir in enumerate(log_dirs):
        one = load(log_dir)
        found = span_named(one, span)
        if found is None:
            continue
        base = min([found[0]] + [ops[0][1] for ops in one["device"].values()
                                 if ops])
        shift = i * SESSION_STRIDE - base
        for plane, ops in one["device"].items():
            joint["device"].setdefault(plane, []).extend(
                [n, s + shift, d] for n, s, d in ops)
        # the tracer's own span marks the window; it owns no idle gap
        joint["host"].extend([n, s + shift, d] for n, s, d in one["host"]
                             if n != span)
        joint["windows"].append((found[0] + shift, found[1] + shift))
    return joint


def window_seconds(trace: dict) -> float:
    return sum(b - a for a, b in trace["windows"]) / 1e9


def load_fixture(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def span_named(trace: dict, name: str):
    """(start_ns, end_ns) of the first host span called `name`, or None."""
    for n, start, dur in trace["host"]:
        if n == name:
            return start, start + dur
    return None


def _clip(ops: list, t0: int, t1: int) -> list:
    """[start, end) of every op, clipped to the window, sorted."""
    out = []
    for _, start, dur in ops:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append((a, b))
    out.sort()
    return out


def busy_intervals(ops: list, t0: int, t1: int) -> list:
    """Union of the ops' intervals inside [t0, t1): nested and overlapping
    ops (a while loop and its body, two cores) count once."""
    merged: list = []
    for a, b in _clip(ops, t0, t1):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def busy_seconds(trace: dict, t0: int, t1: int) -> float:
    """Seconds in which an op ran on the device, averaged over the chips
    that the trace holds."""
    planes = trace["device"]
    if not planes:
        return 0.0
    total = sum(b - a for ops in planes.values()
                for a, b in busy_intervals(ops, t0, t1))
    return total / len(planes) / 1e9


def over_windows(fn, trace: dict, *args):
    """`fn(trace, t0, t1, *args)` for each traced window, as a list."""
    return [fn(trace, t0, t1, *args) for t0, t1 in trace["windows"]]


def merged(ranked_lists: list, n: int = 10) -> list:
    """[[name, seconds], ...] lists added up by name, the `n` largest."""
    total: dict = {}
    for ranked in ranked_lists:
        for name, seconds in ranked:
            total[name] = total.get(name, 0.0) + seconds
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def op_seconds(trace: dict, t0: int, t1: int) -> dict:
    """name -> seconds, summed over chips, of ops that start in [t0, t1).
    Ops that enclose others (`while`, `conditional`, a fusion's parent)
    are counted with their whole length, so the values do not add up to
    the busy time: read them one by one."""
    out: dict = {}
    for ops in trace["device"].values():
        for name, start, dur in ops:
            if t0 <= start < t1:
                out[name] = out.get(name, 0.0) + dur / 1e9
    return out


_RESULT = re.compile(r"[a-z]+\d*\[[\d,]*\]")
_OPCODE = re.compile(r"[\s)]([a-z][a-z\-_]*)\(")


def short_name(name: str) -> str:
    """A trace event's HLO text cut to its name, first result and opcode:
    `%fusion.4 bf16[2048,56,56,64] fusion`."""
    head, _, rest = name.partition(" = ")
    result, opcode = _RESULT.search(rest), _OPCODE.search(rest)
    return " ".join(x for x in (head, result and result.group(0),
                                opcode and opcode.group(1)) if x)[:80]


def top_ops(trace: dict, t0: int, t1: int, n: int = 10 ** 9) -> list:
    by_name = op_seconds(trace, t0, t1)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, seconds] for name, seconds in ranked]


def idle_gaps(trace: dict, t0: int, t1: int, n: int = 10 ** 9) -> list:
    """The idle time of the first chip inside [t0, t1), by what the host
    was doing: each gap between busy intervals goes to the innermost
    host span that covers its middle ("(no span)" where none does);
    the `n` owners with most idle seconds, as [[owner, seconds], ...]."""
    planes = trace["device"]
    if not planes:
        return []
    ops = planes[sorted(planes)[0]]
    busy = busy_intervals(ops, t0, t1)
    edges = [t0] + [t for iv in busy for t in iv] + [t1]
    owners: dict = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) // 2
        owner, width = "(no span)", None
        for name, start, dur in trace["host"]:
            if start > mid:
                break
            if start + dur >= mid and (width is None or dur < width):
                owner, width = name, dur
        owners[owner] = owners.get(owner, 0.0) + (b - a) / 1e9
    ranked = sorted(owners.items(), key=lambda kv: -kv[1])[:n]
    return [[name, seconds] for name, seconds in ranked]
