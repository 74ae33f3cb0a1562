"""Who owns the device's idle time.  `trace.idle_gaps` gives each gap
between the device's busy intervals to the innermost host span that
covers its middle; the program's spans are named `mmlspark_tpu.<what>`
(its three span helpers enter `observe/profiler.annotate`), the
benchmark's own `bench.<what>`, and a gap under neither reads
`(no span)`.
"""

from __future__ import annotations

from benchmark.reduce import trace as T


def owned_share(run, trace, peaks, prefix: str):
    """Idle seconds of the traced windows whose owner's name starts with
    `prefix`, over all their idle seconds, in percent.  None where there
    is no trace, no device in it, or no idle time at all."""
    if trace is None or not trace["device"] or not trace["windows"]:
        return None
    owned = idle = 0.0
    for gaps in T.over_windows(T.idle_gaps, trace):
        for owner, seconds in gaps:
            idle += seconds
            if owner.startswith(prefix):
                owned += seconds
    if idle <= 0.0:
        return None
    return 100.0 * owned / idle
