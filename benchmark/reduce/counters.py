"""Ratios of the program's own counters: what `ServingEngine.stats()`
counted over the window (`run.obs["counters"]`, the window's end less its
start), one sum of counters over another.  The program counts where the
work happens and only ever upwards, so a ratio of two differences is the
window's own: occupancy (`slot_steps_live` over `slot_steps_capacity`),
mean queue wait (`queue_wait_s` over `joined`), the scheduler thread's
wait on the device as a share of its ticks (`fetch_wait_s` over `tick_s`).
"""

from __future__ import annotations


def ratio(run, trace, peaks, num: list, den, scale: float = 1.0):
    """`scale` x (sum of the counters `num`) / (sum of the counters `den`).
    `den` may be "window_s", the window's seconds on the host's clock.
    None where the program has no such counter (a parent commit that lacks
    it, a cell that is not served) or where the denominator is 0."""
    counters = run.obs.get("counters") or {}
    over_window = den == "window_s"
    if any(name not in counters
           for name in list(num) + ([] if over_window else list(den))):
        return None
    below = (run.obs["t1"] - run.obs["t0"] if over_window
             else sum(counters[name] for name in den))
    if below <= 0:
        return None
    return scale * sum(counters[name] for name in num) / below
