"""Set-up from inside: what the program's own compile ledger
(`mmlspark_tpu/observe/compiles.py`) holds between the process's start
and the window's.  The run's process IS the program's, so the reducers
import the ledger and ask it for the rows and phases that ended in
[`run.t_process`, `run.obs["t0"]`]: what the reference compiles after the
window does not count.  Every one returns None where the program has no
ledger (a parent commit), as `counters:ratio` does for a counter.

`setup_s` below is the run's own: `run.obs["t0"] - run.t_process`.
"""

from __future__ import annotations


def ledger():
    """The program's compile ledger, or None where it has none."""
    try:
        from mmlspark_tpu.observe import compiles
    except ImportError:
        return None
    return compiles


def _span(run) -> tuple:
    return run.t_process, run.obs["t0"]


def before_window(run, trace, peaks, keys: list):
    """The sum of the ledger's totals `keys` (`trace_s`, `lower_s`,
    `backend_s`, `cache_load_s`, `programs`) over the programs that
    closed before the window opened."""
    compiles = ledger()
    if compiles is None:
        return None
    totals = compiles.totals(*_span(run))
    return float(sum(totals[key] for key in keys))


def cache_hit_share(run, trace, peaks):
    """100 x hits / (hits + misses) of the persistent compile cache
    before the window: 100 on a warm run, 0 on a first one, between where
    the cache evicts.  None where no program asked the cache."""
    compiles = ledger()
    if compiles is None:
        return None
    totals = compiles.totals(*_span(run))
    asked = totals["cache_hits"] + totals["cache_misses"]
    return 100.0 * totals["cache_hits"] / asked if asked else None


def phase_share(run, trace, peaks, scope: str):
    """100 x the seconds of the `setup.<scope>` phases that closed before
    the window / `setup_s`.  None where no such phase closed."""
    compiles = ledger()
    if compiles is None:
        return None
    since, until = _span(run)
    entry = compiles.by_scope(since, until).get(scope)
    if not entry or not entry["phases"]:
        return None
    return 100.0 * entry["phase_s"] / (until - since)


def unattributed_share(run, trace, peaks):
    """100 x (`setup_s` - the phases that lay inside no other, `import`
    among them - the trace, lowering, backend and load seconds of the
    programs that closed outside every phase) / `setup_s`: what neither a
    span nor a row of the ledger covers.  The interpreter's start, the
    benchmark's own weight making and host copy, the warm-up requests."""
    compiles = ledger()
    if compiles is None:
        return None
    since, until = _span(run)
    table = compiles.by_scope(since, until)
    covered = sum(entry["top_s"] for entry in table.values())
    outside = table.get(None)
    if outside:
        covered += sum(outside[key] for key in (
            "trace_s", "lower_s", "backend_s", "cache_load_s"))
    return 100.0 * (until - since - covered) / (until - since)


def where_setup_goes(run) -> dict:
    """The table PERF.md section 5 prints a cell: `setup_s`, the ledger's
    totals before the window, its scopes and its jitted functions."""
    compiles = ledger()
    if compiles is None:
        return {}
    since, until = _span(run)
    return {"setup_s": until - since,
            "totals": compiles.totals(since, until),
            "by_scope": {str(scope): entry for scope, entry
                         in compiles.by_scope(since, until).items()},
            "by_function": compiles.by_function(since, until)}
