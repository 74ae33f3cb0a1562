"""The compile ledger: what every program of this process cost to trace,
lower and compile or load, and the phases of set-up that paid for it.

`setup_s` is an end-to-end metric of every benchmark cell and most of it
is compilation, which nothing inside the program measured.  JAX publishes
it on `jax.monitoring`, with the jitted function's name; `register()`
(once, at package import, beside `config.setup_compilation_cache`) listens
and keeps a row a compiled-or-loaded program, process-wide sums (also
the `compile.*` counters of `observe/metrics`, so `prometheus_text()`
exports them) and three readers: `totals`, `by_function`, `by_scope`.

How the events arrive (jax 0.9.0), in order and on the compiling thread:
`jaxpr_trace_duration` with `fun_name='f'` (before it one for each inner
jitted function `f` calls: their seconds lie inside `f`'s own, so a row
takes the trace named inside its `jit(...)` and drops the others), then
`jaxpr_to_mlir_module_duration` with `fun_name='jit(f)'`, then
`compile_requests_use_cache` and, on a hit, `cache_hits`,
`compile_time_saved_sec` and `cache_retrieval_time_sec` (none of them
named: held thread-locally for the next backend event of the thread), then
`backend_compile_duration` with `fun_name='jit(f)'`, which closes the row.
Eager primitives (`jit(convert_element_type)`) make rows of their own.

A listener runs only when JAX compiles or loads a program, and
`setup_phase` is entered in set-up and on a new shape class only: a warm
hot path never enters this module.
"""

from __future__ import annotations

import contextlib
import threading
import time
import types
from typing import Iterator, Optional

import jax

from mmlspark_tpu.observe.metrics import inc_counter
from mmlspark_tpu.observe.trace import trace_span

_COMPILE = "/jax/core/compile/"
_CACHE = "/jax/compilation_cache/"
_TRACE = _COMPILE + "jaxpr_trace_duration"
MAX_ROWS = 4096     # rows (and phases) kept; past that the sums only
SUMS = ("programs", "trace_s", "lower_s", "backend_s", "cache_load_s",
        "cache_hits", "cache_misses", "saved_s")

_lock = threading.Lock()
_rows: list = []    # a dict a program, in the order in which they closed
_phases: list = []  # (scope, seconds, perf_counter at its end, depth)
_sums = dict.fromkeys(SUMS, 0.0)
_local = threading.local()
_registered = False
import_s = 0.0      # the package's own import (`note_import`)


def _thread():
    t = _local
    if not hasattr(t, "sums"):
        t.traces, t.row, t.cache = {}, None, "off"
        t.retrieval = t.saved = 0.0
        t.scopes = []
        t.sums = dict.fromkeys(SUMS, 0.0)
        t.mark = dict(t.sums)
    return t


def _on_event(event: str, **_) -> None:
    if event == _CACHE + "compile_requests_use_cache":
        _thread().cache = "miss"        # until a hit says otherwise
    elif event == _CACHE + "cache_hits":
        _thread().cache = "hit"


def _on_duration(event: str, seconds: float, fun_name: str = "", **_) -> None:
    if event == _TRACE:     # thousands a program (every inner jitted
        # function's own trace), so this branch comes first and is short
        _thread().traces[fun_name] = seconds
    elif event == _COMPILE + "jaxpr_to_mlir_module_duration":
        t = _thread()
        t.row = {"fun_name": fun_name, "lower_s": seconds,
                 "trace_s": t.traces.get(_inner(fun_name), 0.0)}
        t.traces.clear()
    elif event == _COMPILE + "backend_compile_duration":
        _close_row(fun_name, seconds)
    elif event == _CACHE + "cache_retrieval_time_sec":
        _thread().retrieval = seconds
    elif event == _CACHE + "compile_time_saved_sec":
        _thread().saved = seconds


def _inner(fun_name: str) -> str:
    """`f` of `jit(f)` / `pmap(f)`: the name its trace event carried."""
    _, paren, rest = fun_name.partition("(")
    return rest[:-1] if paren and rest.endswith(")") else fun_name


def _close_row(fun_name: str, seconds: float) -> None:
    t = _thread()
    row = t.row if t.row and t.row["fun_name"] == fun_name else {
        "fun_name": fun_name, "trace_s": 0.0, "lower_s": 0.0}
    hit = t.cache == "hit"
    row.update(backend_s=seconds, cache=t.cache,
               retrieval_s=t.retrieval if hit else 0.0,
               saved_s=t.saved if hit else 0.0,
               thread=threading.current_thread().name,
               scope=t.scopes[-1] if t.scopes else None)
    t.row, t.cache, t.retrieval, t.saved = None, "off", 0.0, 0.0
    t.traces.clear()
    add = _row_sums(row)
    with _lock:     # `end` under the lock: the list is in its order
        row["end"] = time.perf_counter()
        for key, value in add.items():
            _sums[key] += value
        if len(_rows) < MAX_ROWS:
            _rows.append(row)
    for key, value in add.items():
        t.sums[key] += value
        if value:
            inc_counter("compile." + key, value)


def _row_sums(row: dict) -> dict:
    """What one row adds to the sums: a hit's seconds are its retrieval
    (`cache_load_s`), a miss's (or an uncached program's) the backend's."""
    hit = row["cache"] == "hit"
    return {"programs": 1.0, "trace_s": row["trace_s"],
            "lower_s": row["lower_s"],
            "backend_s": 0.0 if hit else row["backend_s"],
            "cache_load_s": row["retrieval_s"],
            "cache_hits": float(hit),
            "cache_misses": float(row["cache"] == "miss"),
            "saved_s": row["saved_s"]}


def register() -> None:
    """Listen to JAX's compile and cache events; a second call does
    nothing (JAX has no public unregister)."""
    global _registered
    with _lock:
        if _registered:
            return
        _registered = True
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def note_import(t0: float) -> None:
    """The package's `__init__` calls this at its last line with
    `perf_counter()` of its first: the phase `import`."""
    global import_s
    import_s = time.perf_counter() - t0
    _close_phase("import", "import", import_s, 0)


def _close_phase(name: str, scope: str, seconds: float, depth: int) -> None:
    inc_counter(f"setup.{name}_s", seconds)
    with _lock:
        if len(_phases) < MAX_ROWS:
            _phases.append((scope, seconds, time.perf_counter(), depth))


@contextlib.contextmanager
def setup_phase(name: str, **attrs) -> Iterator[types.SimpleNamespace]:
    """A phase of set-up: `trace.trace_span("setup." + name, cat="setup")`
    (so `mmlspark_tpu.setup.<name>` in a profiler session and a span of
    the ambient `Tracer`), which besides is the ledger's `scope` of the
    rows this thread closes inside it (`<name>/<kind>` where a `kind` is
    given) and adds its seconds to the counter `setup.<name>_s`.  Yields
    the phase; once closed it holds `seconds` and, of this thread's rows
    inside it, every sum of `SUMS` (`programs`, `cache_hits`, ...)."""
    t = _thread()
    scope = f"{name}/{attrs['kind']}" if "kind" in attrs else name
    phase = types.SimpleNamespace(scope=scope, span=None, seconds=0.0)
    before = dict(t.sums)
    t.scopes.append(scope)
    t0 = time.perf_counter()
    try:
        with trace_span("setup." + name, cat="setup", **attrs) as span:
            phase.span = span
            yield phase
    finally:
        t.scopes.pop()
        phase.seconds = time.perf_counter() - t0
        for key in SUMS:
            setattr(phase, key, t.sums[key] - before[key])
        _close_phase(name, scope, phase.seconds, len(t.scopes))


def since_mark() -> dict:
    """The sums of the rows this thread closed since it last asked."""
    t = _thread()
    out = {key: t.sums[key] - t.mark[key] for key in SUMS}
    t.mark = dict(t.sums)
    return out


def _between(items: list, end_of, since, until) -> list:
    """Those of `items` (appended in the order of their ends) whose end
    lies in [since, until], the newest first."""
    out = []
    with _lock:
        for item in reversed(items):
            end = end_of(item)
            if since is not None and end < since:
                break
            if until is None or end <= until:
                out.append(item)
    return out


def _rows_between(since, until) -> list:
    return _between(_rows, lambda row: row["end"], since, until)


def _summed(rows: list) -> dict:
    out = dict.fromkeys(SUMS, 0.0)
    for row in rows:
        for key, value in _row_sums(row).items():
            out[key] += value
    return out


def totals(since: Optional[float] = None,
           until: Optional[float] = None) -> dict:
    """The sums (`SUMS`) of the rows whose end lies between two
    `time.perf_counter()` readings; with neither, the process's own sums,
    which also count the rows past `MAX_ROWS`."""
    if since is None and until is None:
        with _lock:
            return dict(_sums)
    return _summed(_rows_between(since, until))


def _grouped(rows: list, key: str) -> dict:
    groups: dict = {}
    for row in rows:
        groups.setdefault(row[key], []).append(row)
    return {name: _summed(group) for name, group in groups.items()}


def by_function(since: Optional[float] = None,
                until: Optional[float] = None) -> list:
    """A row a `fun_name`: programs, seconds by phase, hits and misses;
    the one that took longest first."""
    table = [dict(sums, fun_name=name) for name, sums in _grouped(
        _rows_between(since, until), "fun_name").items()]
    seconds = lambda r: (r["trace_s"] + r["lower_s"] + r["backend_s"]
                         + r["cache_load_s"])
    return sorted(table, key=seconds, reverse=True)


def by_scope(since: Optional[float] = None,
             until: Optional[float] = None) -> dict:
    """scope -> the sums of the rows closed inside that `setup_phase`
    (None: outside every phase), with `phases`, how many such phases
    closed, `phase_s`, their seconds on the host's clock, and `top_s`,
    the seconds of those that lay inside no other phase."""
    table = _grouped(_rows_between(since, until), "scope")
    phases = _between(_phases, lambda p: p[2], since, until)
    for scope in {p[0] for p in phases} - set(table):
        table[scope] = _summed([])
    for entry in table.values():
        entry.update(phases=0, phase_s=0.0, top_s=0.0)
    for scope, seconds, _, depth in phases:
        entry = table[scope]
        entry["phases"] += 1
        entry["phase_s"] += seconds
        entry["top_s"] += 0.0 if depth else seconds
    return table
