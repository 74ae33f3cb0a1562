"""Global configuration tier: one registry for every MMLSPARK_TPU_* knob.

Counterpart of the reference's two config layers — the Typesafe-config
wrapper (Configuration.scala:18-51: packaged defaults overlaid by an
environment-pointed file) and the `defvar` env framework the build/install
system uses (tools/config.sh:53-60: every variable declared with defaults
and documented provenance).  Here a variable is declared exactly once with
its name, type, default, and doc; reads go through `get()` with precedence

    programmatic override (`set()`)  >  process environment  >  default

and `describe()` makes the whole surface discoverable (the reference prints
its defvar table the same way).  Modules never call os.environ for
MMLSPARK_TPU_* values directly — they import this registry.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable

_PREFIX = "MMLSPARK_TPU_"


@dataclasses.dataclass(frozen=True)
class ConfigVar:
    name: str              # full env name, MMLSPARK_TPU_*
    default: Any
    doc: str
    ptype: Callable = str  # parser applied to env-var strings

    def current(self) -> Any:
        if self.name in _overrides:
            return _overrides[self.name]
        raw = os.environ.get(self.name)
        if raw is None:
            return self.default
        return self.ptype(raw)


_registry: dict[str, ConfigVar] = {}
_overrides: dict[str, Any] = {}
_declared_by: dict[str, str] = {}  # var name -> declaring module


def register(name: str, default: Any = None, doc: str = "",
             ptype: Callable = str) -> ConfigVar:
    """Declare a config variable (idempotent for identical declarations)."""
    if not name.startswith(_PREFIX):
        raise ValueError(f"config vars are namespaced {_PREFIX}*; got {name!r}")
    existing = _registry.get(name)
    if existing is not None:
        if (existing.default, existing.doc, existing.ptype) != \
                (default, doc, ptype):
            raise ValueError(f"{name} already registered with different "
                             f"default/doc/ptype; one declaration per "
                             f"variable")
        return existing  # identical re-declaration: keep the one instance
    var = ConfigVar(name, default, doc, ptype)
    _registry[name] = var
    # provenance, so generated docs can list the FRAMEWORK's variables
    # without picking up test/application declarations made in-process
    import sys
    _declared_by[name] = sys._getframe(1).f_globals.get("__name__", "")
    return var


def get(name: str) -> Any:
    """Typed current value: override > environment > default."""
    if name not in _registry:
        raise KeyError(f"unregistered config var {name!r}; known: "
                       f"{sorted(_registry)}")
    return _registry[name].current()


def set(name: str, value: Any) -> None:  # noqa: A001 - mirrors Configuration.set
    """Programmatic override (highest precedence); None removes it."""
    if name not in _registry:
        raise KeyError(f"unregistered config var {name!r}")
    if value is None:
        _overrides.pop(name, None)
    else:
        _overrides[name] = value


def describe() -> list[dict]:
    """Every registered variable with default, doc, current value, and the
    module that declared it (so generated docs can keep test/application
    declarations made in-process out of the framework's reference table)."""
    return [{"name": v.name, "default": v.default, "doc": v.doc,
             "current": v.current(),
             "declared_by": _declared_by.get(v.name, "")} for v in
            sorted(_registry.values(), key=lambda v: v.name)]


def _intp(s: str) -> int:
    return int(s)


def _floatp(s: str) -> float:
    return float(s)


# --------------------------------------------------------------------------
# the framework's variables (one declaration each; consumers import these)
# --------------------------------------------------------------------------

LOG_LEVEL = register(
    "MMLSPARK_TPU_LOG_LEVEL", default=None,
    doc="When set (DEBUG/INFO/...), the framework manages its own log "
        "output: root logger level + stderr handler (observe/logging.py). "
        "Unset: standard library behavior, the application configures.")

NATIVE_CACHE = register(
    "MMLSPARK_TPU_NATIVE_CACHE", default=None,
    doc="Directory for compiled native (C++) decoder artifacts; default "
        "~/.cache/mmlspark_tpu (native_loader.py).")

COORDINATOR = register(
    "MMLSPARK_TPU_COORDINATOR", default=None,
    doc="host:port of the jax.distributed coordinator for multi-host runs "
        "(the reference's MPI hostfile analogue, parallel/distributed.py).")

NUM_PROCESSES = register(
    "MMLSPARK_TPU_NUM_PROCESSES", default=None, ptype=_intp,
    doc="Total process count of the multi-host run.")

PROCESS_ID = register(
    "MMLSPARK_TPU_PROCESS_ID", default=None, ptype=_intp,
    doc="This process's index in the multi-host run (0 = coordinator).")

COLLECTIVE_TIMEOUT_S = register(
    "MMLSPARK_TPU_COLLECTIVE_TIMEOUT_S", default=600.0, ptype=_floatp,
    doc="Bounded wait for named multi-host collectives (barriers, "
        "checkpoint broadcast/gather): on expiry a CollectiveTimeoutError "
        "names the operation instead of the job hanging forever "
        "(parallel/distributed.py).")

TEST_PLATFORM = register(
    "MMLSPARK_TPU_TEST_PLATFORM", default="cpu",
    doc="Test harness: 'cpu' forces the 8-virtual-device CPU mesh; 'tpu' "
        "runs the suite (incl. perf floors) on real chips (tests/conftest.py).")

TEST_BUDGET_S = register(
    "MMLSPARK_TPU_TEST_BUDGET_S", default=30.0, ptype=_floatp,
    doc="Per-test duration alert budget in seconds (reference "
        "TestBase.scala:65 alerts at 3s; XLA compiles are ~10x that).")

TELEMETRY = register(
    "MMLSPARK_TPU_TELEMETRY", default=None,
    doc="Telemetry kill switch: '0'/'off'/'false' makes run_telemetry() "
        "blocks inert (no spans, no files, hot loops keep the zero-cost "
        "fast path); unset or anything else leaves them live "
        "(observe/telemetry.py).")

TELEMETRY_DIR = register(
    "MMLSPARK_TPU_TELEMETRY_DIR", default=None,
    doc="Default output directory for run_telemetry(): run.jsonl event "
        "stream + run_summary.json land here when the block passes no "
        "dir. Unset + no explicit dir: in-memory ring only, no files.")

# the persistent XLA compilation cache when JAX_COMPILATION_CACHE_DIR does
# not place it: one fixed directory beside the package (the cache key
# includes the path, so a directory that moves never hits)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def setup_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Called at package import (mmlspark_tpu/__init__.py).  The directory is
    placed from outside: where `JAX_COMPILATION_CACHE_DIR` is set, JAX's own
    handling of it stands and nothing here names a directory; where it is
    not, the cache lives at `CHECKOUT_CACHE_DIR`.  Warm restarts
    (resume-after-preemption, a second run of the same command) then load
    compiled executables from disk instead of re-compiling.
    """
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    # cache every executable: the default thresholds skip sub-second
    # compiles, but warm-restart wins here come precisely from the many
    # small per-shape programs the scoring/training loops accumulate
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir
