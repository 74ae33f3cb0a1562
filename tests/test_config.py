"""Config registry (reference Configuration.scala:18-51 +
tools/config.sh:53-60 defvar framework)."""

import os

import pytest

from mmlspark_tpu import config


def test_known_vars_registered():
    names = {d["name"] for d in config.describe()}
    assert {"MMLSPARK_TPU_LOG_LEVEL", "MMLSPARK_TPU_NATIVE_CACHE",
            "MMLSPARK_TPU_COORDINATOR", "MMLSPARK_TPU_NUM_PROCESSES",
            "MMLSPARK_TPU_PROCESS_ID", "MMLSPARK_TPU_TEST_PLATFORM",
            "MMLSPARK_TPU_TEST_BUDGET_S"} <= names
    # every var documents itself (discoverability is the point)
    assert all(d["doc"] for d in config.describe())


def test_precedence_override_env_default(monkeypatch):
    name = "MMLSPARK_TPU_NUM_PROCESSES"
    assert config.get(name) is None  # default
    monkeypatch.setenv(name, "4")
    assert config.get(name) == 4     # env, typed
    config.set(name, 8)
    try:
        assert config.get(name) == 8  # programmatic wins
    finally:
        config.set(name, None)
    assert config.get(name) == 4


def test_unregistered_access_rejected():
    with pytest.raises(KeyError):
        config.get("MMLSPARK_TPU_NO_SUCH_VAR")
    with pytest.raises(KeyError):
        config.set("MMLSPARK_TPU_NO_SUCH_VAR", 1)
    with pytest.raises(ValueError):
        config.register("WRONG_PREFIX_X", doc="x")


def test_conflicting_redeclaration_rejected():
    config.register("MMLSPARK_TPU_TEST_DUMMY", default=1, doc="d")
    config.register("MMLSPARK_TPU_TEST_DUMMY", default=1, doc="d")  # idempotent
    with pytest.raises(ValueError):
        config.register("MMLSPARK_TPU_TEST_DUMMY", default=2, doc="d")


def test_every_env_read_goes_through_registry():
    """No module may read MMLSPARK_TPU_* via os.environ directly (the
    registry is the single source of truth); the only exemptions are the
    registry itself and the conftest bootstrap that gates JAX init."""
    import os
    import re
    pkg = os.path.dirname(config.__file__)
    offenders = []
    for root, _, files in os.walk(pkg):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(root, f)
            if os.path.samefile(path, config.__file__):
                continue
            with open(path) as fh:
                src = fh.read()
            for m in re.finditer(r"os\.environ[^\n]*MMLSPARK_TPU_", src):
                offenders.append((path, m.group(0)))
    assert not offenders, offenders


def test_prefetch_vars_registered():
    import mmlspark_tpu.parallel.prefetch  # noqa: F401  (registers on import)
    names = {d["name"] for d in config.describe()}
    assert {"MMLSPARK_TPU_PREFETCH_DEPTH",
            "MMLSPARK_TPU_PREFETCH_WORKERS"} <= names
    assert config.get("MMLSPARK_TPU_PREFETCH_DEPTH") == 8


def test_compilation_cache_wiring(tmp_path, monkeypatch):
    """The persistent XLA cache is placed from outside: with
    JAX_COMPILATION_CACHE_DIR set the program names no directory (JAX's
    own handling stands); unset, it is the fixed in-checkout path."""
    import jax

    prev = jax.config.jax_compilation_cache_dir
    updates = []
    real_update = jax.config.update

    def spy(name, value):
        updates.append(name)
        real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert config.setup_compilation_cache() == prev
        assert "jax_compilation_cache_dir" not in updates

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        checkout = os.path.dirname(os.path.dirname(config.__file__))
        fixed = os.path.join(checkout, ".jax_cache")
        assert config.setup_compilation_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        real_update("jax_compilation_cache_dir", prev)
