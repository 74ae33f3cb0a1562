"""The ONE definition of the virtual CPU test mesh environment.

tests/conftest.py, scripts/regen_benchmarks.py, and scripts/regen_examples.py
must all compute on byte-identical backends or the committed pins (grid CSV,
example metrics) silently diverge from what CI verifies.  Call BEFORE jax
creates a backend: JAX_PLATFORMS and XLA_FLAGS are read when the backend
initializes.  The jax.config updates repeat the two settings for a caller
that imported jax (without touching a device) before calling this — jax
reads the environment once, at import."""

from __future__ import annotations

import os

VIRTUAL_DEVICES = 8


def pin_virtual_cpu_mesh() -> None:
    """Force the 8-virtual-device float32 CPU mesh (the local[*] analogue,
    reference SparkSessionFactory.scala:40-51)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_ENABLE_X64"] = "0"
    # FORCE the device count: a leftover foreign
    # --xla_force_host_platform_device_count (e.g. from multihost-worker
    # experiments) must not leak into pin regeneration
    kept = [f for f in os.environ.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f]
    kept.append(f"--xla_force_host_platform_device_count={VIRTUAL_DEVICES}")
    os.environ["XLA_FLAGS"] = " ".join(kept)
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", False)
