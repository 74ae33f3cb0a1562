"""Opt-in device profiling (what the reference never had — its JNI scoring
loop was unobservable; diagnosing round 2's throughput swing took manual
probing).

    with mmlspark_tpu.profile("/tmp/trace"):
        model.transform(table)

wraps jax.profiler.trace: the dump is a TensorBoard/Perfetto trace showing
host transfer vs MXU occupancy per step.  `annotate(name)` adds a named span
inside an active trace (jax.profiler.TraceAnnotation) around host-side code
so framework phases (batching, padding, fetch) are visible between device
ops.  The framework-side run record (`run_telemetry`'s trace.json,
observe/telemetry.py) uses the same Perfetto timeline idiom, so the two
dumps load side by side.
"""

from __future__ import annotations

import contextlib

import jax

from mmlspark_tpu.observe.logging import get_logger


@contextlib.contextmanager
def profile(log_dir: str, *, host_tracer_level: int = 2):
    """Capture a device+host trace of the block into `log_dir`."""
    # Probe jax's trace() signature BEFORE entering the block: a TypeError
    # raised by user code inside the block must propagate untouched, never
    # be mistaken for an old-jax signature mismatch.
    kwargs: dict = {}
    try:
        import inspect
        if "profiler_options" in inspect.signature(
                jax.profiler.trace).parameters:
            options = jax.profiler.ProfileOptions()
            options.host_tracer_level = host_tracer_level
            kwargs["profiler_options"] = options
    except Exception as exc:
        # a REAL probe failure (import error, renamed API) must be visible
        # — a silently downgraded trace reads as "the chip was idle" and
        # sends the investigation the wrong way.  The trace itself still
        # runs: options are an enhancement, not a requirement.
        get_logger("observe").warning(
            "jax.profiler signature probe failed (%r); tracing without "
            "profiler_options (host_tracer_level=%d not applied)",
            exc, host_tracer_level)
    with jax.profiler.trace(log_dir, **kwargs):
        yield log_dir


# every span the framework writes into a profiler trace starts with this
# (the benchmark's trace reduction keeps host spans by this prefix)
SPAN_PREFIX = "mmlspark_tpu."


def annotate(name: str):
    """Named host-side span, visible inside an active trace.

    The three span helpers of the hot paths (`spans.span_on`,
    `trace.span_on_tracer`, `trace.trace_span`) enter
    `annotate(SPAN_PREFIX + name)` around everything they record, so the
    framework's spans lie on the profiler's clock beside the device's ops
    whenever a profiler session is on, and cost a TraceMe that records
    nothing when none is.  They are the only callers in the package.

    Off-TPU builds (or jax versions) without a working TraceAnnotation
    degrade to an inert context manager — caller code stays unconditional
    — and the downgrade is logged once per call site's first failure
    rather than silently swallowed."""
    try:
        return jax.profiler.TraceAnnotation(name)
    except Exception as exc:
        get_logger("observe").debug(
            "profiler annotation unavailable off-TPU (%r); %r is a no-op",
            exc, name)
        return contextlib.nullcontext()
