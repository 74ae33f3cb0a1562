"""Session-cached environment capability probes (conftest's
`requires_env` marker).

A handful of tier-1 tests exercise constructs this process environment
cannot always run: multiprocess CPU collectives, a model-parallel mesh,
the pip-installed package, data-service worker subprocesses.  Each probe
here answers "can this environment run the construct at all" once per
session (lru_cache), so those tests SKIP with an explicit, actionable
reason instead of erroring at setup.

Probes are deliberately minimal — the smallest program that trips the
same missing capability the real test would, never the workload itself —
so an unavailable capability costs milliseconds (or one tiny subprocess
pair), not a full failing compile.  A probe that fails for an UNEXPECTED
reason still reports unavailable, carrying that reason verbatim: a probe
must never crash the suite it exists to keep clean.
"""

from __future__ import annotations

import functools
import os
import socket
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=None)
def probe(name: str) -> tuple:
    """(available: bool, reason: str) for one named capability; cached
    for the session so N marked tests pay for one probe."""
    try:
        fn = _PROBES[name]
    except KeyError:
        raise ValueError(
            f"unknown capability {name!r}; known: {sorted(_PROBES)}")
    try:
        reason = fn()
    except Exception as e:  # a probe must never take the suite down
        return False, f"probe raised {type(e).__name__}: {e}"
    return (reason is None), (reason or "")


def _probe_mp2():
    """A ('data', 'model') mesh with model=2 running one jitted forward
    whose shard_constraint hint targets the model axis — the smallest
    program that exercises what the tensor-parallel tests need (2+
    devices plus GSPMD honoring a 2-D mesh constraint under jit)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
    from mmlspark_tpu.parallel.partition import shard_constraint, use_mesh
    from jax.sharding import PartitionSpec as P

    devs = jax.devices()
    if len(devs) < 2:
        return ("fewer than 2 devices: a model-parallel ('data','model') "
                "mesh needs at least model=2")
    mesh = make_mesh(MeshSpec(data=1, model=2), devs[:2])

    def fwd(x, w):
        w = shard_constraint(w, P(None, "model"))
        return x @ w

    def meshed(x, w):
        with use_mesh(mesh):
            return fwd(x, w)

    x = jnp.ones((2, 4), jnp.float32)
    w = jnp.ones((4, 8), jnp.float32)
    got = np.asarray(jax.jit(meshed)(x, w))
    if not np.allclose(got, 4.0):
        return "model-sharded matmul returned wrong values"
    return None


_MP_WORKER = """
import sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(sys.argv[1], num_processes=2,
                           process_id=int(sys.argv[2]))
import numpy as np
from jax.experimental import multihost_utils
got = multihost_utils.process_allgather(np.asarray(int(sys.argv[2])))
assert sorted(np.asarray(got).ravel().tolist()) == [0, 1], got
print("MP_PROBE_OK")
"""


def _probe_multiprocess_collectives():
    """Two real processes rendezvous over jax.distributed and allgather
    one scalar — the smallest program that exercises cross-process CPU
    collectives (test_multihost's whole premise)."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    procs = [subprocess.Popen(
        [sys.executable, "-c", _MP_WORKER, f"127.0.0.1:{port}", str(pid)],
        env=env, cwd=ROOT, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for pid in range(2)]
    logs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=120)
            logs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
            p.wait()
        return ("multiprocess CPU collectives probe timed out "
                "(jax.distributed rendezvous/allgather never completed)")
    if any(p.returncode != 0 for p in procs):
        tail = next(log for p, log in zip(procs, logs)
                    if p.returncode != 0).strip().splitlines()
        return ("multiprocess CPU collectives unavailable: "
                + (tail[-1] if tail else "worker failed with no output"))
    return None


def _probe_package_installed():
    """Is mmlspark_tpu importable OUTSIDE the source tree (pip-installed),
    or only via the repo cwd?  test_packaging's import-from-anywhere
    contract needs the former."""
    out = subprocess.run(
        [sys.executable, "-c", "import mmlspark_tpu"],
        cwd=os.path.sep, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if out.returncode != 0:
        return ("mmlspark_tpu is not installed in the environment (only "
                "importable from the source tree); run `make install`")
    return None


def _probe_data_service_workers():
    """Spawn ONE real data-service worker subprocess and complete the
    hello handshake over a localhost socket — the smallest program that
    exercises what process-mode `Dataset.distribute()` needs (python
    subprocess spawn + loopback TCP + the package importable in a fresh
    interpreter).  Sandboxes that forbid either make the process-mode
    tests skip here instead of hanging on accept()."""
    from mmlspark_tpu.data.service import transport

    srv, port = transport.listen()
    proc = transport.spawn_worker(0, "127.0.0.1", port)
    try:
        conn = transport.accept(srv, timeout_s=60.0)
        if conn is None:
            return ("data-service worker subprocess never connected back "
                    "over localhost (spawn or loopback TCP unavailable)")
        conn.setblocking(True)
        buf = transport.FrameBuffer()
        while True:
            data = conn.recv(65536)
            if not data:
                return ("data-service worker closed its socket before "
                        "the hello frame")
            buf.feed(data)
            for frame in buf.frames():
                if frame[0] == "json" and frame[1].get("t") == "hello":
                    transport.send_json(conn, {"t": "stop"})
                    conn.close()
                    return None
    finally:
        srv.close()
        proc.terminate()
        proc.wait(timeout=30)


_PROBES = {
    "mp2": _probe_mp2,
    "multiprocess_collectives": _probe_multiprocess_collectives,
    "package_installed": _probe_package_installed,
    "data_service_workers": _probe_data_service_workers,
}
