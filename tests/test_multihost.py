"""Multi-host training: 2 real processes over jax.distributed (localhost).

The TPU-native replacement for the reference's multi-node MPI launch
(MultiNodeParallelLauncher, CommandBuilders.scala:95-117) is N identical
processes + jax.distributed + XLA collectives.  These tests spawn 2 actual
OS processes, each owning 4 virtual CPU devices, rendezvousing over a
localhost coordinator — the same topology as 2 TPU hosts over DCN — and
assert the distributed run matches the single-process 8-device run.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(ROOT, "tests", "multihost_worker.py")

# every test here spawns the 2-process jax.distributed topology; skip the
# whole module (with the probe's reason) where cross-process CPU
# collectives cannot run at all — tests/capabilities.py
pytestmark = pytest.mark.requires_env("multiprocess_collectives")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _load_worker_module():
    import importlib.util
    spec = importlib.util.spec_from_file_location("multihost_worker", WORKER)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def two_process_run(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("mh"))
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # worker sets its own (4 devices)
        env.update({
            "PYTHONPATH": ROOT + os.pathsep + env.get("PYTHONPATH", ""),
            "MMLSPARK_TPU_COORDINATOR": f"127.0.0.1:{port}",
            "MMLSPARK_TPU_NUM_PROCESSES": "2",
            "MMLSPARK_TPU_PROCESS_ID": str(pid),
        })
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, out], env=env, cwd=ROOT,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    logs = []
    try:
        for p in procs:
            stdout, _ = p.communicate(timeout=300)
            logs.append(stdout)
    finally:
        for p in procs:  # a collective deadlock must not leak workers
            if p.poll() is None:
                p.kill()
                p.wait()
    for pid, (p, log) in enumerate(zip(procs, logs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{log[-3000:]}"
    return out


def test_two_process_loss_matches_single_process(two_process_run):
    """One full-batch train step per epoch on 2 processes must match the
    single-process 8-device run: same global batch, same collectives math."""
    from mmlspark_tpu.train import Trainer

    worker = _load_worker_module()
    x, y = worker.make_data()
    ref = Trainer(worker.trainer_config())
    ref_bundle = ref.fit_arrays(x, y)
    ref_losses = np.asarray([h["loss"] for h in ref.history])
    ref_kernel = np.asarray(
        ref_bundle.variables["params"]["dense0"]["kernel"])

    got = np.load(os.path.join(two_process_run, "result0.npz"))
    np.testing.assert_allclose(got["losses"], ref_losses, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(got["kernel"], ref_kernel, rtol=1e-3,
                               atol=1e-5)
    assert int(got["steps"]) == ref_bundle.metadata["steps"]


def test_both_processes_agree_on_result(two_process_run):
    r0 = np.load(os.path.join(two_process_run, "result0.npz"))
    r1 = np.load(os.path.join(two_process_run, "result1.npz"))
    # bundle_from_state gathers to every process: results must be identical
    np.testing.assert_array_equal(r0["kernel"], r1["kernel"])


def test_restore_broadcasts_from_coordinator(two_process_run):
    """restore_checkpoint reads the file on the coordinator only and
    broadcasts; process 1 (whose checkpoint dir does not even exist) must
    still recover the final trained state."""
    for pid in range(2):
        r = np.load(os.path.join(two_process_run, f"result{pid}.npz"))
        assert int(r["restored_step"]) == int(r["steps"])
        np.testing.assert_array_equal(r["restored_kernel"], r["kernel"])


def test_two_process_scoring_matches_single_process(two_process_run):
    """TPUModel.transform under 2 processes: each process's output rows must
    equal the single-process scoring of its local partition (the reference's
    core distributed behavior, CNTKModel.scala:215-221).  Worker 0 scores an
    uneven partition (3 rows fewer), so step-count lockstep + padding are
    exercised, not just the happy path."""
    from mmlspark_tpu import DataTable
    from mmlspark_tpu.models import TPUModel
    from mmlspark_tpu.train import Trainer

    worker = _load_worker_module()
    x, y = worker.make_data()
    ref = Trainer(worker.trainer_config())
    bundle = ref.fit_arrays(x, y)
    scorer = TPUModel(bundle, inputCol="features", outputCol="scores",
                      miniBatchSize=32)
    ref_scores = np.asarray(
        scorer.transform(DataTable({"features": x}))["scores"])

    rows = len(x) // 2
    r0 = np.load(os.path.join(two_process_run, "result0.npz"))
    r1 = np.load(os.path.join(two_process_run, "result1.npz"))
    assert r0["scores"].shape == (rows - 3, 2)
    assert r1["scores"].shape == (rows, 2)
    np.testing.assert_allclose(r0["scores"], ref_scores[:rows - 3],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(r1["scores"], ref_scores[rows:],
                               rtol=1e-4, atol=1e-5)


def test_unequal_partitions_rotate_all_rows(two_process_run):
    """fit_arrays with 20-vs-12-row partitions: lockstep feeds 12 rows per
    epoch, but every local row must participate across epochs (the rotation
    fix for silent surplus-row dropping)."""
    for pid in range(2):
        r = np.load(os.path.join(two_process_run, f"result{pid}.npz"))
        assert int(r["uneq_rows_seen"]) == int(r["uneq_rows_total"])


def test_epoch_order_rotation_covers_all_rows():
    """Unit view of the same invariant: unshuffled rotation covers n_local
    within ceil(n_local/n) epochs; shuffled sampling draws from the whole
    partition."""
    from mmlspark_tpu.train.trainer import _epoch_order
    n, n_local = 12, 20
    seen = np.zeros(n_local, bool)
    for epoch in range(2):  # ceil(20/12) = 2
        order = _epoch_order(np.random.default_rng(0), epoch, n, n_local,
                             shuffle=False)
        assert order.shape == (n,) and (order < n_local).all()
        seen[order] = True
    assert seen.all()
    # equal partitions, unshuffled: identity order (bit-for-bit the old path)
    np.testing.assert_array_equal(
        _epoch_order(np.random.default_rng(0), 0, 8, 8, False), np.arange(8))
    # shuffled: a permutation prefix drawn from the FULL partition
    rng = np.random.default_rng(1)
    orders = {tuple(_epoch_order(rng, e, n, n_local, True)) for e in range(6)}
    assert len(orders) > 1
    assert any(i >= n for o in orders for i in o)  # reaches beyond first n


def test_only_coordinator_writes_checkpoints(two_process_run):
    from mmlspark_tpu.resilience.checkpoints import latest_valid_checkpoint

    assert latest_valid_checkpoint(
        os.path.join(two_process_run, "ckpt0")) is not None
    # process 1 returned the same path but must not have written its own
    assert not os.path.exists(os.path.join(two_process_run, "ckpt1"))
