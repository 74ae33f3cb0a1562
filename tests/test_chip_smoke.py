"""chip_smoke.py's phases, tiny, on the CPU mesh.

The script itself refuses to run off the TPU; its phase functions take
their sizes as arguments, so the same code paths (TPUModel.transform, the
HTTP server against the DecodeEngine oracle, Trainer.fit_arrays with the
flash kernels in interpret mode, one ring-flash step under shard_map) are
driven here in seconds.  What only the chip can show — Mosaic calls in the
compiled programs — the phases assert on the TPU alone.
"""

import jax
import pytest

import chip_smoke
from mmlspark_tpu.models.definitions import ResNet
from mmlspark_tpu.parallel.mesh import MeshSpec

# one max_len everywhere: the eager flax init is most of each phase's wall
# at this size, and equal shapes share its compiled ops across phases
LM = {"vocab_size": 64, "d_model": 32, "n_heads": 4, "n_layers": 1,
      "dtype": "float32"}
SEQ = 32


@pytest.fixture(scope="module")
def harness():
    with chip_smoke.Harness() as h:
        yield h


def test_score_phase(harness):
    module = ResNet(stage_sizes=(1,), widths=(8,), num_classes=10)
    out = chip_smoke.score_phase(harness, module, 16, 8, 28)
    assert out["rows"] == 28 and out["batches"] == 4
    assert out["mesh"]["data"] == jax.device_count()
    assert out["compile_s"] > 0 and out["run_s"] >= 0


def test_serve_phase(harness):
    out = chip_smoke.serve_phase(harness, dict(LM, max_len=SEQ),
                                 (3, 12, 5, 4), 4, 2)
    assert out["requests"] == 4 and out["buckets"] == [8, 16]
    # off the TPU the cache read is the XLA reference: no Mosaic call
    assert set(out["decode_window_mosaic_calls"].values()) == {0}


def test_train_phase(harness):
    out = chip_smoke.train_phase(harness, LM, SEQ, 8, 3,
                                 mesh_spec=MeshSpec(data=4, model=2),
                                 tensor_parallel=True)
    assert out["mesh"] == {"data": 4, "model": 2, "seq": 1}
    assert out["losses"][-1] < out["losses"][0]


def test_ring_phase(harness):
    out = chip_smoke.ring_phase(harness, LM, SEQ, 4, 2)
    assert out["mesh"] == {"data": 4, "model": 1, "seq": 2}


def test_main_refuses_off_tpu(capsys):
    assert chip_smoke.main() != 0
    assert capsys.readouterr().out == ""
