"""mmlspark_tpu — a TPU-native ML pipeline framework.

A from-scratch reimplementation of the capabilities of MMLSpark
(gdtm86/mmlspark): SparkML-style Estimator/Transformer pipelines with
metadata-carrying schemas, implicit featurization, rich evaluation, image
ingestion/processing, a pretrained-model zoo, and distributed DNN scoring and
training — designed for TPUs.  Execution is JAX/XLA: `jit`-compiled array
programs sharded over a `jax.sharding.Mesh` (ICI/DCN) replace the reference's
CNTK-JNI bridge and MPI ring; batched XLA/Pallas kernels over HBM-resident
image tensors replace per-row OpenCV JNI calls.

Layer map (mirrors SURVEY.md section 1 of the reference analysis):
  core/      - params DSL, schema metadata, pipeline kernel, table runtime
  parallel/  - device mesh, sharding, collectives, multi-host init
  ops/       - batched image/array kernels (XLA + Pallas)
  models/    - flax model definitions + TPUModel distributed scoring
  train/     - in-process distributed trainer (TPULearner)
  ml/        - featurization, auto-ML train stages, evaluation
  stages/    - utility pipeline stages
  io/        - readers (image/binary/csv) and writers
  resilience/- retry/breaker policies, chaos injection, checkpoint
               rotation, preemption handling (docs/resilience.md)
  quant/     - post-training quantization: int8/bf16 bundles, fused
               wrappers, int8 KV cache, accuracy gates (docs/performance.md)
  zoo/       - pretrained model repository client
  native/    - C++ host-side runtime pieces (decode, parse, hash)
"""

import time as _time

_t_import = _time.perf_counter()    # the ledger's phase `import`

__version__ = "0.1.0"

from mmlspark_tpu.core.params import Param, Params
from mmlspark_tpu.core.pipeline import (
    Estimator,
    Pipeline,
    PipelineModel,
    PipelineStage,
    Transformer,
    load_stage,
)
from mmlspark_tpu.core.table import DataTable
from mmlspark_tpu.observe import (MetricData, get_logger, pipeline_timing,
                                  profile, run_telemetry, stage_timing)

# persistent XLA compilation cache (JAX_COMPILATION_CACHE_DIR, else the
# fixed in-checkout directory): wired before any model compiles so warm
# restarts skip recompiles entirely
from mmlspark_tpu.config import setup_compilation_cache as _setup_cc

_setup_cc()
del _setup_cc

# the compile ledger (observe/compiles.py): listens to JAX's compile and
# cache events from here on; this import is its first phase
from mmlspark_tpu.observe import compiles as _compiles

_compiles.register()
_compiles.note_import(_t_import)
del _compiles, _time, _t_import
