"""Published peaks of one chip, keyed by a lowercase part of JAX's
`device_kind`.  A device that is not here is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s a chip.  Only what a metric
reads is kept here.
(Copied from `mmlspark_tpu/utils/perf.py`, which later PRs can change.)
"""

from __future__ import annotations

V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
PEAKS = {"v5 lite": V5E, "v5e": V5E}


def peaks_for(device_kind: str) -> dict:
    kind = device_kind.lower()
    for key, row in PEAKS.items():
        if key in kind:
            return row
    raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                   "add it to benchmark/reduce/peaks.py with its source")
