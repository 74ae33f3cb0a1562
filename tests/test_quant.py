"""Quantized inference: bundle fidelity, fused forwards, int8 KV decode.

Pins the quant/ subsystem contracts (docs/performance.md "Quantized
inference"):

* save->load round-trips quantized trees BYTE-exactly: int8 kernels,
  float32 scale arrays, bfloat16 leaves — dtypes and values (no silent
  upcast on reload).
* dequant(quant(W)) error bounded per channel by construction:
  max(scale/2, amax - 127*scale) — round-to-nearest inside the clip
  range, clip distance outside.
* int8 scoring through TPUModel tracks the f32 model (top-1 agreement),
  and the computeDtype Param gives bf16 compute with f32 table-boundary
  outputs.
* int8 KV-cache decode (DecodeEngine cache_dtype / TextGenerator
  kvCacheDtype) matches the model-dtype cache's greedy tokens on a tiny
  fixed-seed model (CPU-deterministic).
All tests run on the CPU mesh (tier-1).
"""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu import DataTable
from mmlspark_tpu.models import ModelBundle, TPUModel
from mmlspark_tpu.models.bundle import load_bundle, save_bundle
from mmlspark_tpu.models.definitions import build_model
from mmlspark_tpu.models.generate import DecodeEngine, TextGenerator
from mmlspark_tpu.quant import (QuantConv, QuantDense, accuracy_gate,
                                dequantize_array, quantization_mode,
                                quantize_array_int8, quantize_bundle,
                                quantize_kv)
from mmlspark_tpu.quant.quantize import INT8_MAX


def _conv_bundle(dtype=jnp.float32):
    from mmlspark_tpu.models import ConvNetCIFAR10
    return ModelBundle.init(
        ConvNetCIFAR10(widths=(8, 8, 16), dense_width=16, dtype=dtype),
        (1, 16, 16, 3), seed=0)


def _lm_bundle(**overrides):
    cfg = {"vocab_size": 64, "d_model": 32, "n_heads": 4, "n_layers": 2,
           "max_len": 96, "dtype": "float32", **overrides}
    lm = build_model("TransformerLM", cfg)
    return ModelBundle.init(lm, (1, 8), seed=0), lm


def _leaves(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_leaves(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = np.asarray(v)
    return out


# ------------------------------------------------------------- quantize ---

def test_quantize_bundle_rejects_unknown_mode():
    with pytest.raises(ValueError, match="bf16 | int8"):
        quantize_bundle(_conv_bundle(), "fp4")


def test_int8_layout_metadata_and_original_untouched():
    bundle = _conv_bundle()
    before = _leaves(bundle.variables)
    q = quantize_bundle(bundle, "int8")
    assert quantization_mode(q) == "int8"
    assert quantization_mode(bundle) is None
    assert q.config["dtype"] == "bfloat16"
    assert q.metadata["quantization"]["int8_kernels"] == 5  # 3 conv + 2 dense
    leaves = _leaves(q.variables)
    n_int8 = n_scale = 0
    for name, arr in leaves.items():
        if name.endswith("kernel_scale"):
            assert arr.dtype == np.float32
            n_scale += 1
        elif name.endswith("kernel"):
            assert arr.dtype == np.int8
            assert arr.ndim in (2, 4)
            n_int8 += 1
        elif np.issubdtype(arr.dtype, np.floating):
            assert arr.dtype == jnp.bfloat16  # norms/biases -> bf16
    assert n_int8 == n_scale == 5
    # the input bundle's variables were not mutated
    after = _leaves(bundle.variables)
    assert all(np.array_equal(before[k], after[k])
               and before[k].dtype == after[k].dtype for k in before)


def test_bf16_mode_casts_whole_tree():
    q = quantize_bundle(_conv_bundle(), "bf16")
    assert quantization_mode(q) == "bf16"
    for name, arr in _leaves(q.variables).items():
        assert arr.dtype == jnp.bfloat16, name


def test_moe_expert_kernels_stay_unquantized():
    bundle, _ = _lm_bundle(mlp_impl="moe", n_experts=2, moe_group_size=1)
    q = quantize_bundle(bundle, "int8")
    for name, arr in _leaves(q.variables).items():
        if arr.dtype == np.int8:
            assert arr.ndim in (2, 4), name  # rank-3 expert stacks excluded
        if "moe" in name and name.endswith("kernel"):
            assert arr.dtype == jnp.bfloat16, name


@pytest.mark.parametrize("mode", ["int8", "bf16"])
def test_save_load_roundtrip_byte_exact(mode):
    """The satellite contract: dtypes AND values persist exactly —
    including int8 payloads, float32 scale arrays, bfloat16 leaves."""
    q = quantize_bundle(_conv_bundle(), mode)
    with tempfile.TemporaryDirectory() as d:
        save_bundle(q, d)
        r = load_bundle(d)
    assert r.metadata["quantization"] == q.metadata["quantization"]
    want, got = _leaves(q.variables), _leaves(r.variables)
    assert set(want) == set(got)
    for name in want:
        assert want[name].dtype == got[name].dtype, name
        assert np.array_equal(want[name], got[name]), name


def test_dequant_error_bound_per_layer_type():
    """|w - dequant(quant(w))| bounded per channel by construction, pinned
    separately for conv (rank-4) and dense (rank-2) kernels."""
    bundle = _conv_bundle()
    seen_ranks = set()
    for name, w in _leaves(bundle.variables).items():
        if not name.endswith("kernel") or w.ndim not in (2, 4):
            continue
        seen_ranks.add(w.ndim)
        q, scale = quantize_array_int8(w)
        deq = dequantize_array(q, scale)
        red = tuple(range(w.ndim - 1))
        err = np.abs(np.asarray(w, np.float32) - deq).max(axis=red)
        amax = np.abs(np.asarray(w, np.float32)).max(axis=red)
        bound = np.maximum(scale / 2, amax - INT8_MAX * scale) + 1e-6
        assert (err <= bound).all(), name
    assert seen_ranks == {2, 4}  # both layer types exercised


def test_quantize_kv_roundtrip_bound_and_zeros():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 5, 3, 8)).astype(np.float32))
    x = x.at[0, 2].set(0.0)  # a never-written cache slot
    q, scale = quantize_kv(x)
    assert q.dtype == jnp.int8 and q.shape == x.shape
    assert scale.shape == (2, 5, 3)
    deq = np.asarray(q, np.float32) * np.asarray(scale)[..., None]
    err = np.abs(np.asarray(x) - deq)
    assert (err <= np.asarray(scale)[..., None] / 2 + 1e-7).all()
    assert (deq[0, 2] == 0).all() and (np.asarray(scale)[0, 2] == 0).all()


# ------------------------------------------------------ scoring (TPUModel) ---

def test_int8_scoring_tracks_f32():
    bundle = _conv_bundle()
    rng = np.random.default_rng(0)
    t = DataTable({"image": rng.integers(0, 256, size=(32, 16, 16, 3),
                                         dtype=np.uint8)})
    ref = TPUModel(bundle, inputCol="image", outputCol="s",
                   miniBatchSize=16).transform(t)["s"]
    out = TPUModel(quantize_bundle(bundle, "int8"), inputCol="image",
                   outputCol="s", miniBatchSize=16).transform(t)["s"]
    assert out.dtype == np.float32  # table boundary stays f32
    assert out.shape == ref.shape
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert rel < 0.05, rel
    agree = (np.argmax(out, 1) == np.argmax(ref, 1)).mean()
    assert agree >= 0.9, agree


def test_int8_node_selection_still_works():
    bundle = _conv_bundle()
    q = quantize_bundle(bundle, "int8")
    m = TPUModel(q, inputCol="image", outputCol="feat", miniBatchSize=8,
                 outputNodeName="dense1")
    rng = np.random.default_rng(1)
    t = DataTable({"image": rng.integers(0, 256, size=(8, 16, 16, 3),
                                         dtype=np.uint8)})
    feat = m.transform(t)["feat"]
    assert feat.shape == (8, 16)
    assert feat.dtype == np.float32  # quantized bundles cast at the boundary


def test_compute_dtype_param():
    bundle = _conv_bundle()  # built f32
    rng = np.random.default_rng(2)
    t = DataTable({"image": rng.integers(0, 256, size=(16, 16, 16, 3),
                                         dtype=np.uint8)})
    ref = TPUModel(bundle, inputCol="image", outputCol="s",
                   miniBatchSize=8).transform(t)["s"]
    # explicit float32 override == module default for an f32 module
    same = TPUModel(bundle, inputCol="image", outputCol="s", miniBatchSize=8,
                    computeDtype="float32").transform(t)["s"]
    np.testing.assert_array_equal(ref, same)
    # bf16 override: f32 at the boundary, bf16-close to the f32 scores
    bf = TPUModel(bundle, inputCol="image", outputCol="s", miniBatchSize=8,
                  computeDtype="bfloat16").transform(t)["s"]
    assert bf.dtype == np.float32
    assert (np.argmax(bf, 1) == np.argmax(ref, 1)).mean() >= 0.9
    from mmlspark_tpu.core.params import ParamError
    with pytest.raises(ParamError):
        TPUModel(bundle, computeDtype="float16")


def test_compute_dtype_casts_intermediate_nodes_to_f32():
    bundle = _conv_bundle()
    m = TPUModel(bundle, inputCol="image", outputCol="feat", miniBatchSize=8,
                 outputNodeName="conv1", computeDtype="bfloat16")
    rng = np.random.default_rng(3)
    t = DataTable({"image": rng.integers(0, 256, size=(8, 16, 16, 3),
                                         dtype=np.uint8)})
    assert m.transform(t)["feat"].dtype == np.float32


# ------------------------------------------------------------ bundle init ---

def test_bundle_init_derives_token_input_dtype():
    """Satellite: token-input models init with an int32 feed (an f32 feed
    would crash the Embed lookup), float models keep float32."""
    bundle, lm = _lm_bundle()
    assert np.asarray(
        bundle.variables["params"]["lm_head"]["kernel"]).dtype == np.float32
    # explicit override still wins
    b2 = ModelBundle.init(lm, (1, 8), seed=1, input_dtype=np.int64)
    assert b2.architecture == "TransformerLM"


# ------------------------------------------------------------ int8 KV cache ---

def test_int8_kv_cache_greedy_agreement():
    """The satellite pin: int8-KV greedy decode top-1 agreement with the
    model-dtype cache on a tiny fixed-seed model (CPU-deterministic)."""
    bundle, lm = _lm_bundle()
    rng = np.random.default_rng(0)
    prompts = np.zeros((4, 16), np.int32)
    true_len = np.asarray([5, 9, 16, 12], np.int32)
    for i, n in enumerate(true_len):
        prompts[i, :n] = rng.integers(0, 64, n)
    base = DecodeEngine(lm, 24, chunk=16)
    quant = DecodeEngine(lm, 24, chunk=16, cache_dtype="int8")
    g_base = base.generate(bundle.variables, prompts, true_len)
    g_quant = quant.generate(bundle.variables, prompts, true_len)
    assert g_quant.shape == g_base.shape == (4, 24)
    # agreement over the steps both caches decoded from the SAME history:
    # after a row's first flip (a near-tie the ~1/254 quantization error
    # tips) the two greedy chains condition on different tokens, so the
    # tail measures the cascade, not the cache — and which draw has a
    # near-tie moves with the PRNG defaults of the installed jax
    same = g_base == g_quant
    compared = np.where(same.all(axis=1), same.shape[1],
                        same.argmin(axis=1) + 1)
    agreed = compared - ~same.all(axis=1)
    assert agreed.sum() / compared.sum() >= 0.95


def test_int8_kv_cache_rejects_unknown_dtype():
    _, lm = _lm_bundle()
    with pytest.raises(ValueError, match="cache_dtype"):
        DecodeEngine(lm, 4, cache_dtype="int4")


def test_int8_kv_stop_tokens_and_early_exit():
    bundle, lm = _lm_bundle()
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, 64, (3, 8)).astype(np.int32)
    true_len = np.full(3, 8, np.int32)
    probe = DecodeEngine(lm, 16, chunk=8, cache_dtype="int8")
    first = probe.generate(bundle.variables, prompts, true_len)
    stop = int(first[0, 0])  # every row's first token becomes a stop token?
    eng = DecodeEngine(lm, 16, chunk=8, cache_dtype="int8",
                       stop_tokens=(stop,))
    got = eng.generate(bundle.variables, prompts, true_len)
    assert got.shape == (3, 16)
    # stopped rows freeze on the stop token
    for row in got:
        hits = np.nonzero(row == stop)[0]
        if hits.size:
            assert (row[hits[0]:] == stop).all()
    if bool((first == stop).any(axis=1).all()):
        assert eng.last_segments_run <= probe.last_segments_run


def test_text_generator_kv_cache_param():
    bundle, _ = _lm_bundle()
    rng = np.random.default_rng(2)
    rows = np.empty(4, object)
    for i, n in enumerate((3, 7, 11, 6)):
        rows[i] = rng.integers(0, 64, n).astype(np.int32)
    t = DataTable({"prompt": rows})
    base = TextGenerator(bundle, inputCol="prompt", outputCol="out",
                         maxNewTokens=8, cacheChunk=16)
    quant = base.copy(kvCacheDtype="int8")
    out_b = base.transform(t)["out"]
    out_q = quant.transform(t)["out"]
    agree = np.concatenate(
        [(a == b) for a, b in zip(out_b, out_q)]).mean()
    assert agree >= 0.95
    from mmlspark_tpu.core.params import ParamError
    with pytest.raises(ParamError):
        base.copy(kvCacheDtype="fp8")


def test_int8_kv_sampling_is_row_stable():
    """Sampling through the int8 cache keeps the per-row stream contract:
    same seed + row ids -> same draws regardless of batch composition."""
    bundle, lm = _lm_bundle()
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, 64, (4, 8)).astype(np.int32)
    true_len = np.full(4, 8, np.int32)
    eng = DecodeEngine(lm, 6, temperature=0.7, top_k=8, chunk=8,
                       cache_dtype="int8")
    key = jax.random.key(5)
    full = eng.generate(bundle.variables, prompts, true_len, rng=key,
                        row_ids=np.arange(4))
    sub = eng.generate(bundle.variables, prompts[1:3], true_len[1:3],
                       rng=key, row_ids=np.arange(1, 3))
    np.testing.assert_array_equal(full[1:3], sub)


# ------------------------------------------------ quantized decode weights ---

def test_int8_weight_bundle_decodes():
    """int8-quantized TransformerLM bundles generate through the engine
    (quant-aware _dense) without a weight re-export."""
    bundle, _ = _lm_bundle()
    q = quantize_bundle(bundle, "int8")
    rng = np.random.default_rng(4)
    prompts = rng.integers(0, 64, (2, 8)).astype(np.int32)
    eng = DecodeEngine(q.module(), 6, chunk=16)
    got = eng.generate(q.variables, prompts, np.full(2, 8, np.int32))
    assert got.shape == (2, 6)
    assert (got >= 0).all() and (got < 64).all()


# -------------------------------------------------------- fused wrappers ---

def test_quant_dense_module_matches_dequant_math():
    rng = np.random.default_rng(0)
    w = rng.normal(size=(6, 4)).astype(np.float32)
    x = rng.normal(size=(3, 6)).astype(np.float32)
    q, scale = quantize_array_int8(w)
    layer = QuantDense(features=4)
    variables = {"params": {
        "kernel": jnp.asarray(q), "kernel_scale": jnp.asarray(scale),
        "bias": jnp.zeros(4, jnp.bfloat16)}}
    got = np.asarray(layer.apply(variables, x), np.float32)
    want = x @ dequantize_array(q, scale)
    assert np.abs(got - want).max() <= 0.05 * np.abs(want).max() + 1e-3


def test_quant_conv_module_matches_dequant_math():
    import flax.linen as nn
    rng = np.random.default_rng(1)
    w = rng.normal(size=(3, 3, 2, 4)).astype(np.float32)
    x = rng.normal(size=(2, 8, 8, 2)).astype(np.float32)
    q, scale = quantize_array_int8(w)
    layer = QuantConv(features=4, kernel_size=(3, 3))
    variables = {"params": {
        "kernel": jnp.asarray(q), "kernel_scale": jnp.asarray(scale),
        "bias": jnp.zeros(4, jnp.bfloat16)}}
    got = np.asarray(layer.apply(variables, x), np.float32)
    ref_layer = nn.Conv(4, (3, 3), padding="SAME", dtype=jnp.float32)
    want = np.asarray(ref_layer.apply(
        {"params": {"kernel": jnp.asarray(dequantize_array(q, scale)),
                    "bias": jnp.zeros(4)}}, x))
    assert np.abs(got - want).max() <= 0.05 * np.abs(want).max() + 1e-3


def test_quant_wrapper_registry_lookup():
    import flax.linen as nn
    from mmlspark_tpu.quant import modules  # noqa: F401 (registers wrappers)
    from mmlspark_tpu.utils.registry import quant_wrapper_for

    assert quant_wrapper_for(nn.Dense) is not None
    assert quant_wrapper_for(nn.Conv) is not None

    class MyDense(nn.Dense):
        pass

    assert quant_wrapper_for(MyDense) is quant_wrapper_for(nn.Dense)
    assert quant_wrapper_for(nn.LayerNorm) is None


# -------------------------------------------------------------- the gate ---

def test_classification_report_matches_manual():
    from mmlspark_tpu.ml.statistics import classification_report
    y = np.asarray([0, 1, 2, 1, 0, 2, 1, 1])
    p = np.asarray([0, 1, 1, 1, 0, 2, 0, 1])
    acc = float(classification_report(y, p).metrics["accuracy"][0])
    assert acc == pytest.approx((y == p).mean())


def test_accuracy_gate_fields():
    bundle = _conv_bundle()
    q = quantize_bundle(bundle, "int8")
    rng = np.random.default_rng(5)
    imgs = rng.integers(0, 256, size=(24, 16, 16, 3), dtype=np.uint8)
    labels = rng.integers(0, 10, 24)
    gate = accuracy_gate(
        TPUModel(bundle, inputCol="image", outputCol="s", miniBatchSize=8),
        TPUModel(q, inputCol="image", outputCol="s", miniBatchSize=8),
        DataTable({"image": imgs}), labels)
    assert set(gate) == {"baseline_accuracy", "quant_accuracy",
                         "accuracy_delta", "agreement", "n_rows"}
    assert gate["n_rows"] == 24
    assert gate["agreement"] >= 0.9
    assert gate["accuracy_delta"] == pytest.approx(
        gate["quant_accuracy"] - gate["baseline_accuracy"], abs=1e-3)


def test_fuzzing_registry_discovers_quant_stages():
    """quant/ rides the same package walk as every other module (no stage
    classes of its own, but the walk must import it cleanly)."""
    import importlib
    mod = importlib.import_module("mmlspark_tpu.quant")
    assert hasattr(mod, "quantize_bundle")
