"""Weights are placed on the device ONCE (parallel/bridge.place_weights):
no jitted call of `ServingEngine` or `TextGenerator` is handed a host
weight tree, which `jax.jit` would upload again on every call.  What they
place is the RESIDENT tree (`generate.resident_variables`): with bfloat16
compute the kernels are held in bfloat16, so no call casts them again
(tests/test_resident_weights.py pins that the results are bit for bit the
float32 tree's).  Tiny preset on the CPU backend, in both compute dtypes;
nothing here is a timing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from mmlspark_tpu import DataTable
from mmlspark_tpu.models import ModelBundle, TextGenerator
from mmlspark_tpu.models.definitions import build_model
from mmlspark_tpu.models.generate import DecodeEngine, resident_variables
from mmlspark_tpu.parallel.bridge import place_weights
from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
from mmlspark_tpu.quant import quantize_bundle
from mmlspark_tpu.resilience.clock import VirtualClock
from mmlspark_tpu.serve import ServeConfig, ServingEngine
from mmlspark_tpu.serve.engine import READY
from mmlspark_tpu.zoo import truncated_draft_bundle

CFG = {"vocab_size": 64, "d_model": 32, "n_heads": 4, "n_layers": 2,
       "max_len": 64}


def host_tree(tree):
    return jax.tree_util.tree_map(np.asarray, jax.device_get(tree))


def tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))


def resident(bundle):
    """The tree an engine of `bundle` places, still where the bundle's
    leaves are."""
    return resident_variables(bundle.module(), bundle.variables)


def cast_bytes(bundle) -> int:
    """Bytes of the resident leaves that left the bundle's dtype."""
    return sum(got.nbytes for got, src in zip(
        jax.tree_util.tree_leaves(resident(bundle)),
        jax.tree_util.tree_leaves(bundle.variables))
        if got.dtype != src.dtype)


def assert_on_device(placed, source) -> None:
    """Every leaf a `jax.Array` of its source's dtype and shape."""
    leaves, src = (jax.tree_util.tree_leaves(t) for t in (placed, source))
    assert len(leaves) == len(src) > 0
    for got, want in zip(leaves, src):
        assert isinstance(got, jax.Array)
        assert (got.dtype, got.shape) == (want.dtype, want.shape)


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def bundle(request):
    """A bundle as `ModelBundle.init` / `load_bundle` give it: numpy,
    float32 parameters under either compute dtype."""
    model = build_model("TransformerLM", {**CFG, "dtype": request.param})
    variables = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    return ModelBundle.from_module(model, host_tree(variables))


def make_engine(bundle, **kw):
    cfg = dict(max_new_tokens=8, max_batch=2, queue_capacity=8,
               segment_steps=4, default_deadline_s=100.0,
               drain_timeout_s=50.0, cache_chunk=16)
    extra = {k: kw.pop(k) for k in ("degraded_bundle", "draft_bundle",
                                    "mesh") if k in kw}
    cfg.update(kw)
    engine = ServingEngine(bundle, ServeConfig(**cfg), clock=VirtualClock(),
                           **extra)
    # ready without `warmup()`: a tick then compiles only what it runs
    engine._state = READY
    return engine


def drain(engine, requests, max_ticks=100):
    for _ in range(max_ticks):
        if all(r.finished for r in requests):
            return
        engine._tick()
    raise AssertionError([r.status for r in requests])


# -- the one placement function ----------------------------------------------

def test_place_weights_offmesh_is_a_plain_device_put(bundle):
    placed = place_weights(bundle.variables)
    assert_on_device(placed, bundle.variables)
    for leaf in jax.tree_util.tree_leaves(placed):
        # no mesh is built for it: DecodeEngine's `mesh is None` stands
        assert isinstance(leaf.sharding, jax.sharding.SingleDeviceSharding)
        assert leaf.devices() == {jax.devices()[0]}


@pytest.mark.parametrize("spec,replicate_only,qkv_spec", [
    (MeshSpec(data=2), False, P()),
    (MeshSpec(data=2, model=2), False, P(None, "model")),
    (MeshSpec(data=2, model=2), True, P()),
], ids=["data_only_replicates", "model_axis_shards", "draft_replicates"])
def test_place_weights_under_a_mesh(bundle, spec, replicate_only, qkv_spec):
    n = spec.data * max(spec.model, 1)
    mesh = make_mesh(spec, jax.devices()[:n])
    placed = place_weights(bundle.variables, mesh, bundle.partition_rules(),
                           replicate_only=replicate_only)
    assert_on_device(placed, bundle.variables)
    qkv = placed["params"]["block0_w"]["qkv"]["kernel"]
    assert qkv.sharding.mesh == mesh and qkv.sharding.spec == qkv_spec


def test_transfer_guard_refuses_the_host_tree_and_passes_the_placed(bundle):
    """What the placement buys: under the guard a jitted call may not
    upload an argument, and with the placed tree it has none to upload."""
    forward = jax.jit(bundle.module().apply)
    tokens = jnp.zeros((1, 8), jnp.int32)
    placed = place_weights(bundle.variables)
    want = np.asarray(forward(bundle.variables, tokens))
    with jax.transfer_guard_host_to_device("disallow"):
        got = forward(placed, tokens)
        with pytest.raises(Exception, match="[Dd]isallowed host-to-device"):
            forward(bundle.variables, tokens)
    np.testing.assert_array_equal(np.asarray(got), want)


# -- ServingEngine -------------------------------------------------------------

@pytest.mark.parametrize("lane", ["primary", "degraded", "draft"])
def test_engine_places_every_lane_once(bundle, lane):
    """Off-mesh every lane's resident tree, the int8 tree and its scales
    and the draft included, lives on the device: the bundle's dtypes but
    for the kernels a bfloat16 model holds in bfloat16.
    `weights_device_bytes` is their size and `weights_cast_bytes` the
    part in the compute dtype (none of a float32 or an int8 tree)."""
    sources = {"primary": bundle}
    kw = {}
    if lane == "degraded":
        sources[lane] = kw["degraded_bundle"] = quantize_bundle(bundle,
                                                                "int8")
    if lane == "draft":
        sources[lane] = kw["draft_bundle"] = truncated_draft_bundle(bundle)
        kw["spec_tokens"] = 2
    engine = make_engine(bundle, **kw)
    placed = dict(engine._variables)
    if lane == "draft":
        placed["draft"] = engine._draft_vars
    assert sorted(placed) == sorted(sources)
    for name, src in sources.items():
        assert_on_device(placed[name], resident(src))
    stats = engine.stats()
    assert stats["weights_device_bytes"] == sum(
        tree_bytes(resident(b)) for b in sources.values())
    assert stats["weights_cast_bytes"] == sum(
        cast_bytes(b) for b in sources.values())
    assert (cast_bytes(bundle) == 0) == (bundle.module().dtype
                                         == jnp.float32)
    if lane == "degraded":
        assert cast_bytes(sources[lane]) == 0
    assert engine._engines["primary"].mesh is None


def test_engine_hands_the_same_placed_tree_to_every_tick(bundle):
    """`serve_prefill` and the segment get the engine's one device tree,
    the same object tick after tick, and never a numpy leaf."""
    engine = make_engine(bundle)
    eng = engine._engines["primary"]
    seen = {"serve_prefill": [], "serve_step": []}
    for name in seen:
        def spy(variables, *a, _real=getattr(eng, name), _to=seen[name],
                **k):
            _to.append(variables)
            return _real(variables, *a, **k)
        setattr(eng, name, spy)
    r1 = engine.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=8)
    engine._tick()
    r2 = engine.submit(np.arange(2, 9, dtype=np.int32), max_new_tokens=8)
    engine._tick()
    drain(engine, [r1, r2])
    assert len(seen["serve_prefill"]) == 2 and len(seen["serve_step"]) >= 2
    placed = engine._variables["primary"]
    for handed in seen["serve_prefill"] + seen["serve_step"]:
        assert handed is placed
    assert all(isinstance(x, jax.Array)
               for x in jax.tree_util.tree_leaves(placed))


def test_served_tokens_equal_the_host_trees(bundle):
    """The refactor changed no result: greedy tokens served from the
    placed tree are the offline engine's from the bundle's host tree."""
    engine = make_engine(bundle)
    rng = np.random.default_rng(27)
    prompts = [rng.integers(0, 64, (n,)).astype(np.int32) for n in (5, 7)]
    reqs = [engine.submit(p, max_new_tokens=8) for p in prompts]
    drain(engine, reqs)
    offline = DecodeEngine(bundle.module(), 8, chunk=16)
    for p, r in zip(prompts, reqs):
        padded = np.zeros((1, offline.bucket_for(len(p))), np.int32)
        padded[0, :len(p)] = p
        want = offline.generate(bundle.variables, padded,
                                np.asarray([len(p)], np.int32))[0]
        assert r.status == "ok" and r.tokens == want.tolist()


def test_weights_placed_event_beside_warmup_done(bundle, tmp_path):
    from mmlspark_tpu.observe.telemetry import run_telemetry
    with run_telemetry(str(tmp_path)) as rt:
        engine = make_engine(
            bundle, degraded_bundle=quantize_bundle(bundle, "int8"))
        events = rt.summary()["serve"]
    placed = [e for e in events if e["event"] == "weights_placed"]
    assert [e["lane"] for e in placed] == ["primary", "degraded"]
    assert [e["bytes"] for e in placed] == [
        tree_bytes(engine._variables[lane])
        for lane in ("primary", "degraded")]
    assert [e["cast_bytes"] for e in placed] == [cast_bytes(bundle), 0]
    assert all(e["seconds"] >= 0 for e in placed)


def test_stopped_engine_lets_go_of_the_placed_tree(bundle):
    """The device tree dies with the engine's stop, not with the last
    holder of the stopped object (`benchmark/drivers/serve.py` builds the
    float32 reference's own copy on the chip right after)."""
    import gc
    import weakref
    engine = make_engine(bundle, spec_tokens=2,
                         draft_bundle=truncated_draft_bundle(bundle))
    leaves = [weakref.ref(x) for x in jax.tree_util.tree_leaves(
        (engine._variables, engine._draft_vars))]
    req = engine.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
    drain(engine, [req])
    engine.stop()
    assert engine.state == "stopped"
    assert engine._variables == {} and engine._draft_vars is None
    assert engine.stats()["weights_device_bytes"] == 0
    assert engine.stats()["weights_cast_bytes"] == 0
    gc.collect()
    assert [r for r in leaves if r() is not None] == []


# -- TextGenerator -------------------------------------------------------------

def test_textgenerator_places_offmesh_once_and_anew_per_bundle(bundle):
    gen = TextGenerator(bundle, inputCol="prompt", outputCol="out",
                        maxNewTokens=4, specTokens=2)
    gen.set_draft_bundle(truncated_draft_bundle(bundle))
    first, draft = gen._device_variables(), gen._draft_device_variables()
    assert_on_device(first, resident(bundle))
    assert_on_device(draft, resident(gen._draft_bundle))
    assert gen._device_variables() is first
    assert gen._draft_device_variables() is draft
    assert gen._device_vars[None] is first
    assert gen._draft_device_vars[None] is draft
    # whatever changes the weights empties the cache: a new tree follows
    assert gen.set_bundle(bundle)._device_variables() is not first
    assert gen.set_draft_bundle(
        gen._draft_bundle)._draft_device_variables() is not draft
    again = gen._device_variables()
    assert gen.set_mesh(None)._device_variables() is not again


def test_textgenerator_transform_feeds_its_engine_the_placed_tree(
        bundle, monkeypatch):
    gen = TextGenerator(bundle, inputCol="prompt", outputCol="out",
                        maxNewTokens=4)
    handed = []
    real = DecodeEngine.generate

    def spy(self, variables, *a, **k):
        handed.append(variables)
        return real(self, variables, *a, **k)
    monkeypatch.setattr(DecodeEngine, "generate", spy)
    rows = [np.arange(1, 6, dtype=np.int32), np.arange(3, 10, dtype=np.int32)]
    out = [gen.transform(DataTable({"prompt": rows}))["out"]
           for _ in range(2)]
    assert len(handed) == 2 and handed[0] is handed[1]
    assert handed[0] is gen._device_variables()
    assert_on_device(handed[0], resident(bundle))
    # and the tokens are those of the bundle's host tree
    want = [real(DecodeEngine(bundle.module(), 4), bundle.variables,
                 np.pad(r, (0, 8 - len(r)))[None],
                 np.asarray([len(r)], np.int32))[0] for r in rows]
    for got in out:
        for g, r, w in zip(got, rows, want):
            np.testing.assert_array_equal(np.asarray(g)[len(r):], w)
