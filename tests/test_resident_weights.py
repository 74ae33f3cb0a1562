"""The serving programs are handed a RESIDENT weight tree
(`generate.resident_variables`): with float32 parameters and bfloat16
compute, every leaf that the programs read only through a cast to
bfloat16 is held in bfloat16, so no prefill or segment call casts it
again.  Rounding once at placement gives the bits that rounding in every
call gave: everything here is compared bit for bit.  Tiny presets on the
CPU backend; nothing here is a timing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Var

from mmlspark_tpu.models import ModelBundle
from mmlspark_tpu.models.definitions import build_model
from mmlspark_tpu.models.generate import DecodeEngine, resident_variables
from mmlspark_tpu.quant import quantize_bundle
from mmlspark_tpu.resilience.clock import VirtualClock
from mmlspark_tpu.serve import ServeConfig, ServingEngine
from mmlspark_tpu.serve.engine import READY

TLM = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, max_len=64,
           dtype="bfloat16")
HYBRID = dict(vocab_size=97, d_model=32, n_heads=4, n_kv_heads=2,
              layer_types=["conv", "full_attention"],
              n_dense_layers=1, mlp_width=48, n_experts=8,
              experts_per_token=4, expert_width=24, max_len=64,
              dtype="bfloat16")
TLM_DENSE = {"qkv", "proj", "mlp_up", "mlp_down", "lm_head"}
HYBRID_PRODUCTS = {"conv_in", "conv_out", "wq", "wk", "wv", "wo", "w1", "w2",
                   "w3", "embed"}


def _bundle(arch: str, cfg: dict) -> ModelBundle:
    """A bundle as `ModelBundle.init` / `load_bundle` give it: numpy,
    float32 parameters."""
    module = build_model(arch, cfg)
    # jitted: flax's eager init is most of a tiny model's test time
    variables = jax.jit(module.init)(jax.random.key(3),
                                     np.zeros((1, 8), np.int32))
    return ModelBundle.from_module(
        module, jax.tree_util.tree_map(np.asarray,
                                       jax.device_get(variables)))


def _changed(bundle) -> dict:
    """`{path: leaf}` of the resident leaves that left their dtype."""
    resident = resident_variables(bundle.module(), bundle.variables)
    return {jax.tree_util.keystr(path): got for (path, got), src in zip(
        jax.tree_util.tree_leaves_with_path(resident),
        jax.tree_util.tree_leaves(bundle.variables))
        if got.dtype != src.dtype}


def _leaf_names(paths) -> set:
    """The last two names of each path, where the rules live."""
    return {tuple(p.replace("']", "").split("['")[-2:]) for p in paths}


# what each case's rule has to name, checked on the tree itself
def _tlm_rule(bundle):
    named = _leaf_names(_changed(bundle))
    assert named == {(d, leaf) for d in TLM_DENSE
                     for leaf in ("kernel", "bias")}


def _hybrid_rule(bundle):
    named = {leaf for _, leaf in _leaf_names(_changed(bundle))}
    assert named == HYBRID_PRODUCTS
    resident = resident_variables(bundle.module(), bundle.variables)
    experts = resident["params"]["layer1"]
    assert experts["w1"].ndim == 3 and experts["w1"].dtype == jnp.bfloat16
    for stays in ("router", "expert_bias", "op_norm", "ffn_norm"):
        assert experts[stays].dtype == jnp.float32
    assert resident["params"]["layer0"]["conv_taps"].dtype == jnp.float32


def _float32_rule(bundle):
    assert resident_variables(bundle.module(),
                              bundle.variables) is bundle.variables


def _int8_rule(bundle):
    assert bundle.variables["params"]["lm_head"]["kernel"].dtype == jnp.int8
    assert _changed(bundle) == {}


def _moe_rule(bundle):
    named = _leaf_names(_changed(bundle))
    assert named == {(d, leaf) for d in ("qkv", "proj", "lm_head")
                     for leaf in ("kernel", "bias")}
    resident = resident_variables(bundle.module(), bundle.variables)
    for i in range(2):
        assert (resident["params"][f"block{i}_w"]["moe"]
                is bundle.variables["params"][f"block{i}_w"]["moe"])


CASES = {
    "transformer_lm": (lambda: _bundle("TransformerLM", TLM), _tlm_rule),
    "hybrid_lm": (lambda: _bundle("HybridLM", HYBRID), _hybrid_rule),
    "float32_module": (lambda: _bundle(
        "TransformerLM", dict(TLM, dtype="float32")), _float32_rule),
    "int8_bundle": (lambda: quantize_bundle(
        _bundle("TransformerLM", TLM), "int8"), _int8_rule),
    "moe_block": (lambda: _bundle("TransformerLM", dict(
        TLM, mlp_impl="moe", n_experts=4, moe_group_size=1)), _moe_rule),
}
_BUNDLES: dict = {}


def bundle_of(case: str) -> ModelBundle:
    if case not in _BUNDLES:
        _BUNDLES[case] = CASES[case][0]()
    return _BUNDLES[case]


def assert_trees_equal_bitwise(got, want) -> None:
    got, want = (jax.tree_util.tree_leaves(t) for t in (got, want))
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(
            np.asarray(g).view(np.uint8), np.asarray(w).view(np.uint8))


def program_args(bundle, rows=3, bucket=16):
    vocab = bundle.module().vocab_size
    rng = np.random.default_rng(29)
    true_len = np.asarray([5, 16, 11][:rows], np.int32)
    prompts = np.zeros((rows, bucket), np.int32)
    for r, n in enumerate(true_len):
        prompts[r, :n] = rng.integers(0, vocab, n)
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.key(0), i))(
        jnp.arange(rows))
    return prompts, true_len, np.ones(rows, bool), keys


def random_state(eng: DecodeEngine, rows: int, bucket: int):
    """A resident state of the engine's shapes with every element drawn:
    what a segment starts from, whatever prefill wrote it."""
    state = eng.empty_state(rows, bucket)
    leaves, treedef = jax.tree_util.tree_flatten(state)
    keys = jax.random.split(jax.random.key(5), len(leaves))
    return treedef.unflatten([
        jax.random.normal(k, leaf.shape, jnp.float32).astype(leaf.dtype)
        for k, leaf in zip(keys, leaves)])


def run_program(eng: DecodeEngine, variables, program: str, args):
    """One prefill, or one serve segment of 4 steps from a drawn state:
    everything the program returns, device counts included."""
    prompts, true_len, live, keys = args
    rows, bucket = prompts.shape
    if program == "prefill":
        out = eng.serve_prefill(variables, prompts, true_len, live, keys)
    else:
        out = eng.serve_step(
            variables, random_state(eng, rows, bucket),
            jnp.asarray(prompts[:, 0]), jnp.zeros(rows, bool), true_len,
            np.full(rows, 8, np.int32), bucket, np.zeros(rows, np.int32),
            keys, 4, eng.serve_window(bucket, 4, 4))
    return out, list(eng.counts_out)


# -- bit-exact parity ---------------------------------------------------------

@pytest.mark.parametrize("program", ["prefill", "serve_segment"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_a_program_matches_the_float32_tree_bit_for_bit(case, program):
    """Tokens, `done` flags, caches and device counts of a prefill and of
    a serve segment, from the resident tree and from the bundle's own."""
    bundle = bundle_of(case)
    CASES[case][1](bundle)
    eng = DecodeEngine(bundle.module(), 8, chunk=16)
    resident = eng.resident_variables(bundle.variables)
    args = program_args(bundle)
    got = run_program(eng, resident, program, args)
    want = run_program(eng, bundle.variables, program, args)
    assert_trees_equal_bitwise(got, want)
    if program == "serve_segment":
        toks = np.asarray(got[0][1])
        assert toks.shape == (3, 4) and len(np.unique(toks)) > 1


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_cast_leaf_is_the_float32_leaf_rounded_once(case):
    bundle = bundle_of(case)
    source = {jax.tree_util.keystr(p): leaf for p, leaf in
              jax.tree_util.tree_leaves_with_path(bundle.variables)}
    for path, got in _changed(bundle).items():
        assert isinstance(got, jax.Array)      # cast on the device
        np.testing.assert_array_equal(
            np.asarray(got), source[path].astype(jnp.bfloat16))


def make_engine(bundle):
    engine = ServingEngine(bundle, ServeConfig(
        max_new_tokens=8, max_batch=2, queue_capacity=8, segment_steps=4,
        default_deadline_s=100.0, drain_timeout_s=50.0, cache_chunk=16),
        clock=VirtualClock())
    engine._state = READY       # a tick then compiles only what it runs
    return engine


def serve(engine, prompts) -> list:
    reqs = [engine.submit(p, max_new_tokens=8) for p in prompts]
    for _ in range(100):
        if all(r.finished for r in reqs):
            break
        engine._tick()
    assert [r.status for r in reqs] == ["ok"] * len(reqs)
    return [r.tokens for r in reqs]


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_served_request_gets_the_float32_trees_tokens(case, monkeypatch):
    """One `ServingEngine` end to end, late join included, against an
    engine that is handed the bundle's float32 tree as before."""
    bundle = bundle_of(case)
    rng = np.random.default_rng(31)
    vocab = bundle.module().vocab_size
    prompts = [rng.integers(0, vocab, n).astype(np.int32) for n in (5, 9)]
    engine = make_engine(bundle)
    got = serve(engine, prompts)
    with monkeypatch.context() as m:
        m.setattr(DecodeEngine, "resident_variables",
                  lambda self, variables, draft=False: variables)
        plain = make_engine(bundle)
    assert plain.stats()["weights_cast_bytes"] == 0
    assert serve(plain, prompts) == got
    cast = sum(leaf.nbytes for leaf in _changed(bundle).values())
    assert engine.stats()["weights_cast_bytes"] == cast
    assert (cast > 0) == (case in ("transformer_lm", "hybrid_lm",
                                   "moe_block"))


# -- the guard: no program casts a float32 weight to the compute dtype -------

# primitives that only move a weight's elements about: a cast behind one
# of them is still a cast of the weight
_LAYOUT = {"transpose", "reshape", "squeeze", "slice", "dynamic_slice",
           "broadcast_in_dim", "copy"}


def _sub_jaxprs(eqn):
    """`(jaxpr, operands)` of every jaxpr an equation runs, `operands`
    the equation's inputs in the order of the inner jaxpr's."""
    name, p = eqn.primitive.name, eqn.params
    if name == "while":
        nc, nb = p["cond_nconsts"], p["body_nconsts"]
        yield p["body_jaxpr"].jaxpr, eqn.invars[nc:]
        yield p["cond_jaxpr"].jaxpr, eqn.invars[:nc] + eqn.invars[nc + nb:]
        return
    if name == "cond":
        for branch in p["branches"]:
            yield branch.jaxpr, eqn.invars[1:]
        return
    for value in p.values():
        inner = getattr(value, "jaxpr", value)
        if hasattr(inner, "eqns") and hasattr(inner, "invars"):
            same = len(inner.invars) == len(eqn.invars)
            yield inner, eqn.invars if same else [None] * len(inner.invars)


def weight_casts(jaxpr, source: dict, dtype, found: set) -> set:
    """Walk `jaxpr` (a map var -> weight path in `source` for the vars
    that are a weight leaf, moved about or not) and collect the paths of
    float32 weights of rank >= 2 that a `convert_element_type` takes to
    `dtype`."""
    source = dict(source)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        first = eqn.invars[0] if eqn.invars else None
        path = source.get(first) if isinstance(first, Var) else None
        if path is not None and name in _LAYOUT:
            source[eqn.outvars[0]] = path
        if (path is not None and name == "convert_element_type"
                and first.aval.ndim >= 2
                and first.aval.dtype == jnp.float32
                and eqn.params["new_dtype"] == dtype):
            found.add(path)
        for inner, operands in _sub_jaxprs(eqn):
            weight_casts(inner, {
                v: source[o] for v, o in zip(inner.invars, operands)
                if isinstance(o, Var) and o in source}, dtype, found)
    return found


def traced_weight_casts(eng, variables, program: str, args) -> set:
    prompts, true_len, live, keys = args
    rows, bucket = prompts.shape
    if program == "prefill":
        closed = jax.make_jaxpr(eng._prefill)(
            variables, prompts, true_len, live, keys)
    else:
        window = eng.serve_window(bucket, 4, 4)
        zeros = jnp.zeros(rows, jnp.int32)
        closed = jax.make_jaxpr(eng._serve_segment, static_argnums=(0, 1))(
            4, window, variables, eng.empty_state(rows, bucket), zeros,
            jnp.zeros(rows, bool), true_len, zeros + 8,
            jnp.asarray(bucket, jnp.int32), zeros, keys)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(variables)]
    # the variables are the first traced argument: its leaves lead
    source = dict(zip(closed.jaxpr.invars, paths))
    return weight_casts(closed.jaxpr, source,
                        jnp.dtype(eng.module.dtype), set())


@pytest.mark.parametrize("program", ["prefill", "serve_segment"])
@pytest.mark.parametrize("case", ["transformer_lm", "hybrid_lm"])
def test_no_program_casts_a_resident_weight(case, program):
    """A kernel that a new code path reads through `.astype(dtype)`
    without its rule naming it shows up here, on a CPU: traced on the
    resident tree, neither program converts a float32 weight of rank 2
    or more to the compute dtype.  (`TransformerLM`'s two embeddings
    stay float32 by the rule: their sum is cast, not they.)  Traced on
    the bundle's float32 tree, the same walk finds every kernel the rule
    names: it sees what it is there to see."""
    bundle = bundle_of(case)
    eng = DecodeEngine(bundle.module(), 8, chunk=16)
    args = program_args(bundle)
    resident = eng.resident_variables(bundle.variables)
    assert traced_weight_casts(eng, resident, program, args) == set()
    named = {p for p, leaf in _changed(bundle).items() if leaf.ndim >= 2}
    assert traced_weight_casts(eng, bundle.variables, program,
                               args) == named
