"""Pipeline parallelism (parallel/pipeline.py): the GPipe schedule on the
8-virtual-device CPU mesh must match the sequential block stack exactly,
differentiate correctly, and train."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from mmlspark_tpu.parallel.mesh import MeshSpec, batch_sharding, make_mesh
from mmlspark_tpu.parallel.pipeline import (count_pipeline_bubble,
                                            init_pipelined_lm,
                                            make_pipeline_lm_step,
                                            pipeline_param_shardings,
                                            pipelined_lm_apply,
                                            sequential_lm_apply)

CFG = dict(vocab_size=32, d_model=16, n_heads=4, n_layers=4, max_len=12)


@pytest.fixture(scope="module")
def pp_mesh():
    return make_mesh(MeshSpec(data=2, model=4))  # 'model' is the stage axis


@pytest.fixture(scope="module")
def setup(pp_mesh):
    params = init_pipelined_lm(jax.random.key(0), **CFG)
    params = jax.device_put(params,
                            pipeline_param_shardings(pp_mesh, params))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 32, (8, 12)), jnp.int32)
    return params, jax.device_put(tokens, batch_sharding(pp_mesh))


@pytest.mark.parametrize("n_micro", [1, 2, 4])
def test_pipeline_matches_sequential(setup, pp_mesh, n_micro):
    """Every microbatch count must reproduce the sequential stack bit-for-
    rounding: the schedule only reorders work, never changes it."""
    params, tokens = setup
    ref = sequential_lm_apply(jax.device_get(params),
                              jax.device_get(tokens), n_heads=4)
    got = pipelined_lm_apply(pp_mesh, params, tokens, n_heads=4,
                             n_micro=n_micro)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.budget(120)  # differentiating shard_map+scan is a fixed
# ~35-85s XLA compile on the CPU mesh (load-sensitive), regardless of
# model size
@pytest.mark.slow
def test_pipeline_gradients_match_sequential(setup):
    """The autodiff-derived reverse pipeline (transposed ppermutes) must
    produce the same gradients as the sequential reference.  A 2-stage
    mesh keeps the scan-transpose compile down — the schedule math is
    stage-count-generic (forward parity covers 4)."""
    mesh2 = make_mesh(MeshSpec(data=4, model=2))
    params = init_pipelined_lm(jax.random.key(2), **{**CFG, "n_layers": 2})
    params = jax.device_put(params,
                            pipeline_param_shardings(mesh2, params))
    _, tokens = setup
    tokens = jax.device_put(jax.device_get(tokens), batch_sharding(mesh2))
    tgts = jnp.roll(tokens, -1, axis=1)

    def pp_loss(p):
        lp = jax.nn.log_softmax(pipelined_lm_apply(
            mesh2, p, tokens, n_heads=4, n_micro=2).astype(jnp.float32))
        return -jnp.take_along_axis(lp, tgts[..., None], -1).mean()

    host_params, host_tokens = jax.device_get(params), jax.device_get(tokens)
    host_tgts = np.roll(host_tokens, -1, axis=1)

    def seq_loss(p):
        lp = jax.nn.log_softmax(sequential_lm_apply(
            p, host_tokens, n_heads=4).astype(jnp.float32))
        return -jnp.take_along_axis(lp, host_tgts[..., None], -1).mean()

    g_pp = jax.grad(pp_loss)(params)
    g_seq = jax.grad(seq_loss)(host_params)
    flat_pp = jax.tree_util.tree_leaves(g_pp)
    flat_seq = jax.tree_util.tree_leaves(g_seq)
    for a, b in zip(flat_pp, flat_seq):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=1e-5)


@pytest.mark.slow
def test_pipeline_train_step_learns(pp_mesh):
    params = init_pipelined_lm(jax.random.key(1), **CFG)
    params = jax.device_put(params,
                            pipeline_param_shardings(pp_mesh, params))
    tx = optax.adam(1e-2)
    opt = tx.init(params)
    step = make_pipeline_lm_step(pp_mesh, tx, n_heads=4, n_micro=4)
    toks = jnp.asarray(np.arange(96).reshape(8, 12) % 32, jnp.int32)
    toks = jax.device_put(toks, batch_sharding(pp_mesh))
    tgts = jnp.roll(toks, -1, axis=1)
    losses = []
    for _ in range(20):
        params, opt, loss = step(params, opt, toks, tgts)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])


def test_stage_weights_actually_sharded(pp_mesh):
    params = init_pipelined_lm(jax.random.key(0), **CFG)
    params = jax.device_put(params,
                            pipeline_param_shardings(pp_mesh, params))
    leaf = jax.tree_util.tree_leaves(params["blocks"])[0]
    assert not leaf.sharding.is_fully_replicated
    assert params["head"].sharding.is_fully_replicated


def test_bubble_fraction():
    assert count_pipeline_bubble(1, 4) == pytest.approx(3 / 4)
    assert count_pipeline_bubble(16, 4) == pytest.approx(3 / 19)
    assert count_pipeline_bubble(8, 1) == 0.0


def test_multilayer_stage_matches_sequential(pp_mesh):
    """L_local > 1: eight layers over four stages, so the scan over a
    stage's STACKED local layers (two per stage) actually runs — the
    generality round-4 asserted only in a docstring."""
    params = init_pipelined_lm(jax.random.key(3), **{**CFG, "n_layers": 8})
    params = jax.device_put(params,
                            pipeline_param_shardings(pp_mesh, params))
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, 32, (8, 12)), jnp.int32)
    tokens = jax.device_put(tokens, batch_sharding(pp_mesh))
    ref = sequential_lm_apply(jax.device_get(params),
                              jax.device_get(tokens), n_heads=4)
    got = pipelined_lm_apply(pp_mesh, params, tokens, n_heads=4, n_micro=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_bf16_matches_sequential(pp_mesh):
    """PP x bf16: the schedule must be numerics-preserving in the compute
    dtype the real workloads use (params stay f32; block compute bf16)."""
    params = init_pipelined_lm(jax.random.key(4), **CFG)
    params = jax.device_put(params,
                            pipeline_param_shardings(pp_mesh, params))
    tokens = jnp.asarray(
        np.random.default_rng(4).integers(0, 32, (8, 12)), jnp.int32)
    tokens = jax.device_put(tokens, batch_sharding(pp_mesh))
    ref = sequential_lm_apply(jax.device_get(params), jax.device_get(tokens),
                              n_heads=4, dtype=jnp.bfloat16)
    got = pipelined_lm_apply(pp_mesh, params, tokens, n_heads=4, n_micro=2,
                             dtype=jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=0.05, atol=0.05)


@pytest.mark.slow
@pytest.mark.budget(180)
def test_pipeline_remat_gradients_match(setup):
    """PP x remat: rematerializing each stage layer's activations must not
    change the gradients (2-stage mesh, L_local = 2 so the checkpointed
    scan body actually repeats)."""
    mesh2 = make_mesh(MeshSpec(data=4, model=2))
    params = init_pipelined_lm(jax.random.key(5), **CFG)
    params = jax.device_put(params,
                            pipeline_param_shardings(mesh2, params))
    _, tokens = setup
    tokens = jax.device_put(jax.device_get(tokens), batch_sharding(mesh2))
    tgts = jnp.roll(tokens, -1, axis=1)

    def loss(p, remat):
        lp = jax.nn.log_softmax(pipelined_lm_apply(
            mesh2, p, tokens, n_heads=4, n_micro=2,
            remat=remat).astype(jnp.float32))
        return -jnp.take_along_axis(lp, tgts[..., None], -1).mean()

    g_plain = jax.grad(lambda p: loss(p, False))(params)
    g_remat = jax.grad(lambda p: loss(p, True))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_plain),
                    jax.tree_util.tree_leaves(g_remat)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.slow
@pytest.mark.budget(240)
def test_microbatch_sweep_tracks_bubble_model(pp_mesh):
    """The GPipe tick count (M + S - 1) is the schedule's cost model: on
    the CPU mesh, per-microbatch step time across a microbatch sweep must
    scale with ticks/M within generous tolerance (the bubble fraction
    made measurable, not just printed)."""
    import time

    s_stages = 4
    micro_counts = [1, 8]
    params = init_pipelined_lm(jax.random.key(6), **CFG)
    params = jax.device_put(params,
                            pipeline_param_shardings(pp_mesh, params))
    tokens = jnp.asarray(
        np.random.default_rng(6).integers(0, 32, (16, 12)), jnp.int32)
    tokens = jax.device_put(tokens, batch_sharding(pp_mesh))

    measured = {}
    for m in micro_counts:
        fn = jax.jit(lambda p, t, m=m: pipelined_lm_apply(
            pp_mesh, p, t, n_heads=4, n_micro=m))
        fn(params, tokens).block_until_ready()  # compile
        t0 = time.perf_counter()
        for _ in range(20):
            out = fn(params, tokens)
        out.block_until_ready()
        measured[m] = (time.perf_counter() - t0) / 20

    # total work is fixed (the same batch through the same layers), so the
    # bubble model says wall(M) scales with the compute-inflation factor
    # 1/(1 - bubble(M, S)) = (M+S-1)/M, plus per-tick dispatch overhead
    # that only EATS INTO the predicted gain.  Assert the model as an
    # envelope: more microbatches must help (amortized bubble), and the
    # gain cannot exceed what the bubble model allows.
    assert measured[1] > measured[8], measured  # the bubble is real
    inflation = lambda m: 1.0 / (1.0 - count_pipeline_bubble(m, s_stages))
    model_gain = inflation(1) / inflation(8)        # (4/1)/(11/8) ~ 2.9x
    got_gain = measured[1] / measured[8]
    assert 1.1 < got_gain < model_gain * 1.3, (measured, got_gain,
                                               model_gain)
