"""EP and PP as PRODUCT surface: parallel training through Trainer /
TrainerConfig (the reference's one-flag parallel training,
CommandBuilders.scala:79-93), not hand-rolled optax loops.

Round-trip contract on the CPU mesh, for both families:
fit -> checkpoint -> restore -> bundle -> TPUModel scoring.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu import DataTable
from mmlspark_tpu.models import TPUModel
from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
from mmlspark_tpu.train import Trainer, TrainerConfig

RNG = np.random.default_rng(7)
TOKS = RNG.integers(0, 32, (16, 12)).astype(np.int32)
TGTS = np.roll(TOKS, -1, axis=1).astype(np.int32)


# ---------------------------------------------------------------------------
# Pipeline parallelism through Trainer
# ---------------------------------------------------------------------------

PP_MODEL = {"vocab_size": 32, "d_model": 16, "n_heads": 4, "n_layers": 2,
            "max_len": 12, "dtype": "float32"}


@pytest.fixture(scope="module")
def pp_trainer_run(tmp_path_factory):
    """One fitted pipeline run shared by the PP assertions (the
    shard_map+scan autodiff compile is the expensive part)."""
    ckpt = str(tmp_path_factory.mktemp("pp_ckpt"))
    mesh = make_mesh(MeshSpec(data=4, model=2))
    cfg = TrainerConfig(
        architecture="TransformerLM", model_config=dict(PP_MODEL),
        optimizer="adam", learning_rate=1e-2, epochs=2, batch_size=8,
        loss="softmax_xent", seed=0, shuffle_each_epoch=False,
        pipeline_stages=2, pipeline_microbatches=2, checkpoint_dir=ckpt)
    trainer = Trainer(cfg, mesh=mesh)
    bundle = trainer.fit_arrays(TOKS, TGTS)
    return trainer, bundle, ckpt, mesh


@pytest.mark.budget(180)
def test_pp_fit_produces_loadable_transformer_bundle(pp_trainer_run):
    trainer, bundle, _, _ = pp_trainer_run
    assert bundle.architecture == "TransformerLM"
    assert bundle.metadata["steps"] == 4  # 2 epochs x 2 steps
    assert trainer.history[-1]["loss"] < trainer.history[0]["loss"]
    # the bundle is an ORDINARY TransformerLM: its stacked pipeline weights
    # unstacked into block{i}_w, so plain module.apply works
    logits = bundle.module().apply(bundle.variables, jnp.asarray(TOKS[:4]))
    assert logits.shape == (4, 12, 32)


def test_pp_bundle_matches_pipeline_forward(pp_trainer_run):
    """Converter parity: the sequential TransformerLM forward of the
    emitted bundle equals the pipelined forward of the live state."""
    from mmlspark_tpu.parallel.pipeline import pipelined_lm_apply

    trainer, bundle, _, mesh = pp_trainer_run
    state_params = jax.device_get(trainer._last_state.params)
    toks = jnp.asarray(TOKS[:8])
    seq = bundle.module().apply(bundle.variables, toks)
    pp = pipelined_lm_apply(mesh, state_params, toks, n_heads=4, n_micro=2)
    np.testing.assert_allclose(np.asarray(seq), np.asarray(pp),
                               rtol=2e-4, atol=2e-4)


def test_pp_stage_weights_sharded_in_state(pp_trainer_run):
    trainer, _, _, _ = pp_trainer_run
    leaf = jax.tree_util.tree_leaves(trainer._last_state.params["blocks"])[0]
    assert not leaf.sharding.is_fully_replicated
    assert trainer._last_state.params["head"].sharding.is_fully_replicated


def test_pp_checkpoint_restore_roundtrip(pp_trainer_run):
    from mmlspark_tpu.resilience.checkpoints import latest_valid_checkpoint

    trainer, _, ckpt, _ = pp_trainer_run
    assert latest_valid_checkpoint(ckpt) is not None
    state = trainer._last_state
    restored = trainer.restore_checkpoint(state, ckpt)
    assert int(restored.step) == int(state.step)
    for a, b in zip(jax.tree_util.tree_leaves(state.params),
                    jax.tree_util.tree_leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pp_bundle_scores_through_tpumodel(pp_trainer_run):
    _, bundle, _, mesh = pp_trainer_run
    scorer = TPUModel(bundle, inputCol="tokens", outputCol="scores",
                      miniBatchSize=8).set_mesh(mesh)
    scored = scorer.transform(DataTable({"tokens": TOKS[:10]}))
    assert scored["scores"].shape == (10, 12, 32)
    assert np.isfinite(scored["scores"]).all()


def test_pp_warm_start_from_bundle(pp_trainer_run):
    """Fine-tuning a pipeline run from its own bundle resumes the step
    count and converts the flax variables back into the stacked tree."""
    trainer, bundle, _, mesh = pp_trainer_run
    cfg = TrainerConfig(
        architecture="TransformerLM", model_config=dict(PP_MODEL),
        optimizer="adam", learning_rate=1e-3, epochs=1, batch_size=8,
        loss="softmax_xent", pipeline_stages=2, pipeline_microbatches=2)
    t2 = Trainer(cfg, mesh=mesh)
    bundle2 = t2.fit_arrays(TOKS, TGTS, initial_bundle=bundle)
    assert bundle2.metadata["steps"] == bundle.metadata["steps"] + 2


def test_pp_config_validation():
    mesh = make_mesh(MeshSpec(data=4, model=2))
    with pytest.raises(ValueError, match="TransformerLM"):
        Trainer(TrainerConfig(architecture="MLPClassifier",
                              pipeline_stages=2), mesh=mesh)
    with pytest.raises(ValueError, match="axis size"):
        Trainer(TrainerConfig(architecture="TransformerLM",
                              model_config=dict(PP_MODEL),
                              pipeline_stages=4), mesh=mesh)
    with pytest.raises(ValueError, match="divide"):
        Trainer(TrainerConfig(architecture="TransformerLM",
                              model_config=dict(PP_MODEL, n_layers=3),
                              pipeline_stages=2), mesh=mesh)
    with pytest.raises(ValueError, match="dense"):
        Trainer(TrainerConfig(architecture="TransformerLM",
                              model_config=dict(PP_MODEL, mlp_impl="moe"),
                              pipeline_stages=2), mesh=mesh)


# ---------------------------------------------------------------------------
# Expert parallelism through Trainer
# ---------------------------------------------------------------------------

EP_MODEL = {"vocab_size": 32, "d_model": 32, "n_heads": 4, "n_layers": 1,
            "max_len": 12, "dtype": "float32", "mlp_impl": "moe",
            "n_experts": 8, "expert_axis": "model"}


@pytest.fixture(scope="module")
def ep_trainer_run(tmp_path_factory):
    ckpt = str(tmp_path_factory.mktemp("ep_ckpt"))
    mesh = make_mesh(MeshSpec(data=2, model=4))
    cfg = TrainerConfig(
        architecture="TransformerLM", model_config=dict(EP_MODEL),
        optimizer="adam", learning_rate=1e-2, epochs=2, batch_size=8,
        loss="softmax_xent", seed=0, shuffle_each_epoch=False,
        aux_loss_weight=0.01, checkpoint_dir=ckpt)
    trainer = Trainer(cfg, mesh=mesh)
    bundle = trainer.fit_arrays(TOKS, TGTS)
    return trainer, bundle, ckpt, mesh


@pytest.mark.budget(120)
def test_ep_trainer_shards_expert_weights(ep_trainer_run):
    """The trainer's OWN sharding rule must place the (E, D, H) expert
    stacks across the 'model' axis — a MoE model trained through Trainer
    gets expert parallelism, not silent replication (round-4 weak #2)."""
    trainer, _, _, mesh = ep_trainer_run
    w_in = trainer._last_state.params["block0_w"]["moe"]["w_in"]
    assert w_in.shape == (8, 32, 128)
    assert not w_in.sharding.is_fully_replicated
    # the rule itself: expert stacks shard their LEADING (expert) dim; the
    # router is not an expert stack (assert at init, before jit may pick
    # its own output shardings for unconstrained leaves)
    state0 = trainer.init_state((1, 12), input_dtype=np.int32)
    w_in0 = state0.params["block0_w"]["moe"]["w_in"]
    assert w_in0.sharding.spec[0] == "model"
    router0 = state0.params["block0_w"]["moe"]["router"]["kernel"]
    assert router0.sharding.is_fully_replicated


def test_ep_overflow_metric_in_history(ep_trainer_run):
    """The sown moe_overflow_fraction flows into training history and the
    MetricData table, so capacity drops are observable."""
    trainer, _, _, _ = ep_trainer_run
    assert "moe_overflow_fraction" in trainer.history[-1]
    frac = trainer.history[-1]["moe_overflow_fraction"]
    assert 0.0 <= frac <= 1.0
    md = trainer.training_metric_data()
    assert "moe_overflow_fraction" in md.data


def test_ep_fit_checkpoint_restore_score_roundtrip(ep_trainer_run):
    trainer, bundle, ckpt, mesh = ep_trainer_run
    assert trainer.history[-1]["loss"] < trainer.history[0]["loss"]
    restored = trainer.restore_checkpoint(trainer._last_state, ckpt)
    assert int(restored.step) == int(trainer._last_state.step)
    scorer = TPUModel(bundle, inputCol="tokens", outputCol="scores",
                      miniBatchSize=8).set_mesh(mesh)
    scored = scorer.transform(DataTable({"tokens": TOKS[:6]}))
    assert scored["scores"].shape == (6, 12, 32)
    assert np.isfinite(scored["scores"]).all()


def test_ep_indivisible_expert_count_falls_back():
    """n_experts not a multiple of the 'model' axis must fall back (to
    replication / TP), never crash device_put at init (review finding)."""
    mesh = make_mesh(MeshSpec(data=2, model=4))
    cfg = TrainerConfig(
        architecture="TransformerLM",
        model_config=dict(EP_MODEL, n_experts=6),
        epochs=1, batch_size=8)
    state = Trainer(cfg, mesh=mesh).init_state((1, 12), input_dtype=np.int32)
    w_in = state.params["block0_w"]["moe"]["w_in"]
    assert w_in.shape[0] == 6 and w_in.sharding.spec[0] is None


def test_ep_disabled_replicates():
    mesh = make_mesh(MeshSpec(data=2, model=4))
    cfg = TrainerConfig(
        architecture="TransformerLM", model_config=dict(EP_MODEL),
        epochs=1, batch_size=8, expert_parallel=False)
    trainer = Trainer(cfg, mesh=mesh)
    state = trainer.init_state((1, 12), input_dtype=np.int32)
    w_in = state.params["block0_w"]["moe"]["w_in"]
    # no EXPERT sharding (the TP rule may still split the trailing dim)
    assert w_in.sharding.spec[0] is None
