"""Population training (train/sweep.py): the vmapped hyperparameter sweep.

Pins the contracts the auto-ML surface rides on: a vmapped member's
update arithmetic matches a plain Trainer fit from the same init to
float32 rounding; member curves are independent of the population size (fold_in init
keys); the halving mask freezes culled members exactly; the winner
unstacks into an ordinary bundle that round-trips through
save_bundle/TPUModel; and a mid-sweep population checkpoint resumes to
the uninterrupted run's final state.
"""

import jax
import numpy as np
import pytest

from mmlspark_tpu.core.table import DataTable
from mmlspark_tpu.parallel.bridge import stack_trees, unstack_member
from mmlspark_tpu.train import (PopulationTrainer, Trainer, TrainerConfig)


def _cfg(**kw):
    base = dict(architecture="MLPClassifier",
                model_config={"hidden_sizes": [16], "num_classes": 3,
                              "dtype": "float32"},
                optimizer="adam", learning_rate=0.01, epochs=3,
                batch_size=32, loss="softmax_xent", seed=7,
                shuffle_each_epoch=True)
    base.update(kw)
    return TrainerConfig(**base)


def _data(n=96, d=8, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(d, classes)).astype(np.float32)
    y = np.argmax(x @ w, axis=1).astype(np.int32)
    return x, y


def _tree_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(u), np.asarray(v))
               for u, v in zip(la, lb))


def test_population_n1_matches_plain_trainer():
    """One vmapped member IS a plain Trainer fit: warm-starting the
    sequential trainer from the member's fold_in init, every parameter
    agrees to float32 rounding after the full run (same data order, same
    optax chain, the learning rate merely riding in as a vmapped scalar).
    Not byte equality: the vmapped and the plain step are two XLA
    programs, and jaxlib 0.9's CPU backend orders their float ops
    differently (measured: <= 6e-8 absolute after 3 Adam epochs)."""
    cfg = _cfg()
    x, y = _data()
    pt = PopulationTrainer(cfg, 1)
    init = pt.member_init_bundle(0, (1, x.shape[1]))
    result = pt.fit_arrays(x, y)
    pop_params = unstack_member(result.state.params, 0)

    seq = Trainer(cfg)
    bundle = seq.fit_arrays(x, y, initial_bundle=init)
    got = jax.tree_util.tree_leaves(pop_params)
    want = jax.tree_util.tree_leaves(bundle.variables["params"])
    assert len(got) == len(want)
    for u, v in zip(got, want):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v),
                                   rtol=0, atol=1e-6)


def test_member_curve_independent_of_population_size():
    """fold_in(key(seed), k) init keys: member k's loss curve does not
    move when the population grows — candidates never contaminate each
    other through shared RNG or stacked arithmetic."""
    cfg = _cfg(epochs=2)
    x, y = _data()
    members = [{"learning_rate": r} for r in (0.02, 0.005, 0.001, 0.0003)]
    small = PopulationTrainer(cfg, members[:2]).fit_arrays(x, y)
    large = PopulationTrainer(cfg, members).fit_arrays(x, y)
    np.testing.assert_allclose(small.member_loss,
                               large.member_loss[:, :2], rtol=0, atol=1e-6)


def test_halving_mask_freezes_culled_members_exactly():
    """After a rung culls a member, its params never move again: the
    masked update freezes the byte pattern, not approximately."""
    cfg = _cfg(epochs=4)
    x, y = _data()
    # rates chosen so the trailing members lose decisively
    pt = PopulationTrainer(cfg, [{"learning_rate": r}
                                 for r in (0.02, 0.01, 1e-5, 1e-6)],
                           halving_rungs=1, cull_fraction=0.5)
    steps_per_epoch = (len(x) + cfg.batch_size - 1) // cfg.batch_size
    total = steps_per_epoch * cfg.epochs
    rung = total // 2

    # reference: the same population with NO halving, truncated at the rung
    ref_cfg = _cfg(epochs=2)   # epochs*steps/epoch == rung steps
    assert ((len(x) + ref_cfg.batch_size - 1)
            // ref_cfg.batch_size) * ref_cfg.epochs == rung
    ref_pt = PopulationTrainer(ref_cfg, [{"learning_rate": r}
                                         for r in (0.02, 0.01, 1e-5, 1e-6)])
    at_rung = ref_pt.fit_arrays(x, y)

    result = pt.fit_arrays(x, y)
    culled = [k for k in range(4) if result.active[k] == 0.0]
    assert len(culled) == 2
    for k in culled:
        frozen = unstack_member(result.state.params, k)
        at_cull = unstack_member(at_rung.state.params, k)
        assert _tree_equal(frozen, at_cull), \
            f"culled member {k} moved after the rung"
    # survivors DID keep training
    for k in range(4):
        if k in culled:
            continue
        live = unstack_member(result.state.params, k)
        at_cull = unstack_member(at_rung.state.params, k)
        assert not _tree_equal(live, at_cull)


def test_winner_unstacks_and_roundtrips_through_bundle(tmp_path):
    """The winner's unstacked bundle is an ordinary ModelBundle:
    save_bundle/load_bundle round-trips it and TPUModel scores it
    identically to the stacked forward."""
    from mmlspark_tpu.models.bundle import load_bundle, save_bundle
    from mmlspark_tpu.models.tpu_model import TPUModel
    cfg = _cfg()
    x, y = _data()
    pt = PopulationTrainer(cfg, [{"learning_rate": r}
                                 for r in (0.02, 0.005)])
    result = pt.fit_arrays(x, y)
    k = result.best_member
    bundle = result.winner_bundle()
    assert bundle.metadata["sweep"]["member"] == k
    assert bundle.metadata["sweep"]["population"] == 2

    stacked_logits = pt.score_population(result.state, x)[k]

    path = str(tmp_path / "winner")
    save_bundle(bundle, path)
    loaded = load_bundle(path)
    model = TPUModel(loaded, inputCol="feats", outputCol="out",
                     miniBatchSize=32)
    out = model.transform(DataTable({"feats": x}))
    np.testing.assert_allclose(np.stack(list(out["out"])), stacked_logits,
                               rtol=0, atol=1e-5)


def test_mid_sweep_checkpoint_resume_matches_uninterrupted(tmp_path):
    """A population checkpointed mid-sweep and resumed in a fresh trainer
    finishes byte-identical to the uninterrupted run (same data-order
    replay, stacked trees + lr + active restored in one file)."""
    x, y = _data()
    ckpt = str(tmp_path / "ckpt")
    members = [{"learning_rate": r} for r in (0.02, 0.005, 0.001)]

    cfg = _cfg(epochs=4, checkpoint_every_steps=5, async_checkpointing=False)
    full = PopulationTrainer(cfg, members).fit_arrays(x, y)

    # interrupted: train only the first 2 epochs' worth via a copy that
    # stops early (simulating preemption after the step-5 checkpoint)
    cfg_half = _cfg(epochs=2, checkpoint_every_steps=5,
                    async_checkpointing=False)
    PopulationTrainer(cfg_half, members).fit_arrays(x, y, ckpt_dir=ckpt)

    resumed_trainer = PopulationTrainer(cfg, members)
    resumed = resumed_trainer.fit_arrays(x, y, ckpt_dir=ckpt, resume=True)
    assert int(resumed.state.step) == int(full.state.step)
    assert _tree_equal(resumed.state.params, full.state.params)
    assert _tree_equal(resumed.state.opt_state, full.state.opt_state)


def test_sweep_timeline_lands_in_run_summary():
    """Telemetry: the sweep emits start/cull/member_final/winner events
    into run_summary.json's `sweep` timeline and per-member loss attrs
    onto train.step spans — the history store's per-member baselines."""
    from mmlspark_tpu.observe.telemetry import run_telemetry
    cfg = _cfg(epochs=2)
    x, y = _data()
    with run_telemetry(None) as rt:
        PopulationTrainer(cfg, 3, halving_rungs=1).fit_arrays(x, y)
    summary = rt.summary()
    events = summary["sweep"]
    kinds = [e["event"] for e in events]
    assert kinds[0] == "start"
    assert kinds.count("member_final") == 3
    assert "winner" in kinds
    assert "cull" in kinds
    start = events[0]
    assert start["population"] == 3 and len(start["lrs"]) == 3
    assert summary["spans"].get("train.step", {}).get("count", 0) > 0
    steps = [r for r in rt.tracer.records()
             if r.get("name") == "train.step" and "attrs" in r]
    assert steps and len(steps[0]["attrs"]["member_loss"]) == 3


def test_resnet_population_with_batch_stats():
    """BatchNorm models sweep too: stacked batch_stats advance for active
    members and the winner's unstacked bundle carries them."""
    cfg = TrainerConfig(architecture="ResNet",
                        model_config={"stage_sizes": [1], "widths": [4],
                                      "num_classes": 10,
                                      "block_kind": "basic",
                                      "dtype": "float32"},
                        optimizer="momentum", learning_rate=0.01,
                        epochs=1, batch_size=16, loss="softmax_xent", seed=3)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 8, 8, 3)).astype(np.float32)
    y = rng.integers(0, 10, size=32).astype(np.int32)
    pt = PopulationTrainer(cfg, [{"learning_rate": 0.02},
                                 {"learning_rate": 0.005}])
    result = pt.fit_arrays(x, y)
    bundle = result.winner_bundle()
    assert "batch_stats" in bundle.variables
    # running stats moved off their init for the winner
    init = pt.member_init_variables(result.best_member, (1, 8, 8, 3))
    moved = not _tree_equal(bundle.variables["batch_stats"],
                            init["batch_stats"])
    assert moved
    logits = pt.score_population(result.state, x[:8])
    assert logits.shape == (2, 8, 10)


def test_classification_report_batch_matches_serial():
    """The batched multi-model evaluator agrees with per-model
    classification_report exactly (shared confusion arithmetic)."""
    from mmlspark_tpu.ml.statistics import (classification_report,
                                            classification_report_batch)
    rng = np.random.default_rng(1)
    y = rng.integers(0, 3, size=200)
    preds = rng.integers(0, 3, size=(4, 200))
    batch = classification_report_batch(y, preds)
    for i in range(4):
        serial = classification_report(y, preds[i]).metrics
        # classification_report filters to accuracy; compare on it
        assert float(batch["accuracy"][i]) == \
            pytest.approx(float(serial["accuracy"][0]), abs=0)
    # binary stack carries precision/recall + optional AUC columns
    yb = rng.integers(0, 2, size=100)
    pb = rng.integers(0, 2, size=(2, 100))
    probs = rng.random(size=(2, 100))
    rep = classification_report_batch(yb, pb, probs_stack=probs)
    for c in ("accuracy", "precision", "recall", "AUC"):
        assert c in rep.columns


def test_train_classifier_population_sweep_picks_winner():
    """TrainClassifier(populationSize=N) trains the whole candidate grid
    in one program and exposes per-member metrics on the model."""
    from mmlspark_tpu.ml.learners import MultilayerPerceptronClassifier
    from mmlspark_tpu.ml.train_classifier import TrainClassifier
    rng = np.random.default_rng(3)
    n = 120
    x0 = rng.normal(size=(n,))
    x1 = rng.normal(size=(n,))
    y = (x0 + 0.5 * x1 > 0).astype(np.int64)
    t = DataTable({"f0": x0, "f1": x1, "label": y})
    mlp = MultilayerPerceptronClassifier(layers=[-1, 16, -1], maxIter=8,
                                         stepSize=0.01, seed=1)
    model = TrainClassifier(mlp, populationSize=4).fit(t)
    sm = model.sweep_metrics
    assert sm is not None and sm.num_rows == 4
    assert {"model_name", "accuracy", "learning_rate",
            "final_loss", "active"} <= set(sm.columns)
    # the kept model is the best-accuracy member
    scored = model.transform(t)
    from mmlspark_tpu.ml.statistics import ComputeModelStatistics
    acc = float(ComputeModelStatistics().evaluate(scored)
                .metrics["accuracy"][0])
    assert acc == pytest.approx(max(float(a) for a in sm["accuracy"]),
                                abs=1e-9)


def test_stack_unstack_roundtrip():
    trees = [{"w": np.full((2, 3), i, np.float32), "b": np.ones(3) * i}
             for i in range(4)]
    stacked = stack_trees(trees)
    assert stacked["w"].shape == (4, 2, 3)
    for i in range(4):
        got = unstack_member(stacked, i)
        assert np.array_equal(got["w"], trees[i]["w"])
        assert np.array_equal(got["b"], trees[i]["b"])
