"""JAX learners with SparkML-shaped contracts.

The reference trains through Spark MLlib estimators (LogisticRegression,
MultilayerPerceptronClassifier, NaiveBayes, linear/tree regressors —
dispatched in TrainClassifier.scala:74-129).  Here each learner is a
jit-compiled array program: full-batch L-BFGS for the convex models (one
XLA while_loop, matmul-dominated — MXU-friendly), the flax/optax Trainer
for the MLP, and closed-form solves for linear regression.

Output-column contract matches SparkML so TrainClassifier/Regressor can
rename+tag uniformly: `rawPrediction` (margins/logits), `probability`,
`prediction` for classifiers; `prediction` for regressors.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from mmlspark_tpu.core.params import Param, ParamError
from mmlspark_tpu.core.pipeline import Estimator, Transformer, load_stage
from mmlspark_tpu.core.table import DataTable


def _features_matrix(col: np.ndarray) -> np.ndarray:
    if col.dtype == object:
        return (np.stack([np.asarray(v, np.float32).ravel() for v in col])
                if len(col) else np.zeros((0, 1), np.float32))
    arr = col.astype(np.float32)
    return arr[:, None] if arr.ndim == 1 else arr.reshape(len(arr), -1)


# --------------------------------------------------------------------------
# L-BFGS driver (the standard optax while_loop pattern), jitted once per
# objective shape.
# --------------------------------------------------------------------------

def run_lbfgs(loss_fn, init_params, max_iter: int, tol: float):
    opt = optax.lbfgs()
    value_and_grad = optax.value_and_grad_from_state(loss_fn)

    def step(carry):
        params, state = carry
        value, grad = value_and_grad(params, state=state)
        updates, state = opt.update(grad, state, params, value=value,
                                    grad=grad, value_fn=loss_fn)
        params = optax.apply_updates(params, updates)
        return params, state

    def cont(carry):
        _, state = carry
        count = optax.tree_utils.tree_get(state, "count")
        grad = optax.tree_utils.tree_get(state, "grad")
        err = optax.tree_utils.tree_norm(grad)
        return (count == 0) | ((count < max_iter) & (err >= tol))

    final_params, _ = jax.lax.while_loop(cont, step,
                                         (init_params, opt.init(init_params)))
    return final_params


@jax.jit
def _sigmoid(z):
    return jax.nn.sigmoid(z)


# --------------------------------------------------------------------------
# Classifier model base: transform() contract
# --------------------------------------------------------------------------

class ClassifierModel(Transformer):
    """Adds rawPrediction / probability / prediction columns."""

    featuresCol = Param("features", "features column", ptype=str)
    rawPredictionCol = Param("rawPrediction", "margins output", ptype=str)
    probabilityCol = Param("probability", "probability output", ptype=str)
    predictionCol = Param("prediction", "label-index output", ptype=str)

    @property
    def num_classes(self) -> int:
        raise NotImplementedError

    def _score(self, X: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(raw, probability, prediction) for a dense feature matrix."""
        raise NotImplementedError

    def transform(self, table: DataTable) -> DataTable:
        X = _features_matrix(table[self.featuresCol])
        raw, prob, pred = self._score(X)
        out = table.with_column(self.rawPredictionCol, np.asarray(raw))
        out = out.with_column(self.probabilityCol, np.asarray(prob))
        return out.with_column(self.predictionCol,
                               np.asarray(pred, np.float64))


class RegressorModel(Transformer):
    featuresCol = Param("features", "features column", ptype=str)
    predictionCol = Param("prediction", "prediction output", ptype=str)

    def _predict(self, X: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def transform(self, table: DataTable) -> DataTable:
        X = _features_matrix(table[self.featuresCol])
        return table.with_column(self.predictionCol,
                                 np.asarray(self._predict(X), np.float64))


# --------------------------------------------------------------------------
# Logistic regression (binary) — IRLS-class convergence via L-BFGS
# --------------------------------------------------------------------------

class LogisticRegressionModel(ClassifierModel):
    def __init__(self, w: Optional[np.ndarray] = None, b: float = 0.0, **kw):
        super().__init__(**kw)
        self.w = np.asarray(w, np.float32) if w is not None else None
        self.b = float(b)

    @property
    def num_classes(self) -> int:
        return 2

    def _score(self, X):
        z = X @ self.w + self.b
        p = np.asarray(_sigmoid(jnp.asarray(z)))
        raw = np.stack([-z, z], axis=1)
        prob = np.stack([1.0 - p, p], axis=1)
        return raw, prob, (p > 0.5).astype(np.float64)

    def _save_extra(self, path):
        np.savez(os.path.join(path, "coef.npz"), w=self.w, b=self.b)

    def _load_extra(self, path):
        d = np.load(os.path.join(path, "coef.npz"))
        self.w, self.b = d["w"], float(d["b"])


class LogisticRegression(Estimator):
    """Binary logistic regression (Spark's LogisticRegression counterpart;
    multiclass goes through OneVsRest as in TrainClassifier.scala:87-95)."""

    featuresCol = Param("features", "features column", ptype=str)
    labelCol = Param("label", "label column (0/1)", ptype=str)
    regParam = Param(0.0, "L2 regularization strength", ptype=float)
    maxIter = Param(100, "max L-BFGS iterations", ptype=int)
    tol = Param(1e-6, "gradient-norm convergence tolerance", ptype=float)
    fitIntercept = Param(True, "fit an intercept term", ptype=bool)

    def fit(self, table: DataTable) -> LogisticRegressionModel:
        X = _features_matrix(table[self.featuresCol])
        y = np.asarray(table[self.labelCol], np.float32)
        w, b = _fit_binary_lr(jnp.asarray(X), jnp.asarray(y),
                              float(self.regParam), int(self.maxIter),
                              float(self.tol), bool(self.fitIntercept))
        return LogisticRegressionModel(
            np.asarray(w), float(b), featuresCol=self.featuresCol)


def _fit_binary_lr(X, y, reg, max_iter, tol, fit_intercept):
    d = X.shape[1]

    def loss(params):
        w, b = params
        z = X @ w + (b if fit_intercept else 0.0)
        ll = optax.sigmoid_binary_cross_entropy(z, y).mean()
        return ll + 0.5 * reg * jnp.sum(w * w)

    init = (jnp.zeros((d,), jnp.float32), jnp.zeros((), jnp.float32))
    w, b = run_lbfgs(loss, init, max_iter, tol)
    return w, (b if fit_intercept else jnp.zeros(()))


def _fit_binary_lr_multi(X, Y, reg, max_iter, tol, fit_intercept):
    """All K one-vs-rest fits as ONE vmapped L-BFGS: the per-class
    objectives are identical in shape, so a single compile drives K lanes
    on the same matmuls (vs the serial K compile+fit cycles a naive OvR
    loop costs).  Y is (K, N); returns w (K, D), b (K,)."""
    fit_one = lambda yk: _fit_binary_lr(X, yk, reg, max_iter, tol,
                                        fit_intercept)
    return jax.jit(jax.vmap(fit_one))(Y)


class OneVsRestModel(ClassifierModel):
    def __init__(self, models: Optional[list] = None, **kw):
        super().__init__(**kw)
        self._models = list(models or [])

    @property
    def num_classes(self) -> int:
        return len(self._models)

    def _score(self, X):
        # column k = positive-class score of the k-th binary model
        pos = np.stack([m._score(X)[1][:, 1] for m in self._models], axis=1)
        denom = np.maximum(pos.sum(axis=1, keepdims=True), 1e-12)
        prob = pos / denom
        return pos, prob, np.argmax(pos, axis=1).astype(np.float64)

    def _save_extra(self, path):
        for i, m in enumerate(self._models):
            m.save(os.path.join(path, f"class_{i:03d}"))
        with open(os.path.join(path, "n.txt"), "w") as f:
            f.write(str(len(self._models)))

    def _load_extra(self, path):
        with open(os.path.join(path, "n.txt")) as f:
            n = int(f.read())
        self._models = [load_stage(os.path.join(path, f"class_{i:03d}"))
                        for i in range(n)]


class OneVsRest(Estimator):
    """Multiclass reduction over a binary classifier
    (reference TrainClassifier.scala:87-95 wraps LR in Spark's OneVsRest)."""

    featuresCol = Param("features", "features column", ptype=str)
    labelCol = Param("label", "label column (class indices)", ptype=str)

    def __init__(self, classifier: Optional[Estimator] = None, **kw):
        super().__init__(**kw)
        self._classifier = classifier

    def fit(self, table: DataTable) -> OneVsRestModel:
        if self._classifier is None:
            raise ParamError("OneVsRest: no base classifier set")
        y = np.asarray(table[self.labelCol], np.int64)
        n_classes = int(y.max()) + 1 if len(y) else 0
        if type(self._classifier) is LogisticRegression:
            # fast path: one vmapped fit over all classes.  Exact-type gate:
            # a subclass with overridden fit() must take the generic path,
            # not be silently fitted with base-class math
            base = self._classifier
            X = _features_matrix(table[self.featuresCol])
            Y = (y[None, :] == np.arange(n_classes)[:, None]).astype(np.float32)
            w, b = _fit_binary_lr_multi(
                jnp.asarray(X), jnp.asarray(Y), float(base.regParam),
                int(base.maxIter), float(base.tol), bool(base.fitIntercept))
            w, b = np.asarray(w), np.asarray(b)
            models = [LogisticRegressionModel(w[k], float(b[k]),
                                              featuresCol=self.featuresCol)
                      for k in range(n_classes)]
            return OneVsRestModel(models, featuresCol=self.featuresCol)
        models = []
        for k in range(n_classes):
            binary = table.with_column(self.labelCol,
                                       (y == k).astype(np.float32))
            est = self._classifier.copy(featuresCol=self.featuresCol,
                                        labelCol=self.labelCol)
            models.append(est.fit(binary))
        return OneVsRestModel(models, featuresCol=self.featuresCol)


# --------------------------------------------------------------------------
# Linear regression — closed form on device
# --------------------------------------------------------------------------

class LinearRegressionModel(RegressorModel):
    def __init__(self, w: Optional[np.ndarray] = None, b: float = 0.0, **kw):
        super().__init__(**kw)
        self.w = np.asarray(w, np.float32) if w is not None else None
        self.b = float(b)

    def _predict(self, X):
        return X @ self.w + self.b

    def _save_extra(self, path):
        np.savez(os.path.join(path, "coef.npz"), w=self.w, b=self.b)

    def _load_extra(self, path):
        d = np.load(os.path.join(path, "coef.npz"))
        self.w, self.b = d["w"], float(d["b"])


class LinearRegression(Estimator):
    """Ridge/OLS via the normal equations, solved on device in float32
    (the matmul-heavy path XLA maps straight onto the MXU)."""

    featuresCol = Param("features", "features column", ptype=str)
    labelCol = Param("label", "target column", ptype=str)
    regParam = Param(0.0, "L2 regularization", ptype=float)
    fitIntercept = Param(True, "fit an intercept", ptype=bool)

    def fit(self, table: DataTable) -> LinearRegressionModel:
        X = _features_matrix(table[self.featuresCol])
        y = np.asarray(table[self.labelCol], np.float32)
        w, b = _solve_ridge(jnp.asarray(X), jnp.asarray(y),
                            float(self.regParam), bool(self.fitIntercept))
        return LinearRegressionModel(np.asarray(w), float(b),
                                     featuresCol=self.featuresCol)


def _solve_ridge(X, y, reg, fit_intercept):
    # least-squares on X itself (not the normal equations): squaring the
    # condition number in float32 destroys the solve whenever featurization
    # emits collinear blocks (e.g. a one-hot family summing to the
    # intercept); lstsq's min-norm solution stays stable.  Ridge becomes
    # sqrt(lambda) augmentation rows, keeping one code path.
    if fit_intercept:
        mu_x, mu_y = X.mean(0), y.mean()
        Xc, yc = X - mu_x, y - mu_y
    else:
        Xc, yc = X, y
    d = X.shape[1]
    lam = reg * len(y)
    if lam > 0:
        Xc = jnp.concatenate(
            [Xc, jnp.sqrt(lam) * jnp.eye(d, dtype=X.dtype)])
        yc = jnp.concatenate([yc, jnp.zeros((d,), y.dtype)])
    w = jnp.linalg.lstsq(Xc, yc)[0]
    b = (mu_y - mu_x @ w) if fit_intercept else jnp.zeros(())
    return w, b


# --------------------------------------------------------------------------
# Multinomial naive Bayes — native multiclass
# --------------------------------------------------------------------------

class NaiveBayesModel(ClassifierModel):
    def __init__(self, log_prior: Optional[np.ndarray] = None,
                 log_prob: Optional[np.ndarray] = None, **kw):
        super().__init__(**kw)
        self.log_prior = (np.asarray(log_prior, np.float32)
                          if log_prior is not None else None)
        self.log_prob = (np.asarray(log_prob, np.float32)
                         if log_prob is not None else None)

    @property
    def num_classes(self) -> int:
        return len(self.log_prior)

    def _score(self, X):
        raw = X @ self.log_prob.T + self.log_prior
        prob = np.asarray(jax.nn.softmax(jnp.asarray(raw), axis=1))
        return raw, prob, np.argmax(raw, axis=1).astype(np.float64)

    def _save_extra(self, path):
        np.savez(os.path.join(path, "nb.npz"),
                 log_prior=self.log_prior, log_prob=self.log_prob)

    def _load_extra(self, path):
        d = np.load(os.path.join(path, "nb.npz"))
        self.log_prior, self.log_prob = d["log_prior"], d["log_prob"]


class NaiveBayes(Estimator):
    """Multinomial NB with Laplace smoothing (Spark NaiveBayes counterpart;
    requires non-negative features, e.g. hashed counts)."""

    featuresCol = Param("features", "features column (non-negative)", ptype=str)
    labelCol = Param("label", "label column (class indices)", ptype=str)
    smoothing = Param(1.0, "Laplace smoothing", ptype=float)

    def fit(self, table: DataTable) -> NaiveBayesModel:
        X = _features_matrix(table[self.featuresCol])
        if (X < 0).any():
            raise ValueError("NaiveBayes requires non-negative features")
        y = np.asarray(table[self.labelCol], np.int64)
        n_classes = int(y.max()) + 1 if len(y) else 0
        onehot = np.zeros((len(y), n_classes), np.float32)
        onehot[np.arange(len(y)), y] = 1.0
        counts = jnp.asarray(onehot).T @ jnp.asarray(X)  # (C, D)
        alpha = float(self.smoothing)
        smoothed = counts + alpha
        log_prob = jnp.log(smoothed) - jnp.log(
            smoothed.sum(axis=1, keepdims=True))
        class_count = onehot.sum(axis=0)
        log_prior = np.log(np.maximum(class_count, 1e-12) / len(y))
        return NaiveBayesModel(np.asarray(log_prior), np.asarray(log_prob),
                               featuresCol=self.featuresCol)


# --------------------------------------------------------------------------
# Multilayer perceptron — flax module + the distributed Trainer
# --------------------------------------------------------------------------

class MultilayerPerceptronClassifierModel(ClassifierModel):
    def __init__(self, bundle=None, **kw):
        super().__init__(**kw)
        self._bundle = bundle
        self._apply = None

    @property
    def num_classes(self) -> int:
        return self._bundle.module().num_classes

    def _score(self, X):
        if self._apply is None:
            module = self._bundle.module()
            self._apply = jax.jit(lambda v, x: module.apply(v, x))
        raw = np.asarray(self._apply(self._bundle.variables, jnp.asarray(X)))
        prob = np.asarray(jax.nn.softmax(jnp.asarray(raw), axis=1))
        return raw, prob, np.argmax(raw, axis=1).astype(np.float64)

    def _save_extra(self, path):
        from mmlspark_tpu.models.bundle import save_bundle
        save_bundle(self._bundle, os.path.join(path, "bundle"))

    def _load_extra(self, path):
        from mmlspark_tpu.models.bundle import load_bundle
        self._bundle = load_bundle(os.path.join(path, "bundle"))
        self._apply = None


class MultilayerPerceptronClassifier(Estimator):
    """MLP classifier (Spark's MultilayerPerceptronClassifier counterpart,
    TrainClassifier.scala:96-101).  `layers` = [in, hidden..., classes];
    the input size is autosized by TrainClassifier when left as -1."""

    featuresCol = Param("features", "features column", ptype=str)
    labelCol = Param("label", "label column (class indices)", ptype=str)
    layers = Param(None, "layer sizes [input, hidden..., output]",
                   ptype=(list, tuple), required=True)
    maxIter = Param(100, "training epochs", ptype=int)
    stepSize = Param(0.005, "learning rate", ptype=float)
    seed = Param(0, "init/shuffle seed", ptype=int)

    def _fit_inputs(self, table: DataTable):
        """(X, y, trainer config) shared by the single fit and the
        population sweep — both train the IDENTICAL program per member."""
        from mmlspark_tpu.train import TrainerConfig
        self._check_required()
        layers = list(self.layers)
        if len(layers) < 2:
            raise ParamError("layers needs at least [input, output]")
        X = _features_matrix(table[self.featuresCol])
        if layers[0] in (-1, 0, None):
            layers[0] = X.shape[1]
        elif layers[0] != X.shape[1]:
            raise ParamError(f"layers[0]={layers[0]} != feature dim {X.shape[1]}")
        y = np.asarray(table[self.labelCol], np.int64)
        cfg = TrainerConfig(
            architecture="MLPClassifier",
            model_config={"hidden_sizes": layers[1:-1],
                          "num_classes": layers[-1], "dtype": "float32"},
            optimizer="adam", learning_rate=float(self.stepSize),
            epochs=int(self.maxIter),
            batch_size=int(min(max(len(X), 1), 4096)),
            loss="softmax_xent", seed=int(self.seed))
        return X, y, cfg

    def fit(self, table: DataTable) -> MultilayerPerceptronClassifierModel:
        from mmlspark_tpu.train import Trainer
        X, y, cfg = self._fit_inputs(table)
        trainer = Trainer(cfg)
        bundle = trainer.fit_arrays(X, y.astype(np.int32))
        return MultilayerPerceptronClassifierModel(
            bundle, featuresCol=self.featuresCol)

    def fit_population(self, table: DataTable, learning_rates,
                       halving_rungs: int = 0):
        """Train one MLP candidate per learning rate as a vmapped
        population (train/sweep.py) — N models in ONE compiled program —
        then pick the winner by a single batched evaluation: one vmapped
        forward scores every member, one `classification_report_batch`
        ranks them (no per-candidate transform/evaluate round trips).

        Returns (winner model, per-member metrics DataTable ordered like
        `learning_rates`, with `learning_rate`/`final_loss`/`active`
        columns joined on)."""
        from mmlspark_tpu.ml.statistics import classification_report_batch
        from mmlspark_tpu.train import PopulationTrainer
        X, y, cfg = self._fit_inputs(table)
        rates = [float(r) for r in learning_rates]
        if not rates:
            raise ParamError("fit_population needs at least one rate")
        pt = PopulationTrainer(cfg, [{"learning_rate": r} for r in rates],
                               halving_rungs=int(halving_rungs))
        result = pt.fit_arrays(X, y.astype(np.int32))
        logits = pt.score_population(result.state, X)   # (N, rows, classes)
        preds = np.argmax(logits, axis=-1)
        report = classification_report_batch(
            y, preds, model_uids=[f"member_{k}_lr={r:g}"
                                  for k, r in enumerate(rates)])
        acc = np.asarray(report["accuracy"], np.float64)
        ranked = np.where(result.active > 0, acc, -np.inf)
        best = int(np.argmax(ranked))
        report = report.with_column("learning_rate",
                                    np.asarray(rates, np.float64))
        report = report.with_column("final_loss",
                                    result.final_losses().astype(np.float64))
        report = report.with_column("active",
                                    result.active.astype(np.float64))
        model = MultilayerPerceptronClassifierModel(
            result.member_bundle(best), featuresCol=self.featuresCol)
        return model, report
