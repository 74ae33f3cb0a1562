"""Training through `Trainer.fit_arrays`, fed from host arrays
(chip_smoke.py's train phase with a timed window put round it).

One `fit_arrays` call: a warm-up epoch, then whole epochs until the
window's seconds have passed; the window runs from the end of the warm-up
epoch to the end of the last epoch, read on this file's clock in the
trainer's per-epoch log callback (which the trainer calls after it has
fetched the epoch's losses).  The callback ends the fit by raising once
the window has closed: `epochs` is set far beyond what a window holds.

The trainer is the program's own class with one method wrapped:
`make_train_step` returns the program's compiled step behind a function
that, for the first `check_steps` calls only, keeps what the comparison
needs: the batch as fed, the loss, after step 1 the norm of each leaf of
Adam's first moment (the gradient as the optimizer got it, times 1 - b1),
after the last checked step the parameters.  Later calls pass through.

Traffic parameters: `seq`, `batch`, `steps_per_epoch`, `optimizer`,
`learning_rate`, `b1`, `check_steps`, `trainer` (further TrainerConfig
arguments), `limits`.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.reduce import flops as F
from benchmark.reference import lm, weights

B1, B2, EPS = 0.9, 0.999, 1e-8        # optax.adam's defaults


class WindowClosed(Exception):
    """Raised from the epoch callback to end the fit at the window's end."""


def _first_moment(opt_state):
    found = [s.mu for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError("the optimizer state holds no single Adam `mu`")
    return found[0]


@jax.jit
def leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


def make_trainer(cfg, steps: int, seen: dict):
    from mmlspark_tpu.train import Trainer

    class Watched(Trainer):
        def make_train_step(self):
            step = super().make_train_step()

            def watched(state, x, y, mask, *rest):
                out = step(state, x, y, mask, *rest)
                k = seen["calls"] = seen["calls"] + 1
                if k <= steps:
                    seen["x"].append(np.asarray(x))
                    seen["y"].append(np.asarray(y))
                    seen["loss"].append(float(out[1]))
                    if k == 1:
                        seen["mu_norms"] = jax.device_get(leaf_norms(
                            _first_moment(out[0].opt_state)))
                    if k == steps:
                        seen["params"] = harness.host_tree(out[0].params)
                return out
            return watched

    return Watched(cfg)


def setup(run) -> dict:
    from mmlspark_tpu.models import ModelBundle
    from mmlspark_tpu.models.definitions import build_model
    from mmlspark_tpu.train import TrainerConfig
    t, c = run.traffic, dict(run.config["constructor"])
    if t["steps_per_epoch"] < t["check_steps"]:
        raise ValueError("the warm-up epoch must hold the checked steps")
    c["max_len"] = t["seq"]
    cfg = TrainerConfig(
        architecture=run.config["architecture"], model_config=c,
        optimizer=t["optimizer"], learning_rate=t["learning_rate"],
        loss="softmax_xent", batch_size=t["batch"], epochs=1_000_000,
        seed=run.seed & 0x7FFFFFFF, **t.get("trainer", {}))
    module = build_model(cfg.architecture, dict(c))
    shapes = lm.shapes_for(c)
    harness.same_tree(jax.eval_shape(
        module.init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 8), np.int32)), shapes)
    variables = harness.host_tree(weights.make_variables(shapes, run.seed))
    run.lap("imports_and_weights")
    rng = np.random.default_rng(run.seed)
    rows = t["steps_per_epoch"] * t["batch"]
    tokens = rng.integers(0, c["vocab_size"],
                          (rows, t["seq"])).astype(np.int32)
    seen = {"calls": 0, "x": [], "y": [], "loss": []}
    return {"trainer": make_trainer(cfg, t["check_steps"], seen),
            "bundle": ModelBundle.from_module(module, variables),
            "tokens": tokens, "targets": np.roll(tokens, -1, axis=1),
            "seen": seen, "shapes": shapes}


def window(run, state: dict) -> None:
    t = run.traffic
    marks: list = []        # host clock at each epoch's end
    mark = [None]

    def epoch_end(_line: str) -> None:
        now = time.perf_counter()
        marks.append(now)
        if len(marks) == 1:             # the warm-up epoch has ended
            mark[0] = run.compiles.mark()
            run.start_trace()
        elif now - marks[0] >= run.seconds:
            raise WindowClosed

    try:
        with run.annotate("fit_arrays"):
            state["trainer"].fit_arrays(
                state["tokens"], state["targets"],
                initial_bundle=state["bundle"], log_every=1,
                log_fn=epoch_end)
    except WindowClosed:
        pass
    t0, t1 = marks[0], marks[-1]
    steps = (len(marks) - 1) * t["steps_per_epoch"]
    history = state["trainer"].history[1:]
    failed = sum(not np.isfinite(r["loss"]) for r in history) \
        * t["steps_per_epoch"]
    c = run.config["constructor"]
    per_step = F.lm_train_flops(t["batch"], t["seq"], c["d_model"],
                                c["n_layers"], c["vocab_size"],
                                c.get("mlp_ratio", 4))["total"]
    run.obs.update(
        t0=t0, t1=t1, attempted=steps, failed=failed,
        end_to_end={"train_tokens_per_s":
                    steps * t["batch"] * t["seq"] / (t1 - t0)},
        work={"train_flops": steps * per_step},
        compiles_in_window=run.compiles.since(mark[0])[0])


def reference_steps(make_params, xs: list, ys: list, n_heads: int,
                    lr: float, mode: str = "f32", rows: int | None = None,
                    live=None) -> dict:
    """Follow the fed batches with the plain reference and Adam by hand.
    `make_params()` gives the parameters as made from the seed, on the
    device.  So that it fits beside the gradient (a 613 M-parameter tree
    is 2.45 GB in float32, and one chip has to hold the parameters, the
    summed gradient, one row's gradient and the backward's temporaries),
    the gradient is taken a row at a time, Adam's two moments live on the
    host and the update runs leaf by leaf.  Returns the losses, the
    per-leaf norms of the first gradient and of the parameters' change.
    `rows` plants the fault "part of the batch left out, the mean taken
    over the rest"; `live` takes the mask of the elements that count from
    an earlier, sound, reference run."""
    grad = jax.jit(jax.value_and_grad(lm.loss),
                   static_argnames=("n_heads", "mode"))

    @jax.jit
    def add(acc, g, w):
        return jax.tree_util.tree_map(lambda a, b: a + w * b, acc, g)

    @jax.jit
    def adam_leaf(a, m, v, g, k):
        m = B1 * m + (1 - B1) * g
        v = B2 * v + (1 - B2) * g * g
        a = a - lr * (m / (1 - B1 ** k)) / (jnp.sqrt(v / (1 - B2 ** k)) + EPS)
        return a, m, v

    @jax.jit
    def mark_live(live, g):
        # an element counts where its gradient reaches a thousandth of its
        # leaf's root mean square (or of the median leaf's, if larger)
        rms = jax.tree_util.tree_map(
            lambda a: jnp.sqrt(jnp.mean(jnp.square(a))), g)
        floor = 1e-3 * jnp.median(jnp.stack(jax.tree_util.tree_leaves(rms)))
        return jax.tree_util.tree_map(
            lambda l, a, r: l | (jnp.abs(a) >= jnp.maximum(1e-3 * r, floor)),
            live, g, rms)

    p = make_params()
    leaves, treedef = jax.tree_util.tree_flatten(p)
    m = [np.zeros(a.shape, np.float32) for a in leaves]
    v = [np.zeros(a.shape, np.float32) for a in leaves]
    del leaves
    given = live is not None
    if not given:
        live = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, bool), p)
    out = {"loss": []}
    for k, (x, y) in enumerate(zip(xs, ys), 1):
        n = len(x) if rows is None else rows
        g, total = jax.tree_util.tree_map(jnp.zeros_like, p), 0.0
        for r in range(n):
            value, gr = grad(p, x[r:r + 1], y[r:r + 1], n_heads=n_heads,
                             mode=mode)
            g = add(g, gr, 1.0 / n)
            total += float(value) / n
        del gr
        out["loss"].append(total)
        if k == 1:
            out["grad_norms"] = jax.device_get(leaf_norms(g))
        if not given:
            live = mark_live(live, g)
        new = []
        for i, (a, gi) in enumerate(zip(jax.tree_util.tree_leaves(p),
                                        jax.tree_util.tree_leaves(g))):
            a, mi, vi = adam_leaf(a, m[i], v[i], gi, float(k))
            m[i], v[i] = np.asarray(mi), np.asarray(vi)
            new.append(a)
        p = jax.tree_util.tree_unflatten(treedef, new)
        del g, new, a, gi, mi, vi
    del m, v
    out["live"] = live
    out["change_norms"] = jax.device_get(
        masked_change(p, make_params(), live))
    return out


@jax.jit
def masked_change(p, p0, live):
    """Per leaf, the norm of the parameters' change over the elements that
    count (`live`)."""
    return leaf_norms(jax.tree_util.tree_map(
        lambda a, b, l: jnp.where(l, a - b, 0.0), p, p0, live))


def gaps(seen_loss, seen_grad, seen_change, ref: dict) -> dict:
    """The numbers compared.  Norms by the worst leaf: the gap between the
    program's norm and the reference's, against the reference's norm of
    that leaf or of the median leaf, whichever is larger.  Elements whose
    reference gradient is nought to rounding in every checked step (a
    key's bias under softmax, a third of the fused QKV bias) move under
    Adam by round-off alone; `reference_steps` leaves them out of the
    change on both sides, by a rule on the reference's gradient."""
    flat = lambda tree: np.asarray(
        [float(x) for x in jax.tree_util.tree_leaves(tree)])
    g_ref, g_got = flat(ref["grad_norms"]), flat(seen_grad)
    d_ref, d_got = flat(ref["change_norms"]), flat(seen_change)
    worst = lambda got, want: float(np.max(
        np.abs(got - want) / np.maximum(want, np.median(want))))
    loss = max(abs(a - b) / abs(b) for a, b in zip(seen_loss, ref["loss"]))
    return {"loss_gap": loss, "grad_norm_gap": worst(g_got, g_ref),
            "change_norm_gap": worst(d_got, d_ref)}


def program_readings(seen: dict, params0, live) -> tuple:
    """(losses, per-leaf gradient norms, per-leaf change norms) of the
    program's checked steps; `params0` as made from the seed, `live` the
    reference's mask of the elements that count."""
    grad = jax.tree_util.tree_map(lambda n: n / (1 - B1), seen["mu_norms"])
    after = jax.tree_util.tree_map(jnp.asarray, seen.pop("params"))
    delta = jax.device_get(masked_change(after, params0, live))
    return seen["loss"], grad, delta


def check(run, state: dict) -> dict:
    seen, shapes = state["seen"], state["shapes"]
    n_heads = run.config["constructor"]["n_heads"]
    lr = run.traffic["learning_rate"]
    state.clear()           # the trainer, its state and the bundle
    gc.collect()
    if seen["calls"] < run.traffic["check_steps"]:
        raise RuntimeError("the fit ended before its checked steps")
    make_params = lambda: weights.make_variables(shapes, run.seed)["params"]
    ref = reference_steps(make_params, seen["x"], seen["y"], n_heads, lr)
    loss, grad, delta = program_readings(seen, make_params(), ref["live"])
    got = gaps(loss, grad, delta, ref)
    run.obs["kept"] = {"seen": seen, "ref": ref, "shapes": shapes}
    limits = run.traffic["limits"]
    return {k: (v, limits[k]) for k, v in got.items() if k in limits}


def control(run) -> dict:
    """The reference put in the program's place (calibrate.py; no
    benchmark run computes these): in float8, and with half of the batch
    left out and the mean taken over the rest."""
    kept = run.obs["kept"]
    seen, ref = kept["seen"], kept["ref"]
    n_heads = run.config["constructor"]["n_heads"]
    lr = run.traffic["learning_rate"]
    make_params = lambda: weights.make_variables(
        kept["shapes"], run.seed)["params"]
    out = {}
    for name, how in (("fp8", {"mode": "fp8"}),
                      ("half_batch", {"rows": run.traffic["batch"] // 2})):
        low = reference_steps(make_params, seen["x"], seen["y"], n_heads, lr,
                              live=ref["live"], **how)
        got = gaps(low["loss"], low["grad_norms"], low["change_norms"], ref)
        out.update({f"{k}.{name}": v for k, v in got.items()})
    return out
