"""`serve_arch.py`'s closed loop for a cell whose rows are too long for the
reference's one call: a row of 33,280 tokens has 33,280 x 73,448 logits
(9.8 GB in float32, and `serve_arch.served_gaps` holds them twice) beside
11 GB of float32 weights, on a chip of 16 GB.  Set-up, the window, the pick
of the compared requests and `served_gap` itself are `serve_arch.py`'s and
`serve.py`'s; what differs is how the reference is run:

  * its weights stay on the host and come up a layer at a time;
  * its head sees the served positions only.

So the reference module gives, beside `serve_arch.py`'s interface, the
pieces its `forward` is made of:

  embed(params, tokens, spec)            -> x (B, S, d)
  layer(p, x, spec, kind, mode)          -> x     one layer's own weights
  head(params, x, spec, mode)            -> logits of the rows of x given

A reference whose selection has near-ties that bfloat16 may decide
otherwise returns margins from `forward` and runs under `serve_arch.py`; one
that returns none (`reach` is empty) is compared at every served position,
and `uncompared_share` reads 0 here.

Two numbers are compared over those positions: `served_gap`, the widest gap
as `serve.py` has it, and `served_gap_mean`, the mean gap.  A gap is 0
wherever the served token is the reference's best, and elsewhere as wide as
the reference's two best logits lie apart where the program's error passed
that.  The widest of some 1,400 is the far tail of the program's error (19
runs read 0.017-0.034, one 0.076: PERF.md section 2) and lies 2 x under the
float8 control's; the mean grows with the square of the error at EVERY
position and lies 17 x under the control's and 5 x under a program's that
reads every visible block, so it is the number that tells them apart.
"""

from __future__ import annotations

import functools
import gc
import sys

import jax
import numpy as np

from benchmark import harness
from benchmark.drivers.serve import pick, stop
from benchmark.drivers.serve_arch import (_gap_of, reference_of,  # noqa: F401
                                          setup, window)


@functools.lru_cache(maxsize=None)
def _pieces(ref):
    return (jax.jit(ref.embed, static_argnames=("spec",)),
            jax.jit(ref.layer, static_argnames=("spec", "kind", "mode"),
                    donate_argnums=(1,)),
            jax.jit(ref.head, static_argnames=("spec", "mode")),
            jax.jit(lambda x, first, count: jax.lax.dynamic_slice_in_dim(
                x, first, count, axis=1), static_argnames=("count",)))


def logits_at(ref, params: dict, row: np.ndarray, spec, first: int,
              count: int, mode: str = "f32"):
    """The reference's logits at positions first..first+count-1 of `row`
    (1, S), `params` a tree of host arrays."""
    embed, layer, head, cut = _pieces(ref)
    x = embed({"embed": params["embed"]}, row, spec=spec)
    for i, kind in enumerate(spec.layer_types):
        x = layer(params[f"layer{i}"], x, spec=spec, kind=kind, mode=mode)
    top = {name: params[name] for name in ("out_norm", "head")}
    return head(top, cut(x, first, count=count), spec=spec, mode=mode)


def served_gaps(ref, params: dict, spec, rec: dict, length: int, count: int,
                control: bool = False) -> np.ndarray:
    """For each token the server produced for `rec`: how far its logit
    lies below the reference's best at that position, the reference run
    once over the prompt and the served tokens, padded to `length`.
    `control`: the gap of the token that the float8 reference puts first
    there."""
    n_prompt, n = len(rec["prompt"]), len(rec["tokens"])
    row = np.zeros((1, length), np.int32)
    row[0, :n_prompt] = rec["prompt"]
    row[0, n_prompt:n_prompt + n] = rec["tokens"]
    logits = logits_at(ref, params, row, spec, n_prompt - 1, count)[0]
    if control:
        low = logits_at(ref, params, row, spec, n_prompt - 1, count, "fp8")
        picked = low[0].argmax(-1).astype(np.int32)
    else:
        picked = np.asarray(row[0, n_prompt:n_prompt + count])
    return np.asarray(_gap_of(logits, picked))[:n]


NUMBERS = ("served_gap", "served_gap_mean", "uncompared_share")


def _compare(run, records: list, control: bool) -> dict:
    """`served_gap`, the widest gap over every served position of
    `records`, and `served_gap_mean`, the mean gap over them (the module's
    docstring says why two); the reference's weights made once and brought
    to the host."""
    ref, weights = reference_of(run)
    c = run.config["constructor"]
    if ref.reach(c):
        raise ValueError("a reference with margins runs under serve_arch")
    params = harness.host_tree(
        weights.make_variables(ref.shapes_for(c), run.seed))["params"]
    count = run.traffic["new_tokens"][1]
    length = run.traffic["prompt_len"][1] + count
    gaps = [served_gaps(ref, params, ref.spec_for(c), rec, length, count,
                        control) for rec in records]
    if not gaps:
        return {"served_gap": float("inf"), "served_gap_mean": float("inf"),
                "uncompared_share": 0.0}
    every = np.concatenate(gaps)
    print("benchmark: %s, served gaps of %d positions: widest %.4f, mean "
          "%.6f, %d over 0, %d over 0.03; a request (prompt, tokens, widest "
          "at): %s" % (
              "float8 control" if control else "program", every.size,
              every.max(), every.mean(), (every > 0).sum(),
              (every > 0.03).sum(), "; ".join(
                  "%d, %d, %.4f at %d" % (len(r["prompt"]), len(g), g.max(),
                                          g.argmax())
                  for r, g in zip(records, gaps))), file=sys.stderr)
    return {"served_gap": float(every.max()),
            "served_gap_mean": float(every.mean()), "uncompared_share": 0.0}


def check(run, state: dict) -> dict:
    """The widest and the mean gap, over the served tokens of the picked
    requests, by which a served token's logit lies below the reference's
    best."""
    stop(state)
    picked = pick(run, state["sent"])
    state.clear()           # the engine, its state and its weights
    gc.collect()
    got = _compare(run, picked, control=False)
    run.obs["checked_tokens"] = sum(len(r["tokens"]) for r in picked)
    run.obs["kept"] = {"picked": picked}
    limits = run.traffic["limits"]
    return {k: (got[k], limits[k]) for k in NUMBERS}


def control(run) -> dict:
    """At each served position of the same prompts and tokens, the gap of
    the token that the float8 reference puts first (calibrate.py; no
    benchmark run computes this)."""
    got = _compare(run, run.obs["kept"]["picked"], control=True)
    return {k + ".fp8": v for k, v in got.items()}
