"""`serve.py`'s closed loop with the model behind an interface: the
configuration file names its plain reference (`"reference": "<module of
benchmark/reference/>"`, and `"weights"`, the module holding its weights'
rule, `weights` if absent), and the reference module gives

  shapes_for(constructor)            the variable tree both sides are fed
  spec_for(constructor)              what `forward` needs beside the weights
  forward(params, tokens, spec, mode)  -> (logits, margins (layers, B, S))
  reach(constructor)                 positions a changed routing choice reaches
  forward_flops(constructor, first, last)   active operations

so the next architecture lands as a configuration and a reference, and no
driver.  The clients, the request plan and the pick of the compared
requests are `serve.py`'s own.

What `correct` compares, beside `serve.py`'s `served_gap`: a router's
near-ties.  Where the k-th and (k+1)-th selection scores of an expert layer
lie within `limits.tie_band` of each other at a position, bfloat16 may
choose another expert there with no fault, and the served logits move by
more than rounding at every position that choice reaches (`reach`).  Those
served positions are left out of `served_gap`, and their share of all
served positions is compared too (`uncompared_share`), so that a band wide
enough to hide a fault fails by its own limit.
"""

from __future__ import annotations

import functools
import gc
import importlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.drivers.serve import Clients, pick, request_plan, stop


def reference_of(run):
    """(reference module, module with `make_variables`) of the cell."""
    name = lambda key, default: importlib.import_module(
        "benchmark.reference." + run.config.get(key, default))
    return name("reference", "lm"), name("weights", "weights")


def setup(run) -> dict:
    from mmlspark_tpu.models import ModelBundle
    from mmlspark_tpu.models.definitions import build_model
    from mmlspark_tpu.serve import ServeConfig, ServingEngine
    from mmlspark_tpu.serve.lifecycle import start_engine, start_http
    t, c = run.traffic, run.config["constructor"]
    ref, weights = reference_of(run)
    module = build_model(run.config["architecture"], dict(c))
    shapes = ref.shapes_for(c)
    harness.same_tree(jax.eval_shape(
        module.init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 8), np.int32)), shapes)
    variables = harness.host_tree(weights.make_variables(shapes, run.seed))
    run.lap("imports_and_weights")
    e = dict(t["engine"])
    e["warmup_buckets"] = tuple(e["warmup_buckets"])
    engine = ServingEngine(ModelBundle.from_module(module, variables),
                           ServeConfig(**e))
    del variables
    start_engine(engine, install_sigterm=False)
    server = start_http(engine, port=0)
    run.lap("engine_warmup")
    clients = Clients(server.server_address[1],
                      request_plan(t, c["vocab_size"], run.seed),
                      t["clients"], run.annotate)
    clients.start()
    while clients.completed() < t["warmup_requests"]:
        if not any(th.is_alive() for th in clients.threads):
            raise RuntimeError("every client stopped during warm-up")
        time.sleep(0.05)
    return {"engine": engine, "server": server, "clients": clients}


def window(run, state: dict) -> None:
    clients, engine = state["clients"], state["engine"]
    ref, _ = reference_of(run)
    mark = run.compiles.mark()
    before = engine.stats()
    run.start_trace()
    t0 = time.perf_counter()
    time.sleep(run.seconds)
    t1 = time.perf_counter()
    after = engine.stats()
    compiles = run.compiles.since(mark)[0]
    # every number of the engine's stats, the window's end less its start:
    # right for its counts (a gauge's difference means nothing)
    number = lambda v: isinstance(v, (int, float)) and not isinstance(v, bool)
    counters = {k: v - before.get(k, 0) for k, v in after.items()
                if number(v) and number(before.get(k, 0))}
    all_ended = clients.finish(90.0)
    c = run.config["constructor"]
    sent = [r for r in clients.records if t0 <= r["t_send"] < t1]
    tokens, ops = 0, 0
    for r in clients.records:
        n_prompt, seen = len(r["prompt"]), 0
        for at, n in r["arrivals"]:
            if t0 <= at < t1:
                tokens += n
                if seen == 0:       # the prefill made the first token
                    ops += ref.forward_flops(c, 0, n_prompt)
                ops += ref.forward_flops(c, n_prompt + max(seen, 1) - 1,
                                         n_prompt + seen + n - 1)
            seen += n
    ttft = [(r["arrivals"][0][0] - r["t_send"]) * 1e3
            for r in sent if r["arrivals"]]
    gaps = [(r["arrivals"][-1][0] - r["arrivals"][0][0]) * 1e3
            / (len(r["tokens"]) - 1)
            for r in sent if r["ok"] and len(r["tokens"]) > 1]
    failed = sum(not r["ok"] for r in sent) + (0 if all_ended else 1)
    notes = [f"request {r['index']}: {r['error']}"
             for r in sent if not r["ok"]][:5]
    if ttft:
        notes.append("ttft ms over %d requests: p50 %.0f p90 %.0f p95 %.0f "
                     "max %.0f; %d compiles in the window" % (
                         len(ttft), *(harness.percentile(ttft, q)
                                      for q in (50, 90, 95, 100)), compiles))
    notes.append("state bytes held at the window's end: window %d, fixed %d"
                 % (after.get("state_bytes_window", 0),
                    after.get("state_bytes_fixed", 0)))
    run.obs.update(
        t0=t0, t1=t1, attempted=len(sent), failed=failed, notes=notes,
        end_to_end={"serve_tokens_per_s": tokens / (t1 - t0)},
        work={"serve_flops": ops},
        ttft_p95_ms=harness.percentile(ttft, 95.0) if ttft else None,
        token_gap_p95_ms=harness.percentile(gaps, 95.0) if gaps else None,
        compiles_in_window=compiles, counters=counters)
    state["sent"] = sent


@jax.jit
def _gap_of(logits, picked):
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, picked[:, None], -1)[:, 0]
    return best - got


@functools.lru_cache(maxsize=None)
def _forward(ref):
    return jax.jit(ref.forward, static_argnames=("spec", "mode"))


def compared_mask(margins: np.ndarray, reaches: list, band: float,
                  first: int, count: int) -> np.ndarray:
    """Which of the `count` served positions first..first+count-1 are
    compared: those no near-tie reaches.  `margins` (expert layers,
    positions); a margin under `band` at position q of a layer whose reach
    is r rules out q..q+r (every later position where r is None)."""
    ruled_out = np.zeros(margins.shape[1], bool)
    for margin, r in zip(margins, reaches):
        for q in np.nonzero(margin < band)[0]:
            ruled_out[q:None if r is None else q + r + 1] = True
    return ~ruled_out[first:first + count]


def served_gaps(ref, params, spec, rec: dict, length: int,
                control: bool = False) -> tuple:
    """For each token the server produced for `rec`: how far its logit
    lies below the reference's best at that position, the reference run
    once over the prompt and the served tokens; and the run's margins
    (expert layers, positions).  `control`: the gap of the token that the
    float8 reference puts first there."""
    n_prompt, n = len(rec["prompt"]), len(rec["tokens"])
    row = np.zeros((1, length), np.int32)
    row[0, :n_prompt] = rec["prompt"]
    row[0, n_prompt:n_prompt + n] = rec["tokens"]
    logits, margins = _forward(ref)(params, row, spec=spec)
    if control:
        low, _ = _forward(ref)(params, row, spec=spec, mode="fp8")
        picked = low[0, :-1].argmax(-1).astype(jnp.int32)
    else:
        picked = jnp.asarray(row[0, 1:])
    gaps = np.asarray(_gap_of(logits[0, :-1], picked))
    return gaps[n_prompt - 1:n_prompt + n - 1], np.asarray(margins)[:, 0]


def _compare(run, records: list, control: bool) -> dict:
    """`served_gap` over the compared positions of `records` and the share
    of their served positions that was not compared; the reference's
    weights made once, every row padded to the mix's longest prompt and
    answer."""
    ref, weights = reference_of(run)
    c = run.config["constructor"]
    params = weights.make_variables(ref.shapes_for(c), run.seed)["params"]
    length = run.traffic["prompt_len"][1] + run.traffic["new_tokens"][1]
    band = run.traffic["limits"]["tie_band"]
    # the cell's band first, and beside it (standard error only) what
    # narrower and wider bands would have read: the curve it was chosen on
    bands = [band] + [band * f for f in (0.0, 0.25, 0.5, 2.0, 4.0)]
    widest = [-1.0] * len(bands)
    compared = [0] * len(bands)
    served = 0
    for rec in records:
        gaps, margins = served_gaps(ref, params, ref.spec_for(c), rec,
                                    length, control)
        served += len(gaps)
        for i, b in enumerate(bands):
            mask = compared_mask(margins, ref.reach(c), b,
                                 len(rec["prompt"]) - 1, len(gaps))
            compared[i] += int(mask.sum())
            if mask.any():
                widest[i] = max(widest[i], float(gaps[mask].max()))
    print("benchmark: %s, tie band -> served_gap, uncompared share: %s" % (
        "float8 control" if control else "program", "; ".join(
            "%g -> %.4f, %.3f" % (b, w, 1.0 - n / max(served, 1))
            for b, w, n in sorted(zip(bands, widest, compared)))),
        file=sys.stderr)
    if not compared[0]:
        return {"served_gap": float("inf"), "uncompared_share": 1.0}
    return {"served_gap": widest[0],
            "uncompared_share": 1.0 - compared[0] / served}


def check(run, state: dict) -> dict:
    """The widest gap, over the compared served tokens of the picked
    requests, by which a served token's logit lies below the reference's
    best; and the share of their served tokens left out as near-ties."""
    stop(state)
    picked = pick(run, state["sent"])
    state.clear()           # the engine, its state and its weights
    gc.collect()
    got = _compare(run, picked, control=False)
    run.obs["checked_tokens"] = sum(len(r["tokens"]) for r in picked)
    run.obs["kept"] = {"picked": picked}
    limits = run.traffic["limits"]
    return {k: (got[k], limits[k]) for k in ("served_gap",
                                             "uncompared_share")}


def control(run) -> dict:
    """At each compared position of the same prompts and tokens, the gap
    of the token that the float8 reference puts first (calibrate.py; no
    benchmark run computes this)."""
    got = _compare(run, run.obs["kept"]["picked"], control=True)
    return {k + ".fp8": v for k, v in got.items()}
