"""Serving over HTTP in a closed loop: `clients` threads each send their
next streamed `POST /generate` when the last returned (chip_smoke.py's
serve phase, its HTTP client reading the stream line by line, with a timed
window put round it).  The loop starts in set-up and runs on through the
window, so the window sees a standing queue from its first instant.

Traffic parameters: `clients`; `shapes` (how many (prompt, answer) length
pairs the mix holds, drawn once from `shape_seed`: prompts log-uniform in
`prompt_len`, answers uniform in `new_tokens`; every run seed sends the
same pairs in another order, with other token ids); `engine` (ServeConfig
arguments); `warmup_requests` (completed before the window opens);
`check_requests`; `limits`.
"""

from __future__ import annotations

import gc
import http.client
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.reduce import flops as F
from benchmark.reference import lm, weights


def request_plan(traffic: dict, vocab: int, seed: int):
    """index -> (prompt tokens, new tokens): the mix's fixed pairs of
    lengths in an order drawn from the seed, cycled; token ids drawn from
    (seed, index)."""
    shape_rng = np.random.default_rng(traffic["shape_seed"])
    lo, hi = traffic["prompt_len"]
    n = traffic["shapes"]
    prompts = np.exp(shape_rng.uniform(np.log(lo), np.log(hi + 1), n))
    prompts = np.clip(prompts.astype(int), lo, hi)
    news = shape_rng.integers(traffic["new_tokens"][0],
                              traffic["new_tokens"][1] + 1, n)
    order = np.random.default_rng(seed).permutation(n)

    def plan(i: int):
        j = order[i % n]
        ids = np.random.default_rng([seed, i]).integers(
            0, vocab, int(prompts[j]))
        return ids.astype(np.int32), int(news[j])
    return plan


def send(port: int, index: int, prompt: np.ndarray, n_new: int,
         annotate) -> dict:
    """One streamed POST /generate, read line by line; every chunk of
    tokens is stamped as it reaches this client."""
    rec = {"index": index, "prompt": prompt, "n_new": n_new, "ok": False,
           "arrivals": [], "tokens": [], "t_send": time.perf_counter()}
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600.0)
    try:
        with annotate("client.send"):
            conn.request("POST", "/generate", json.dumps({
                "prompt": prompt.tolist(), "max_new_tokens": n_new,
                "deadline_ms": 300_000, "stream": True}),
                {"Content-Type": "application/json"})
        with annotate("client.wait"):
            resp = conn.getresponse()
            if resp.status != 200:
                rec["error"] = f"{resp.status} {resp.read(200)!r}"
                return rec
            partial: list = []
            while True:
                line = resp.readline()
                if not line:
                    rec["error"] = "stream ended with no final line"
                    return rec
                now = time.perf_counter()
                msg = json.loads(line)
                if msg.get("restart"):
                    partial, rec["arrivals"] = [], []
                elif msg.get("done"):
                    final = msg
                    break
                elif msg.get("tokens"):
                    partial.extend(msg["tokens"])
                    rec["arrivals"].append((now, len(msg["tokens"])))
        rec["tokens"] = final.get("tokens", [])
        if final.get("status") != "ok":
            rec["error"] = f"status {final.get('status')}"
        elif partial != rec["tokens"]:
            rec["error"] = "streamed chunks differ from the final tokens"
        elif len(rec["tokens"]) != n_new:
            rec["error"] = f"{len(rec['tokens'])} tokens for {n_new} asked"
        else:
            rec["ok"] = True
    except (OSError, ValueError, http.client.HTTPException) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    return rec


class Clients:
    """The closed loop: each thread takes the next index of the plan."""

    def __init__(self, port: int, plan, n: int, annotate):
        self.port, self.plan, self.annotate = port, plan, annotate
        self.records: list = []
        self.stop = threading.Event()
        self._lock = threading.Lock()
        self._next = 0
        self.threads = [threading.Thread(target=self._loop, daemon=True,
                                         name=f"bench-client-{k}")
                        for k in range(n)]

    def _loop(self) -> None:
        while not self.stop.is_set():
            with self._lock:
                i, self._next = self._next, self._next + 1
            rec = send(self.port, i, *self.plan(i), self.annotate)
            with self._lock:
                self.records.append(rec)

    def start(self) -> None:
        for t in self.threads:
            t.start()

    def completed(self) -> int:
        with self._lock:
            return len(self.records)

    def finish(self, timeout_s: float) -> bool:
        """Let every client end its request in flight; True if all did."""
        self.stop.set()
        deadline = time.perf_counter() + timeout_s
        for t in self.threads:
            t.join(max(0.0, deadline - time.perf_counter()))
        return not any(t.is_alive() for t in self.threads)


def setup(run) -> dict:
    from mmlspark_tpu.models import ModelBundle
    from mmlspark_tpu.models.definitions import build_model
    from mmlspark_tpu.serve import ServeConfig, ServingEngine
    from mmlspark_tpu.serve.lifecycle import start_engine, start_http
    t, c = run.traffic, run.config["constructor"]
    module = build_model(run.config["architecture"], dict(c))
    shapes = lm.shapes_for(c)
    harness.same_tree(jax.eval_shape(
        module.init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 8), np.int32)), shapes)
    variables = harness.host_tree(weights.make_variables(shapes, run.seed))
    run.lap("imports_and_weights")
    e = dict(t["engine"])
    e["warmup_buckets"] = tuple(e["warmup_buckets"])
    engine = ServingEngine(ModelBundle.from_module(module, variables),
                           ServeConfig(**e))
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
    dims = (c["d_model"], c["n_layers"], c["vocab_size"],
            c.get("mlp_ratio", 4))
    sent = [r for r in clients.records if t0 <= r["t_send"] < t1]
    tokens, ops = 0, 0
    for r in clients.records:
        n_prompt, seen = len(r["prompt"]), 0
        for at, n in r["arrivals"]:
            if t0 <= at < t1:
                tokens += n
                if seen == 0:       # the prefill made the first token
                    ops += F.lm_forward_flops(0, n_prompt, *dims)
                ops += F.lm_forward_flops(n_prompt + max(seen, 1) - 1,
                                          n_prompt + seen + n - 1, *dims)
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
    run.obs.update(
        t0=t0, t1=t1, attempted=len(sent), failed=failed, notes=notes,
        end_to_end={"serve_tokens_per_s": tokens / (t1 - t0)},
        work={"serve_flops": ops},
        ttft_p95_ms=harness.percentile(ttft, 95.0) if ttft else None,
        token_gap_p95_ms=harness.percentile(gaps, 95.0) if gaps else None,
        compiles_in_window=compiles, counters=counters)
    state["sent"] = sent


def served_gaps(params, rec: dict, n_heads: int, length: int,
                control: bool = False) -> np.ndarray:
    """For each token the server produced for `rec`: how far its logit
    lies below the reference's best at that position, the reference run
    once over the prompt and the served tokens.  `control`: the same for
    the token that the fp8 reference puts first there."""
    n_prompt, n = len(rec["prompt"]), len(rec["tokens"])
    row = np.zeros((1, length), np.int32)
    row[0, :n_prompt] = rec["prompt"]
    row[0, n_prompt:n_prompt + n] = rec["tokens"]
    gaps = np.asarray(_gaps(params, row, n_heads, control))
    return gaps[n_prompt - 1:n_prompt + n - 1]


@jax.jit
def _gap_of(logits, picked):
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, picked[:, None], -1)[:, 0]
    return best - got


_forward = jax.jit(lm.forward, static_argnames=("n_heads", "mode", "remat"))


def _gaps(params, row, n_heads: int, control: bool):
    logits = _forward(params, row, n_heads=n_heads)[0, :-1]
    if control:
        low = _forward(params, row, n_heads=n_heads, mode="fp8")[0, :-1]
        picked = low.argmax(-1).astype(jnp.int32)
    else:
        picked = jnp.asarray(row[0, 1:])
    return _gap_of(logits, picked)


def _reference_gaps(run, records: list, control: bool):
    """`served_gaps` of each record, the reference's weights made once and
    every row padded to the mix's longest prompt and answer."""
    c = run.config["constructor"]
    params = weights.make_variables(lm.shapes_for(c), run.seed)["params"]
    length = run.traffic["prompt_len"][1] + run.traffic["new_tokens"][1]
    for rec in records:
        yield served_gaps(params, rec, c["n_heads"], length, control)


def pick(run, sent: list) -> list:
    """The requests compared: the longest that finished, and others drawn
    from the seed."""
    done = [r for r in sent if r["ok"]]
    if not done:
        return []
    done.sort(key=lambda r: -(len(r["prompt"]) + len(r["tokens"])))
    rng = np.random.default_rng(run.seed)
    n = min(run.traffic["check_requests"], len(done)) - 1
    rest = rng.choice(np.arange(1, len(done)), n, replace=False) \
        if n > 0 else []
    return [done[0]] + [done[i] for i in rest]


def stop(state: dict) -> None:
    from mmlspark_tpu.serve.lifecycle import stop_http
    state["engine"].stop()
    stop_http(state["server"])


def check(run, state: dict) -> dict:
    """The widest gap, over the served tokens of the picked requests, by
    which a served token's logit lies below the reference's best."""
    stop(state)
    picked = pick(run, state["sent"])
    state.clear()           # the engine, its caches and its weights
    gc.collect()
    widest = max((float(gaps.max())
                  for gaps in _reference_gaps(run, picked, control=False)),
                 default=float("inf"))
    run.obs["checked_tokens"] = sum(len(r["tokens"]) for r in picked)
    run.obs["kept"] = {"picked": picked}
    return {"served_gap": (widest, run.traffic["limits"]["served_gap"])}


def control(run) -> dict:
    """At each served position of the same prompts and tokens, the gap of
    the token that the float8 reference puts first (calibrate.py; no
    benchmark run computes this)."""
    return {"served_gap.fp8": max(
        float(gaps.max()) for gaps in _reference_gaps(
            run, run.obs["kept"]["picked"], control=True))}
