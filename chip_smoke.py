#!/usr/bin/env python3
"""chip_smoke.py: does the system still start on the chip?

One process drives the three main paths through the entry points a user
calls, at the full width of models the repo supports (depth cut, weights
random from a seed), and checks what comes out by the repo's own means:

  score  ResNet-50 @ 224x224x3, 1000 classes, through
         `TPUModel.transform(DataTable)` over >= 4 minibatches of uint8
         images with a ragged last batch; agrees with a direct
         `module.apply` on one batch.
  serve  TransformerLM (vocab 8192, d_model 1024, 8 heads of 128, 4 layers,
         bf16) behind `ServingEngine` -> `start_engine` -> `start_http`;
         real HTTP `POST /generate` (one streamed) over three prompt
         buckets; tokens byte-equal to `DecodeEngine.generate`.
  train  the same widths through `Trainer.fit_arrays` with the flash
         kernels, a few steps at seq 2048 on one repeated batch; the loss
         is finite and falls.

With more than one device the phases run on meshes that span all of them
(dp scoring and training, dp x mp training, model-parallel serving, one
ring-flash step under shard_map) and each asserts that every device
gained resident data.

It is not a benchmark: nothing here is a rate, and `"claim": null`.  It
fails (non-zero exit, no result line) off the TPU, when a phase raises,
when a Pallas kernel that should be in a compiled program is not, and when
either kernel module recorded a fallback.  Sizes are function arguments so
tests/test_chip_smoke.py can run the same phases tiny on the CPU.
"""

from __future__ import annotations

import gc
import http.client
import json
import os
import re
import shutil
import sys
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from mmlspark_tpu import DataTable
from mmlspark_tpu.models import DecodeEngine, ModelBundle, TPUModel
from mmlspark_tpu.models.definitions import build_model, resnet50
from mmlspark_tpu.ops import decode_attention, flash_attention
from mmlspark_tpu.parallel.mesh import MeshSpec, default_mesh, make_mesh
from mmlspark_tpu.parallel.partition import UNMATCHED_REPLICATE, shard_tree
from mmlspark_tpu.parallel.ring import make_seq_parallel_lm_step, shard_tokens
from mmlspark_tpu.serve import ServeConfig, ServingEngine
from mmlspark_tpu.serve.lifecycle import start_engine, start_http, stop_http
from mmlspark_tpu.train import Trainer, TrainerConfig

LM_WIDTHS = {"vocab_size": 8192, "d_model": 1024, "n_heads": 8,
             "n_layers": 4, "dtype": "bfloat16"}
MOSAIC_CALL = "tpu_custom_call"  # what a compiled Pallas TPU kernel lowers to


def device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def cache_entries() -> int:
    """Executables in the persistent compilation cache right now."""
    path = jax.config.jax_compilation_cache_dir
    if not os.path.isdir(path):
        return 0
    return sum(not name.endswith("-atime") for name in os.listdir(path))


class Harness:
    """What a run observes about JAX itself, with nothing added to the
    package: seconds spent lowering and compiling (jax.monitoring), and the
    lowered text of every program the process compiles (`jax_dump_ir_to`),
    which is where a phase looks for its kernels."""

    def __enter__(self) -> "Harness":
        self._lock = threading.Lock()
        self._compile_s = 0.0
        self._active = True
        self._prev_dump = jax.config.read("jax_dump_ir_to")
        self._dump_dir = tempfile.mkdtemp(prefix="chip_smoke_ir_")
        jax.config.update("jax_dump_ir_to", self._dump_dir)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        self._active = False  # jax.monitoring has no public unregister
        jax.config.update("jax_dump_ir_to", self._prev_dump)
        shutil.rmtree(self._dump_dir, ignore_errors=True)

    _COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                       "/jax/core/compile/backend_compile_duration")

    def _on_event(self, event: str, seconds: float, **_) -> None:
        # lowering + backend compile, one event each per program (a
        # persistent-cache load counts as the backend compile it replaces;
        # trace events nest inside one another, so they are left out)
        if self._active and event in self._COMPILE_EVENTS:
            with self._lock:
                self._compile_s += seconds

    def compile_seconds(self) -> float:
        with self._lock:
            return self._compile_s

    def mark(self) -> int:
        return len(os.listdir(self._dump_dir))

    def programs(self, since: int, name: str) -> list:
        """Lowered text of the programs named `jit_<name>` compiled after
        `mark()` returned `since`."""
        found = []
        for fname in sorted(os.listdir(self._dump_dir)):
            m = re.match(r"jax_ir(\d+)_jit_(.+)_compile\.mlir$", fname)
            if m and int(m.group(1)) >= since and m.group(2) == name:
                with open(os.path.join(self._dump_dir, fname)) as f:
                    found.append(f.read())
        return found


class _Phase:
    """Wall, compile and run seconds of one phase; `run_s` is the wall
    that JAX did not spend lowering or compiling."""

    def __init__(self, h: Harness):
        self.h = h

    def __enter__(self) -> "_Phase":
        gc.collect()  # earlier phases' device arrays must not count here
        self.resident0 = _resident_bytes()
        self.mark = self.h.mark()
        self.t0, self.c0 = time.perf_counter(), self.h.compile_seconds()
        return self

    def __exit__(self, *exc) -> None:
        self.wall_s = time.perf_counter() - self.t0
        self.compile_s = self.h.compile_seconds() - self.c0

    def seconds(self) -> dict:
        return {"wall_s": round(self.wall_s, 2),
                "compile_s": round(self.compile_s, 2),
                "run_s": round(self.wall_s - self.compile_s, 2)}

    def check_spread(self, label: str, mesh=None) -> None:
        """Every device of `mesh` (None: the default device alone) must
        hold more than it did when the phase began; 'everything on device
        0' fails here.  Call while the phase's arrays are alive."""
        devices = ([jax.devices()[0]] if mesh is None
                   else list(mesh.devices.flat))
        now = _resident_bytes()
        idle = [str(d) for d in devices if now[d] <= self.resident0[d]]
        if idle:
            raise AssertionError(
                f"{label}: devices {idle} hold no data from this phase "
                f"(of {len(devices)})")


def _resident_bytes() -> dict:
    """Bytes resident per device: the allocator's figure where the backend
    reports one (TPU), else the live arrays' addressable shards."""
    stats = {d: d.memory_stats() for d in jax.devices()}
    if all(s is not None for s in stats.values()):
        return {d: s["bytes_in_use"] for d, s in stats.items()}
    held = {d: 0 for d in jax.devices()}
    for arr in jax.live_arrays():
        for shard in arr.addressable_shards:
            held[shard.device] += shard.data.nbytes
    return held


def _mosaic_calls(text: str) -> int:
    return text.count(MOSAIC_CALL)


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


# ---------------------------------------------------------------------------
# score
# ---------------------------------------------------------------------------

def score_phase(h: Harness, module, image_hw: int, batch: int,
                rows: int, seed: int = 0) -> dict:
    """`rows` uint8 images through TPUModel on the default mesh (dp over
    every device), minibatches of `batch`, ragged tail."""
    if rows % batch == 0 or rows < 3 * batch:
        raise ValueError("rows must give >= 4 batches with a ragged last")
    with _Phase(h) as ph:
        bundle = ModelBundle.init(module, (1, image_hw, image_hw, 3),
                                  seed=seed)
        rng = np.random.default_rng(seed)
        images = rng.integers(0, 256, size=(rows, image_hw, image_hw, 3),
                              dtype=np.uint8)
        model = TPUModel(bundle, inputCol="image", outputCol="scores",
                         miniBatchSize=batch)
        scores = model.transform(DataTable({"image": images}))["scores"]
        ph.check_spread("score", default_mesh())
        # the reference: the module applied directly to the first batch
        direct = np.asarray(jax.jit(module.apply)(
            bundle.variables, images[:batch].astype(np.float32)))
    if scores.shape != (rows, module.num_classes):
        raise AssertionError(f"score: output shape {scores.shape}")
    if not np.isfinite(scores).all():
        raise AssertionError("score: non-finite scores")
    # bf16 compute, 8 mantissa bits through ~50 layers, and the mesh may
    # tile the batch differently from the one-device reference: agreement
    # is judged against the batch's own score range (a wrong row or a bad
    # pad is off by the whole range)
    err = float(np.abs(scores[:batch] - direct).max())
    span = float(np.abs(direct).max())
    if err > 5e-2 * span:
        raise AssertionError(
            f"score: transform and module.apply differ by {err} "
            f"(score range {span})")
    return {"rows": rows, "batches": -(-rows // batch),
            "mesh": dict(default_mesh().shape),
            "max_abs_err_vs_apply": err, "score_range": span,
            **ph.seconds()}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _http(port: int, method: str, path: str, body=None,
          timeout: float = 600.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        payload = None if body is None else json.dumps(body)
        conn.request(method, path, payload,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read().decode()
    finally:
        conn.close()


def _generate(port: int, prompt: np.ndarray, n_new: int,
              stream: bool) -> list:
    """One POST /generate; returns the tokens.  A streamed response is
    NDJSON: partial token lines, then the authoritative final line."""
    status, text = _http(port, "POST", "/generate", {
        "prompt": prompt.tolist(), "max_new_tokens": n_new,
        "deadline_ms": 300_000, "stream": stream})
    if status != 200:
        raise AssertionError(f"serve: POST /generate -> {status} {text}")
    if not stream:
        return json.loads(text)["tokens"]
    lines = [json.loads(line) for line in text.splitlines() if line]
    final = lines[-1]
    if not final.get("done") or final.get("status") != "ok":
        raise AssertionError(f"serve: stream ended with {final}")
    partial = [t for line in lines[:-1] for t in line.get("tokens", [])]
    if partial != final["tokens"]:
        raise AssertionError("serve: streamed chunks != final tokens")
    return final["tokens"]


def _signature(text: str) -> str:
    """The `@main` line of a lowered program: its argument/result types."""
    main = text[text.index("func.func public @main"):]
    return main[:main.index("\n")]


def _cache_window(text: str, module) -> int:
    """The widest KV window in a lowered decode program's signature: a
    leaf (B, W, H, D), or head-folded (B, W, H*D) where the engine keeps
    its windows so (`TransformerDecoding.folds`)."""
    dh = module.d_model // module.n_heads
    return max(int(w) for w in re.findall(
        rf"tensor<\d+x(\d+)x(?:{module.n_heads}x{dh}|{module.d_model})x",
        _signature(text)))


def _segment_windows(h: Harness, since: int, module) -> dict:
    """window -> Mosaic calls, over every decode-segment program (the
    serving engine's and the oracle's) compiled since `since`."""
    out: dict = {}
    for name in ("serve_segment_meshed", "segment_meshed"):
        for text in h.programs(since, name):
            w = _cache_window(text, module)
            out[w] = min(out.get(w, 1 << 30), _mosaic_calls(text))
    return out


TIE_ULPS = 3.0  # how far below the top logit a "tied" token may sit


def _tie_gap_ulps(module, variables, prompt, want: list, got: list) -> float:
    """Where `got` first leaves `want`: how far the lower of the two
    tokens sits below the top logit of the model's plain forward over the
    shared history, in units in the last place of the model dtype at the
    logits' magnitude.  Two differently-shaped programs may round a
    sharded matmul's partial sums in a different order, which is all it
    takes to pick the other of two tied tokens; a wide gap is a wrong
    answer."""
    t = next(i for i, (a, b) in enumerate(zip(want, got)) if a != b)
    history = np.concatenate([prompt, np.asarray(want[:t], np.int32)])
    logits = np.asarray(jax.jit(module.apply)(variables, history[None]))
    last = logits[0, -1]
    top = float(last.max())
    ulp = float(jnp.finfo(module.dtype).eps) * 2.0 ** np.floor(
        np.log2(max(abs(top), 1e-30)))
    return (top - float(min(last[want[t]], last[got[t]]))) / ulp


def serve_phase(h: Harness, lm_cfg: dict, prompt_lens: tuple,
                max_new_tokens: int, max_batch: int, mesh=None,
                seed: int = 0) -> dict:
    """Start the HTTP server, answer len(prompt_lens) requests (the first
    three one at a time, the second of them streamed; the rest at once, so
    late arrivals join a running batch), and hold every answer against the
    DecodeEngine oracle: byte for byte on one device; under a mesh an
    answer may leave the oracle's at a tie (`_tie_gap_ulps`)."""
    module = build_model("TransformerLM", lm_cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, module.vocab_size, n).astype(np.int32)
               for n in prompt_lens]
    with _Phase(h) as ph:
        bundle = ModelBundle.init(module, (1, 8), seed=seed)
        oracle = DecodeEngine(module, max_new_tokens, mesh=mesh)
        buckets = sorted({oracle.bucket_for(n) for n in prompt_lens})
        # warmup_joins: every late-join shape class compiles before
        # readiness, so which programs a run compiles does not depend on
        # how the concurrent arrivals happened to group
        engine = ServingEngine(bundle, ServeConfig(
            max_new_tokens=max_new_tokens, max_batch=max_batch,
            warmup_buckets=tuple(buckets), warmup_joins=True), mesh=mesh)
        c0 = h.compile_seconds()
        t0 = time.perf_counter()
        start_engine(engine, install_sigterm=False)
        warmup_s = time.perf_counter() - t0
        warmup_compile_s = h.compile_seconds() - c0
        server = start_http(engine, port=0)
        try:
            port = server.server_address[1]
            answers: list = [None] * len(prompts)

            def ask(i: int) -> None:
                answers[i] = _generate(port, prompts[i], max_new_tokens,
                                       stream=(i == 1))

            t0, c0 = time.perf_counter(), h.compile_seconds()
            for i in range(min(3, len(prompts))):
                ask(i)
            late = [threading.Thread(target=ask, args=(i,), daemon=True)
                    for i in range(3, len(prompts))]
            for t in late:
                t.start()
            for t in late:
                t.join(600.0)
            requests_s = time.perf_counter() - t0
            requests_compile_s = h.compile_seconds() - c0
            if any(a is None for a in answers):
                raise AssertionError("serve: a request did not return")
            health, _ = _http(port, "GET", "/healthz")
            status, statz = _http(port, "GET", "/statz")
            stats = json.loads(statz)
            if health != 200 or status != 200 \
                    or stats.get("ok") != len(prompts):
                raise AssertionError(
                    f"serve: healthz {health}, statz {status} {stats}")
            ph.check_spread("serve", mesh)
        finally:
            engine.stop()
            stop_http(server)
        if engine.state != "stopped":
            raise AssertionError(f"serve: engine is {engine.state}")

        # the oracle: the same bundle through DecodeEngine.generate, one
        # request at a time on its zero-padded bucket
        if mesh is None:
            variables = bundle.variables
        else:
            variables = shard_tree(bundle.variables, mesh,
                                   bundle.partition_rules(),
                                   on_unmatched=UNMATCHED_REPLICATE)

        def padded(prompt):
            row = np.zeros((1, oracle.bucket_for(len(prompt))), np.int32)
            row[0, :len(prompt)] = prompt
            return row, np.asarray([len(prompt)], np.int32)

        ties = 0
        for i, prompt in enumerate(prompts):
            want = oracle.generate(variables, *padded(prompt))[0].tolist()
            if answers[i] == want:
                continue
            gap = None
            if mesh is not None and len(answers[i]) == len(want):
                gap = _tie_gap_ulps(module, variables, prompt, want,
                                    answers[i])
            if gap is None or gap > TIE_ULPS:
                raise AssertionError(
                    f"serve: request {i} (prompt {len(prompt)}) returned "
                    f"{answers[i]}, DecodeEngine.generate gives {want} "
                    f"(gap at the first difference: {gap} ulps)")
            ties += 1
        # once with the int8 cache (sublane tile 32, per-slot scale blocks)
        # on a bucket whose first window is an odd multiple of the chunk
        odd = next((p for p in prompts if oracle.serve_window(
            oracle.bucket_for(len(p)), 0, 1) // oracle.chunk % 2),
            prompts[0])
        int8_toks = DecodeEngine(module, max_new_tokens, mesh=mesh,
                                 cache_dtype="int8").generate(
            variables, *padded(odd))[0]
        if int8_toks.min() < 0 or int8_toks.max() >= module.vocab_size:
            raise AssertionError("serve: int8-cache tokens out of range")

    windows = _segment_windows(h, ph.mark, module)
    prefill = {}
    for text in h.programs(ph.mark, "prefill_meshed"):
        p = int(re.search(r"tensor<\d+x(\d+)xi32>",
                          _signature(text)).group(1))
        prefill[p] = min(prefill.get(p, 1 << 30), _mosaic_calls(text))
    if _on_tpu():
        from mmlspark_tpu.models.hybrid_lm import PREFILL_FLASH_MIN
        missing = [p for p, n in prefill.items()
                   if p >= PREFILL_FLASH_MIN and n < module.n_layers]
        if missing:
            raise AssertionError(
                f"serve: prefill at {missing} compiled without the flash "
                f"kernel ({prefill})")
        # under a mesh the engine reads the cache with the einsum GSPMD
        # can partition (DecodeEngine: fused = mesh is None), so the
        # fused kernel is only owed on one device
        if mesh is None:
            bare = [w for w, n in windows.items() if n < module.n_layers]
            if bare:
                raise AssertionError(
                    f"serve: decode windows {bare} compiled without the "
                    f"fused kernel ({windows})")
    return {"requests": len(prompts), "byte_exact": len(prompts) - ties,
            "left_oracle_at_a_tie": ties, "buckets": buckets,
            "mesh": dict(mesh.shape) if mesh is not None else None,
            "prefill_mosaic_calls": prefill,
            "decode_window_mosaic_calls": windows,
            "warmup_s": round(warmup_s, 2),
            "warmup_compile_s": round(warmup_compile_s, 2),
            "requests_s": round(requests_s, 2),
            "requests_compile_s": round(requests_compile_s, 2),
            "latency_p50_s": stats.get("latency_p50_s"),
            **ph.seconds()}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_phase(h: Harness, lm_cfg: dict, seq: int, batch: int, steps: int,
                mesh_spec: MeshSpec = MeshSpec(),
                tensor_parallel: bool = False, seed: int = 0) -> dict:
    """`steps` optimizer steps on one repeated batch through
    Trainer.fit_arrays (one step an epoch), flash attention fwd + bwd."""
    cfg = TrainerConfig(
        architecture="TransformerLM",
        model_config={**lm_cfg, "max_len": seq, "attn_impl": "flash"},
        optimizer="adam", learning_rate=1e-3, loss="softmax_xent",
        batch_size=batch, epochs=steps, seed=seed, mesh=mesh_spec,
        tensor_parallel=tensor_parallel)
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, lm_cfg["vocab_size"],
                          (batch, seq)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    with _Phase(h) as ph:
        trainer = Trainer(cfg)
        trainer.fit_arrays(tokens, targets)
        ph.check_spread("train", trainer.mesh)
        losses = [float(r["loss"]) for r in trainer.history]
    if len(losses) != steps or not np.isfinite(losses).all():
        raise AssertionError(f"train: losses {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"train: loss did not fall: {losses}")
    step_texts = (h.programs(ph.mark, "meshed_step")
                  + h.programs(ph.mark, "plain_step"))
    calls = min(map(_mosaic_calls, step_texts))
    # forward, dQ and dK/dV: three kernels a layer
    if _on_tpu() and calls < 3 * lm_cfg["n_layers"]:
        raise AssertionError(
            f"train: the step holds {calls} Mosaic calls, flash fwd+bwd "
            f"needs {3 * lm_cfg['n_layers']}")
    return {"mesh": dict(trainer.mesh.shape),
            "tensor_parallel": tensor_parallel, "seq": seq, "batch": batch,
            "losses": [round(x, 4) for x in losses],
            "step_mosaic_calls": calls, **ph.seconds()}


def ring_phase(h: Harness, lm_cfg: dict, seq: int, batch: int,
               seq_shards: int, seed: int = 0) -> dict:
    """One ring-flash LM step with the sequence split over `seq_shards`
    devices: pallas_call inside a shard_map manual region."""
    mesh = make_mesh(MeshSpec(data=-1, seq=seq_shards))
    cfg = {**lm_cfg, "max_len": seq}
    ring = build_model("TransformerLM", {**cfg, "attn_impl": "ring_flash",
                                         "seq_axis": "seq"})
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg["vocab_size"],
                          (batch, seq)).astype(np.int32)
    with _Phase(h) as ph:
        # parameters come from the dense twin: the ring model's forward
        # needs the seq axis in scope, its parameter tree is the same
        params = build_model("TransformerLM", cfg).init(
            jax.random.key(seed), tokens[:1, :seq // seq_shards])
        tx = optax.adam(1e-3)
        step = make_seq_parallel_lm_step(ring, tx, mesh)
        toks = shard_tokens(tokens, mesh)
        tgts = shard_tokens(np.roll(tokens, -1, axis=1), mesh)
        mask = shard_tokens(np.ones(tokens.shape, np.float32), mesh)
        params, _, loss = step(params, tx.init(params), toks, tgts, mask)
        loss = float(loss)
        ph.check_spread("ring", mesh)
    if not np.isfinite(loss):
        raise AssertionError(f"ring: loss {loss}")
    calls = min(map(_mosaic_calls, h.programs(ph.mark, "step")))
    if _on_tpu() and calls < 3 * lm_cfg["n_layers"]:
        raise AssertionError(
            f"ring: the step holds {calls} Mosaic calls, ring-flash "
            f"fwd+bwd needs {3 * lm_cfg['n_layers']}")
    return {"mesh": dict(mesh.shape), "seq": seq, "batch": batch,
            "loss": round(loss, 4), "step_mosaic_calls": calls,
            **ph.seconds()}


# ---------------------------------------------------------------------------

def main() -> int:
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev}", file=sys.stderr)
        return 1

    def emit(phase: str, **fields) -> None:
        print(json.dumps({"phase": phase, **dev, **fields}), flush=True)

    n = dev["count"]
    entries0 = cache_entries()
    emit("start", jax=jax.__version__,
         cache_dir=jax.config.jax_compilation_cache_dir,
         cache_entries=entries0)
    lm = dict(LM_WIDTHS, max_len=2048)
    prompt_lens = (100, 200, 700, 65, 250, 900, 128, 256)
    with Harness() as h:
        emit("score", **score_phase(h, resnet50(), 224, 256, 868))
        mesh = None if n == 1 else make_mesh(MeshSpec(data=1, model=n))
        emit("serve", **serve_phase(h, lm, prompt_lens, 24, 4, mesh=mesh))
        emit("train", **train_phase(h, LM_WIDTHS, 2048, 8, 5))
        if n > 1:
            emit("train", **train_phase(
                h, LM_WIDTHS, 2048, 8, 5,
                mesh_spec=MeshSpec(data=n // 2, model=2),
                tensor_parallel=True))
            emit("ring", **ring_phase(h, LM_WIDTHS, 2048 * n, 2, n))
        compile_s = h.compile_seconds()
    fallbacks = sorted(map(str, flash_attention._warned_fallbacks
                           | decode_attention._warned_fallbacks))
    entries1 = cache_entries()
    emit("end", compile_s=round(compile_s, 2), cache_entries=entries1,
         cache_entries_added=entries1 - entries0,
         kernel_fallbacks=fallbacks, claim=None)
    if fallbacks:
        print(f"chip_smoke: kernel fallbacks fired: {fallbacks}",
              file=sys.stderr)
        return 1
    # the result line: these two keys and no others (the driver's contract)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
