"""TPUModel: distributed DNN scoring as a pipeline Transformer.

The centerpiece replacement for the reference's CNTKModel
(CNTKModel.scala:174-228): where the reference broadcasts model bytes to
Spark executors and runs a per-partition JNI minibatch loop with four
JVM<->C++ copies per batch (applyModel, CNTKModel.scala:29-105), TPUModel
compiles the forward function once with `jit`, replicates weights into HBM
across a device mesh, and streams zero-padded fixed-shape minibatches through
it — each device computing its shard of the batch, with XLA handling layout
and (on multi-chip meshes) ICI transfers.

Node selection (`outputNodeName` / `outputNodeIndex`, reference
CNTKModel.scala:151-168, 185-193) resolves against the module's sown named
nodes at trace time; unused heads are dead-code-eliminated by XLA, so scoring
an early layer (ImageFeaturizer's layer cutting) costs only the truncated
graph.
"""

from __future__ import annotations

import contextlib
from typing import Any, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from mmlspark_tpu.core.params import Param
from mmlspark_tpu.core.pipeline import Transformer
from mmlspark_tpu.core.table import DataTable
from mmlspark_tpu.models.bundle import ModelBundle, load_bundle, save_bundle
from mmlspark_tpu.observe.compiles import setup_phase
from mmlspark_tpu.observe.costmodel import capture_program_cost
from mmlspark_tpu.observe.spans import active_timings, span_on
from mmlspark_tpu.observe.telemetry import active_run
from mmlspark_tpu.observe.trace import (active_tracer, current_span_id,
                                        span_on_tracer)
from mmlspark_tpu.parallel.bridge import (pad_to_multiple, put_sharded,
                                          replicate_tree, reshard)
from mmlspark_tpu.parallel.mesh import (MODEL_AXIS, batch_sharding,
                                        default_mesh, replicated)
from mmlspark_tpu.parallel.partition import (UNMATCHED_REPLICATE, shard_tree,
                                             use_mesh)
from mmlspark_tpu.data import Dataset
from mmlspark_tpu.parallel.prefetch import OncePerTable, resolve_depth


# what a batch of a shape class already scored runs in (`_first_call`)
_SEEN = contextlib.nullcontext()


class TPUModel(Transformer):
    """Score a table column through a compiled model over the device mesh.

    Quantized bundles (quant/quantize.py) score transparently: int8
    bundles run each registered layer's fused int8-weight forward
    (weights stay int8 in HBM; dequant is part of the compiled program),
    bf16 bundles compute natively at bf16.  Un-quantized bundles get bf16
    MXU rates via the `computeDtype` Param; either way the output column
    is float32 at the table boundary.
    """

    inputCol = Param(None, "input column (numeric array per row)", ptype=str)
    outputCol = Param("output", "output column for scores", ptype=str)
    miniBatchSize = Param(
        256, "rows per compiled step; last batch is zero-padded "
        "(reference default was 10, CNTKModel.scala:164-168 — TPU batches "
        "are wide to keep the MXU fed)", ptype=int,
        validator=lambda v: v > 0)
    outputNodeName = Param(None, "named node to output (None = final)", ptype=str)
    outputNodeIndex = Param(None, "index into the ordered named nodes", ptype=int)
    prefetchDepth = Param(
        None, "pipeline depth: staged batches in flight (host prep + "
        "device_put overlap the compiled forward); None defers to "
        "MMLSPARK_TPU_PREFETCH_DEPTH, positive values pin the depth, "
        "0 hands it to the data-layer Autotuner (parallel/prefetch."
        "resolve_depth), -1 disables overlap entirely (synchronous "
        "per-batch round trips — the pre-autotuner meaning of 0)",
        ptype=int, validator=lambda v: v >= -1)
    computeDtype = Param(
        None, "compute-dtype override for the compiled forward: 'bfloat16' "
        "runs an un-quantized float32 bundle at bf16 MXU rates, 'float32' "
        "forces exact f32; None keeps the bundle module's own dtype.  When "
        "an override (or a quantized bundle) is active, outputs are cast "
        "back to float32 at the table boundary", ptype=str,
        domain=("float32", "bfloat16"))

    def __init__(self, bundle: Optional[ModelBundle] = None, **kwargs):
        super().__init__(**kwargs)
        self._bundle = bundle
        self._mesh = None
        self._device_vars: dict[Any, Any] = {}   # per-mesh replicated weights
        self._compiled: dict[tuple, Any] = {}    # per-(mesh, node) apply fns
        self._seen_shapes: set = set()           # batch shape classes scored
        # (jit specializes per shape class: a NEW key here is a recompile,
        # surfaced as a telemetry `compile` event and counted as a gauge)
        self._program_costs: dict[str, dict] = {}  # shape class -> cost row
        # (captured once at the recompile; replayed into every later
        # run_telemetry block, so a warm model's steady-state runs still
        # get roofline rows without paying a fresh AOT capture)
        self._called_shapes: set = set()    # (shape, dtype) of the batches
        # scored, traced or not: a NEW one enters `setup.score_program`

    # -- model/mesh wiring ---------------------------------------------
    def set_bundle(self, bundle: ModelBundle) -> "TPUModel":
        self._bundle = bundle
        self._device_vars.clear()
        self._compiled.clear()
        self._seen_shapes.clear()
        self._called_shapes.clear()
        self._program_costs.clear()
        return self

    @property
    def bundle(self) -> Optional[ModelBundle]:
        return self._bundle

    def set_mesh(self, mesh) -> "TPUModel":
        self._mesh = mesh
        self._device_vars.clear()
        self._compiled.clear()
        self._seen_shapes.clear()
        self._called_shapes.clear()
        self._program_costs.clear()
        return self

    def _get_mesh(self):
        if self._mesh is None:
            # best_mesh() (dp-only) unless the MMLSPARK_TPU_MESH_* knobs
            # ask for a dp x mp topology (parallel/mesh.default_mesh)
            self._mesh = default_mesh()
        return self._mesh

    @staticmethod
    def _mesh_is_multiprocess(mesh) -> bool:
        """Dispatch rule: the MESH decides the scoring topology, not
        `jax.process_count()`.  A mesh spanning processes takes the lockstep
        global path (`_transform_multihost`: every process dispatches the
        same step count, collectives stay aligned); a local-devices mesh —
        the `best_mesh()` default under multi-host — scores this process's
        rows independently with the ordinary windowed loop, because scoring
        over row partitions is embarrassingly parallel (the reference's
        per-executor eval loop, CNTKModel.scala:215-221) and needs no
        cross-host collectives or lockstep batching."""
        return len({d.process_index for d in mesh.devices.flat}) > 1

    # -- forward construction ------------------------------------------
    def _select_output(self, final, intermediates: dict):
        name = self.outputNodeName
        idx = self.outputNodeIndex
        nodes = {k: v[0] if isinstance(v, tuple) else v
                 for k, v in intermediates.items()}
        if name is not None:
            if name not in nodes:
                raise KeyError(
                    f"model has no node '{name}'; nodes: {list(nodes)}")
            return nodes[name]
        if idx is not None:
            keys = list(nodes)
            if idx >= len(keys):
                raise IndexError(
                    f"outputNodeIndex {idx} out of range; nodes: {keys}")
            return nodes[keys[idx]]
        return final

    def _quant_mode(self):
        """'bf16' / 'int8' for a quantized bundle (quant/quantize.py
        metadata contract), None for a plain one."""
        if self._bundle is None:
            return None
        return ((self._bundle.metadata or {}).get("quantization")
                or {}).get("mode")

    def _scoring_module(self):
        """The module the compiled forward applies: the bundle's, with its
        compute dtype rebuilt to `computeDtype` when the Param is set (and
        the architecture has a dtype field — custom registered models
        without one keep their own)."""
        module = self._bundle.module()
        cd = self.computeDtype
        if cd is not None and "dtype" in getattr(
                module, "__dataclass_fields__", {}):
            from mmlspark_tpu.models.definitions import build_model
            module = build_model(self._bundle.architecture,
                                 {**self._bundle.config, "dtype": cd})
        return module

    def _make_apply(self, mesh, variables):
        module = self._scoring_module()
        quant_mode = self._quant_mode()
        # an explicit dtype override or a quantized bundle computes in a
        # reduced precision internally; the table boundary stays float32
        cast_f32 = self.computeDtype is not None or quant_mode is not None
        if quant_mode == "int8":
            from mmlspark_tpu.quant import quantized_call
        else:
            from contextlib import nullcontext as quantized_call

        def forward(vars_, x):
            # uint8 inputs (decoded image bytes) travel the host->HBM link
            # at 1/4 the bytes of float32 and are cast on device — the
            # transfer link is the scoring bottleneck, not the MXU.  Wider
            # integer dtypes are NOT cast: they are token ids (TransformerLM
            # and friends embed them; a float cast would break Embed)
            if x.dtype == jnp.uint8:
                x = x.astype(jnp.float32)
            # int8 bundles: layers whose params carry the int8 layout run
            # their fused wrappers (quant/modules.py) — weights stay int8
            # in HBM, dequant lives inside this compiled program.
            # use_mesh scopes the TRACE: shard_constraint hints in the
            # forward (attention heads / MLP hidden on 'model') bake this
            # mesh into the compiled program; no-ops on a 1-D mesh
            with use_mesh(mesh), quantized_call():
                out, state = module.apply(vars_, x, mutable=["intermediates"])
            inter = state.get("intermediates", {})
            inter = {k: v for k, v in inter.items() if not isinstance(v, dict)}
            out = self._select_output(out, inter)
            if cast_f32 and jnp.issubdtype(out.dtype, jnp.floating):
                out = out.astype(jnp.float32)
            return out

        # weights enter under whatever layout _device_state placed them
        # in (replicated on dp-only meshes, rule-sharded at mp >= 2), so
        # the compiled program never silently re-gathers a sharded tree
        var_shardings = jax.tree_util.tree_map(
            lambda a: a.sharding if isinstance(a, jax.Array)
            else replicated(mesh), variables)
        return jax.jit(
            forward,
            in_shardings=(var_shardings, batch_sharding(mesh)),
            out_shardings=batch_sharding(mesh),
        )

    def _device_state(self):
        """Mesh, replicated variables, and the compiled step (cached).

        Weights are replicated once per mesh; node selections share them
        (only the compiled apply differs per node).  Caches key on the Mesh
        itself (hashable, equality by devices+axes) — an `id()` key could
        alias a dead mesh's entry to a new mesh after GC reuses the address.
        """
        if self._bundle is None:
            raise ValueError("TPUModel has no model bundle; call set_bundle()")
        mesh = self._get_mesh()
        if mesh not in self._device_vars:
            if mesh.shape.get(MODEL_AXIS, 1) > 1:
                # tensor-parallel scoring: weights follow the bundle's
                # own partition rules (metadata round-trip) — or
                # DEFAULT_RULES for a pre-partition bundle — instead of
                # replicating, so each chip holds 1/mp of the matched
                # kernels (the dp-only HBM cap lifts)
                self._device_vars[mesh] = shard_tree(
                    self._bundle.variables, mesh,
                    self._bundle.partition_rules(),
                    on_unmatched=UNMATCHED_REPLICATE)
            else:
                self._device_vars[mesh] = replicate_tree(
                    self._bundle.variables, mesh)
        variables = self._device_vars[mesh]
        key = (mesh, self.outputNodeName, self.outputNodeIndex,
               self.computeDtype)
        if key not in self._compiled:
            self._compiled[key] = self._make_apply(mesh, variables)
        return mesh, variables, self._compiled[key]

    def _first_call(self, dev):
        """The context a batch's call runs in: for the first batch of a
        shape class the phase `setup.score_program` (jit specializes per
        class, so that call traces, lowers and compiles or loads a
        program), for every later one `_SEEN`, which does nothing."""
        cls = (dev.shape, dev.dtype)
        if cls in self._called_shapes:
            return _SEEN
        self._called_shapes.add(cls)
        return setup_phase("score_program",
                           shape_class=f"{tuple(dev.shape)}:{dev.dtype}")

    def _effective_batch_size(self, mesh) -> int:
        """miniBatchSize rounded down to a data-axis multiple (floor at one
        row per data shard); all dispatch entry points must agree on it."""
        bs = max(self.miniBatchSize, mesh.shape["data"])
        return bs - bs % mesh.shape["data"] or mesh.shape["data"]

    def _prefetch_depth(self) -> int:
        """The pipeline depth every dispatch loop uses: the Param when set,
        else the MMLSPARK_TPU_PREFETCH_DEPTH config default — resolved
        through the shared knob contract, so 0 (autotune) yields the
        autotuner's floor and -1 yields 0 (synchronous)."""
        return resolve_depth(self.prefetchDepth)[0]

    @staticmethod
    def _tensor_column(col: np.ndarray) -> np.ndarray:
        if col.dtype == object:
            if not len(col):
                return np.zeros((0, 1), np.float32)
            stacked = np.stack([np.asarray(v) for v in col])
            # integer rows stay integer (token ids feeding Embed layers);
            # everything else normalizes to float32 as before
            if np.issubdtype(stacked.dtype, np.integer):
                return stacked
            return stacked.astype(np.float32)
        return col

    # -- transform ------------------------------------------------------
    def transform(self, table: DataTable) -> DataTable:
        self._check_required()
        in_col = self.inputCol
        if in_col is None:
            raise ValueError("TPUModel: inputCol is not set")
        # CheckpointData may have pre-staged this column in device memory
        # (stages/basic.py); repeated passes then skip the host->HBM transfer.
        dev_col = getattr(table, "_device_cache", {}).get(in_col)
        mesh, variables, apply_fn = self._device_state()
        multiproc = self._mesh_is_multiprocess(mesh)
        if dev_col is None and not multiproc:
            # ONE canonical pipelined dispatch loop (transform_batches):
            # a single table is a one-element stream.  Delegate BEFORE any
            # column conversion so the work isn't done twice.
            [scored] = list(self.transform_batches([table]))
            return scored
        col = self._tensor_column(table[in_col])
        bs = self._effective_batch_size(mesh)
        if multiproc:
            result = self._transform_multihost(col, mesh, variables,
                                               apply_fn, bs)
            return table.with_column(self.outputCol, result)
        sharding = batch_sharding(mesh)

        # CheckpointData fast path: the column is already HBM-resident —
        # batches are on-device slices (a no-op re-shard when CheckpointData
        # staged with the mesh batch sharding, stages/basic.py), with the
        # same windowed async-fetch pipeline as the streaming loop.  The
        # cached array may carry divisibility padding, so valid counts come
        # from the HOST column's length, never the device shape.
        window = self._prefetch_depth()
        timings = active_timings()
        tracer = active_tracer()
        run = active_run()
        n = len(col)
        in_flight: list[tuple[Any, int]] = []
        results: list[np.ndarray] = []

        def drain(count: int):
            while len(in_flight) > count:
                out, valid = in_flight.pop(0)
                with span_on(timings, "drain"):
                    results.append(np.asarray(out)[:valid])

        for start in range(0, n, bs):
            valid = min(bs, n - start)
            with span_on(timings, "transfer"):
                chunk = dev_col[start:start + bs]
                if int(chunk.shape[0]) < bs:
                    pad = [(0, bs - int(chunk.shape[0]))] \
                        + [(0, 0)] * (chunk.ndim - 1)
                    chunk = jnp.pad(chunk, pad)
                dev = reshard(chunk, sharding)  # on-device reshard
            first_call = self._first_call(dev)
            if tracer is None:
                with first_call, span_on(timings, "compute"):
                    out = apply_fn(variables, dev)
            else:
                key = f"{tuple(dev.shape)}:{dev.dtype}"
                with first_call:    # round the probe too: it compiles
                    if key not in self._seen_shapes:
                        self._seen_shapes.add(key)
                        tracer.event("recompile", parent=current_span_id(),
                                     cat="compile", where="tpu_model",
                                     shape_class=key)
                        rec = capture_program_cost(
                            apply_fn, (variables, dev), where="tpu_model",
                            program=key, run=run, probe=True)
                        if rec is not None:
                            self._program_costs[key] = rec
                    with tracer.span("score.batch",
                                     parent=current_span_id(), cat="batch",
                                     shape_class=key, rows=valid,
                                     device_cached=True) as bsp, \
                            span_on(timings, "compute"):
                        out = apply_fn(variables, dev)
                if run is not None:
                    # dispatch wall only (async) — the roofline uses the
                    # capture probe's synced step time instead.  The cost
                    # row is replayed from the model's remembered capture
                    # so runs over a warm model (no recompile) still get
                    # roofline rows (record_program_cost is idempotent)
                    if key in self._program_costs:
                        run.record_program_cost("tpu_model", key,
                                                self._program_costs[key])
                    run.add_program_time("tpu_model", key, bsp.elapsed(),
                                         basis="dispatch")
            try:
                out.copy_to_host_async()
            except (AttributeError, RuntimeError):
                pass  # committed-to-host backends need no prefetch
            in_flight.append((out, valid))
            drain(window)
        drain(0)
        if run is not None:
            run.gauge("tpu_model.compiled_programs", len(self._compiled))
            run.gauge("tpu_model.shape_classes", len(self._seen_shapes))
        if results:
            result = np.concatenate(results, axis=0)
        else:
            result = self._empty_output(col, variables, apply_fn, bs)
        return table.with_column(self.outputCol, result)

    def _empty_output(self, col, variables, apply_fn, bs: int) -> np.ndarray:
        """Zero-row result preserving the model's output shape/dtype."""
        var_shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), variables)
        out_shape = jax.eval_shape(
            apply_fn, var_shapes,
            jax.ShapeDtypeStruct((bs,) + col.shape[1:], col.dtype))
        return np.zeros((0,) + out_shape.shape[1:], out_shape.dtype)

    def transform_batches(self, tables) -> Iterator[DataTable]:
        """Streaming scoring: for each incoming table (e.g. from
        `read_images_iter`) yield it back with the output column appended.

        Out-of-core by construction — only the dispatch window's batches are
        resident on host or in HBM, so corpus size is unbounded (reference
        BinaryFileReader.scala:28-69 streams partitions the same way).  The
        pipelined window is kept OPEN across table boundaries: the
        transfer link never drains between tables, unlike calling
        `transform` per table, which would pay a full round-trip flush each
        time (ruinous over high-latency links).

        The host half of every batch — `_tensor_column` stacking, padding,
        and the host->HBM `device_put` — runs on a `Dataset` map stage's
        worker threads, overlapping the compiled forward of earlier
        batches; the dispatch thread only launches `apply_fn` and drains
        results.  `prefetchDepth` bounds staged + in-flight batches
        (backpressure): positive pins the window, 0 lets the data-layer
        Autotuner size it from measured stalls, and -1 collapses to the
        serial alternating loop.
        """
        self._check_required()
        in_col = self.inputCol
        if in_col is None:
            raise ValueError("TPUModel: inputCol is not set")
        mesh, variables, apply_fn = self._device_state()
        bs = self._effective_batch_size(mesh)
        if self._mesh_is_multiprocess(mesh):
            # per-table lockstep path (no cross-table window: every process
            # must agree on dispatch order)
            for table in tables:
                yield self.transform(table)
            return
        sharding = batch_sharding(mesh)
        timings = active_timings()  # captured HERE: workers have no context
        # telemetry handles, captured by the same closure rule: the tracer
        # and the phase span id travel into the staging workers by value
        tracer = active_tracer()
        run = active_run()
        score_span = tracer.span(
            "score.transform_batches", parent=current_span_id(),
            cat="phase", batch_size=bs) if tracer is not None else None
        score_id = score_span.span_id if score_span is not None else None
        in_flight: list[tuple[Any, int, dict]] = []
        ready: list[DataTable] = []
        pending: list[dict] = []

        def plans():
            # one item per minibatch, in strict (table, batch) order; the
            # expensive np.stack is NOT done here — each table carries a
            # OncePerTable so the first staged batch pays it once, on a
            # staging thread
            for table in tables:
                n = len(table[in_col])
                column = OncePerTable(
                    lambda t=table: self._tensor_column(t[in_col]))
                if n == 0:
                    yield ("empty", {"table": table}, column, 0)
                    continue
                rec = {"table": table, "parts": [], "n_left": -(-n // bs)}
                for start in range(0, n, bs):
                    yield ("batch", rec, column, start)

        def stage(item):
            kind, rec, column, start = item
            if kind == "empty":
                rec["n_left"] = 0
                rec["parts"] = [self._empty_output(
                    column.get(), variables, apply_fn, bs)]
                return ("empty", rec, None, 0)
            with span_on_tracer(tracer, "score.stage", parent=score_id,
                                cat="stage"):
                with span_on(timings, "host"):
                    col = column.get()
                    chunk, valid = pad_to_multiple(col[start:start + bs], bs)
                with span_on(timings, "transfer"):
                    dev = put_sharded(chunk, sharding)
            return ("batch", rec, dev, valid)

        def drain(limit: int):
            while len(in_flight) > limit:
                out, valid, rec = in_flight.pop(0)
                with span_on(timings, "drain"):
                    rec["parts"].append(np.asarray(out)[:valid])
                rec["n_left"] -= 1
            while pending and pending[0]["n_left"] == 0:
                rec = pending.pop(0)
                result = (rec["parts"][0] if len(rec["parts"]) == 1
                          else np.concatenate(rec["parts"], axis=0))
                ready.append(
                    rec["table"].with_column(self.outputCol, result))

        staged = (Dataset.from_iterable(plans)
                  .map(stage, name="score", depth=self.prefetchDepth,
                       span=None)
                  .iterator())
        # the device in-flight window follows the staging depth LIVE, so
        # an autotuner widen deepens dispatch pipelining in the same step
        score_runner = staged.stage("score").runner
        try:
            for kind, rec, dev, valid in staged:
                if rec.get("queued") is None:
                    # first staged batch of this record: results arrive in
                    # plan order, so pending stays in table order
                    rec["queued"] = True
                    pending.append(rec)
                if kind == "empty":
                    # an empty record rides the ordered pending queue with
                    # its result pre-filled — flush only finished records
                    # (an interleaved empty table must not stall the
                    # cross-table pipeline)
                    drain(len(in_flight))
                else:
                    first_call = self._first_call(dev)
                    if tracer is None:
                        with first_call, span_on(timings, "compute"):
                            out = apply_fn(variables, dev)
                    else:
                        # the span walls the DISPATCH (async — no sync is
                        # added), which is where jit pays compilation: a
                        # new shape class shows as a long batch span plus
                        # an explicit `compile` event
                        key = f"{tuple(dev.shape)}:{dev.dtype}"
                        with first_call:    # round the probe: it compiles
                            if key not in self._seen_shapes:
                                self._seen_shapes.add(key)
                                tracer.event(
                                    "recompile", parent=score_id,
                                    cat="compile", where="tpu_model",
                                    shape_class=key)
                                cost_rec = capture_program_cost(
                                    apply_fn, (variables, dev),
                                    where="tpu_model", program=key, run=run,
                                    probe=True)
                                if cost_rec is not None:
                                    self._program_costs[key] = cost_rec
                            with tracer.span(
                                    "score.batch", parent=score_id,
                                    cat="batch", shape_class=key,
                                    rows=valid) as bsp, \
                                    span_on(timings, "compute"):
                                out = apply_fn(variables, dev)
                        if run is not None:
                            # dispatch wall (async); roofline prefers the
                            # capture probe's synced step time.  The cost
                            # row is replayed from the model's remembered
                            # capture so warm-model runs (no recompile)
                            # still get roofline rows (idempotent)
                            if key in self._program_costs:
                                run.record_program_cost(
                                    "tpu_model", key,
                                    self._program_costs[key])
                            run.add_program_time("tpu_model", key,
                                                 bsp.elapsed(),
                                                 basis="dispatch")
                    try:
                        out.copy_to_host_async()
                    except (AttributeError, RuntimeError):
                        pass
                    in_flight.append((out, valid, rec))
                    drain(score_runner.depth)
                while ready:
                    yield ready.pop(0)
            drain(0)
            while ready:
                yield ready.pop(0)
        finally:
            staged.close()
            if score_span is not None:
                score_span.finish()
            if run is not None:
                run.gauge("tpu_model.compiled_programs",
                          len(self._compiled))
                run.gauge("tpu_model.shape_classes",
                          len(self._seen_shapes))

    def _transform_multihost(self, col, mesh, variables, apply_fn,
                             bs: int) -> np.ndarray:
        """Scoring under process_count > 1: each process feeds its LOCAL
        table partition (the same per-process data convention as
        Trainer.fit_arrays) and gets back scores for exactly its own rows.

        The reference's only *required* distributed behavior is this one —
        CNTKModel scoring partitions on every executor
        (CNTKModel.scala:215-221).  Here every process contributes
        bs/process_count rows per step via `put_sharded` (no host ever
        holds the global batch), all processes run the same number of
        jitted steps (collectives in lockstep — processes with fewer rows
        feed padding), and each extracts its addressable output rows with
        `global_array_to_host_local_array`.
        """
        from jax.experimental import multihost_utils
        from jax.sharding import PartitionSpec as P

        from mmlspark_tpu.parallel.bridge import put_sharded
        from mmlspark_tpu.parallel.mesh import DATA_AXIS

        nproc = jax.process_count()
        mesh_procs = {d.process_index for d in mesh.devices.flat}
        if len(mesh_procs) != nproc:
            # a mesh spanning a strict SUBSET of processes would make the
            # cluster-wide allgather below (and put_sharded's global
            # assembly) undefined for non-member processes — fail loudly
            # rather than hang
            raise ValueError(
                f"multi-host scoring mesh spans {len(mesh_procs)} of "
                f"{nproc} processes; use a mesh over ALL processes' "
                f"devices, or a local-devices mesh for independent "
                f"per-process scoring")
        n_data = mesh.shape[DATA_AXIS]
        if n_data % nproc:
            raise ValueError(
                f"multi-host scoring needs the data axis ({n_data}) to be "
                f"a multiple of the process count ({nproc})")
        bs_local = bs // nproc
        n_local = len(col)
        # every process must run the same step count or collectives deadlock
        n_steps = int(np.ceil(multihost_utils.process_allgather(
            np.asarray(n_local)).max() / bs_local)) or 1
        sharding = batch_sharding(mesh)
        out_spec = P(DATA_AXIS)
        # lockstep dispatch: the window is parameterized but staging stays
        # on the dispatch thread — every process must issue the same puts
        # and steps in the same order, so no background staging here
        window = max(1, self._prefetch_depth())
        timings = active_timings()
        in_flight: list[tuple[Any, int]] = []
        results: list[np.ndarray] = []

        def drain(count: int):
            while len(in_flight) > count:
                out, valid = in_flight.pop(0)
                with span_on(timings, "drain"):
                    local = multihost_utils.global_array_to_host_local_array(
                        out, mesh, out_spec)
                    results.append(np.asarray(local)[:valid])

        feed_shape = (bs_local,) + col.shape[1:]
        for step in range(n_steps):
            with span_on(timings, "host"):
                chunk = col[step * bs_local:(step + 1) * bs_local]
                valid = int(chunk.shape[0])
                if valid < bs_local:
                    feed = np.zeros(feed_shape, col.dtype)
                    feed[:valid] = chunk
                    chunk = feed
                chunk = np.ascontiguousarray(chunk)
            with span_on(timings, "transfer"):
                dev = put_sharded(chunk, sharding)
            with span_on(timings, "compute"):
                out = apply_fn(variables, dev)
            in_flight.append((out, valid))
            drain(window)
        drain(0)
        # n_steps >= 1 always, so results is never empty (a zero-row local
        # partition still yields one [:0]-trimmed batch of the right rank)
        return np.concatenate(results, axis=0)

    # -- persistence ----------------------------------------------------
    def _save_extra(self, path: str) -> None:
        if self._bundle is not None:
            save_bundle(self._bundle, f"{path}/bundle")

    def _load_extra(self, path: str) -> None:
        import os
        self._bundle = (load_bundle(f"{path}/bundle")
                        if os.path.exists(f"{path}/bundle") else None)
        self._mesh = None
        self._device_vars = {}
        self._compiled = {}
        self._seen_shapes = set()
        self._called_shapes = set()
