"""In-process distributed trainer: optax + jit over a device mesh.

Replaces the reference's out-of-process training path — write CNTKText files,
generate BrainScript, `mpiexec -n <gpus> cntk configFile=...`
(CNTKLearner.scala:52-162, CommandBuilders.scala:60-93) — with a single
jit-compiled train step.  Parallelism is declarative:

  * data parallelism: batches sharded along the mesh 'data' axis; XLA inserts
    the gradient all-reduce over ICI (the MPI ring's replacement);
  * tensor parallelism: dense kernels' output dim sharded along 'model' when
    it divides evenly (new-design headroom beyond the reference, SURVEY 2b);
  * multi-host: the same code under jax.distributed (parallel/distributed.py).

Padding rows in the final minibatch are masked out of the loss — the
reference instead zero-padded and let garbage rows into the batch
(CNTKModel.scala:71-76); masking keeps loss gradients exact.  Pad rows are
filled by cycling real rows (never zeros) so stateful normalization layers
(BatchNorm) compute their batch statistics over real data; a partial final
batch therefore sees some rows duplicated in the statistics, which is the
standard drop-nothing tradeoff.
"""

from __future__ import annotations

import inspect
import os
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import PartitionSpec as P

from mmlspark_tpu.models.bundle import ModelBundle, _to_plain
from mmlspark_tpu.models.definitions import build_model
from mmlspark_tpu.observe import MetricData, get_logger
from mmlspark_tpu.observe.compiles import setup_phase
from mmlspark_tpu.observe.costmodel import capture_program_cost
from mmlspark_tpu.observe.metrics import inc_counter
from mmlspark_tpu.observe.numerics import (DivergenceError, LossSpikeDetector,
                                           NonFiniteError, tree_health)
from mmlspark_tpu.observe.spans import active_timings, monotonic, span_on
from mmlspark_tpu.observe.telemetry import active_run
from mmlspark_tpu.observe.trace import (active_tracer, current_span_id,
                                        span_on_tracer, trace_event,
                                        trace_span)
from mmlspark_tpu.parallel.bridge import (gather_replicated, gather_to_host,
                                          put_like, put_sharded, put_tree,
                                          put_tree_like, snapshot_tree)
from mmlspark_tpu.parallel.distributed import (barrier, initialize_distributed,
                                               is_coordinator, run_collective)
from mmlspark_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, batch_sharding, make_mesh, replicated
from mmlspark_tpu.parallel.partition import (UNMATCHED_REPLICATE,
                                             compatible_spec, leaf_spec,
                                             named_sharding, path_str,
                                             rules_to_json, use_mesh)
from mmlspark_tpu.data import Dataset
from mmlspark_tpu.resilience.chaos import get_injector
from mmlspark_tpu.resilience.checkpoints import (checkpoint_meta,
                                                 checkpoint_name,
                                                 latest_valid_checkpoint)
from mmlspark_tpu.resilience.ckpt_writer import (CheckpointWriter,
                                                 read_checkpoint)
from mmlspark_tpu.resilience.preemption import (HungStepError, Preempted,
                                                PreemptionGuard, StepWatchdog)
from mmlspark_tpu.train.config import TrainerConfig


@struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any
    batch_stats: Any  # {} for stateless models


def _param_sharding_rule(mesh, tensor_parallel: bool,
                         expert_parallel: bool = True,
                         partition_rules=None):
    """Map each param leaf to a sharding.  The partition-rule registry
    (parallel/partition.py) is consulted first: a leaf whose matched spec
    survives `compatible_spec` demotion gets the registry layout — the
    Megatron split for TransformerLM trees (column-parallel qkv/mlp_up/
    lm_head, row-parallel proj/mlp_down), expert stacks over 'model'.
    Leaves the registry replicates fall back to the legacy heuristics —
    EP for MoE expert stacks (ops/moe.py expert_parallel_rules folded
    into the product surface) and generic last-dim TP for wide dense
    kernels — so non-transformer architectures (ConvNet) keep their
    sharded training path unchanged."""
    model_size = mesh.shape.get(MODEL_AXIS, 1)

    from mmlspark_tpu.ops.moe import is_expert_stack
    from mmlspark_tpu.parallel.partition import DEFAULT_RULES
    rules = tuple(DEFAULT_RULES if partition_rules is None
                  else partition_rules)

    def rule(path, leaf: jax.ShapeDtypeStruct):
        shape = leaf.shape
        if tensor_parallel and model_size > 1:
            spec = compatible_spec(
                leaf_spec(path_str(path), shape, rules,
                          UNMATCHED_REPLICATE), shape, mesh)
            # expert_parallel=False must win over the registry's moe rule
            if len(spec) and (expert_parallel
                              or not is_expert_stack(path, shape,
                                                     model_size)):
                return named_sharding(mesh, spec)
        if (expert_parallel and model_size > 1
                and is_expert_stack(path, shape, model_size)):
            return named_sharding(mesh, P(MODEL_AXIS, None, None))
        if (tensor_parallel and model_size > 1 and len(shape) >= 2
                and shape[-1] % model_size == 0 and shape[-1] >= model_size * 8):
            spec = [None] * len(shape)
            spec[-1] = MODEL_AXIS
            return named_sharding(mesh, P(*spec))
        return replicated(mesh)

    return rule


def build_optimizer(cfg: TrainerConfig, total_steps: int,
                    learning_rate=None) -> optax.GradientTransformation:
    """The config's optax chain.  `learning_rate` overrides the config's
    base rate and may be a TRACED scalar — the population trainer
    (train/sweep.py) passes each sweep member's rate through `vmap`, so
    one compiled step trains N members at N different learning rates.
    The chain structure is identical either way, so a vmapped member's
    update arithmetic matches a plain Trainer fit at the same rate to
    float32 rounding (two XLA programs; tests/test_sweep.py)."""
    base = cfg.learning_rate if learning_rate is None else learning_rate
    if cfg.lr_schedule == "constant":
        lr = base
    elif cfg.lr_schedule == "cosine":
        lr = optax.cosine_decay_schedule(base, max(total_steps, 1))
    elif cfg.lr_schedule == "warmup_cosine":
        lr = optax.warmup_cosine_decay_schedule(
            0.0, base, cfg.warmup_steps,
            max(total_steps, cfg.warmup_steps + 1))
    else:
        raise ValueError(f"unknown lr_schedule {cfg.lr_schedule}")
    if cfg.optimizer == "sgd":
        tx = optax.sgd(lr)
    elif cfg.optimizer == "momentum":
        tx = optax.sgd(lr, momentum=cfg.momentum)
    elif cfg.optimizer == "adam":
        tx = optax.adam(lr)
    else:
        tx = optax.adamw(lr, weight_decay=cfg.weight_decay)
    if cfg.optimizer != "adamw" and cfg.weight_decay:
        tx = optax.chain(optax.add_decayed_weights(cfg.weight_decay), tx)
    if cfg.gradient_clip_norm:
        tx = optax.chain(optax.clip_by_global_norm(cfg.gradient_clip_norm), tx)
    return tx


def _make_loss(kind: str) -> Callable:
    def loss_fn(logits, labels, mask):
        mask = mask.astype(jnp.float32)
        denom = jnp.maximum(mask.sum(), 1.0)
        if kind == "softmax_xent":
            ll = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels.astype(jnp.int32))
        elif kind == "sigmoid_xent":
            ll = optax.sigmoid_binary_cross_entropy(
                logits.squeeze(-1), labels.astype(jnp.float32))
        elif kind == "mse":
            pred = logits.squeeze(-1) if logits.ndim > labels.ndim else logits
            ll = jnp.square(pred - labels.astype(jnp.float32))
        elif kind == "mae":
            pred = logits.squeeze(-1) if logits.ndim > labels.ndim else logits
            ll = jnp.abs(pred - labels.astype(jnp.float32))
        else:
            raise ValueError(f"unknown loss {kind}")
        if ll.ndim > 1:
            ll = ll.mean(axis=tuple(range(1, ll.ndim)))
        return (ll * mask).sum() / denom

    return loss_fn


def _fold_metrics(metrics_tree) -> dict:
    """Collapse a sown "metrics" collection (nested, one tuple entry per
    sow call) to {metric_name: mean scalar} — e.g. every MoE layer's
    overflow fraction averaged into one `moe_overflow_fraction` series.
    Runs under jit (static structure, scalar reductions only)."""
    grouped: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(metrics_tree):
        name = next((p.key for p in reversed(path)
                     if hasattr(p, "key") and not str(p.key).isdigit()),
                    "metric")
        grouped.setdefault(str(name), []).append(
            jnp.asarray(leaf, jnp.float32).mean())
    return {k: jnp.stack(v).mean() for k, v in grouped.items()}


def _epoch_order(rng, epoch: int, n: int, n_local: int,
                 shuffle: bool) -> np.ndarray:
    """The `n` local row indices this epoch feeds, drawn from `n_local`
    available rows.  When partitions are unequal (n < n_local under
    multi-host lockstep), surplus rows are not dropped: shuffling samples
    the whole partition each epoch, and the unshuffled path rotates a
    window so every row participates within ceil(n_local/n) epochs."""
    if shuffle:
        return rng.permutation(n_local)[:n]
    if n == n_local:
        return np.arange(n)
    return (np.arange(n) + epoch * n) % n_local


class Trainer:
    """Drives the jit-compiled training loop for one model."""

    def __init__(self, config: TrainerConfig, mesh=None):
        self.config = config
        self.module = build_model(config.architecture, config.model_config)
        # wire up jax.distributed from env when launched multi-host (no-op
        # in the common single-process case); must precede mesh construction
        # so the mesh spans all hosts' devices
        initialize_distributed()
        self.mesh = mesh if mesh is not None else make_mesh(config.mesh)
        sig = inspect.signature(type(self.module).__call__)
        self._has_train_arg = "train" in sig.parameters
        self._loss = _make_loss(config.loss)
        self.history: list[dict] = []
        self._pp = config.pipeline_stages > 1
        # background checkpoint writers, one per directory (resilience/
        # ckpt_writer.py); created lazily, closed at the end of each fit
        self._writers: dict[str, CheckpointWriter] = {}
        self._effective_batch_size: Optional[int] = None
        if self._pp:
            self._validate_pipeline()

    def _validate_pipeline(self) -> None:
        """Pipeline parallelism preconditions, checked at construction so a
        bad config fails fast, not at the first compiled step."""
        cfg = self.config
        if cfg.architecture != "TransformerLM":
            raise ValueError(
                "pipeline_stages > 1 supports architecture='TransformerLM' "
                f"(got {cfg.architecture!r}); the stage schedule partitions "
                "a transformer block stack")
        m = self.module
        if m.attn_impl != "dense" or m.mlp_impl != "dense":
            raise ValueError(
                "pipeline training runs dense transformer blocks; compose "
                "long-context/MoE via attn_impl/mlp_impl WITHOUT "
                "pipeline_stages, or keep the pipelined model dense "
                f"(got attn_impl={m.attn_impl!r}, mlp_impl={m.mlp_impl!r})")
        if m.remat and m.remat_policy != "full":
            raise ValueError(
                "pipeline training supports remat_policy='full' only (the "
                "stage scan checkpoints whole layers); got "
                f"remat_policy={m.remat_policy!r}")
        stages = self.mesh.shape.get(MODEL_AXIS, 1)
        if stages != cfg.pipeline_stages:
            raise ValueError(
                f"pipeline_stages={cfg.pipeline_stages} must equal the "
                f"mesh's '{MODEL_AXIS}' axis size ({stages}) — the stage "
                "ring rides that axis")
        if m.n_layers % cfg.pipeline_stages:
            raise ValueError(
                f"n_layers={m.n_layers} must divide evenly into "
                f"pipeline_stages={cfg.pipeline_stages} stages")
        if cfg.pipeline_microbatches < 1:
            raise ValueError("pipeline_microbatches must be >= 1")

    # -- optimizer ------------------------------------------------------
    def _build_optimizer(self, total_steps: int) -> optax.GradientTransformation:
        return build_optimizer(self.config, total_steps)

    # -- state ----------------------------------------------------------
    def init_state(self, input_shape: tuple, total_steps: int = 1,
                   initial_bundle: Optional[ModelBundle] = None,
                   input_dtype=np.float32) -> TrainState:
        """Initialize (or warm-start, for fine-tuning) the sharded TrainState."""
        self._tx = self._build_optimizer(total_steps)
        if self._pp:
            return self._init_state_pipelined(initial_bundle)
        if initial_bundle is not None:
            variables = _to_plain(initial_bundle.variables)
        else:
            x = np.zeros(input_shape, input_dtype)
            variables = _to_plain(
                self.module.init(jax.random.key(self.config.seed), x))
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})

        rule = _param_sharding_rule(self.mesh, self.config.tensor_parallel,
                                    self.config.expert_parallel,
                                    getattr(self.config, "partition_rules",
                                            None))
        shardings = jax.tree_util.tree_map_with_path(
            lambda path, leaf: rule(
                path, jax.ShapeDtypeStruct(np.shape(leaf),
                                           np.asarray(leaf).dtype)),
            params)
        params = put_tree(params, shardings)
        batch_stats = put_tree(
            batch_stats, jax.tree_util.tree_map(
                lambda _: replicated(self.mesh), batch_stats))
        # opt_state leaves mirror params; EAGER init follows each param
        # leaf's NamedSharding (a jitted init commits the fresh zeros to
        # one device instead, leaving a mixed-device state that a later
        # checkpoint gather or post-restore step rejects)
        opt_state = self._tx.init(params)
        # warm starts resume the global step (bundle_from_state stamps it)
        # so checkpoint_every_steps boundaries align across fit() calls
        start = int((initial_bundle.metadata or {}).get("steps", 0)) \
            if initial_bundle is not None else 0
        return TrainState(step=jnp.asarray(start, jnp.int32), params=params,
                          opt_state=opt_state, batch_stats=batch_stats)

    # -- pipeline parallelism (pipeline_stages > 1) ----------------------
    def _init_state_pipelined(self, initial_bundle) -> TrainState:
        """TrainState whose params are the pipeline's stacked tree, block
        layers sharded over the stage ('model') axis.  Warm starts convert
        an ordinary TransformerLM bundle by stacking its blocks."""
        from mmlspark_tpu.parallel.pipeline import (
            init_pipelined_lm, pipeline_param_shardings,
            pipeline_params_from_variables)
        m = self.module
        if initial_bundle is not None:
            params = pipeline_params_from_variables(
                _to_plain(initial_bundle.variables), m.n_layers)
        else:
            params = init_pipelined_lm(
                jax.random.key(self.config.seed), vocab_size=m.vocab_size,
                d_model=m.d_model, n_heads=m.n_heads, n_layers=m.n_layers,
                max_len=m.max_len, mlp_ratio=m.mlp_ratio)
        params = put_tree(params, pipeline_param_shardings(self.mesh, params))
        # eager init: opt_state shardings mirror the stage-sharded params
        # (see init_state — jitted init would commit to one device)
        opt_state = self._tx.init(params)
        start = int((initial_bundle.metadata or {}).get("steps", 0)) \
            if initial_bundle is not None else 0
        return TrainState(step=jnp.asarray(start, jnp.int32), params=params,
                          opt_state=opt_state, batch_stats={})

    def _make_pipeline_train_step(self):
        from mmlspark_tpu.parallel.pipeline import pipelined_lm_apply
        mesh, m, cfg = self.mesh, self.module, self.config
        loss_fn, tx = self._loss, self._tx
        aux_w = float(cfg.aux_loss_weight)

        def train_step(state: TrainState, x, y, mask):
            def compute(params):
                logits = pipelined_lm_apply(
                    mesh, params, x, n_heads=m.n_heads,
                    n_micro=cfg.pipeline_microbatches,
                    stage_axis=MODEL_AXIS, mlp_ratio=m.mlp_ratio,
                    dtype=m.dtype, remat=m.remat)
                return loss_fn(logits, y, mask)

            loss, grads = jax.value_and_grad(compute)(state.params)
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            new_state = TrainState(step=state.step + 1, params=new_params,
                                   opt_state=new_opt,
                                   batch_stats=state.batch_stats)
            return new_state, loss, {"grad_norm": optax.global_norm(grads)}

        del aux_w  # dense pipeline blocks sow no losses (validated in init)
        return jax.jit(train_step, donate_argnums=(0,))

    # -- the compiled step ----------------------------------------------
    def make_train_step(self):
        if self._pp:
            return self._make_pipeline_train_step()
        module, loss_fn = self.module, self._loss
        has_train = self._has_train_arg
        tx = self._tx
        mesh = self.mesh

        aux_w = float(self.config.aux_loss_weight)
        # numerics health (observe/numerics.py): when the probe cadence is
        # on, the step takes a traced `probe` flag and returns the health
        # dict under lax.cond — off-cadence steps pay one predicate, the
        # reductions only run on probe steps, and the step stays ONE
        # compiled program either way
        with_health = self.config.numerics_cadence > 0

        def train_step(state: TrainState, x, y, mask, probe=False):
            def compute(params):
                variables = {"params": params}
                if state.batch_stats:
                    variables["batch_stats"] = state.batch_stats
                if has_train:
                    out, mut = module.apply(
                        variables, x, train=True,
                        mutable=["batch_stats", "losses", "metrics"])
                    new_stats = mut.get("batch_stats", state.batch_stats)
                else:
                    out, mut = module.apply(variables, x,
                                            mutable=["losses", "metrics"])
                    new_stats = state.batch_stats
                loss = loss_fn(out, y, mask)
                if aux_w:
                    # model-sown auxiliary losses (e.g. the MoE
                    # load-balance term, ops/moe.py) join the objective
                    loss = loss + aux_w * sum(
                        jnp.asarray(v).sum() for v in
                        jax.tree_util.tree_leaves(mut.get("losses", {})))
                return loss, (new_stats,
                              _fold_metrics(mut.get("metrics", {})), out)

            (loss, (new_stats, metrics, logits)), grads = \
                jax.value_and_grad(compute, has_aux=True)(state.params)
            # the global gradient norm joins the per-step diagnostics (one
            # tree reduction under jit — noise next to the backward pass);
            # history gains a grad_norm column and telemetry step spans
            # carry it as an attr
            metrics = {**metrics, "grad_norm": optax.global_norm(grads)}
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            new_state = TrainState(step=state.step + 1, params=new_params,
                                   opt_state=new_opt, batch_stats=new_stats)
            if with_health:
                def probed():
                    return tree_health(new_params, grads, updates,
                                       acts=logits)

                metrics["health"] = jax.lax.cond(
                    probe, probed,
                    lambda: {k: jnp.zeros((), jnp.float32)
                             for k in jax.eval_shape(probed)})
            return new_state, loss, metrics

        # `use_mesh` scopes the TRACE (the body runs inside jit tracing):
        # shard_constraint hints in the module forward (transformer heads
        # / MLP hidden, parallel/partition.py) bake this trainer's mesh
        # into the compiled step; on a 1-D mesh they are no-ops
        if not with_health:
            def plain_step(state, x, y, mask):
                with use_mesh(mesh):
                    return train_step(state, x, y, mask)
            return jax.jit(plain_step, donate_argnums=(0,))

        def meshed_step(state, x, y, mask, probe=False):
            with use_mesh(mesh):
                return train_step(state, x, y, mask, probe)
        return jax.jit(meshed_step, donate_argnums=(0,))

    # -- the loop --------------------------------------------------------
    def fit_arrays(self, x: np.ndarray, y: np.ndarray,
                   initial_bundle: Optional[ModelBundle] = None,
                   log_every: int = 50,
                   log_fn: Optional[Callable[[str], None]] = None,
                   ckpt_dir: Optional[str] = None,
                   resume: bool = False,
                   skip_data_windows: Optional[Sequence] = None
                   ) -> ModelBundle:
        """Train on arrays; under multi-host, `x`/`y` are this process's
        local data partition (the per-node data shard of the reference's
        MPI topology, CommandBuilders.scala:95-117) and each process
        contributes `batch_size / process_count` rows per global step via
        `put_sharded` — no host ever holds the global batch.

        Preemption safety (docs/resilience.md): `ckpt_dir` (default:
        config.checkpoint_dir) arms a SIGTERM guard — on preemption the
        in-flight step finishes, an emergency checkpoint is written, and
        `Preempted` is raised for the job runner to exit cleanly on.
        `resume=True` restarts from the newest VALID checkpoint in
        `ckpt_dir` (torn/corrupt files are skipped by checksum), replaying
        the same data order and skipping already-completed steps, so a
        preempted-and-resumed run finishes with the same step count as an
        uninterrupted one.

        Elastic resume: the checkpoint's `.meta.json` records the
        topology and EFFECTIVE batch size it was written under; a resume
        onto a different device count adopts the saved batch size (when
        it still divides the new data axis) so step numbering and data
        order replay identically, and restore re-commits the gathered
        full-shape arrays onto the new mesh's shardings (put_tree_like).

        `skip_data_windows` ([(first_step, last_step)] inclusive global
        executed-step ranges, normally supplied by the recovery
        supervisor) skips those steps' optimizer updates AND their data:
        the step counter advances (total step numbering is preserved —
        the loss-scaling "skip step" convention) but the offending
        window's batches are never staged or fed.
        """
        cfg = self.config
        ckpt_dir = ckpt_dir if ckpt_dir is not None else cfg.checkpoint_dir
        nproc = jax.process_count()
        n_local = len(x)
        n = n_local
        data_size = self.mesh.shape[DATA_AXIS]
        if nproc > 1:
            if data_size % nproc:
                raise ValueError(
                    f"multi-host training needs the data axis "
                    f"({data_size}) to be a multiple of the process count "
                    f"({nproc}); keep tensor/sequence parallelism within a "
                    "host (over ICI) and scale data parallelism across "
                    "hosts (over DCN)")
            # all processes must agree on the step count or the collectives
            # deadlock; each epoch feeds the smallest partition's row count,
            # but surplus rows on larger partitions ROTATE into later epochs
            # (epoch-order logic below) instead of being silently dropped
            from jax.experimental import multihost_utils
            sizes = multihost_utils.process_allgather(np.asarray(len(x)))
            n = int(sizes.min())
            if n != n_local:
                get_logger("train").warning(
                    "unequal data partitions %s: each epoch uses %d of this "
                    "process's %d rows (lockstep step count); surplus rows "
                    "rotate into later epochs", np.asarray(sizes).tolist(),
                    n, n_local)
            # save_checkpoint is a collective: every process must take the
            # checkpoint branches in lockstep or the job deadlocks
            flags = np.asarray([int(bool(ckpt_dir)),
                                int(cfg.checkpoint_every_steps or 0),
                                int(bool(resume))], np.int64)
            all_flags = multihost_utils.process_allgather(flags)
            if not (all_flags == flags).all():
                raise ValueError(
                    "checkpoint_dir/checkpoint_every_steps must be set "
                    "consistently on every process (checkpointing is a "
                    f"collective); got {all_flags.tolist()}")
        bs = cfg.batch_size
        bs = max(bs - bs % data_size, data_size)
        if self._pp:
            # each data-shard's local batch must split into whole
            # microbatches for the GPipe schedule
            unit = data_size * cfg.pipeline_microbatches
            bs = max(bs - bs % unit, unit)
        # elastic resume: a checkpoint written under a different device
        # count may have clamped a different effective batch size; adopt
        # the SAVED one (when it still divides the new data axis) so the
        # resumed run replays the identical step numbering and data order
        # the original fed.  Meta is read on the coordinator only — the
        # single-writer of the directory — and is advisory (missing meta
        # = no adjustment, pre-meta checkpoints keep restoring).
        if resume and ckpt_dir and is_coordinator():
            saved = checkpoint_meta(latest_valid_checkpoint(ckpt_dir)) or {}
            # mid-epoch data position saved by snapshot() ops: arm the
            # restore registry so the NEXT build of each tagged pipeline
            # fast-forwards past the already-consumed prefix
            if saved.get("data_snapshots"):
                from mmlspark_tpu.data.snapshot import set_restore_offsets
                set_restore_offsets(saved["data_snapshots"])
            saved_bs = int(saved.get("effective_batch_size") or 0)
            saved_dp = int(saved.get("data_devices") or 0)
            saved_mp = int(saved.get("model_devices") or 0)
            model_size = self.mesh.shape.get(MODEL_AXIS, 1)
            if saved_mp and self._pp and saved_mp != model_size:
                # the pipeline stage ring is NOT elastic: stage-sharded
                # block stacks cannot re-partition across a different
                # stage count mid-run
                raise ValueError(
                    f"checkpoint written under dp={saved_dp or '?'} x "
                    f"mp={saved_mp} cannot resume onto the current "
                    f"dp={data_size} x mp={model_size} mesh: pipeline "
                    f"training requires the same stage count "
                    f"(pipeline_stages == '{MODEL_AXIS}' axis size)")
            if saved_dp and (saved_dp != data_size
                             or (saved_mp and saved_mp != model_size)):
                trace_event("train.elastic_resume", cat="resilience",
                            saved_dp=saved_dp, dp=data_size,
                            saved_mp=saved_mp or 1, mp=model_size,
                            saved_batch=saved_bs or bs, batch=bs)
                inc_counter("train.elastic_resumes")
                get_logger("train").info(
                    "elastic resume: checkpoint written under dp=%d x "
                    "mp=%d, restoring onto dp=%d x mp=%d "
                    "(reshard-on-restore)", saved_dp, saved_mp or 1,
                    data_size, model_size)
            if saved_bs and saved_bs != bs:
                unit = data_size * (cfg.pipeline_microbatches
                                    if self._pp else 1)
                if saved_bs % unit:
                    raise ValueError(
                        f"elastic resume: checkpoint written under "
                        f"dp={saved_dp or '?'} x mp={saved_mp or 1} with "
                        f"effective batch size {saved_bs} cannot replay "
                        f"onto the current dp={data_size} x "
                        f"mp={model_size} mesh ({saved_bs} does not "
                        f"divide into the new data-axis unit {unit}); "
                        f"pick a batch_size divisible by both topologies "
                        f"to keep resumed runs reproducible")
                get_logger("train").info(
                    "elastic resume: adopting the checkpoint's effective "
                    "batch size %d (config clamped to %d) so data order "
                    "replays identically", saved_bs, bs)
                bs = saved_bs
        self._effective_batch_size = bs
        # rows this process feeds per global step; data_size % nproc == 0
        # and bs % data_size == 0 guarantee equal whole-row shares >= 1
        bs_local = bs // nproc
        steps_per_epoch = max(1, (n + bs_local - 1) // bs_local)
        total_steps = steps_per_epoch * cfg.epochs

        with setup_phase("train_state"):    # state init and placement
            state = self.init_state((1,) + x.shape[1:], total_steps,
                                    initial_bundle,
                                    input_dtype=np.asarray(x).dtype)
        # the step numbering this run starts from (0, or the warm-start
        # bundle's recorded step); a resume checkpoint advances past it
        base_step = int(state.step)
        skip_until = base_step
        if resume and ckpt_dir:
            # every process must agree whether a restore happens (it is a
            # collective); the coordinator's directory decides
            found = int(latest_valid_checkpoint(ckpt_dir) is not None) \
                if is_coordinator() else 0
            if nproc > 1:
                from jax.experimental import multihost_utils
                found = int(run_collective(
                    "resume.poll", lambda: multihost_utils.
                    broadcast_one_to_all(np.asarray(found, np.int32))))
            if found:
                state = self.restore_checkpoint(state, ckpt_dir)
                skip_until = int(state.step)
                trace_event("train.resume", cat="resilience",
                            step=skip_until, ckpt_dir=ckpt_dir,
                            skipped_steps=skip_until - base_step)
                get_logger("train").info(
                    "resuming from checkpoint at step %d "
                    "(skipping %d completed steps)", skip_until,
                    skip_until - base_step)
        step_fn = self.make_train_step()
        x_sh = batch_sharding(self.mesh)

        # distinct per-process streams so partitions shuffle independently;
        # a nonzero rng_fold (recovery retries) folds the attempt number in
        # so the retry shuffles DIFFERENT batches past the restore point —
        # fold 0 keeps the historical stream byte-identical
        seed_key = cfg.seed + jax.process_index()
        rng = np.random.default_rng(
            seed_key if not cfg.rng_fold else [seed_key, int(cfg.rng_fold)])
        t0 = monotonic()
        # host-side counter seeded once from this run's base step so
        # checkpoint_every_steps boundaries stay aligned across fit()
        # calls; never sync on state.step mid-epoch.  On resume it replays
        # the original numbering, skipping steps below `skip_until` —
        # the epoch/batch order is identical, so the resumed run feeds
        # exactly the batches the preempted one never saw.
        chaos = get_injector()
        self._rows_seen = np.zeros(n_local, bool)  # coverage, inspectable
        # double-buffered staging (config.prefetch_depth, default 2): while
        # the jitted step k runs, the staging thread builds step k+1's
        # index/mask arrays and starts their device_put — the transfer
        # overlaps compute instead of alternating with it.  Numerics are
        # untouched: the plan below yields exactly the (epoch, step, batch)
        # sequence the serial loop fed, and rng consumption order is
        # identical (orders are drawn epoch-by-epoch on the consumer
        # thread as the staging window tops up).  The knob follows the
        # shared contract (parallel/prefetch.resolve_depth): positive
        # pins, 0 autotunes from the floor, -1 is fully serial.
        depth_knob = int(getattr(cfg, "prefetch_depth", 2))
        timings = active_timings()  # captured: workers have no context
        # telemetry (observe/trace.py): the tracer handle and the fit-level
        # span id are captured HERE on the consumer thread and passed into
        # the staging closure by value — the same capture-by-closure rule
        # as `timings` above, since worker threads never inherit contextvars
        tracer = active_tracer()
        run = active_run()  # the run's cost/gauge tables (same capture rule)
        fit_span = tracer.span(
            "train.fit", parent=current_span_id(), cat="phase",
            architecture=cfg.architecture, total_steps=total_steps,
            batch_size=bs, resume_from=skip_until - base_step or 0,
        ) if tracer is not None else None
        fit_id = fit_span.span_id if fit_span is not None else None
        # numerics health (observe/numerics.py): probe every `cadence`
        # executed steps; the loss-spike detector sees the probe steps'
        # losses; halt_on_nonfinite raises before any checkpoint write.
        # Detection granularity IS the cadence — keep it at or below
        # checkpoint_every_steps so a poisoned state cannot slip into a
        # rotation between probes.
        cadence = max(0, int(cfg.numerics_cadence)) if not self._pp else 0
        detector = LossSpikeDetector() if cadence else None
        self.last_health: Optional[dict] = None
        prog_key: Optional[str] = None
        # recovery skip windows (inclusive executed-step ranges): those
        # steps advance the counter but stage no data and run no update —
        # the supervisor's "skip the offending data window" lever
        windows = [(int(a), int(b)) for a, b in (skip_data_windows or [])]
        # hung-step watchdog: bounded-wait step execution (HungStepError
        # past the deadline; resilience/preemption.py)
        watchdog = StepWatchdog(cfg.step_timeout_s) \
            if cfg.step_timeout_s and not self._pp else None

        def _skipped(step_c: int) -> bool:
            return any(a <= step_c <= b for a, b in windows)

        def plan():
            step_c = base_step
            for epoch in range(cfg.epochs):
                order = _epoch_order(rng, epoch, n, n_local,
                                     cfg.shuffle_each_epoch)
                self._rows_seen[order] = True
                for start in range(0, n, bs_local):
                    if step_c < skip_until:  # completed before preemption
                        step_c += 1
                        continue
                    if _skipped(step_c):
                        # a recovery skip window: the marker (order=None)
                        # advances the step counter downstream, and the
                        # window's rows are never staged or transferred
                        yield (epoch, step_c, None, start)
                        step_c += 1
                        continue
                    yield (epoch, step_c, order, start)
                    step_c += 1

        def stage(item):
            epoch, step_c, order, start = item
            if order is None:  # skip-window marker: nothing to stage
                return epoch, step_c, None, None, None
            with span_on_tracer(tracer, "train.stage", parent=fit_id,
                                cat="stage", step=step_c):
                with span_on(timings, "host"):
                    idx = order[start:start + bs_local]
                    valid = len(idx)
                    if valid < bs_local:
                        # cycle real rows into the pad (module docstring)
                        idx = np.concatenate([idx,
                                              np.resize(order,
                                                        bs_local - valid)])
                    mask = np.zeros(bs_local, np.float32)
                    mask[:valid] = 1.0
                    xh, yh = x[idx], y[idx]
                with span_on(timings, "transfer"):
                    xb = put_sharded(xh, x_sh)
                    yb = put_sharded(yh, x_sh)
                    mask_d = put_sharded(mask, x_sh)
            return epoch, step_c, xb, yb, mask_d

        losses: list = []
        step_metrics: list = []
        cur_epoch: Optional[int] = None

        def finish_epoch():
            # one history row per epoch that executed at least one step
            # (epochs fully skipped by resume produce no staged items)
            if cur_epoch is None or not losses:
                return
            n_batches = len(losses)
            # the consumer thread's wait for the epoch's steps in flight
            with span_on(timings, "drain"):
                epoch_loss = float(np.sum(jax.device_get(losses)))
            rec = {"epoch": cur_epoch,
                   "loss": epoch_loss / max(n_batches, 1),
                   "wall_s": monotonic() - t0}
            if step_metrics:
                # model-sown diagnostics (e.g. MoE overflow fraction)
                # averaged over the epoch's steps, one history column each
                fetched = jax.device_get(step_metrics)
                for key in fetched[0]:
                    rec[key] = float(np.mean([m[key] for m in fetched]))
            self.history.append(rec)
            emit = log_fn if log_fn is not None \
                else get_logger("train").info
            if cur_epoch % max(1, log_every) == 0 \
                    or cur_epoch == cfg.epochs - 1:
                emit(f"epoch {cur_epoch}: loss={rec['loss']:.5f} "
                     f"({rec['wall_s']:.1f}s)")

        # NO `prefetch` op below the plan: its pulls must stay on the
        # consumer thread (rng orders are drawn as the map stage tops up)
        staged = (Dataset.from_iterable(plan)
                  .map(stage, name="train", depth=depth_knob, span=None)
                  .iterator())
        first_exec = True  # the first executed step pays the jit compile
        exec_count = 0     # watchdog warmup: see `dog` below
        with PreemptionGuard(install=bool(ckpt_dir)) as guard:
            try:
                for epoch, step_c, xb, yb, mask_d in staged:
                    if epoch != cur_epoch:
                        finish_epoch()
                        cur_epoch = epoch
                        losses, step_metrics = [], []
                    if xb is None:
                        # recovery skip window: the optimizer update and
                        # the window's data are skipped, but the step
                        # counter advances so total step numbering (and
                        # checkpoint naming) is preserved — the classic
                        # loss-scaling "skip step" convention
                        state = state.replace(step=state.step + 1)
                        inc_counter("train.skipped_steps")
                        trace_event("train.step_skipped", cat="resilience",
                                    step=step_c, epoch=epoch)
                        continue
                    chaos.on_step(step_c)  # may deliver simulated SIGTERM
                    if chaos.poison_nan(step_c):
                        # dtype-agnostic poison: a NaN loss mask drives
                        # the loss, gradients, and update non-finite —
                        # the numerics-probe drill
                        mask_d = mask_d * jnp.nan
                    probe_now = bool(cadence) and step_c % cadence == 0
                    step_args = (state, xb, yb, mask_d) + \
                        ((probe_now,) if cadence else ())
                    if prog_key is None:
                        prog_key = f"{tuple(xb.shape)}:{xb.dtype}"
                    if run is not None and first_exec:
                        # compile-time cost capture (observe/costmodel.py)
                        # BEFORE the first execution — the step donates
                        # its state, so lowering afterwards would see
                        # deleted buffers.  One AOT compile per run, and
                        # never a probe execution (donation).
                        capture_program_cost(step_fn, step_args,
                                             where="trainer",
                                             program=prog_key, run=run)

                    # watchdog warmup: the first execution pays the jit
                    # compile and the second may recompile at the
                    # donation/layout fixed point (the output state's
                    # layouts differ from eager init's) — both are
                    # legitimately slow, minutes on big models, so the
                    # step deadline arms from the third execution on
                    # (an early wedge is bounded by the collective
                    # timeouts instead)
                    dog = watchdog if exec_count >= 2 else None

                    def exec_step(args=step_args, step=step_c):
                        chaos.maybe_hang(step)  # hung-device drill hazard
                        out = step_fn(*args)
                        if dog is not None:
                            # the watchdog bounds a SYNCED execution: an
                            # async dispatch that never finishes must
                            # count as hung, not slip past the deadline
                            jax.block_until_ready(out)
                        return out

                    run_step = exec_step if dog is None else (
                        lambda: dog.run(exec_step, step=step_c,
                                        ckpt_dir=ckpt_dir))
                    if first_exec:
                        # the first call of the step this fit built: its
                        # trace, lowering and compile (or cache load)
                        # happen inside it
                        def run_step(call=run_step):
                            with setup_phase("train_step"):
                                return call()
                    if tracer is None:
                        with span_on(timings, "compute"):
                            state, loss, metrics = run_step()
                    else:
                        # per-step span: the scalar fetches force the step
                        # to FINISH inside the span, so its wall is the
                        # true step wall (the sync is the known, pinned
                        # cost of running with telemetry on)
                        with tracer.span(
                                "train.step", parent=fit_id, cat="step",
                                step=step_c, epoch=epoch,
                                first_step_compile=first_exec) as sp, \
                                span_on(timings, "compute"):
                            state, loss, metrics = run_step()
                            sp.attrs["loss"] = float(jax.device_get(loss))
                            if "grad_norm" in metrics:
                                sp.attrs["grad_norm"] = float(
                                    jax.device_get(metrics["grad_norm"]))
                            dur = sp.elapsed()
                            if dur > 0:
                                sp.attrs["rows_per_sec"] = round(
                                    bs_local / dur, 1)
                        if run is not None:
                            # synced step spans are true walls — the
                            # roofline joins them directly
                            run.add_program_time("trainer", prog_key, dur,
                                                 basis="step_wall")
                    first_exec = False
                    exec_count += 1
                    health = metrics.pop("health", None) if cadence else None
                    losses.append(loss)  # device array; fetched at epoch end
                    if metrics:
                        step_metrics.append(metrics)
                    if probe_now and health is not None:
                        # may raise NonFiniteError — BEFORE the
                        # step-boundary checkpoint below, so a poisoned
                        # state never rotates over the last finite one
                        self._numerics_check(step_c, loss, health,
                                             detector, run, ckpt_dir)
                    step = step_c + 1
                    if ckpt_dir and cfg.checkpoint_every_steps and \
                            step % cfg.checkpoint_every_steps == 0:
                        # async by default: the gather stays on this
                        # thread (collective), serialization + disk move
                        # to the writer thread (resilience/ckpt_writer.py)
                        self.save_checkpoint(state, ckpt_dir, step=step,
                                             sync=not cfg.async_checkpointing)
                    # the in-flight step finished; honor a pending SIGTERM
                    # at the step boundary (lockstep under multi-host:
                    # every process must agree before the collective save).
                    # The already-staged next batch is simply discarded —
                    # staged.close() below cancels the staging pool.
                    preempt_now = guard.triggered
                    if nproc > 1:
                        from jax.experimental import multihost_utils
                        preempt_now = bool(run_collective(
                            "preempt.sync", lambda: int(np.asarray(
                                multihost_utils.process_allgather(
                                    np.asarray(int(guard.triggered))))
                                .max())))
                    if preempt_now:
                        # emergency save is a BARRIER (sync=True): the
                        # checkpoint must be durable before the process
                        # exits on the preemption grace window
                        self.save_checkpoint(state, ckpt_dir, step=step,
                                             sync=True)
                        self._last_state = state
                        trace_event("train.preempted", cat="resilience",
                                    step=step, ckpt_dir=ckpt_dir)
                        raise Preempted(step=step, ckpt_dir=ckpt_dir)
                finish_epoch()
            except HungStepError:
                # the hung step never completed, so `state` is still the
                # last COMPLETED boundary state — write a best-effort
                # emergency checkpoint of it.  If the hung dispatch
                # already consumed (donated) the state's buffers, the
                # save fails and the rotation's newest periodic
                # checkpoint remains the restore point; either way the
                # abort is clean and a supervisor can resume.
                if ckpt_dir:
                    try:
                        path = self.save_checkpoint(state, ckpt_dir,
                                                    sync=True)
                        trace_event("train.hung_step_checkpoint",
                                    cat="resilience", path=path)
                    except Exception as e:
                        get_logger("train").warning(
                            "emergency checkpoint after hung step "
                            "failed (donated buffers?): %s", e)
                raise
            finally:
                staged.close()
                self._close_writers()
                if fit_span is not None:
                    fit_span.finish()
        if ckpt_dir:
            self.save_checkpoint(state, ckpt_dir, sync=True)
            self._close_writers()
        # the run's loss curve through the typed contract (Metrics.scala:37-47)
        self.training_metric_data().log("train", "debug")
        self._last_state = state  # inspectable (sharding asserts, resume)
        return self.bundle_from_state(state)

    def _numerics_check(self, step: int, loss, health: dict, detector,
                        run, ckpt_dir: Optional[str]) -> None:
        """One probe-step health pass (observe/numerics.py): fetch the
        jitted probe's scalars, feed the loss-spike detector, emit
        resilience-style events, and — with halt_on_nonfinite armed —
        raise NonFiniteError before any checkpoint write."""
        fetched = {k: float(v)
                   for k, v in jax.device_get(health).items()}
        loss_val = float(jax.device_get(loss))
        self.last_health = {"step": step, "loss": loss_val, **fetched}
        nonfinite = (fetched.get("nonfinite_params", 0.0)
                     + fetched.get("nonfinite_grads", 0.0)
                     + fetched.get("nonfinite_acts", 0.0)
                     + (0.0 if np.isfinite(loss_val) else 1.0))
        verdict = detector.update(loss_val) if detector is not None \
            else "ok"
        if run is not None:
            for key, value in fetched.items():
                run.gauge(f"numerics.{key}", value, step=step)
        trace_event("numerics.probe", cat="numerics", step=step,
                    loss=loss_val, verdict=verdict,
                    nonfinite_elements=nonfinite)
        if nonfinite:
            inc_counter("numerics.nonfinite_probes")
            trace_event("numerics.nonfinite", cat="resilience", step=step,
                        loss=loss_val, nonfinite_elements=nonfinite,
                        halting=bool(self.config.halt_on_nonfinite))
            get_logger("train").warning(
                "numerics: non-finite training state at step %d "
                "(%g element(s), loss=%g)", step, nonfinite, loss_val)
            if self.config.halt_on_nonfinite:
                raise NonFiniteError(
                    step, f"{nonfinite:g} non-finite element(s), "
                          f"loss={loss_val:g}", ckpt_dir)
        elif verdict in ("spike", "divergence"):
            inc_counter(f"numerics.loss_{verdict}")
            trace_event(f"numerics.loss_{verdict}", cat="resilience",
                        step=step, loss=loss_val,
                        threshold=detector.threshold())
            get_logger("train").warning(
                "numerics: loss %s at step %d (loss=%g, threshold=%g)",
                verdict, step, loss_val, detector.threshold())
            if verdict == "divergence" and self.config.halt_on_divergence:
                # same contract as NonFiniteError: raised BEFORE the
                # step-boundary checkpoint, so the newest checkpoint on
                # disk is the last pre-divergence state
                raise DivergenceError(step, loss_val,
                                      detector.threshold(), ckpt_dir)

    def training_metric_data(self) -> MetricData:
        """This trainer's history as a typed metric table (loss/wall plus
        any model-sown diagnostic columns, e.g. moe_overflow_fraction)."""
        extras = sorted({k for r in self.history for k in r}
                        - {"epoch", "loss", "wall_s"})
        cols = {key: [r.get(key, float("nan")) for r in self.history]
                for key in ("epoch", "loss", "wall_s", *extras)}
        return MetricData.create_table(
            cols, "training", self.config.architecture)

    def bundle_from_state(self, state: TrainState) -> ModelBundle:
        # collective under multi-host (gathers TP/EP/PP-sharded leaves);
        # every process gets the full bundle
        gathered = gather_to_host(state.params, self.mesh)
        if self._pp:
            # unstack the pipeline tree back into ordinary TransformerLM
            # variables: the bundle scores through TPUModel like any other
            from mmlspark_tpu.parallel.pipeline import (
                variables_from_pipeline_params)
            variables = variables_from_pipeline_params(
                gathered, self.module.n_layers)
        else:
            variables = {"params": gathered}
        if state.batch_stats:
            variables["batch_stats"] = gather_to_host(state.batch_stats,
                                                      self.mesh)
        # the bundle carries the layout it was trained under: the rule
        # set (JSON form, parallel/partition.py round-trip) and the mesh
        # shape, so scoring/decode re-shard the SAME way and a restore
        # onto a different dp x mp topology can name both in errors.
        # Arrays themselves are gathered full-shape — topology-portable.
        from mmlspark_tpu.parallel.partition import DEFAULT_RULES
        rules = getattr(self.config, "partition_rules", None) \
            or DEFAULT_RULES
        metadata = {
            "steps": int(state.step),
            "partition": {
                "rules": rules_to_json(rules),
                "mesh": {"data": int(self.mesh.shape.get(DATA_AXIS, 1)),
                         "model": int(self.mesh.shape.get(MODEL_AXIS, 1))},
            },
        }
        return ModelBundle.from_module(self.module, variables,
                                       metadata=metadata)

    # -- checkpoint / resume (absent in the reference; first-class here) --
    def _writer_for(self, ckpt_dir: str) -> CheckpointWriter:
        writer = self._writers.get(ckpt_dir)
        if writer is None:
            writer = self._writers[ckpt_dir] = CheckpointWriter(ckpt_dir)
        return writer

    def _close_writers(self) -> None:
        """Drain and stop every checkpoint writer (end-of-fit barrier);
        best-effort — a failed background write was already surfaced at
        its submit/drain, and a finally-block close must never mask the
        exception unwinding through it."""
        for writer in self._writers.values():
            writer.close(best_effort=True)
        self._writers.clear()

    def _ckpt_meta(self, step: int) -> dict:
        """The elastic-resume meta sidecar: the topology and EFFECTIVE
        batch size this checkpoint was written under, so a resume onto a
        different device count can replay the identical data order."""
        meta = {
            "step": int(step),
            "data_devices": int(self.mesh.shape.get(DATA_AXIS, 1)),
            "model_devices": int(self.mesh.shape.get(MODEL_AXIS, 1)),
            "process_count": int(jax.process_count()),
            "effective_batch_size": self._effective_batch_size,
            "seed": int(self.config.seed),
            "rng_fold": int(self.config.rng_fold),
            "format": 1,
        }
        # mid-epoch data position: every live snapshot() op's consumed
        # count rides the sidecar, so a resume replays exactly the
        # remaining elements (data/snapshot.py; docs/data-service.md)
        from mmlspark_tpu.data.snapshot import snapshot_offsets
        offsets = snapshot_offsets()
        if offsets:
            meta["data_snapshots"] = offsets
        return meta

    def save_checkpoint(self, state: TrainState, ckpt_dir: str, *,
                        step: Optional[int] = None,
                        sync: bool = True) -> str:
        """Write one rotation checkpoint (keep-last-K + LATEST pointer +
        sha256 sidecar + elastic meta, resilience/checkpoints.py).

        The gather is a collective under multi-host (it runs on every
        process, bounded by the collective timeout) but only the
        coordinator writes, so concurrent hosts sharing a filesystem
        never race.  The write itself rides the background writer
        (resilience/ckpt_writer.py): `sync=False` returns right after
        handing off the gathered device arrays (the step loop's async
        path — D2H + serialization + disk happen on the writer thread);
        `sync=True` drains first (emergency/final saves, external
        callers).  `step` supplies the host-known step so the async path
        never synchronizes on the device scalar."""
        with trace_span("checkpoint.save", cat="checkpoint", sync=sync):
            tree = {"step": state.step, "params": state.params,
                    "opt_state": state.opt_state,
                    "batch_stats": state.batch_stats}
            if jax.process_count() == 1:
                # every shard is addressable: a same-sharding snapshot
                # copy is the whole device-side cost (no n_devices-wide
                # replication) and protects the pending async write from
                # the next step's buffer donation; the writer assembles
                # shards during its device_get
                dev = snapshot_tree(tree)
            else:
                dev = run_collective(
                    "checkpoint.gather",
                    lambda: gather_replicated(tree, self.mesh))
            step = int(state.step) if step is None else int(step)
            if not is_coordinator():
                # the gather ran (collective); skip the D2H copy + write
                return os.path.join(ckpt_dir, checkpoint_name(step))
            return self._writer_for(ckpt_dir).submit(
                step, dev, meta=self._ckpt_meta(step), sync=sync)

    def restore_checkpoint(self, state: TrainState, ckpt_dir: str) -> TrainState:
        """Restore from the newest VALID checkpoint in the coordinator's
        `ckpt_dir` (checksum-validated; torn/corrupt files are skipped, a
        legacy single-file layout is accepted).  Under multi-host only the
        coordinator reads the file (matching coordinator-only writes — no
        shared filesystem required); values reach the other hosts via a
        broadcast collective, with a named barrier + bounded waits so a
        dead peer raises a diagnostic instead of hanging the job.

        Elastic by construction: the payload holds gathered full-shape
        arrays and the target layout comes from the LIVE state's
        shardings (`put_tree_like`), so a checkpoint saved under dp=N
        restores onto an M-device mesh with byte-identical weights."""
        with trace_span("checkpoint.restore", cat="checkpoint",
                        ckpt_dir=ckpt_dir):
            return self._restore_checkpoint(state, ckpt_dir)

    def _restore_checkpoint(self, state: TrainState,
                            ckpt_dir: str) -> TrainState:
        # deserialization needs only shapes/dtypes/structure — build the
        # template locally (no collectives, no D2H of live state); global
        # logical shapes are device-count-independent, which is what
        # makes the restore elastic
        template = jax.tree_util.tree_map(
            lambda a: np.zeros(np.shape(a), a.dtype),
            {"step": state.step, "params": state.params,
             "opt_state": state.opt_state, "batch_stats": state.batch_stats})
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            # all peers must be alive before committing to the broadcast:
            # the barrier converts a dead host into a CollectiveTimeoutError
            # naming this rendezvous, not an indefinite wedge
            barrier("restore_checkpoint")
            path = latest_valid_checkpoint(ckpt_dir) if is_coordinator() \
                else None
            # agree on readability first: if the coordinator raised while
            # the others sat in the broadcast collective, the job would
            # hang with no pointer to the cause
            readable = int(run_collective(
                "restore.readable", lambda: multihost_utils.
                broadcast_one_to_all(np.asarray(int(path is not None),
                                                np.int32))))
            if not readable:
                raise FileNotFoundError(
                    f"coordinator has no valid checkpoint in {ckpt_dir}")
            host = read_checkpoint(template, path) if is_coordinator() \
                else template
            restored = run_collective(
                "restore.broadcast",
                lambda: multihost_utils.broadcast_one_to_all(host))
        else:
            path = latest_valid_checkpoint(ckpt_dir)
            if path is None:
                raise FileNotFoundError(
                    f"no valid checkpoint in {ckpt_dir}")
            restored = read_checkpoint(template, path)
        # mesh= commits scalar leaves (step, optax counters) replicated on
        # the trainer's mesh rather than copying their single-device init
        # placement: when the mesh is a strict subset of the process's
        # devices (elastic resume onto fewer chips), a default-device
        # scalar would mix device sets inside the jitted train step
        return TrainState(
            step=put_like(jnp.asarray(restored["step"], jnp.int32),
                          state.step, mesh=self.mesh),
            params=put_tree_like(restored["params"], state.params,
                                 mesh=self.mesh),
            opt_state=put_tree_like(restored["opt_state"], state.opt_state,
                                    mesh=self.mesh),
            batch_stats=put_tree_like(restored["batch_stats"],
                                      state.batch_stats, mesh=self.mesh),
        )
