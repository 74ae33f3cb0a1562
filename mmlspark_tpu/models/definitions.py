"""Model definitions: flax modules with named nodes.

Replaces the reference's CNTK computation graphs (`.model` files loaded via
JNI, CNTKModel.scala:122-132).  CNTK models expose named nodes — the
reference selects outputs by `outputNodeName`/`outputNodeIndex`
(CNTKModel.scala:151-168) and ImageFeaturizer cuts layers by `layerNames`
(ImageFeaturizer.scala:98-103).  Here every module `sow`s its named
intermediate activations, so any node is addressable without re-defining the
network: the TPU-native equivalent of CNTK's graph-node lookup, resolved at
trace time with zero runtime cost (XLA dead-code-eliminates unused heads).

All matmul/conv compute defaults to bfloat16 on the MXU with float32
parameters (the standard TPU mixed-precision recipe); pass dtype=float32 for
exact-parity runs.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

Dtype = Any


class NodeMixin:
    """Helper for recording named nodes (CNTK graph-node equivalent)."""

    def node(self, name: str, value: jax.Array) -> jax.Array:
        self.sow("intermediates", name, value)
        return value


class MLPClassifier(nn.Module, NodeMixin):
    """Multi-layer perceptron (reference MLP learner, TrainClassifier.scala:96-101,
    with input-layer autosizing done by the caller as at lines 143-150)."""

    hidden_sizes: Sequence[int] = (100,)
    num_classes: int = 2
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        for i, h in enumerate(self.hidden_sizes):
            x = nn.Dense(h, dtype=self.dtype, name=f"dense{i}")(x)
            x = self.node(f"h{i}", nn.relu(x))
        z = nn.Dense(self.num_classes, dtype=self.dtype, name="out")(x)
        return self.node("z", z.astype(jnp.float32))


class LinearModel(nn.Module, NodeMixin):
    """Linear/logistic model head (LR learners in TrainClassifier/Regressor)."""

    num_outputs: int = 1
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        z = nn.Dense(self.num_outputs, dtype=self.dtype, name="out")(
            x.astype(self.dtype))
        return self.node("z", z.astype(jnp.float32))


class ConvNetCIFAR10(nn.Module, NodeMixin):
    """The flagship scoring model: CIFAR-10 ConvNet.

    Mirrors the capability of the reference's bundled ConvNet_CIFAR10.model
    fixture (cntk-model tests, CNTKTestUtils.scala:12-36): 3 conv blocks +
    2 dense layers over 32x32x3 images, 10-class logits at node "z".
    Named nodes: conv1..conv3, pool1..pool3, dense1, z.
    """

    num_classes: int = 10
    widths: Sequence[int] = (64, 128, 256)
    dense_width: int = 512
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        # x: (B, H, W, C) float in [0, 255] or [0,1]; NHWC is XLA's preferred
        # conv layout on TPU.
        x = x.astype(self.dtype)
        for i, w in enumerate(self.widths, start=1):
            x = nn.Conv(w, (3, 3), padding="SAME", dtype=self.dtype,
                        name=f"conv{i}_w")(x)
            x = self.node(f"conv{i}", nn.relu(x))
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
            x = self.node(f"pool{i}", x)
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(self.dense_width, dtype=self.dtype, name="dense1_w")(x)
        x = self.node("dense1", nn.relu(x))
        z = nn.Dense(self.num_classes, dtype=self.dtype, name="out")(x)
        return self.node("z", z.astype(jnp.float32))


class ResNetBlock(nn.Module):
    filters: int
    strides: tuple[int, int] = (1, 1)
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        residual = x
        y = nn.Conv(self.filters, (3, 3), self.strides, padding="SAME",
                    use_bias=False, dtype=self.dtype)(x)
        y = nn.BatchNorm(use_running_average=not train, dtype=self.dtype)(y)
        y = nn.relu(y)
        y = nn.Conv(self.filters, (3, 3), padding="SAME", use_bias=False,
                    dtype=self.dtype)(y)
        y = nn.BatchNorm(use_running_average=not train, dtype=self.dtype)(y)
        if residual.shape != y.shape:
            residual = nn.Conv(self.filters, (1, 1), self.strides,
                               use_bias=False, dtype=self.dtype)(residual)
            residual = nn.BatchNorm(use_running_average=not train,
                                    dtype=self.dtype)(residual)
        return nn.relu(y + residual)


class ResNetBottleneckBlock(nn.Module):
    """1x1 reduce -> 3x3 -> 1x1 expand (x4), the ResNet-50/101/152 block."""

    filters: int                       # bottleneck width; output is 4x this
    strides: tuple[int, int] = (1, 1)
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        residual = x
        y = nn.Conv(self.filters, (1, 1), use_bias=False, dtype=self.dtype)(x)
        y = nn.BatchNorm(use_running_average=not train, dtype=self.dtype)(y)
        y = nn.relu(y)
        y = nn.Conv(self.filters, (3, 3), self.strides, padding="SAME",
                    use_bias=False, dtype=self.dtype)(y)
        y = nn.BatchNorm(use_running_average=not train, dtype=self.dtype)(y)
        y = nn.relu(y)
        y = nn.Conv(4 * self.filters, (1, 1), use_bias=False,
                    dtype=self.dtype)(y)
        y = nn.BatchNorm(use_running_average=not train, dtype=self.dtype,
                         scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = nn.Conv(4 * self.filters, (1, 1), self.strides,
                               use_bias=False, dtype=self.dtype)(residual)
            residual = nn.BatchNorm(use_running_average=not train,
                                    dtype=self.dtype)(residual)
        return nn.relu(y + residual)


class ResNet(nn.Module, NodeMixin):
    """ResNet image featurizer (the zoo's ResNet50-class models,
    ImageFeaturizerSuite.scala:45-53 asserts a 1000-wide output).

    block_kind 'basic' gives the 18/34 layouts; 'bottleneck' the 50/101/152
    layouts (widths are the bottleneck widths; stage outputs are 4x).
    Named nodes: stem, stage1..stageN, pool (global average — the transfer-
    learning feature layer), z (classifier logits).
    """

    stage_sizes: Sequence[int] = (2, 2, 2, 2)  # ResNet-18 layout
    widths: Sequence[int] = (64, 128, 256, 512)
    num_classes: int = 1000
    block_kind: str = "basic"          # basic | bottleneck
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        block_cls = {"basic": ResNetBlock,
                     "bottleneck": ResNetBottleneckBlock}[self.block_kind]
        x = x.astype(self.dtype)
        x = nn.Conv(64, (7, 7), (2, 2), padding="SAME", use_bias=False,
                    dtype=self.dtype, name="stem_conv")(x)
        x = nn.BatchNorm(use_running_average=not train, dtype=self.dtype)(x)
        x = self.node("stem", nn.relu(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, (n_blocks, w) in enumerate(zip(self.stage_sizes, self.widths), 1):
            for b in range(n_blocks):
                strides = (2, 2) if b == 0 and i > 1 else (1, 1)
                x = block_cls(w, strides, dtype=self.dtype)(x, train)
            x = self.node(f"stage{i}", x)
        x = jnp.mean(x, axis=(1, 2))
        x = self.node("pool", x.astype(jnp.float32))
        z = nn.Dense(self.num_classes, dtype=self.dtype, name="out")(x)
        return self.node("z", z.astype(jnp.float32))


def resnet50(num_classes: int = 1000, dtype: Dtype = jnp.bfloat16) -> "ResNet":
    """The canonical ResNet-50 (the reference zoo's headline featurizer,
    ModelDownloader CDN models; pool node is 2048-dim)."""
    return ResNet(stage_sizes=(3, 4, 6, 3), widths=(64, 128, 256, 512),
                  num_classes=num_classes, block_kind="bottleneck",
                  dtype=dtype)


class TransformerBlock(nn.Module):
    """Pre-norm decoder block with pluggable attention execution."""

    d_model: int
    n_heads: int
    mlp_ratio: int = 4
    dtype: Dtype = jnp.bfloat16
    attn_impl: str = "dense"    # dense | flash | ring | ring_flash | ulysses
    seq_axis: Optional[str] = None    # mesh axis for ring variants/ulysses
    mlp_impl: str = "dense"           # dense | moe
    n_experts: int = 8                # experts when mlp_impl == "moe"
    expert_axis: Optional[str] = None  # mesh axis experts shard over (EP)
    moe_router_k: int = 1             # 1 = Switch top-1, 2 = GShard top-2
    moe_group_size: int = 512         # routing group (bounds dispatch memory)

    @nn.compact
    def __call__(self, x):
        from mmlspark_tpu.ops.attention import (attention, ring_attention,
                                                ring_flash_attention,
                                                ulysses_attention)
        from mmlspark_tpu.parallel.partition import (HEADS_SPEC, HIDDEN_SPEC,
                                                     shard_constraint)
        b, s, _ = x.shape
        d_head = self.d_model // self.n_heads
        h = nn.LayerNorm(dtype=self.dtype)(x)
        qkv = nn.Dense(3 * self.d_model, dtype=self.dtype, name="qkv")(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = (b, s, self.n_heads, d_head)
        # tensor-parallel hint (no-op off-mesh): heads ride the 'model'
        # axis, matching the column-parallel qkv kernel split — each chip
        # attends over its own head slice.  Sequence-sharded variants run
        # under shard_map, where GSPMD hints do not apply (manual axes).
        seq_sharded = self.seq_axis is not None and self.attn_impl != "dense"
        def heads(t):
            return t if seq_sharded else shard_constraint(t, HEADS_SPEC)
        q, k, v = (heads(t.reshape(shape)) for t in (q, k, v))
        if self.attn_impl == "dense":
            o = attention(q, k, v, causal=True)
        elif self.attn_impl == "flash":
            # import inside the branch: pallas is a slow import that
            # dense/ring users must not pay
            from mmlspark_tpu.ops.flash_attention import flash_attention
            o = flash_attention(q, k, v, causal=True)
        elif self.attn_impl == "ring":
            o = ring_attention(q, k, v, axis_name=self.seq_axis, causal=True)
        elif self.attn_impl == "ring_flash":
            # flash local op + LSE ring merge, differentiable (custom VJP):
            # the long-context TRAINING configuration
            o = ring_flash_attention(q, k, v, axis_name=self.seq_axis,
                                     causal=True)
        elif self.attn_impl == "ulysses":
            o = ulysses_attention(q, k, v, axis_name=self.seq_axis,
                                  causal=True)
        else:
            raise ValueError(f"unknown attn_impl '{self.attn_impl}'")
        # named for selective rematerialization: remat_policy
        # 'save_attention' stores this tensor so the backward never re-runs
        # the attention op (the flash backward already recomputes its own
        # P = exp(S - LSE) internally — re-running the forward kernel on
        # top of that is pure waste)
        from jax.ad_checkpoint import checkpoint_name
        o = checkpoint_name(heads(o), "attn_out")
        x = x + nn.Dense(self.d_model, dtype=self.dtype,
                         name="proj")(o.reshape(b, s, self.d_model))
        h = nn.LayerNorm(dtype=self.dtype)(x)
        if self.mlp_impl == "moe":
            # sparse conditional compute: Switch/GShard experts
            # (ops/moe.py); the expert dimension shards over
            # `expert_axis` via expert_parallel_rules (GSPMD EP).
            # KV-cache decode builds the same module
            # (models/transformer_decoding.py::_mlp;
            # tests/test_decoding_seam.py holds the two together)
            from mmlspark_tpu.ops.moe import MoEMLP
            return x + MoEMLP(self.d_model, n_experts=self.n_experts,
                              mlp_ratio=self.mlp_ratio, dtype=self.dtype,
                              expert_axis=self.expert_axis,
                              router_k=self.moe_router_k,
                              group_size=self.moe_group_size,
                              name="moe")(h)
        h = nn.Dense(self.mlp_ratio * self.d_model, dtype=self.dtype,
                     name="mlp_up")(h)
        # the hidden slice rides 'model' with the column-parallel mlp_up
        # kernel; mlp_down (row-parallel) contracts it back with one psum
        if not seq_sharded:
            h = shard_constraint(h, HIDDEN_SPEC)
        h = nn.gelu(h)
        return x + nn.Dense(self.d_model, dtype=self.dtype,
                            name="mlp_down")(h)


class TransformerLM(nn.Module, NodeMixin):
    """Decoder-only language model — the long-context flagship.

    New-design headroom over the reference (which has no sequence axis,
    SURVEY §5): with attn_impl='ring'/'ring_flash'/'ulysses' and seq_axis
    set, the model
    runs under shard_map with its sequence sharded over the mesh
    (parallel/ring.py), and position embeddings use GLOBAL positions
    derived from the device's ring index.  Named nodes: embed, block0..N,
    final_norm, z.
    """

    vocab_size: int = 256
    d_model: int = 128
    n_heads: int = 8
    n_layers: int = 2
    max_len: int = 2048
    mlp_ratio: int = 4
    dtype: Dtype = jnp.bfloat16
    attn_impl: str = "dense"
    seq_axis: Optional[str] = None
    mlp_impl: str = "dense"            # dense | moe (Switch/GShard experts)
    n_experts: int = 8
    expert_axis: Optional[str] = None  # mesh axis for expert parallelism
    moe_router_k: int = 1              # top-k routing (1=Switch, 2=GShard)
    moe_group_size: int = 512          # routing group size (memory bound)
    remat: bool = False  # rematerialize each block's activations in the
    # backward (jax.checkpoint): trades ~1 extra forward of FLOPs for
    # O(n_layers) less activation HBM — the long-context training lever
    remat_policy: str = "full"  # full | save_attention: 'save_attention'
    # stores each block's attention output (+ the flash kernel's out/lse
    # residuals) so the backward recomputes only the cheap dense ops, not
    # the attention kernel itself — costs O(B*S*D) extra HBM per layer,
    # nothing O(S^2)

    @nn.compact
    def __call__(self, tokens):
        # tokens: (B, S_local) int — S_local == S unless sequence-sharded
        s_local = tokens.shape[1]
        if self.seq_axis is not None and self.attn_impl != "dense":
            offset = jax.lax.axis_index(self.seq_axis) * s_local
        else:
            offset = 0
        pos = offset + jnp.arange(s_local)
        tok_emb = nn.Embed(self.vocab_size, self.d_model,
                           dtype=self.dtype, name="tok_embed")(tokens)
        pos_emb = nn.Embed(self.max_len, self.d_model,
                           dtype=self.dtype, name="pos_embed")(pos)
        x = self.node("embed", tok_emb + pos_emb[None])
        if not self.remat:
            block_cls = TransformerBlock
        elif self.remat_policy == "save_attention":
            block_cls = nn.remat(
                TransformerBlock,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "attn_out", "flash_out", "flash_lse"))
        elif self.remat_policy == "full":
            block_cls = nn.remat(TransformerBlock)
        else:
            raise ValueError(
                f"unknown remat_policy '{self.remat_policy}' "
                "(full | save_attention)")
        for i in range(self.n_layers):
            x = block_cls(
                self.d_model, self.n_heads, self.mlp_ratio, self.dtype,
                self.attn_impl, self.seq_axis, self.mlp_impl,
                self.n_experts, self.expert_axis, self.moe_router_k,
                self.moe_group_size, name=f"block{i}_w")(x)
            x = self.node(f"block{i}", x)
        x = nn.LayerNorm(dtype=self.dtype, name="final_norm_w")(x)
        x = self.node("final_norm", x)
        z = nn.Dense(self.vocab_size, dtype=self.dtype, name="lm_head")(x)
        return self.node("z", z.astype(jnp.float32))


# --------------------------------------------------------------------------
# Registry — serialized bundles name their architecture; build_model
# reconstructs it (the analogue of CNTK's self-describing .model files).
# --------------------------------------------------------------------------

from mmlspark_tpu.models.hybrid_lm import HybridLM  # noqa: E402

MODEL_REGISTRY: dict[str, Callable[..., nn.Module]] = {
    "MLPClassifier": MLPClassifier,
    "LinearModel": LinearModel,
    "ConvNetCIFAR10": ConvNetCIFAR10,
    "ResNet": ResNet,
    "TransformerLM": TransformerLM,
    "HybridLM": HybridLM,
}


def build_model(name: str, config: Optional[dict] = None) -> nn.Module:
    if name not in MODEL_REGISTRY:
        raise KeyError(f"unknown model '{name}'; known: {sorted(MODEL_REGISTRY)}")
    cfg = dict(config or {})
    if isinstance(cfg.get("dtype"), str):
        cfg["dtype"] = jnp.dtype(cfg["dtype"]).type
    for k in ("stage_sizes", "layer_types", "hidden_sizes", "widths"):
        if k in cfg:
            cfg[k] = tuple(cfg[k])
    return MODEL_REGISTRY[name](**cfg)


def register_model(name: str, ctor: Callable[..., nn.Module]) -> None:
    MODEL_REGISTRY[name] = ctor


def model_config(module: nn.Module) -> dict:
    """Extract the JSON-safe constructor config of a registered module."""
    cfg = {}
    for field in module.__dataclass_fields__:
        if field in ("parent", "name"):
            continue
        v = getattr(module, field)
        if isinstance(v, tuple):
            v = list(v)
        elif not isinstance(v, (int, float, str, bool, type(None))):
            v = jnp.dtype(v).name  # a dtype-like (the only non-scalar field kind)
        cfg[field] = v
    return cfg
