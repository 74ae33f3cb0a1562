"""ModelBundle: the serialized-model format (CNTK `.model` file replacement).

The reference ships opaque CNTK graph files loaded through JNI
(CNTKModel.scala:122-132) and even smuggles model bytes through a base64
string param (CNTKModel.scala:143-149).  Here a model is a self-describing
directory:

    bundle.json      {"architecture": <registry name>, "config": {...},
                      "metadata": {...}}
    params.msgpack   flax-serialized variables (params + batch_stats ...)

sha256 integrity is handled by the zoo layer (zoo/downloader.py), matching
the reference's Schema.scala:35-41.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional

import flax.linen as nn
import jax
import numpy as np
from flax import serialization

from mmlspark_tpu.models.definitions import (
    MODEL_REGISTRY,
    build_model,
    model_config,
)
from mmlspark_tpu.observe.compiles import setup_phase


def registry_name(module: nn.Module) -> str:
    """Registry key for a module — may differ from the class name when the
    model was registered via register_model under a custom key."""
    cls = type(module)
    name = cls.__name__
    if MODEL_REGISTRY.get(name) is cls:
        return name
    for k, v in MODEL_REGISTRY.items():
        if v is cls:
            return k
    raise KeyError(
        f"model class {name} is not registered; call register_model first")


@dataclasses.dataclass
class ModelBundle:
    """An architecture + its variables, ready to apply or fine-tune."""

    architecture: str
    config: dict
    variables: dict            # {"params": ..., possibly "batch_stats": ...}
    metadata: dict = dataclasses.field(default_factory=dict)

    def module(self) -> nn.Module:
        return build_model(self.architecture, self.config)

    def partition_rules(self) -> Optional[tuple]:
        """The partition-rule set this bundle was trained under (carried
        as JSON in metadata["partition"]["rules"], the
        parallel/partition.py round-trip), or None for a pre-partition
        bundle — consumers then fall back to DEFAULT_RULES."""
        data = (self.metadata or {}).get("partition", {}).get("rules")
        if not data:
            return None
        from mmlspark_tpu.parallel.partition import rules_from_json
        return rules_from_json(data)

    def partition_mesh_shape(self) -> Optional[dict]:
        """{"data": dp, "model": mp} the bundle was produced under, or
        None; arrays are always full-shape, so this is advisory (error
        messages, bench provenance) — any topology can load the bundle."""
        shape = (self.metadata or {}).get("partition", {}).get("mesh")
        return dict(shape) if shape else None

    @staticmethod
    def from_module(module: nn.Module, variables: dict,
                    metadata: Optional[dict] = None) -> "ModelBundle":
        return ModelBundle(
            architecture=registry_name(module),
            config=model_config(module),
            variables=variables,
            metadata=dict(metadata or {}),
        )

    @staticmethod
    def init(module: nn.Module, input_shape: tuple, seed: int = 0,
             metadata: Optional[dict] = None,
             input_dtype=None) -> "ModelBundle":
        """Fresh-init variables for `module` fed zeros of `input_shape`.

        The feed dtype is derived from the module when not given: token-
        input models (anything with a `vocab_size` field — their first op
        is an Embed lookup, which requires integer indices) get int32;
        everything else float32.  Pass `input_dtype` explicitly for custom
        architectures whose input convention differs.
        """
        if input_dtype is None:
            input_dtype = (np.int32
                           if getattr(module, "vocab_size", None) is not None
                           else np.float32)
        x = np.zeros(input_shape, input_dtype)
        # the eager flax init: every initializer is a program of its own
        with setup_phase("bundle"):
            variables = module.init(jax.random.key(seed), x)
            # unfreeze to plain dict for serialization uniformity
            variables = jax.tree_util.tree_map(np.asarray,
                                               _to_plain(variables))
        return ModelBundle.from_module(module, variables, metadata)


def _to_plain(tree):
    if hasattr(tree, "unfreeze"):
        tree = tree.unfreeze()
    if isinstance(tree, dict):
        return {k: _to_plain(v) for k, v in tree.items()}
    return tree


def _full_host_array(x) -> np.ndarray:
    """One leaf -> a full-logical-shape host array.  Model-sharded leaves
    under single-process meshes are fully addressable (np.asarray
    reassembles the shards); multi-host shards are gathered through a
    replicated identity first.  Either way what lands on disk carries the
    full shape — checkpoints stay topology-portable (restore re-commits
    onto whatever dp x mp mesh is live)."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from jax.sharding import PartitionSpec as P

        from mmlspark_tpu.parallel.partition import named_sharding
        rep = named_sharding(x.sharding.mesh, P())
        x = jax.jit(lambda t: t, out_shardings=rep)(x)
    return np.asarray(jax.device_get(x))


def save_bundle(bundle: ModelBundle, path: str) -> None:
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "bundle.json"), "w") as f:
        json.dump({
            "architecture": bundle.architecture,
            "config": bundle.config,
            "metadata": bundle.metadata,
        }, f, indent=1)
    host_vars = jax.tree_util.tree_map(_full_host_array,
                                       _to_plain(bundle.variables))
    with open(os.path.join(path, "params.msgpack"), "wb") as f:
        f.write(serialization.to_bytes(host_vars))


def load_bundle(path: str) -> ModelBundle:
    with open(os.path.join(path, "bundle.json")) as f:
        info = json.load(f)
    module = build_model(info["architecture"], info["config"])
    # Re-init with dummy shapes is avoided: from_bytes restores into a
    # None-target pytree of raw dicts/arrays.
    with open(os.path.join(path, "params.msgpack"), "rb") as f:
        variables = serialization.msgpack_restore(f.read())
    return ModelBundle(info["architecture"], info["config"], variables,
                       info.get("metadata", {}))
