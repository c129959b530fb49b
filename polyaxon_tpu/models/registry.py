"""Model registry: `program.model.name` → builder.

The reference runs arbitrary user containers (SURVEY.md §1: training compute
is not in-repo); the TPU rebuild owns the training loop, so models live here
as flax modules selected by name from the Polyaxonfile `program:` block.

A builder takes the `program.model.config` dict and returns a `ModelBundle`:
the flax module plus everything the trainer needs to drive it generically
(input synthesis for init, loss selection, logical-axis sharding rules).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import flax.linen as nn
import jax.numpy as jnp

_REGISTRY: dict[str, Callable[[dict], "ModelBundle"]] = {}


@dataclasses.dataclass
class ModelBundle:
    """Everything the generic trainer needs about a model.

    - `module`: the flax module; `__call__(batch_inputs, train=...)` → logits.
    - `example_inputs(batch_size)`: abstract/concrete inputs for `init` and
      shape inference — static shapes so XLA compiles once.
    - `loss`: default loss name (ops/losses.py) if the train spec doesn't pick.
    - `sharding_rules`: (param-path-regex, PartitionSpec-axes) pairs consumed
      by parallel/sharding.py; axes name *logical* mesh axes ("model", "fsdp",
      None) so one rule set serves any mesh shape.
    - `task`: "classification" | "mlm" | "lm" — selects batch schema.
    """

    name: str
    module: nn.Module
    example_inputs: Callable[[int], Any]
    loss: str = "softmax_cross_entropy"
    sharding_rules: tuple = ()
    task: str = "classification"
    rngs: tuple[str, ...] = ("dropout",)
    # If non-empty: only params whose path matches one of these regexes are
    # trained; the rest are frozen — the trainer wraps the optimizer in
    # optax.multi_transform with set_to_zero() for non-matching params
    # (NOT optax.masked, which would pass raw grads through as updates).
    trainable_patterns: tuple = ()
    # Extra collections the module carries through apply (e.g. batch_stats).
    mutable: tuple[str, ...] = ()
    # True if the module sows auxiliary losses into the `losses` collection
    # (e.g. MoE load balancing); the trainer adds them to the total loss.
    aux_losses: bool = False
    # Per-step readings the module sows into the `step_collections`
    # (`moe_stats` of routed layers: local assignments, load, overflow;
    # `ssm_stats` of Mamba layers: step size, in-chunk decay), reduced over
    # the layers to a flat dict by `step_metrics({collection: sown})`; the
    # trainer reports them as step metrics.
    step_metrics: Optional[Callable] = None
    step_collections: tuple[str, ...] = ()
    # Optional fused head+loss: (params, features, batch) -> scalar. When
    # set, the trainer applies the module with return_features=True and
    # computes the loss from pre-head features — the [B, S, V] logits
    # never materialize (ops/losses.fused_linear_masked_lm).
    fused_loss: Optional[Callable] = None
    # The rungs of the Trainer's remat ladder (`train.remat: true`) that the
    # module understands beyond "all", most kept first: each is a value of a
    # static `keep=` argument of `module.__call__` that says what the backward
    # finds kept ("block": each block's input, the block run again from it).
    # Empty: the module names no block boundary, and the ladder's second rung
    # is a checkpoint of the whole apply.
    keep_rungs: tuple[str, ...] = ()


def register(name: str):
    def deco(fn: Callable[[dict], ModelBundle]):
        _REGISTRY[name] = fn
        return fn

    return deco


def build_model(name: str, config: Optional[dict] = None) -> ModelBundle:
    if name not in _REGISTRY:
        raise ValueError(f"unknown model {name!r}; registered: {sorted(_REGISTRY)}")
    return _REGISTRY[name](dict(config or {}))


def registered_models() -> list[str]:
    return sorted(_REGISTRY)


def f32_images(shape: tuple[int, ...]):
    def make(batch_size: int):
        return jnp.zeros((batch_size, *shape), jnp.float32)

    return make


def i32_tokens(seq_len: int):
    def make(batch_size: int):
        return jnp.zeros((batch_size, seq_len), jnp.int32)

    return make
