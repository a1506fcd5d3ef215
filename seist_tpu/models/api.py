"""Model construction / initialization helpers.

Counterpart of the reference's ``models/_factory.py:41-56`` ``create_model``;
checkpoint save/load lives in seist_tpu/models/checkpoint.py.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from seist_tpu.registry import MODELS


def create_model(model_name: str, in_channels: int = 3, in_samples: int = 8192, **kwargs):
    """Instantiate a registered model module."""
    return MODELS.create(
        model_name, in_channels=in_channels, in_samples=in_samples, **kwargs
    )


def example_input(model, batch_size: int, in_samples: int, in_channels: int,
                  *, abstract: bool = False):
    """The input a model is initialised on: a float waveform (N, L, C), or
    integer ids (N, L) for a model that says ``input_kind = "tokens"``."""
    if getattr(model, "input_kind", "waveform") == "tokens":
        shape, dtype = (batch_size, in_samples), jnp.int32
    else:
        shape, dtype = (batch_size, in_samples, in_channels), jnp.float32
    if abstract:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jnp.zeros(shape, dtype)


def init_variables(
    model,
    seed: int = 0,
    in_samples: int = 8192,
    in_channels: int = 3,
    batch_size: int = 1,
) -> Dict[str, Any]:
    """Initialize model variables ({'params', 'batch_stats', ...}).

    The whole init is jitted: flax init executed op-by-op compiles hundreds of
    tiny XLA programs; one fused program is ~50x faster.
    """
    x = example_input(model, batch_size, in_samples, in_channels)
    key = jax.random.PRNGKey(seed)

    @jax.jit
    def _init(key, x):
        pk, dk = jax.random.split(key)
        return model.init({"params": pk, "dropout": dk}, x, train=False)

    return _init(key, x)


def param_shapes(
    model, in_samples: int = 8192, in_channels: int = 3
) -> Dict[str, Any]:
    """Shape-only init (no compute) — for counting/inspection."""
    x = example_input(model, 1, in_samples, in_channels, abstract=True)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.eval_shape(
        lambda k, x: model.init({"params": k, "dropout": k}, x, train=False), key, x
    )


def count_params(tree) -> int:
    import numpy as np

    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(tree))
