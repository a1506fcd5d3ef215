"""An expert layer that is told which experts it holds.

Expert parallelism gives each chip ``count`` of the layer's ``n_experts``
routed experts. The router is whole on every chip: it scores a token over
all experts and picks its ``top_k``; the chip then computes the part of the
layer's result that its own experts give — for the token-slots that fell on
them — and nothing stands in for the rest. With ``experts_held = (0,
n_experts)`` that part is the whole routed result. The sum over all shares
of these parts is the uncut layer's routed output (tests/test_nemotron_h.py
ties the share to the model that way); on one chip there is no exchange.

Shapes are static: the token-slots that fall on experts held are sorted by
expert into a buffer of ``capacity`` rows (the model states it as a
multiple of :func:`expected_rows`, ``tokens * top_k * count / n_experts``),
one grouped product per projection runs over the buffer
(``jax.lax.ragged_dot``), and the rows are added back to their tokens. A
slot that finds no room is **counted** (``overflow_rows``), never dropped in
silence: a run whose count is not 0 computed something else than the model,
and the benchmark's check fails it.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def route(
    x: jax.Array,
    w_router: jax.Array,
    score_bias: jax.Array,
    *,
    top_k: int,
    scaling: float,
) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid router in float32 over all experts: the ``top_k`` largest of
    ``s + score_bias``; the weights are the chosen ``s`` (without the bias)
    over their sum, times ``scaling``. ``x`` (T, D) -> ids, weights (T, k)."""
    logits = jnp.dot(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    s = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(s + score_bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * scaling
    return ids, w


def expected_rows(tokens: int, top_k: int, count: int, n_experts: int) -> int:
    return -(-tokens * top_k * count // n_experts)


def local_experts(
    x: jax.Array,
    ids: jax.Array,
    weights: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    first: int,
    capacity: int,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The held experts' part of the routed result. ``x`` (T, D); ``ids``,
    ``weights`` (T, k) from :func:`route`; ``w_up`` (count, D, F) and
    ``w_down`` (count, F, D) are experts ``first .. first + count - 1``, each
    ``W_down relu(W_up x)^2``. Returns (T, D) in ``x``'s dtype and the
    counts ``slots_local`` (token-slots on experts held), ``overflow_rows``
    (those beyond ``capacity``) and ``load_max_over_mean`` (largest over
    mean load of the experts held)."""
    tokens, top_k = ids.shape
    count = w_up.shape[0]
    local = ids.reshape(-1) - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count).astype(jnp.int32)
    loads = jnp.sum(
        key[:, None] == jnp.arange(count, dtype=jnp.int32)[None, :], axis=0
    ).astype(jnp.int32)
    slots_local = jnp.sum(loads)

    rows = jnp.argsort(key, stable=True)[:capacity]  # held slots first
    row_expert = key[rows]
    row_token = rows // top_k
    row_weight = jnp.where(
        row_expert < count, weights.reshape(-1)[rows], 0.0
    )
    ends = jnp.minimum(jnp.cumsum(loads), capacity)
    group_sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)

    # Rows past the groups hold nothing of the model. The grouped product
    # leaves them unwritten, forward AND backward (on the TPU that is
    # whatever the memory held: a gradient 48000 times too large reached the
    # layer's input on the first chip run of PR 29), so they are masked on
    # the way in — which masks the gradient on its way out — and on the way
    # out.
    in_use = (row_expert < count)[:, None]
    xs = jnp.where(in_use, x[row_token], 0)
    h = jax.lax.ragged_dot(xs, w_up.astype(x.dtype), group_sizes)
    h = jnp.square(jax.nn.relu(jnp.where(in_use, h, 0)))
    ys = jax.lax.ragged_dot(h, w_down.astype(x.dtype), group_sizes)
    ys = jnp.where(in_use, ys * row_weight[:, None].astype(ys.dtype), 0)
    out = jnp.zeros((tokens, x.shape[1]), ys.dtype).at[row_token].add(ys)
    stats = {
        "slots_local": slots_local,
        "overflow_rows": jnp.maximum(slots_local - capacity, 0),
        "load_max_over_mean": jnp.max(loads).astype(jnp.float32)
        / jnp.maximum(jnp.mean(loads.astype(jnp.float32)), 1e-9),
    }
    return out.astype(x.dtype), stats
