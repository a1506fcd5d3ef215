"""Loss library (8 losses), channel-last.

TPU-native re-design of the reference's ``models/loss.py:8-210``. Semantics
match the reference exactly — losses consume **probabilities** (models end in
softmax/sigmoid) with eps=1e-6 inside logs — but arrays are channels-last:
dense outputs are ``(N, L, C)`` and class outputs ``(N, Classes)``, so the
class/channel axis is always ``-1`` (the reference reduces dim=1 on
``(N, C, L)``; the reductions are equivalent).

Losses are plain callables usable inside ``jax.jit``/``jax.grad``.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

_EPS = 1e-6

Array = jnp.ndarray


def _as_weight(weight) -> Array:
    """Normalize a reference-style weight spec (possibly nested lists like
    ``[[0.5], [1], [1]]``) to a flat per-channel vector."""
    if weight is None:
        return jnp.asarray(1.0, dtype=jnp.float32)
    w = np.asarray(weight, dtype=np.float32).reshape(-1)
    return jnp.asarray(w)


class CELoss:
    """Cross entropy on probability outputs (ref: loss.py:8-29).

    Input shape: ``(N, L, C)`` or ``(N, Classes)``.
    """

    def __init__(self, weight=None):
        self.weight = _as_weight(weight)

    def __call__(self, preds: Array, targets: Array) -> Array:
        loss = -targets * jnp.log(preds + _EPS)
        loss = loss * self.weight
        return loss.sum(axis=-1).mean()


class BCELoss:
    """Binary cross entropy on probability outputs (ref: loss.py:32-56)."""

    def __init__(self, weight=None):
        self.weight = _as_weight(weight)

    def __call__(self, preds: Array, targets: Array) -> Array:
        loss = -(
            targets * jnp.log(preds + _EPS)
            + (1.0 - targets) * jnp.log(1.0 - preds + _EPS)
        )
        loss = loss * self.weight
        return loss.mean()


class FocalLoss:
    """Focal loss (ref: loss.py:59-92). ``has_softmax`` applies softmax over
    the class axis (the reference's dim=1 on logits)."""

    def __init__(self, gamma: float = 2.0, weight=None, has_softmax: bool = True):
        self.gamma = gamma
        self.weight = _as_weight(weight)
        self.has_softmax = has_softmax

    def __call__(self, preds: Array, targets: Array) -> Array:
        if self.has_softmax:
            preds = jnp.exp(preds - jnp.max(preds, axis=-1, keepdims=True))
            preds = preds / preds.sum(axis=-1, keepdims=True)
        loss = -targets * jnp.log(preds + _EPS)
        loss = loss * jnp.power(1.0 - preds, self.gamma)
        loss = loss * self.weight
        return loss.sum(axis=-1).mean()


class BinaryFocalLoss:
    """Binary focal loss on sigmoid outputs (ref: loss.py:95-130)."""

    def __init__(self, gamma: float = 2.0, alpha: float = 1.0, weight=None):
        self.gamma = gamma
        self.alpha = alpha
        self.weight = _as_weight(weight)

    def __call__(self, preds: Array, targets: Array) -> Array:
        loss = -(
            self.alpha
            * jnp.power(1.0 - preds, self.gamma)
            * targets
            * jnp.log(preds + _EPS)
            + (1.0 - self.alpha)
            * jnp.power(preds, self.gamma)
            * (1.0 - targets)
            * jnp.log(1.0 - preds + _EPS)
        )
        loss = loss * self.weight
        return loss.mean()


class MSELoss:
    """Mean squared error (ref: loss.py:133-152)."""

    def __init__(self, weight=None):
        self.weight = _as_weight(weight)

    def __call__(self, preds: Array, targets: Array) -> Array:
        loss = (preds - targets) ** 2
        loss = loss * self.weight
        return loss.mean()


class HuberLoss:
    """Huber loss, delta=1, mean reduction (torch.nn.HuberLoss parity;
    re-exported by the reference at loss.py:3)."""

    def __init__(self, delta: float = 1.0):
        self.delta = delta

    def __call__(self, preds: Array, targets: Array) -> Array:
        err = preds - targets
        abs_err = jnp.abs(err)
        quad = jnp.minimum(abs_err, self.delta)
        lin = abs_err - quad
        return (0.5 * quad**2 + self.delta * lin).mean()


class CombinationLoss:
    """Weighted sum of per-output losses for multi-task models
    (ref: loss.py:155-190)."""

    def __init__(
        self,
        losses: Sequence[Callable],
        losses_weights: Optional[Sequence[float]] = None,
    ):
        assert len(losses) > 0
        if len(losses) == 1:
            raise ValueError(
                "CombinationLoss requires at least two loss modules; "
                f"use {losses[0]} directly instead."
            )
        if losses_weights is not None:
            assert len(losses) == len(losses_weights)
            self.losses_weights = list(losses_weights)
        else:
            self.losses_weights = [1.0] * len(losses)
        self.losses = [L() for L in losses]

    @property
    def reduction(self) -> str:
        """'sum' if any component is sum-reduced (a weighted sum of sums is
        still a sum over the batch), else 'mean'."""
        return (
            "sum"
            if any(getattr(fn, "reduction", "mean") == "sum" for fn in self.losses)
            else "mean"
        )

    def __call__(self, preds: Tuple[Array, ...], targets: Tuple[Array, ...]) -> Array:
        total = 0.0
        for pred, target, loss_fn, w in zip(
            preds, targets, self.losses, self.losses_weights
        ):
            total = total + loss_fn(pred, target) * w
        return total


class MousaviLoss:
    """Heteroscedastic regression loss for MagNet / dist-PT
    (ref: loss.py:193-210). ``preds`` is ``(N, 2)``: (y_hat, log sigma^2).

    Sum-reduced over the batch (matching the reference's ``torch.sum``) —
    consumers that decompose losses per-sample (the masked eval step) check
    ``reduction`` to pick the right recombination.
    """

    reduction = "sum"

    def __call__(self, preds: Array, targets: Array) -> Array:
        y_hat = preds[:, 0].reshape(-1, 1)
        s = preds[:, 1].reshape(-1, 1)
        return jnp.sum(
            0.5 * jnp.exp(-1.0 * s) * jnp.square(jnp.abs(targets - y_hat)) + 0.5 * s
        )


__all__ = [
    "CELoss",
    "BCELoss",
    "FocalLoss",
    "BinaryFocalLoss",
    "MSELoss",
    "HuberLoss",
    "CombinationLoss",
    "MousaviLoss",
]


def _token_targets(logits, targets):
    valid = targets >= 0
    return valid, jnp.where(valid, targets, 0).astype(jnp.int32)


class TokenCELoss:
    """Mean next-token cross-entropy in float32 over the vocabulary the
    logits span. ``logits`` (N, L, V) in any float dtype, ``targets`` (N, L)
    integer ids, negative where a position has no target (the last of a
    window). No auxiliary term."""

    reduction = "mean"

    def __call__(self, logits, targets):
        valid, safe = _token_targets(logits, targets)
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        nll = jnp.where(valid, lse - picked, 0.0)
        return nll.sum() / jnp.maximum(valid.sum(), 1).astype(jnp.float32)


def token_hits(logits, targets):
    """(positions whose largest logit is the target, positions with a
    target): token accuracy's numerator and denominator."""
    valid, safe = _token_targets(logits, targets)
    hit = (jnp.argmax(logits, axis=-1).astype(jnp.int32) == safe) & valid
    return hit.sum(), valid.sum()
