"""Plain ``jax.numpy`` building blocks of the references: float32, no
kernels, no fused or composed lowerings, nothing imported from the program.

``q`` is the precision hook: every convolution and matrix product applies it
to both operands. The reference proper passes the identity (and runs under
``jax.default_matmul_precision("highest")``); the control passes a cast to a
lower precision and back, i.e. operands in that precision, float32
accumulation — the step a later PR would be tempted to take.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

BN_EPSILON = 1e-5
Array = jnp.ndarray


def identity(a: Array) -> Array:
    return a


def through(dtype) -> Callable[[Array], Array]:
    """Operands rounded to ``dtype`` (e.g. ``jnp.float8_e4m3fn``)."""
    return lambda a: a.astype(dtype).astype(jnp.float32)


def auto_pad(x: Array, k: int, stride: int = 1) -> Array:
    """Asymmetric 'same' padding so that L_out = ceil(L / stride)."""
    length = x.shape[1]
    pds = (stride - length % stride) % stride + k - stride
    return jnp.pad(x, ((0, 0), (pds // 2, pds - pds // 2), (0, 0)))


def same_pad(x: Array, k: int) -> Array:
    lp = (k - 1) // 2
    return jnp.pad(x, ((0, 0), (lp, k - 1 - lp), (0, 0)))


def conv1d(x: Array, w: Array, q, stride: int = 1, groups: int = 1) -> Array:
    """VALID 1-D convolution, x (N, L, Cin), w (k, Cin / groups, Cout)."""
    return jax.lax.conv_general_dilated(
        q(x), q(w), (stride,), "VALID",
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=groups,
    )


def conv1d_transpose(x: Array, w: Array, q, stride: int) -> Array:
    """Transposed convolution with no padding: L_out = (L - 1) * s + k
    (kernel not flipped, as flax's ConvTranspose and lax.conv_transpose)."""
    k = w.shape[0]
    pad_a = k - 1
    pad_b = k + stride - 2 + max(k - stride, 0) - pad_a
    return jax.lax.conv_general_dilated(
        q(x), q(w), (1,), [(pad_a, pad_b)], lhs_dilation=(stride,),
        dimension_numbers=("NWC", "WIO", "NWC"),
    )


def dense(x: Array, p: Dict[str, Array], q) -> Array:
    y = jnp.einsum("...i,io->...o", q(x), q(p["kernel"]))
    return y + p["bias"] if "bias" in p else y


def gelu(x: Array) -> Array:
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def pool_ceil(x: Array, k: int) -> Array:
    """avg + max pooling, window = stride = k, ceil mode: the last partial
    window averages over its valid samples only."""
    if k == 1:
        return x
    n, length, c = x.shape
    n_out = -(-length // k)
    pad = n_out * k - length
    xs = jnp.pad(x, ((0, 0), (0, pad), (0, 0))).reshape(n, n_out, k, c)
    xm = jnp.pad(x, ((0, 0), (0, pad), (0, 0)), constant_values=-jnp.inf)
    counts = jnp.full((n_out,), float(k)).at[-1].set(float(k - pad))
    avg = xs.sum(axis=2) / counts[None, :, None]
    return avg + xm.reshape(n, n_out, k, c).max(axis=2)


def interpolate_linear(x: Array, out_size: int) -> Array:
    """torch F.interpolate(mode='linear', align_corners=False)."""
    l_in = x.shape[1]
    if l_in == out_size:
        return x
    src = (jnp.arange(out_size, dtype=jnp.float32) + 0.5) * (l_in / out_size) - 0.5
    src = jnp.clip(src, 0.0, l_in - 1)
    lo = jnp.floor(src).astype(jnp.int32)
    hi = jnp.minimum(lo + 1, l_in - 1)
    w = (src - lo.astype(jnp.float32))[None, :, None]
    return x[:, lo, :] * (1.0 - w) + x[:, hi, :] * w


class Norms:
    """Batch normalisation over (N, L): running statistics in eval mode,
    batch statistics in train mode (collected under the same names, the
    unbiased variance as the running one)."""

    def __init__(self, stats: Dict, train: bool) -> None:
        self.stats, self.train = stats, train
        self.new: Dict = {}

    def __call__(self, x: Array, p: Dict[str, Array], path: Tuple[str, ...]) -> Array:
        if self.train:
            mean = jnp.mean(x, (0, 1))
            var = jnp.maximum(jnp.mean(jnp.square(x), (0, 1)) - jnp.square(mean), 0.0)
            n = x.shape[0] * x.shape[1]
            put(self.new, path, {"mean": mean, "var": var * (n / max(n - 1, 1))})
        else:
            s = get(self.stats, path)
            mean, var = s["mean"], s["var"]
        return (x - mean) * (jax.lax.rsqrt(var + BN_EPSILON) * p["scale"]) + p["bias"]


def get(tree: Dict, path: Tuple[str, ...]):
    for k in path:
        tree = tree[k]
    return tree


def put(tree: Dict, path: Tuple[str, ...], value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def make_weights(key, shapes: Dict) -> Dict:
    """Weights from a key, one leaf per entry of ``shapes`` (a nested dict
    whose leaves are ``(shape, kind)``): kernels ~ N(0, 0.05), biases ~
    N(0, 0.05), norm scales ~ 1 + N(0, 0.1), running means 0, variances 1.
    Any weights will do for speed and for agreement; these keep every
    branch of the network alive."""
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda v: isinstance(v, tuple) and isinstance(v[1], str)
    )
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (shape, kind) in zip(keys, leaves):
        noise = jax.random.normal(k, shape, jnp.float32)
        out.append({
            "kernel": 0.05 * noise, "bias": 0.05 * noise,
            "scale": 1.0 + 0.1 * noise, "mean": jnp.zeros(shape, jnp.float32),
            "var": jnp.ones(shape, jnp.float32),
        }[kind])
    return jax.tree.unflatten(treedef, out)


def norm_shapes(c: int) -> Tuple[Dict, Dict]:
    return ({"scale": ((c,), "scale"), "bias": ((c,), "bias")},
            {"mean": ((c,), "mean"), "var": ((c,), "var")})
