"""Shared building blocks for the model zoo (channels-last, Flax linen).

Geometry parity helpers mirror the reference exactly (a stated hard part,
SURVEY.md §7): ``auto_pad_1d`` reproduces ``models/seist.py:12-48`` /
``magnet.py:16-33``; ceil-mode pooling reproduces torch's
``MaxPool1d/AvgPool1d(ceil_mode=True)`` including the partial-window divisor
of AvgPool; ``interpolate_linear`` reproduces ``F.interpolate(mode='linear',
align_corners=False)``.

All arrays are ``(N, L, C)``. All modules take ``train: bool`` and use the
'dropout' RNG stream for dropout and stochastic depth.
"""

from __future__ import annotations

import contextlib
import math
import os
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

Array = jnp.ndarray

# Default init mirroring the SeisT reference (trunc normal 0.02,
# seist.py:816-831). Other models use flax defaults (init distribution is not
# a behavior-parity surface).
trunc_normal_init = nn.initializers.truncated_normal(stddev=0.02)


# --------------------------------------------------------------------- padding
def auto_pad_amount(length: int, kernel_size: int, stride: int = 1) -> Tuple[int, int]:
    """'same'-style asymmetric padding so L_out = ceil(L/stride)
    (ref: seist.py:41-47)."""
    assert kernel_size >= stride, (
        f"`kernel_size` must be >= `stride`, got {kernel_size}, {stride}"
    )
    pds = (stride - (length % stride)) % stride + kernel_size - stride
    return pds // 2, pds - pds // 2


def auto_pad_1d(
    x: Array, kernel_size: int, stride: int = 1, padding_value: float = 0.0
) -> Array:
    """Pad the length axis (-2) of an (N, L, C) array (ref: seist.py:12-48)."""
    lp, rp = auto_pad_amount(x.shape[-2], kernel_size, stride)
    pads = [(0, 0)] * x.ndim
    pads[-2] = (lp, rp)
    return jnp.pad(x, pads, constant_values=padding_value)


def same_pad_amount(kernel_size: int) -> Tuple[int, int]:
    """torch-style static 'same' padding for stride-1 convs
    (ref: phasenet.py:45-48)."""
    return (kernel_size - 1) // 2, kernel_size - 1 - (kernel_size - 1) // 2


def same_pad_1d(x: Array, kernel_size: int, padding_value: float = 0.0) -> Array:
    lp, rp = same_pad_amount(kernel_size)
    pads = [(0, 0)] * x.ndim
    pads[-2] = (lp, rp)
    return jnp.pad(x, pads, constant_values=padding_value)


def causal_pad_1d(x: Array, kernel_size: int, dilation: int = 1) -> Array:
    """Left-only padding for causal TCNs (ref: distpt_network.py:17-34)."""
    pds = (kernel_size - 1) * dilation
    pads = [(0, 0)] * x.ndim
    pads[-2] = (pds, 0)
    return jnp.pad(x, pads)


def channel_pad_multiple() -> int:
    """``SEIST_CHANNEL_PAD``: round conv OUT-channel axes up to this
    multiple in the composed/fused dense-conv lowerings (0 = off,
    default). Candidate MFU lowering for the tiny-channel stems
    (out_dim 8-24 vs the TPU's 128-lane registers): zero-padded out-channels compute zeros that are
    sliced away before BN, so values and the checkpoint tree are
    untouched — only XLA's layout/tiling choice changes. Promote or
    revert on a measured same-chip A/B (not measured on this
    installation); until then it is off everywhere."""
    return int(os.environ.get("SEIST_CHANNEL_PAD", "0"))


def pad_kernel_out_channels(kernel: Array) -> Tuple[Array, int]:
    """Zero-pad a conv kernel's trailing (out-channel) axis up to the
    SEIST_CHANNEL_PAD multiple. Returns (kernel, true_out_channels);
    slice the conv result back to ``true_out_channels`` channels."""
    out = kernel.shape[-1]
    mult = channel_pad_multiple()
    if mult <= 0 or out % mult == 0:
        return kernel, out
    pads = [(0, 0)] * (kernel.ndim - 1) + [(0, mult - out % mult)]
    return jnp.pad(kernel, pads), out


# --------------------------------------------------------------------- pooling
def ceil_len(length: int, stride: int) -> int:
    return -(-length // stride)


def max_pool_1d_ceil(x: Array, kernel_size: int) -> Array:
    """MaxPool1d(k, ceil_mode=True) parity: stride=k, right-pad with -inf."""
    L = x.shape[-2]
    pad_r = ceil_len(L, kernel_size) * kernel_size - L
    return jax.lax.reduce_window(
        x,
        -jnp.inf,
        jax.lax.max,
        window_dimensions=(1, kernel_size, 1),
        window_strides=(1, kernel_size, 1),
        padding=((0, 0), (0, pad_r), (0, 0)),
    )


def avg_pool_1d_ceil(x: Array, kernel_size: int) -> Array:
    """AvgPool1d(k, ceil_mode=True) parity: the partial last window divides by
    the count of *valid* elements (verified against torch)."""
    L = x.shape[-2]
    pad_r = ceil_len(L, kernel_size) * kernel_size - L
    sums = jax.lax.reduce_window(
        x,
        0.0,
        jax.lax.add,
        window_dimensions=(1, kernel_size, 1),
        window_strides=(1, kernel_size, 1),
        padding=((0, 0), (0, pad_r), (0, 0)),
    )
    # Valid-count divisor per output position (static, computed in Python).
    n_out = ceil_len(L, kernel_size)
    counts = jnp.full((n_out,), float(kernel_size))
    last_valid = L - (n_out - 1) * kernel_size
    counts = counts.at[-1].set(float(last_valid))
    return sums / counts[None, :, None].astype(x.dtype)


def max_pool_1d(x: Array, kernel_size: int) -> Array:
    """MaxPool1d(k) floor-mode parity (drops the trailing partial window)."""
    L = x.shape[-2]
    n_out = L // kernel_size
    return jax.lax.reduce_window(
        x[:, : n_out * kernel_size],
        -jnp.inf,
        jax.lax.max,
        window_dimensions=(1, kernel_size, 1),
        window_strides=(1, kernel_size, 1),
        padding="VALID",
    )


def global_avg_pool(x: Array) -> Array:
    """AdaptiveAvgPool1d(1) + flatten: (N, L, C) -> (N, C)."""
    return x.mean(axis=-2)


# ---------------------------------------------------------------- interpolate
def interpolate_linear(x: Array, out_size: int) -> Array:
    """F.interpolate(mode='linear', align_corners=False) parity for (N, L, C).

    src = (dst + 0.5) * L_in/L_out - 0.5, clamped; linear blend of the two
    nearest source samples (ref usage: seist.py:566, ditingmotion nearest uses
    interpolate_nearest below).

    Integer upscale factors (the dpk head's whole ladder) take a pure
    arithmetic path — per output phase j the source pair is a fixed
    (shift, weight), so the result is r weighted blends of two shifted
    copies, interleaved by reshape. No gather: TPU lowers this to plain
    vector ops instead of a gather HLO.
    """
    L_in = x.shape[-2]
    if L_in == out_size:
        return x
    if out_size % L_in == 0:
        return _interpolate_linear_intscale(x, out_size // L_in)
    scale = L_in / out_size
    dst = jnp.arange(out_size, dtype=jnp.float32)
    src = (dst + 0.5) * scale - 0.5
    src = jnp.clip(src, 0.0, L_in - 1)
    lo = jnp.floor(src).astype(jnp.int32)
    hi = jnp.minimum(lo + 1, L_in - 1)
    w = (src - lo.astype(jnp.float32))[None, :, None].astype(x.dtype)
    return x[:, lo, :] * (1.0 - w) + x[:, hi, :] * w


def _interpolate_linear_intscale(x: Array, r: int) -> Array:
    """Gather-free linear upsampling by integer factor ``r``.

    For output index d = i*r + j: src = i + (j + 0.5 - r/2)/r, so phase j
    blends x[i] with its left (o_j < 0) or right (o_j > 0) neighbor with a
    static weight; edge clamping reproduces the gather path's jnp.clip.
    """
    x_prev = jnp.concatenate([x[:, :1], x[:, :-1]], axis=1)
    x_next = jnp.concatenate([x[:, 1:], x[:, -1:]], axis=1)
    phases = []
    for j in range(r):
        o = (j + 0.5 - r / 2.0) / r
        if o < 0:
            phases.append(x * (1.0 + o) + x_prev * (-o))
        elif o > 0:
            phases.append(x * (1.0 - o) + x_next * o)
        else:
            phases.append(x)
    out = jnp.stack(phases, axis=2)  # (N, L, r, C)
    n, l, _, c = out.shape
    return out.reshape(n, l * r, c)


def interpolate_nearest(x: Array, out_size: int) -> Array:
    """F.interpolate(mode='nearest') parity for (N, L, C).

    Integer upscale factors take the gather-free ``jnp.repeat`` path —
    ``floor(d * L/out)`` with ``out = r*L`` is exactly ``d // r`` — so the
    backward is a clean windowed reduce instead of a scatter (same
    motivation as the integer path of :func:`interpolate_linear`)."""
    L_in = x.shape[-2]
    if L_in == out_size:
        return x
    if out_size % L_in == 0:
        return jnp.repeat(x, out_size // L_in, axis=-2)
    idx = jnp.floor(jnp.arange(out_size, dtype=jnp.float32) * (L_in / out_size))
    return x[:, idx.astype(jnp.int32), :]


def upsample_x2(x: Array) -> Array:
    """nn.Upsample(scale_factor=2) (nearest) parity (ref: eqtransformer.py:384)."""
    return jnp.repeat(x, 2, axis=-2)


# --------------------------------------------------------------------- helpers
def make_divisible(v: int, divisor: int) -> int:
    """Channel rounding (ref: seist.py:51-60)."""
    new_v = max(divisor, int(v + divisor / 2) // divisor * divisor)
    if new_v < 0.9 * v:
        new_v += divisor
    return new_v


# --------------------------------------------------------------------- modules
class DepthwiseConv1D(nn.Module):
    """Depthwise conv1d with a TPU-friendly shift-FMA lowering.

    Param tree matches ``nn.Conv(features, (k,), feature_group_count=
    features)`` exactly — ``kernel`` of shape (k, 1, C) — so checkpoints and
    the torch converter are unaffected by the impl choice.

    Why not XLA's grouped conv: with the SeisT stem's tiny channel counts
    (8-24 vs the TPU's 128-wide lanes, seist.py presets) the grouped-conv
    lowering runs at <1% MFU and dominates the whole model's step time
    (seen on an earlier installation; not measured on this one).
    ``impl='shift'`` computes
    ``y[n,l,c] = sum_j x[n, l*s+j, c] * w[j,c]`` as k strided-slice
    multiply-adds — pure VPU elementwise work XLA fuses into one kernel.
    ``impl='grouped'`` keeps the lax.conv path (used off-TPU where grouped
    convs lower fine and for A/B benchmarking via SEIST_DWCONV_IMPL).
    """

    features: int
    kernel_size: int
    stride: int = 1
    kernel_init: Any = trunc_normal_init
    # None -> env SEIST_DWCONV_IMPL, else 'shift' on TPU / 'grouped' off-TPU
    impl: Optional[str] = None

    @nn.compact
    def __call__(self, x: Array) -> Array:
        kernel = self.param(
            "kernel", self.kernel_init, (self.kernel_size, 1, self.features)
        )
        impl = self.impl or os.environ.get("SEIST_DWCONV_IMPL") or (
            "shift" if jax.default_backend() == "tpu" else "grouped"
        )
        if impl not in ("shift", "grouped"):
            raise ValueError(f"unknown depthwise impl {impl!r}")
        if impl == "grouped":
            return jax.lax.conv_general_dilated(
                x,
                kernel.astype(x.dtype),
                window_strides=(self.stride,),
                padding="VALID",
                dimension_numbers=("NWC", "WIO", "NWC"),
                feature_group_count=self.features,
            )
        return depthwise_shift_fma(
            x, kernel[:, 0, :].astype(x.dtype), self.stride
        )


def depthwise_shift_fma(x: Array, w: Array, stride: int) -> Array:
    """VALID depthwise conv as k shifted multiply-adds.

    ``x`` is (N, L, C), ``w`` is (k, C); returns (N, L_out, C). Pure VPU
    elementwise work that XLA fuses into one kernel — the lowering behind
    :class:`DepthwiseConv1D` (impl='shift'), shared with the merged stem
    path in models/seist.py which runs it on a zero-padded multi-kernel
    bank.

    For ``stride > 1`` the taps are NOT taken as strided slices
    ``x[..., j:j+span:s, :]``: the transpose (gradient) of a strided slice
    lowers on TPU to generic scatter-adds with s32 index vectors and flips
    the activation layout to batch-minor with full-tensor copies — profiled
    at ~6 ms/step in each of SeisT's two stride-2 stems (the same pathology
    that sank the merged-stem lowering). Instead the length
    axis is phase-split by a reshape ``(N, L/s, s, C)``; tap ``j`` is then a
    *contiguous* slice of phase plane ``j % s`` shifted by ``j // s``, whose
    gradient is a plain zero-pad that XLA fuses (pad_add_fusion) like the
    stride-1 case."""
    k, s = int(w.shape[0]), stride
    out_len = (x.shape[-2] - k) // s + 1
    if s == 1:
        acc = x[..., 0:out_len, :] * w[0]
        for j in range(1, k):
            acc = acc + x[..., j : j + out_len, :] * w[j]
        return acc
    # Right-pad with zeros to a multiple of s covering every tap's window.
    # The padding is never read: tap j uses phase rows j//s .. j//s+out_len-1
    # and (out_len-1) + (k-1)//s < ceil(L/s) by construction.
    lead = x.shape[:-2]
    L, C = x.shape[-2], x.shape[-1]
    n_rows = -(-L // s)
    pads = [(0, 0)] * x.ndim
    pads[-2] = (0, n_rows * s - L)
    xp = jnp.pad(x, pads).reshape(*lead, n_rows, s, C)
    acc = None
    for phase in range(s):
        plane = xp[..., :, phase, :]
        taps = [j for j in range(k) if j % s == phase]
        if not taps:
            continue
        part = plane[..., taps[0] // s : taps[0] // s + out_len, :] * w[taps[0]]
        for j in taps[1:]:
            part = part + plane[..., j // s : j // s + out_len, :] * w[j]
        acc = part if acc is None else acc + part
    return acc


class GroupedConv1D(nn.Module):
    """Grouped conv1d with selectable TPU lowerings.

    Param tree matches ``nn.Conv(features, (k,), feature_group_count=G)``
    — ``kernel`` of shape (k, Cin/G, Cout), output feature o served by
    group ``o // (Cout/G)`` — so checkpoints/converters are unaffected.

    Lowerings (pick via ``impl`` or env SEIST_GCONV_IMPL; see
    DepthwiseConv1D for the small-channel TPU context):

    * ``grouped`` — XLA's native grouped conv.
    * ``einsum``  — k shifted batched matmuls
      ``y[n,l,g,e] = sum_j sum_d x[n, l*s+j, g, d] * w[j,d,g,e]``.
    * ``dense``   — expand to a block-diagonal DENSE kernel and run one
      ordinary conv: G× more FLOPs, but dense conv1d is the one shape XLA
      maps well onto the MXU at these sizes and the FLOPs are ~2% of peak
      anyway.
    """

    features: int
    group_count: int
    kernel_size: int
    stride: int = 1
    kernel_init: Any = trunc_normal_init
    # None -> env SEIST_GCONV_IMPL, else 'dense' on TPU / 'grouped' off-TPU
    impl: Optional[str] = None

    @nn.compact
    def __call__(self, x: Array) -> Array:
        cin = x.shape[-1]
        g = self.group_count
        if cin % g or self.features % g:
            raise ValueError(
                f"channels {cin}->{self.features} not divisible by {g} groups"
            )
        ci, co = cin // g, self.features // g
        kernel = self.param(
            "kernel", self.kernel_init, (self.kernel_size, ci, self.features)
        )
        impl = self.impl or os.environ.get("SEIST_GCONV_IMPL") or (
            "dense" if jax.default_backend() == "tpu" else "grouped"
        )
        if impl not in ("grouped", "einsum", "dense"):
            raise ValueError(f"unknown grouped impl {impl!r}")
        k, s = self.kernel_size, self.stride
        kern = kernel.astype(x.dtype)
        if impl == "grouped":
            return jax.lax.conv_general_dilated(
                x, kern,
                window_strides=(s,),
                padding="VALID",
                dimension_numbers=("NWC", "WIO", "NWC"),
                feature_group_count=g,
            )
        if impl == "einsum":
            n, L = x.shape[0], x.shape[1]
            out_len = (L - k) // s + 1
            span = (out_len - 1) * s + 1
            xg = x.reshape(n, L, g, ci)
            # o = grp*co + og  =>  (k, ci, g, co) with g the major O axis.
            wk = kern.reshape(k, ci, g, co)
            acc = jnp.einsum(
                "nlgd,dge->nlge", xg[:, 0:span:s], wk[0]
            )
            for j in range(1, k):
                acc = acc + jnp.einsum(
                    "nlgd,dge->nlge", xg[:, j : j + span : s], wk[j]
                )
            return acc.reshape(n, out_len, self.features)
        # dense: scatter the grouped kernel into a block-diagonal (k, Cin,
        # Cout) kernel; the masked positions are structural zeros, so
        # gradients to them vanish and the param stays exactly grouped.
        wg = kern.reshape(k, ci, g, co)
        dense = jnp.zeros((k, cin, self.features), x.dtype)
        for grp in range(g):
            dense = dense.at[
                :, grp * ci : (grp + 1) * ci, grp * co : (grp + 1) * co
            ].set(wg[:, :, grp])
        return jax.lax.conv_general_dilated(
            x, dense,
            window_strides=(s,),
            padding="VALID",
            dimension_numbers=("NWC", "WIO", "NWC"),
        )


# Cross-framework mask injection for DropPath (training-dynamics parity,
# tools/train_dynamics.py): when active, every train-mode DropPath call
# consumes the next row of a shared (max_calls, batch) uniform array
# instead of drawing from the flax 'dropout' stream, in call order — the
# torch reference's stubbed timm DropPath consumes the SAME rows in the
# same order, so both frameworks drop identical residual paths. The rows
# are uniforms (not thresholded masks) so each instance applies its OWN
# keep probability. The context is read at trace time; pass the uniforms
# as an argument of the jitted step so the compiled program threads them.
_DROPPATH_INJECT: Optional[dict] = None


@contextlib.contextmanager
def droppath_mask_injection(uniforms):
    """Route DropPath randomness to shared ``uniforms`` rows for the
    duration of the context (trace-time). Yields the injection record;
    after the traced/eager call its ``"i"`` holds the number of
    DropPath calls that consumed a row."""
    global _DROPPATH_INJECT
    prev = _DROPPATH_INJECT
    record = {"uniforms": uniforms, "i": 0}
    _DROPPATH_INJECT = record
    try:
        yield record
    finally:
        _DROPPATH_INJECT = prev


class DropPath(nn.Module):
    """Per-sample stochastic depth (timm DropPath parity, scale_by_keep)."""

    rate: float

    @nn.compact
    def __call__(self, x: Array, train: bool) -> Array:
        if not train or self.rate <= 0.0:
            return x
        keep = 1.0 - self.rate
        shape = (x.shape[0],) + (1,) * (x.ndim - 1)
        if _DROPPATH_INJECT is not None:
            inj = _DROPPATH_INJECT
            u = inj["uniforms"][inj["i"]]
            inj["i"] += 1
            mask = (u < keep).reshape(shape)
        else:
            rng = self.make_rng("dropout")
            mask = jax.random.bernoulli(rng, keep, shape)
        return jnp.where(mask, x / keep, 0.0)


class ScaledActivation(nn.Module):
    """activation(x) * scale (ref: seist.py:63-70); bounds regression heads."""

    act: Callable[[Array], Array]
    scale_factor: float

    def __call__(self, x: Array) -> Array:
        return self.act(x) * self.scale_factor


def gelu(x: Array) -> Array:
    """Exact (erf) GELU — torch ``nn.GELU()`` parity. flax's ``nn.gelu``
    defaults to the tanh approximation, which drifts up to ~1e-3 per layer
    and breaks golden-parity comparison against the shipped checkpoints."""
    import jax

    return jax.nn.gelu(x, approximate=False)


# torch BatchNorm1d defaults (torch momentum 0.1 == flax-convention 0.9).
# Single source of truth for BOTH BatchNorm1dParity and merged lowerings
# that re-derive its math (models/seist.py StemBlock._merged_paths).
BN_MOMENTUM = 0.9
BN_EPSILON = 1e-5


class BatchNorm1dParity(nn.Module):
    """BatchNorm over (N, L, C) with exact torch ``BatchNorm1d`` semantics.

    Differences from ``flax.linen.BatchNorm`` that matter for parity
    (verified by the train-mode gradient/BN test in
    tests/test_golden_parity.py):

    * the running variance is updated with the UNBIASED batch variance
      (x N/(N-1)), while normalization uses the biased one — torch does
      exactly this; flax uses the biased variance for both.
    * statistics are always computed in fp32; under a bf16 precision
      policy only the *output* is cast down (fp32 running stats would
      otherwise promote every activation back to fp32 and undo mixed
      precision network-wide).

    Param/variable naming matches flax BatchNorm ('scale'/'bias',
    batch_stats 'mean'/'var') so checkpoints and the torch->flax converter
    are unaffected. Under global-view jit with a batch-sharded mesh the
    reductions below span the GLOBAL batch — the reference's SyncBatchNorm
    semantics (ref train.py:374) with zero extra code.
    """

    use_running_average: bool
    momentum: float = BN_MOMENTUM  # flax convention: new = m*old + (1-m)*batch
    epsilon: float = BN_EPSILON
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x: Array) -> Array:
        features = x.shape[-1]
        scale = self.param(
            "scale", nn.initializers.ones, (features,), jnp.float32
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (features,), jnp.float32
        )
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((features,), jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((features,), jnp.float32)
        )

        if self.use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            xf = x.astype(jnp.float32)
            axes = tuple(range(x.ndim - 1))
            mean = jnp.mean(xf, axes)
            var = jnp.maximum(
                jnp.mean(jnp.square(xf), axes) - jnp.square(mean), 0.0
            )
            if not self.is_initializing():
                n = math.prod(x.shape[a] for a in axes)
                unbiased = var * (n / max(n - 1, 1))
                m = self.momentum
                ra_mean.value = m * ra_mean.value + (1 - m) * mean
                ra_var.value = m * ra_var.value + (1 - m) * unbiased

        inv = jax.lax.rsqrt(var + self.epsilon) * scale
        y = (x.astype(jnp.float32) - mean) * inv + bias
        return y.astype(self.dtype or x.dtype)


def make_norm(
    norm: str, *, use_running_average: bool, name: Optional[str] = None
) -> nn.Module:
    """Normalization factory. 'batch' matches torch BatchNorm1d exactly
    (momentum 0.1 -> our momentum 0.9, eps 1e-5, unbiased running-var
    update — see :class:`BatchNorm1dParity`). Under global-view jit with
    a batch-sharded mesh the batch statistics are computed over the
    *global* batch, which is exactly the reference's SyncBatchNorm
    semantics (train.py:374) with zero extra code.
    """
    from seist_tpu.train.precision import policy_dtype

    dtype = policy_dtype()
    if norm == "batch":
        return BatchNorm1dParity(
            use_running_average=use_running_average,
            momentum=BN_MOMENTUM,
            epsilon=BN_EPSILON,
            dtype=dtype,
            name=name,
        )
    if norm == "layer":
        return nn.LayerNorm(dtype=dtype, name=name)
    if norm == "group":
        return nn.GroupNorm(num_groups=8, dtype=dtype, name=name)
    raise NotImplementedError(f"Unknown norm '{norm}'")


def _lstm_unroll() -> int:
    """Scan unroll factor for LSTM recurrences (env SEIST_LSTM_UNROLL).

    The per-step matmuls are tiny (hidden 16-64), so a serial scan is
    latency-bound on TPU; unrolling the scan body lets XLA software-
    pipeline consecutive steps. Pure scheduling — the math is unchanged
    for any factor (lax.scan semantics)."""
    return int(os.environ.get("SEIST_LSTM_UNROLL", "8"))


class LSTM(nn.Module):
    """Unidirectional LSTM over (N, L, C) returning (outputs, final_h).

    torch ``nn.LSTM`` parity at the architecture level; the recurrence is a
    ``lax.scan`` per flax nn.RNN (SURVEY.md §7 'LSTM baselines on TPU'),
    unrolled by :func:`_lstm_unroll` steps per scan iteration.
    """

    hidden: int

    @nn.compact
    def __call__(self, x: Array) -> Tuple[Array, Array]:
        from seist_tpu.train.precision import policy_dtype, policy_param_dtype

        # Mixed-precision coverage (irlint f32-matmul-under-bf16-policy):
        # OptimizedLSTMCell initializes its (c, h) carry via param_dtype —
        # fp32 by default — and the fp32 h then PROMOTES every recurrent
        # matmul (and the whole decoder downstream) back to fp32 under the
        # bf16 policy. Pinning cell dtype + carry dtype to the trace-time
        # policy keeps the recurrence in the compute dtype; params are
        # already cast by the step-level policy (train/precision.py), and
        # at init time the policy is inactive so params still init fp32.
        cell = nn.OptimizedLSTMCell(
            features=self.hidden,
            dtype=policy_dtype(),
            param_dtype=policy_param_dtype(),
        )
        carry, outputs = nn.RNN(
            cell, return_carry=True, unroll=_lstm_unroll()
        )(x)
        # carry = (c, h) for OptimizedLSTMCell
        return outputs, carry[1]


class BiLSTM(nn.Module):
    """Bidirectional LSTM over (N, L, C); returns (outputs_2H, final_h_2H)."""

    hidden: int

    @nn.compact
    def __call__(self, x: Array) -> Tuple[Array, Array]:
        fwd_out, fwd_h = LSTM(self.hidden, name="fwd")(x)
        bwd_out, bwd_h = LSTM(self.hidden, name="bwd")(x[:, ::-1, :])
        outputs = jnp.concatenate([fwd_out, bwd_out[:, ::-1, :]], axis=-1)
        final = jnp.concatenate([fwd_h, bwd_h], axis=-1)
        return outputs, final
