"""Seismogram Transformer (SeisT) — the flagship backbone, TPU-native.

Architecture parity with the reference ``models/seist.py:63-852`` (Li et al.,
IEEE TGRS 2024), re-designed channels-last for XLA/TPU:

* arrays are ``(N, L, C)``; 1x1 convs become ``nn.Dense`` (pure MXU matmuls,
  no transposes);
* pooled-K/V attention (``AttentionBlock``, ref :321-393) is an einsum pair
  that XLA fuses with the surrounding projections;
* ceil-mode pooling / asymmetric 'same' padding geometry matches torch
  exactly (see seist_tpu/models/common.py);
* optional per-stage rematerialization replaces torch.utils.checkpoint
  (ref :841-847) via ``nn.remat``.

15 registered variants: seist_{s,m,l}_{dpk,pmp,emg,baz,dis}
(ref :855-1170).
"""

from __future__ import annotations

import math
import os
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from seist_tpu.models import common
from seist_tpu.models.common import DropPath, make_divisible, trunc_normal_init
from seist_tpu.registry import register_model

Array = jnp.ndarray

_dense_kw = dict(kernel_init=trunc_normal_init)
_conv_kw = dict(kernel_init=trunc_normal_init)


def _active_seq_mesh():
    """The mesh of the step being traced when its `seq` axis is sharded
    (--seq-shards > 1), else None — see parallel.mesh.use_mesh."""
    from seist_tpu.parallel import mesh as mesh_lib

    m = mesh_lib.active_mesh()
    if m is not None and m.shape.get(mesh_lib.AXIS_SEQ, 1) > 1:
        return m
    return None


class LocalAwareAggregationBlock(nn.Module):
    """(avg+max pool, ceil mode) -> 1x1 proj -> norm (ref: seist.py:73-96).
    Used as stage downsampler and attention K/V downsampler."""

    out_dim: int
    kernel_size: int
    norm: str = "batch"

    @nn.compact
    def __call__(self, x: Array, train: bool) -> Array:
        if self.kernel_size > 1:
            x = common.avg_pool_1d_ceil(x, self.kernel_size) + common.max_pool_1d_ceil(
                x, self.kernel_size
            )
        x = nn.Dense(self.out_dim, use_bias=False, name="proj", **_dense_kw)(x)
        x = common.make_norm(self.norm, use_running_average=not train, name="norm")(x)
        return x


class MLP(nn.Module):
    """1x1-conv feedforward (ref: seist.py:99-121)."""

    out_dim: int
    mlp_ratio: float
    bias: bool
    mlp_drop_rate: float
    act: Callable = common.gelu

    @nn.compact
    def __call__(self, x: Array, train: bool) -> Array:
        ffwd_dim = int(x.shape[-1] * self.mlp_ratio)
        x = nn.Dense(ffwd_dim, use_bias=self.bias, name="lin0", **_dense_kw)(x)
        x = self.act(x)
        x = nn.Dense(self.out_dim, use_bias=self.bias, name="lin1", **_dense_kw)(x)
        x = nn.Dropout(self.mlp_drop_rate, deterministic=not train)(x)
        return x


def _triple_product_kernel(w_in: Array, w_d: Array, w_p: Array) -> Array:
    """The DSConv pipeline's exact collapse into one dense conv kernel:
    ``A[j,c,o] = sum_d w_in[c,d] * w_d[j,d] * w_p[d,o]`` (valid because the
    three stages have no bias and no nonlinearity between them). fp32
    accumulation regardless of the compute dtype — under bf16 policy this
    rounds ONCE (at the caller's cast) where the staged pipeline rounds
    after each of the three matmuls. Shared by DSConvNormAct._composed and
    StemBlock._fused_paths."""
    return jnp.einsum(
        "cd,jd,do->jco",
        w_in,
        w_d,
        w_p,
        preferred_element_type=jnp.float32,
    )


class DSConvNormAct(nn.Module):
    """Depthwise-separable conv (ref: seist.py:124-155).

    Two checkpoint-identical lowerings (``impl`` / env SEIST_DSCONV_IMPL):

    * ``'paths'`` — the literal pipeline: 1x1 in-proj -> depthwise k ->
      1x1 pconv (3 device passes over the activation).
    * ``'composed'`` (TPU default) — algebraic collapse: with no bias and
      no nonlinearity between the three stages, the pipeline is EXACTLY
      one dense conv whose kernel is the tap-wise triple product
      ``A[j,c,o] = sum_d Win[c,d] * w[j,d] * Wp[d,o]`` (tiny einsum over
      the weights, recomputed per step). One dense conv1d is the shape
      XLA maps best onto the MXU at these channel counts, and the
      activation is read and
      written ONCE in each direction instead of three times — the stems
      built from this block were 42% of the seist_l step before.
    """

    in_dim: int
    out_dim: int
    kernel_size: int
    stride: int
    norm: str = "batch"
    act: Callable = common.gelu
    # None -> env SEIST_DSCONV_IMPL, else 'composed' on TPU / 'paths' off
    impl: Optional[str] = None

    @nn.compact
    def __call__(self, x: Array, train: bool) -> Array:
        impl = self.impl or os.environ.get("SEIST_DSCONV_IMPL") or (
            "composed" if jax.default_backend() == "tpu" else "paths"
        )
        if impl not in ("paths", "composed"):
            raise ValueError(f"unknown dsconv impl {impl!r}")
        if impl == "composed":
            x = self._composed(x)
        else:
            x = nn.Dense(
                self.in_dim, use_bias=False, name="in_proj", **_dense_kw
            )(x)
            x = common.auto_pad_1d(x, self.kernel_size, self.stride)
            # Shift-FMA depthwise lowering (same dconv/kernel param tree as
            # the grouped nn.Conv it replaces) — see common.DepthwiseConv1D
            # for why XLA's grouped conv is pathological at these channel
            # counts.
            x = common.DepthwiseConv1D(
                self.in_dim,
                self.kernel_size,
                stride=self.stride,
                name="dconv",
                **_conv_kw,
            )(x)
            x = nn.Dense(
                self.out_dim, use_bias=False, name="pconv", **_dense_kw
            )(x)
        x = common.make_norm(self.norm, use_running_average=not train, name="norm")(x)
        return self.act(x)

    def _composed(self, x: Array) -> Array:
        """in_proj∘dconv∘pconv as ONE dense conv (same param tree: the
        _Kernel twins declare the identical leaves the per-stage modules
        would). Padding commutes exactly: in_proj is 1x1 with no bias, so
        padding the input with zeros equals padding its output."""
        w_in = _Kernel((x.shape[-1], self.in_dim), name="in_proj")()
        w_d = _Kernel((self.kernel_size, 1, self.in_dim), name="dconv")()
        w_p = _Kernel((self.in_dim, self.out_dim), name="pconv")()
        kernel = _triple_product_kernel(w_in, w_d[:, 0, :], w_p).astype(x.dtype)
        # SEIST_CHANNEL_PAD (off by default): lane-multiple out channels,
        # zeros sliced away — values identical (common.py docstring).
        kernel, out = common.pad_kernel_out_channels(kernel)
        xp = common.auto_pad_1d(x, self.kernel_size, self.stride)
        h = jax.lax.conv_general_dilated(
            xp,
            kernel,
            window_strides=(self.stride,),
            padding="VALID",
            dimension_numbers=("NWC", "WIO", "NWC"),
        )
        return h[..., :out]


class _Kernel(nn.Module):
    """Declares one ``kernel`` param leaf (same name/shape/init as the
    nn.Dense / DepthwiseConv1D it twins) and returns it raw, so a parent
    module can compute a merged lowering over several paths' weights while
    the checkpoint tree stays identical to the per-path modules."""

    shape: Tuple[int, ...]

    @nn.compact
    def __call__(self) -> Array:
        return self.param("kernel", trunc_normal_init, self.shape)


class _BNLeaves(nn.Module):
    """Param/variable twin of :class:`common.BatchNorm1dParity` (same leaf
    names, shapes, inits). Returns (scale, bias, mean_ref, var_ref)."""

    features: int

    @nn.compact
    def __call__(self):
        scale = self.param(
            "scale", nn.initializers.ones, (self.features,), jnp.float32
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (self.features,), jnp.float32
        )
        mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((self.features,), jnp.float32)
        )
        var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((self.features,), jnp.float32)
        )
        return scale, bias, mean, var


class _DSConvPathLeaves(nn.Module):
    """Param-tree twin of one :class:`DSConvNormAct` path: declares the
    exact same leaves (conv{i}/in_proj/kernel, dconv/kernel, pconv/kernel,
    norm/{scale,bias,mean,var}) without computing anything, for the merged
    StemBlock lowering below."""

    prev_dim: int
    in_dim: int
    out_dim: int
    kernel_size: int

    @nn.compact
    def __call__(self):
        w_in = _Kernel((self.prev_dim, self.in_dim), name="in_proj")()
        w_d = _Kernel((self.kernel_size, 1, self.in_dim), name="dconv")()
        w_p = _Kernel((self.in_dim, self.out_dim), name="pconv")()
        bn = _BNLeaves(self.out_dim, name="norm")()
        return w_in, w_d, w_p, bn


class StemBlock(nn.Module):
    """3 parallel DSConv paths with kernels k, k+4, k+8 (ref: seist.py:158-195).

    Two checkpoint-identical lowerings (``impl`` / env SEIST_STEM_IMPL):

    * ``'paths'`` (default) — the literal architecture: 3 independent
      DSConvNormAct calls.
    * ``'merged'`` — horizontal fusion of the 3 paths: one in-projection
      matmul on the concatenated kernels (the input is read once instead
      of 3x), one shift-FMA depthwise pass over a zero-padded multi-kernel
      bank, one block-diagonal pointwise matmul (3C lanes instead of C),
      and one merged BatchNorm whose per-channel stats are exactly the
      per-path norms'.
    * ``'fused'`` — ONE dense conv for all 3 paths: each path collapses
      to a dense kernel via the DSConvNormAct triple product, the three
      kernels are tap-centered into one (K, Cin, 3*Cout) bank, and the
      path concat becomes the conv's out-channel axis (see _fused_paths).

    ``'merged'`` was a NEGATIVE result on an earlier installation
    (about -12% on seist_l_dpk fp32; not re-measured on this one) and
    therefore not the default.
    The fwd pass does get fewer passes, but XLA lowers the backward of
    the merged strided-slice FMA (stride-2 stems) to generic scatter-adds
    with s32 index vectors and flips the activation layout to {0,2,1},
    inserting full-tensor copies — costing more than the saved reads.
    Kept env-selectable for future XLA versions / other topologies.

    Both lowerings produce the same param/batch_stats tree and the same
    values up to fp reassociation (tested in tests/test_models.py).
    """

    in_dim: int
    out_dim: int
    kernel_size: int
    stride: int
    norm: str = "batch"
    act: Callable = common.gelu
    npath: int = 3
    # None -> env SEIST_STEM_IMPL, else 'paths' (see docstring: 'merged'
    # measured slower on v5e)
    impl: Optional[str] = None

    @nn.compact
    def __call__(self, x: Array, train: bool) -> Array:
        impl = self.impl or os.environ.get("SEIST_STEM_IMPL") or "paths"
        if impl not in ("merged", "paths", "fused"):
            raise ValueError(f"unknown stem impl {impl!r}")
        if impl in ("merged", "fused") and self.norm != "batch":
            raise ValueError(
                f"SEIST_STEM_IMPL={impl} only supports norm='batch' "
                f"(got {self.norm!r}); use the 'paths' impl"
            )
        if impl == "merged":
            x = self._merged_paths(x, train)
        elif impl == "fused":
            x = self._fused_paths(x, train)
        else:
            outs = [
                DSConvNormAct(
                    self.in_dim,
                    self.out_dim,
                    self.kernel_size + 4 * dk,
                    self.stride,
                    self.norm,
                    self.act,
                    name=f"conv{dk}",
                )(x, train)
                for dk in range(self.npath)
            ]
            x = jnp.concatenate(outs, axis=-1)
        x = nn.Dense(self.out_dim, use_bias=False, name="out_proj", **_dense_kw)(x)
        x = common.make_norm(self.norm, use_running_average=not train, name="norm")(x)
        return x

    def _merged_paths(self, x: Array, train: bool) -> Array:
        """All 3 DSConvNormAct paths in 3 device passes instead of ~9."""
        P, C, O = self.npath, self.in_dim, self.out_dim
        ks = [self.kernel_size + 4 * dk for dk in range(P)]
        K = ks[-1]
        leaves = [
            _DSConvPathLeaves(x.shape[-1], C, O, k, name=f"conv{i}")()
            for i, k in enumerate(ks)
        ]
        # one in-projection matmul — x is streamed once for all paths
        w_in = jnp.concatenate([l[0] for l in leaves], axis=1)  # (Cin, P*C)
        h = x @ w_in
        # one depthwise pass over a zero-padded multi-kernel bank: path i's
        # k_i-tap kernel sits at tap offset (K - k_i)//2, which under the
        # K-kernel 'same' padding reproduces the path's own asymmetric
        # padding exactly (left-pad difference LP_K - lp_i == (K - k_i)//2
        # because kernel sizes differ by the even 4*dk; ref geometry:
        # seist.py:12-48).
        bank = jnp.zeros((K, P * C), dtype=h.dtype)
        for i, (k_i, l) in enumerate(zip(ks, leaves)):
            off = (K - k_i) // 2
            bank = bank.at[off : off + k_i, i * C : (i + 1) * C].set(
                l[1][:, 0, :].astype(h.dtype)
            )
        h = common.auto_pad_1d(h, K, self.stride)
        h = common.depthwise_shift_fma(h, bank, self.stride)
        # one block-diagonal pointwise matmul (P*C -> P*O)
        w_p = jax.scipy.linalg.block_diag(*[l[2] for l in leaves])
        h = h @ w_p
        return self._merged_bn_act(h, leaves, train, x.dtype)

    def _merged_bn_act(self, h: Array, leaves, train: bool, in_dtype) -> Array:
        """Merged BatchNorm1dParity (common.py) over path-concatenated
        channels: per-channel batch stats are identical to the per-path
        norms'; running stats are written back into each path's own
        batch_stats leaves. Shared by the 'merged' and 'fused' lowerings."""
        from seist_tpu.train.precision import policy_dtype

        O = self.out_dim
        scale = jnp.concatenate([l[3][0] for l in leaves])
        bias = jnp.concatenate([l[3][1] for l in leaves])
        if not train:
            mean = jnp.concatenate([l[3][2].value for l in leaves])
            var = jnp.concatenate([l[3][3].value for l in leaves])
        else:
            hf = h.astype(jnp.float32)
            mean = jnp.mean(hf, (0, 1))
            var = jnp.maximum(
                jnp.mean(jnp.square(hf), (0, 1)) - jnp.square(mean), 0.0
            )
            if not self.is_initializing():
                n = h.shape[0] * h.shape[1]
                unbiased = var * (n / max(n - 1, 1))
                m = common.BN_MOMENTUM
                for i, l in enumerate(leaves):
                    sl = slice(i * O, (i + 1) * O)
                    l[3][2].value = m * l[3][2].value + (1 - m) * mean[sl]
                    l[3][3].value = m * l[3][3].value + (1 - m) * unbiased[sl]
        inv = jax.lax.rsqrt(var + common.BN_EPSILON) * scale
        h = (h.astype(jnp.float32) - mean) * inv + bias
        h = h.astype(policy_dtype() or in_dtype)
        return self.act(h)

    def _fused_paths(self, x: Array, train: bool) -> Array:
        """All 3 paths as ONE dense conv. Composes DSConvNormAct._composed
        (per-path triple-product kernels A_i, exact — no bias and no
        nonlinearity inside a path) with the merged-stem tap geometry:
        path i's K_i-tap kernel sits at tap offset (K - k_i)//2 of the
        K-tap bank, which under K-kernel 'same' padding reproduces the
        path's own asymmetric padding exactly (even kernel-size deltas;
        see _merged_paths). The path concat disappears entirely — the
        conv's out-channel axis IS the concatenation — so the input is
        read once and one (N, L_out, P*O) tensor is written where 'paths'
        reads x three times and writes 3 tensors plus a concat copy.
        Unlike 'merged' (a measured -12%: shift-FMA strided-slice
        backward scatter), the dense conv's backward is XLA's native
        conv-transpose — no scatter, no layout flip."""
        P, C, O = self.npath, self.in_dim, self.out_dim
        ks = [self.kernel_size + 4 * dk for dk in range(P)]
        K = ks[-1]
        leaves = [
            _DSConvPathLeaves(x.shape[-1], C, O, k, name=f"conv{i}")()
            for i, k in enumerate(ks)
        ]
        cin = x.shape[-1]
        kern = jnp.zeros((K, cin, P * O), jnp.float32)
        for i, (k_i, l) in enumerate(zip(ks, leaves)):
            a = _triple_product_kernel(l[0], l[1][:, 0, :], l[2])
            off = (K - k_i) // 2
            kern = kern.at[off : off + k_i, :, i * O : (i + 1) * O].set(a)
        xp = common.auto_pad_1d(x, K, self.stride)
        # SEIST_CHANNEL_PAD (off by default): lane-multiple out channels,
        # zeros sliced away — values identical (common.py docstring).
        kern_p, out = common.pad_kernel_out_channels(kern.astype(x.dtype))
        h = jax.lax.conv_general_dilated(
            xp,
            kern_p,
            window_strides=(self.stride,),
            padding="VALID",
            dimension_numbers=("NWC", "WIO", "NWC"),
        )[..., :out]
        return self._merged_bn_act(h, leaves, train, x.dtype)


class GroupConvBlock(nn.Module):
    """Grouped conv + MLP, both with residual DropPath (ref: seist.py:198-256)."""

    io_dim: int
    groups: int
    kernel_size: int
    path_drop_rate: float
    mlp_drop_rate: float
    mlp_ratio: float
    mlp_bias: bool
    norm: str = "batch"
    act: Callable = common.gelu

    @nn.compact
    def __call__(self, x: Array, train: bool) -> Array:
        x1 = common.auto_pad_1d(x, self.kernel_size, 1)
        # Selectable grouped-conv lowering (same conv/kernel param tree as
        # grouped nn.Conv) — see common.GroupedConv1D.
        x1 = common.GroupedConv1D(
            self.io_dim,
            self.groups,
            self.kernel_size,
            name="conv",
            **_conv_kw,
        )(x1)
        x1 = common.make_norm(self.norm, use_running_average=not train, name="norm0")(x1)
        x1 = self.act(x1)
        x1 = nn.Dense(self.io_dim, use_bias=False, name="proj", **_dense_kw)(x1)
        x = x + DropPath(self.path_drop_rate)(x1, train)

        x1 = common.make_norm(self.norm, use_running_average=not train, name="norm1")(x)
        x1 = MLP(
            self.io_dim, self.mlp_ratio, self.mlp_bias, self.mlp_drop_rate, self.act,
            name="mlp",
        )(x1, train)
        x = x + DropPath(self.path_drop_rate)(x1, train)
        return x


class MultiScaleMixedConv(nn.Module):
    """Channel-split parallel GroupConvBlocks at different kernel sizes
    (ref: seist.py:259-318)."""

    io_dim: int
    groups: int
    kernel_sizes: Sequence[int]
    path_drop_rate: float
    mlp_drop_rate: float
    mlp_ratio: float
    mlp_bias: bool
    norm: str = "batch"
    act: Callable = common.gelu

    @nn.compact
    def __call__(self, x: Array, train: bool) -> Array:
        # Region scope (obs/scopes.py REGIONS): this block and the
        # transformer layer share their instance names
        # (stage<i>_block<j>), so the scope says which one an op is of.
        with jax.named_scope("msmc"):
            group_size = self.io_dim // self.groups
            dims_ = []
            outs = []
            for i, kernel_size in enumerate(self.kernel_sizes):
                dim = make_divisible(
                    (self.io_dim - sum(dims_)) // (len(self.kernel_sizes) - len(dims_)),
                    group_size,
                )
                assert dim > 0
                dims_.append(dim)
                xi = nn.Dense(dim, use_bias=False, name=f"proj{i}", **_dense_kw)(x)
                xi = common.make_norm(
                    self.norm, use_running_average=not train, name=f"norm{i}"
                )(xi)
                xi = xi + GroupConvBlock(
                    io_dim=dim,
                    groups=dim // group_size,
                    kernel_size=kernel_size,
                    path_drop_rate=self.path_drop_rate,
                    mlp_drop_rate=self.mlp_drop_rate,
                    mlp_ratio=self.mlp_ratio,
                    mlp_bias=self.mlp_bias,
                    norm=self.norm,
                    act=self.act,
                    name=f"conv{i}",
                )(xi, train)
                outs.append(xi)
            x = jnp.concatenate(outs, axis=-1)
            x = common.make_norm(self.norm, use_running_average=not train, name="out_norm")(x)
        return x


class AttentionBlock(nn.Module):
    """MHA with K/V from a pooled sequence: full-length Q attends to L/r keys,
    cost L x (L/r) (ref: seist.py:321-393)."""

    io_dim: int
    head_dim: int
    qkv_bias: bool
    attn_drop_rate: float
    key_drop_rate: float
    proj_drop_rate: float
    attn_aggr_ratio: int
    norm: str = "batch"

    @nn.compact
    def __call__(self, x: Array, train: bool) -> Array:
        N, L, C = x.shape
        num_heads = self.io_dim // self.head_dim
        E = C // num_heads

        q = nn.Dense(self.io_dim, use_bias=self.qkv_bias, name="q_proj", **_dense_kw)(x)
        q = q.reshape(N, L, num_heads, E)

        if self.attn_aggr_ratio > 1:
            x = LocalAwareAggregationBlock(
                self.io_dim, self.attn_aggr_ratio, self.norm, name="aggr"
            )(x, train)
            x = common.make_norm(self.norm, use_running_average=not train, name="norm")(x)

        k = nn.Dense(self.io_dim, use_bias=self.qkv_bias, name="k_proj", **_dense_kw)(x)
        v = nn.Dense(self.io_dim, use_bias=self.qkv_bias, name="v_proj", **_dense_kw)(x)
        M = x.shape[1]
        k = k.reshape(N, M, num_heads, E)
        v = v.reshape(N, M, num_heads, E)
        k = nn.Dropout(self.key_drop_rate, deterministic=not train)(k)

        rate = self.attn_drop_rate if train else 0.0
        seed = None
        if rate > 0.0:
            seed = jax.random.randint(
                self.make_rng("dropout"),
                (1,),
                0,
                jnp.iinfo(jnp.int32).max,
                dtype=jnp.int32,
            )
        mesh = _active_seq_mesh()
        if mesh is not None:
            # --seq-shards: sequence-parallel exact attention over the
            # mesh's `seq` axis (Q blocks resident, K/V rotating on ICI —
            # ops/ring_attention.py). Long-context path the reference lacks.
            # Probability dropout (ref seist.py:383-388) applies inside the
            # ring accumulation with the SAME counter-based mask as the
            # dense/fused paths, so seq-parallel training semantics match
            # single-device training exactly.
            from seist_tpu.ops.ring_attention import ring_attention

            out = ring_attention(
                q,
                k,
                v,
                mesh,
                batch_axis="data",
                scale=1.0 / math.sqrt(E),
                dropout_rate=rate,
                dropout_seed=seed,
            )
        else:
            # Fused Pallas kernel on TPU (qk + softmax + dropout + pv in
            # VMEM, no (N,H,L,M) HBM tensor); identical-math einsum fallback
            # elsewhere. Probability dropout (ref seist.py:383-388) runs
            # *inside* the kernel from a counter-based PRNG seeded off the
            # flax 'dropout' stream.
            from seist_tpu.ops.pallas_attention import fused_pooled_attention

            out = fused_pooled_attention(
                q,
                k,
                v,
                1.0 / math.sqrt(E),
                dropout_rate=rate,
                dropout_seed=seed,
            )
        out = out.reshape(N, L, C)

        out = nn.Dense(
            self.io_dim, use_bias=self.qkv_bias, name="out_proj", **_dense_kw
        )(out)
        out = nn.Dropout(self.proj_drop_rate, deterministic=not train)(out)
        return out


class MultiPathTransformerLayer(nn.Module):
    """Channel-split dual path: attention on ~attn_ratio of channels, grouped
    conv on the rest; shared MLP (ref: seist.py:396-504)."""

    io_dim: int
    path_drop_rate: float
    attn_aggr_ratio: int
    attn_ratio: float
    head_dim: int
    qkv_bias: bool
    mlp_ratio: float
    mlp_bias: bool
    attn_drop_rate: float
    key_drop_rate: float
    attn_out_drop_rate: float
    mlp_drop_rate: float
    norm: str = "batch"
    act: Callable = common.gelu

    @nn.compact
    def __call__(self, x: Array, train: bool) -> Array:
        assert 0 <= self.attn_ratio <= 1
        attn_out_dim = (
            make_divisible(int(self.io_dim * self.attn_ratio), self.head_dim)
            if self.attn_ratio > 0
            else 0
        )
        conv_out_dim = max(self.io_dim - attn_out_dim, 0)

        outs = []
        if attn_out_dim > 0:
            with jax.named_scope("attn_path"):
                x1 = nn.Dense(attn_out_dim, use_bias=False, name="attn_proj", **_dense_kw)(x)
                x1 = common.make_norm(self.norm, use_running_average=not train, name="norm0")(x1)
                a = AttentionBlock(
                    io_dim=attn_out_dim,
                    head_dim=self.head_dim,
                    qkv_bias=self.qkv_bias,
                    attn_drop_rate=self.attn_drop_rate,
                    key_drop_rate=self.key_drop_rate,
                    proj_drop_rate=self.attn_out_drop_rate,
                    attn_aggr_ratio=self.attn_aggr_ratio,
                    norm=self.norm,
                    name="attention",
                )(x1, train)
                x1 = x1 + DropPath(self.path_drop_rate * self.attn_ratio)(a, train)
            outs.append(x1)

        if conv_out_dim > 0:
            with jax.named_scope("gconv_path"):
                x2 = nn.Dense(conv_out_dim, use_bias=False, name="conv_proj", **_dense_kw)(x)
                x2 = common.make_norm(self.norm, use_running_average=not train, name="norm1")(x2)
                g = GroupConvBlock(
                    io_dim=conv_out_dim,
                    groups=conv_out_dim // self.head_dim,
                    kernel_size=3,
                    path_drop_rate=self.path_drop_rate,
                    mlp_drop_rate=self.mlp_drop_rate,
                    mlp_ratio=self.mlp_ratio,
                    mlp_bias=self.mlp_bias,
                    norm=self.norm,
                    act=self.act,
                    name="gconv",
                )(x2, train)
                x2 = x2 + DropPath(self.path_drop_rate * (1 - self.attn_ratio))(g, train)
            outs.append(x2)

        x = jnp.concatenate(outs, axis=-1)
        with jax.named_scope("mlp_path"):
            x = common.make_norm(self.norm, use_running_average=not train, name="norm2")(x)
            m = MLP(
                self.io_dim, self.mlp_ratio, self.mlp_bias, self.mlp_drop_rate, self.act,
                name="mlp",
            )(x, train)
            x = x + DropPath(self.path_drop_rate)(m, train)
        return x


class HeadDetectionPicking(nn.Module):
    """Interpolate+conv upsampling ladder back to input length
    (ref: seist.py:507-572)."""

    layer_channels: Sequence[int]
    layer_kernel_sizes: Sequence[int]
    out_channels: int = 1
    out_act: Optional[Callable] = None
    norm: str = "batch"
    act: Callable = common.gelu

    def _upsampling_sizes(self, in_size: int, out_size: int) -> Sequence[int]:
        depth = len(self.layer_channels)
        sizes = [out_size] * depth
        factor = (out_size / in_size) ** (1 / depth)
        for i in range(depth - 2, -1, -1):
            sizes[i] = int(sizes[i + 1] / factor)
        return sizes

    @nn.compact
    def __call__(self, x: Array, x0: Array, train: bool) -> Array:
        assert len(self.layer_channels) == len(self.layer_kernel_sizes)
        out_chs = list(self.layer_channels[:-1]) + [self.out_channels * 2]
        up_sizes = self._upsampling_sizes(x.shape[-2], x0.shape[-2])
        for i, (outc, kers) in enumerate(zip(out_chs, self.layer_kernel_sizes)):
            x = common.interpolate_linear(x, up_sizes[i])
            x = common.auto_pad_1d(x, kers, 1)
            x = nn.Conv(outc, (kers,), padding="VALID", name=f"conv{i}", **_conv_kw)(x)
            x = common.make_norm(
                self.norm, use_running_average=not train, name=f"norm{i}"
            )(x)
            x = self.act(x)
        x = nn.Conv(
            self.out_channels, (7,), padding=[(3, 3)], name="out_conv", **_conv_kw
        )(x)
        if self.out_act is not None:
            x = self.out_act(x)
        return x


class HeadClassification(nn.Module):
    """GAP -> linear -> softmax (ref: seist.py:575-591)."""

    num_classes: int
    out_act: Optional[Callable] = None

    @nn.compact
    def __call__(self, x: Array, x0: Array, train: bool) -> Array:
        x = common.global_avg_pool(x)
        x = nn.Dense(self.num_classes, name="lin", **_dense_kw)(x)
        if self.out_act is not None:
            x = self.out_act(x)
        return x


class HeadRegression(nn.Module):
    """GAP -> linear -> scaled sigmoid (ref: seist.py:594-610)."""

    out_act: Optional[Callable] = None

    @nn.compact
    def __call__(self, x: Array, x0: Array, train: bool) -> Array:
        x = common.global_avg_pool(x)
        x = nn.Dense(1, name="lin", **_dense_kw)(x)
        if self.out_act is not None:
            x = self.out_act(x)
        return x


class SeismogramTransformer(nn.Module):
    """Stem -> 4 stages (aggregation + MSMC/MPTL blocks) -> task head
    (ref: seist.py:613-852)."""

    in_channels: int = 3
    stem_channels: Sequence[int] = (16, 8, 16, 16)
    stem_kernel_sizes: Sequence[int] = (11, 5, 5, 7)
    stem_strides: Sequence[int] = (2, 1, 1, 2)
    layer_blocks: Sequence[int] = (2, 3, 6, 2)
    layer_channels: Sequence[int] = (24, 32, 64, 96)
    attn_blocks: Sequence[int] = (1, 1, 2, 1)
    stage_aggr_ratios: Sequence[int] = (2, 2, 2, 2)
    attn_aggr_ratios: Sequence[int] = (8, 4, 2, 1)
    head_dims: Sequence[int] = (8, 8, 16, 32)
    msmc_kernel_sizes: Sequence[int] = (3, 5)
    path_drop_rate: float = 0.2
    attn_drop_rate: float = 0.1
    key_drop_rate: float = 0.1
    mlp_drop_rate: float = 0.2
    other_drop_rate: float = 0.1
    attn_ratio: float = 0.6
    mlp_ratio: float = 2.0
    qkv_bias: bool = True
    mlp_bias: bool = True
    norm: str = "batch"
    act: Callable = common.gelu
    use_checkpoint: bool = False
    head_type: str = "dpk"  # dpk | cls | reg
    head_out_channels: int = 3
    head_num_classes: int = 2
    head_scale: float = 1.0

    @nn.compact
    def __call__(
        self,
        x: Array,
        train: bool = False,
        *,
        mode: str = "full",
        features: Optional[Array] = None,
    ) -> Array:
        """Forward pass, optionally split at the trunk/head boundary.

        ``mode`` selects what runs (a static Python switch — jit callers
        close over it):

        * ``'full'`` (default) — stem + stages + task head, byte-identical
          to the pre-split behavior.
        * ``'backbone'`` — stem + stages only; returns the (N, L/64, C')
          trunk features every task head consumes. The trunk is the ~90%
          of serving FLOPs the paper's five task heads share — the serve
          pool runs it ONCE per trace and fans out (serve/pool.py).
        * ``'head'`` — task head only; ``features`` is a trunk output and
          ``x`` is the ORIGINAL model input (the dpk upsampling ladder
          needs its length to rebuild full resolution).

        The param tree is identical in all modes (all submodules carry
        explicit names), so one checkpoint serves all three; head-only
        application simply never reads the trunk leaves.
        """
        if mode not in ("full", "backbone", "head"):
            raise ValueError(f"unknown mode {mode!r}")
        if mode == "head":
            if features is None:
                raise ValueError("mode='head' requires features")
            return self._head(features, x, train)
        feats = self._backbone(x, train)
        if mode == "backbone":
            return feats
        return self._head(feats, x, train)

    def _backbone(self, x: Array, train: bool) -> Array:
        """Stem + 4 stages — the shared trunk (ref: seist.py:686-770)."""
        assert (
            len(self.stem_channels)
            == len(self.stem_kernel_sizes)
            == len(self.stem_strides)
        )
        assert (
            len(self.layer_blocks)
            == len(self.layer_channels)
            == len(self.stage_aggr_ratios)
            == len(self.attn_aggr_ratios)
            == len(self.attn_blocks)
            == len(self.head_dims)
        )

        # Stem: 4 StemBlocks, strides [2,1,1,2] => L/4 (ref: seist.py:686-703)
        stem_in = [self.in_channels] + list(self.stem_channels[:-1])
        for i, (inc, outc, kers, strd) in enumerate(
            zip(stem_in, self.stem_channels, self.stem_kernel_sizes, self.stem_strides)
        ):
            x = StemBlock(
                inc, outc, kers, strd, self.norm, self.act, name=f"stem{i}"
            )(x, train)

        # Stochastic-depth schedule over all blocks (ref: seist.py:705)
        total_blocks = sum(self.layer_blocks)
        pdprs = [
            self.path_drop_rate * i / max(total_blocks - 1, 1)
            for i in range(total_blocks)
        ]

        for i, num_blocks in enumerate(self.layer_blocks):
            lc = self.layer_channels[i]

            def stage_fn(mdl_self, x, train, _i=i, _lc=lc, _nb=num_blocks):
                x = LocalAwareAggregationBlock(
                    _lc, mdl_self.stage_aggr_ratios[_i], mdl_self.norm,
                    name=f"stage{_i}_aggr",
                )(x, train)
                for j in range(_nb):
                    pdpr = pdprs[sum(self.layer_blocks[:_i]) + j]
                    if j >= _nb - mdl_self.attn_blocks[_i]:
                        x = MultiPathTransformerLayer(
                            io_dim=_lc,
                            path_drop_rate=pdpr,
                            attn_aggr_ratio=mdl_self.attn_aggr_ratios[_i],
                            attn_ratio=mdl_self.attn_ratio,
                            head_dim=mdl_self.head_dims[_i],
                            qkv_bias=mdl_self.qkv_bias,
                            mlp_ratio=mdl_self.mlp_ratio,
                            mlp_bias=mdl_self.mlp_bias,
                            attn_drop_rate=mdl_self.attn_drop_rate,
                            key_drop_rate=mdl_self.key_drop_rate,
                            attn_out_drop_rate=mdl_self.other_drop_rate,
                            mlp_drop_rate=mdl_self.mlp_drop_rate,
                            norm=mdl_self.norm,
                            act=mdl_self.act,
                            name=f"stage{_i}_block{j}",
                        )(x, train)
                    else:
                        x = MultiScaleMixedConv(
                            io_dim=_lc,
                            groups=_lc // mdl_self.head_dims[_i],
                            kernel_sizes=mdl_self.msmc_kernel_sizes,
                            path_drop_rate=pdpr,
                            mlp_drop_rate=mdl_self.mlp_drop_rate,
                            mlp_ratio=mdl_self.mlp_ratio,
                            mlp_bias=mdl_self.mlp_bias,
                            norm=mdl_self.norm,
                            act=mdl_self.act,
                            name=f"stage{_i}_block{j}",
                        )(x, train)
                return x

            if self.use_checkpoint:
                # Rematerialize the stage to trade FLOPs for HBM
                # (replaces torch.utils.checkpoint, ref: seist.py:841-847).
                x = nn.remat(stage_fn, static_argnums=(2,))(self, x, train)
            else:
                x = stage_fn(self, x, train)
        return x

    def _head(self, x: Array, x_input: Array, train: bool) -> Array:
        # Output head (ref: seist.py:773-812)
        if self.head_type == "dpk":
            out_layer_channels = []
            out_layer_kernel_sizes = []
            for channel, kernel, stride in zip(
                [self.in_channels]
                + list(self.stem_channels)
                + list(self.layer_channels[:-1]),
                list(self.stem_kernel_sizes)
                + [max(self.msmc_kernel_sizes)] * len(self.layer_channels),
                list(self.stem_strides) + list(self.stage_aggr_ratios),
            ):
                if stride > 1:
                    out_layer_channels.insert(0, channel)
                    out_layer_kernel_sizes.insert(0, kernel)
            return HeadDetectionPicking(
                layer_channels=out_layer_channels,
                layer_kernel_sizes=out_layer_kernel_sizes,
                out_channels=self.head_out_channels,
                out_act=nn.sigmoid,
                norm=self.norm,
                act=self.act,
                name="out_head",
            )(x, x_input, train)
        if self.head_type == "cls":
            return HeadClassification(
                num_classes=self.head_num_classes,
                out_act=lambda v: nn.softmax(v, axis=-1),
                name="out_head",
            )(x, x_input, train)
        if self.head_type == "reg":
            scale = self.head_scale
            return HeadRegression(
                out_act=lambda v: nn.sigmoid(v) * scale, name="out_head"
            )(x, x_input, train)
        raise NotImplementedError(f"Unknown head_type '{self.head_type}'")


# ------------------------------------------------------- trunk/head split API
def supports_trunk_split(model: Any) -> bool:
    """True when ``model`` exposes the backbone/head apply modes (the
    SeisT family); other registered models (phasenet, eqtransformer, ...)
    are single-task and serve through the plain forward."""
    return isinstance(model, SeismogramTransformer)


def backbone_apply(model: Any, variables: Any, x: Array) -> Array:
    """Run ONLY the shared trunk (stem + stages): (N, L, C) waveforms ->
    (N, L/64, C') features. Inference-mode (train=False), jittable."""
    return model.apply(variables, x, train=False, mode="backbone")


def head_apply(model: Any, variables: Any, features: Array, x_input: Array) -> Array:
    """Run ONLY the task head on trunk ``features``. ``x_input`` is the
    original waveform batch — the dpk upsampling ladder reads its length
    (never its values) to rebuild full-resolution picks; cls/reg heads
    ignore it. Head-only application reads just the ``out_head`` subtree
    of ``variables``; unused trunk leaves are ignored by flax."""
    return model.apply(
        variables, x_input, train=False, mode="head", features=features
    )


# ---------------------------------------------------------------- size presets
_PRESET_S = dict(
    stem_channels=(16, 8, 16, 16),
    stem_kernel_sizes=(11, 5, 5, 7),
    stem_strides=(2, 1, 1, 2),
    layer_blocks=(2, 2, 3, 2),
    layer_channels=(16, 24, 32, 64),
    attn_blocks=(1, 1, 1, 1),
    stage_aggr_ratios=(2, 2, 2, 2),
    attn_aggr_ratios=(8, 4, 2, 1),
    head_dims=(8, 8, 8, 16),
    msmc_kernel_sizes=(5, 7),
    path_drop_rate=0.1,
    attn_drop_rate=0.1,
    key_drop_rate=0.1,
    mlp_drop_rate=0.1,
    other_drop_rate=0.1,
    attn_ratio=0.6,
    mlp_ratio=2.0,
)

_PRESET_M = dict(
    stem_channels=(16, 8, 16, 16),
    stem_kernel_sizes=(11, 5, 5, 7),
    stem_strides=(2, 1, 1, 2),
    layer_blocks=(2, 3, 6, 2),
    layer_channels=(24, 32, 64, 96),
    attn_blocks=(1, 1, 1, 1),
    stage_aggr_ratios=(2, 2, 2, 2),
    attn_aggr_ratios=(8, 4, 2, 1),
    head_dims=(8, 8, 16, 32),
    msmc_kernel_sizes=(5, 7),
    path_drop_rate=0.1,
    attn_drop_rate=0.1,
    key_drop_rate=0.1,
    mlp_drop_rate=0.1,
    other_drop_rate=0.1,
    attn_ratio=0.6,
    mlp_ratio=2.0,
)

_PRESET_L = dict(
    stem_channels=(16, 8, 16, 16),
    stem_kernel_sizes=(11, 5, 5, 7),
    stem_strides=(2, 1, 1, 2),
    layer_blocks=(2, 3, 6, 3),
    layer_channels=(32, 32, 64, 128),
    attn_blocks=(1, 1, 2, 1),
    stage_aggr_ratios=(2, 2, 2, 2),
    attn_aggr_ratios=(8, 4, 2, 1),
    head_dims=(8, 8, 16, 32),
    msmc_kernel_sizes=(3, 5, 7, 11),
    path_drop_rate=0.2,
    attn_drop_rate=0.2,
    key_drop_rate=0.1,
    mlp_drop_rate=0.2,
    other_drop_rate=0.1,
    attn_ratio=0.6,
    mlp_ratio=3.0,
)

_PRESETS = {"s": _PRESET_S, "m": _PRESET_M, "l": _PRESET_L}


def _drops(rate: float) -> dict:
    return dict(
        path_drop_rate=rate,
        attn_drop_rate=rate,
        key_drop_rate=rate,
        mlp_drop_rate=rate,
        other_drop_rate=rate,
    )


def _build(size: str, head: dict, overrides: dict, **kwargs) -> SeismogramTransformer:
    args = dict(_PRESETS[size])
    args.update(overrides)
    args.update(head)
    kwargs.pop("in_samples", None)
    args.update(
        {k: v for k, v in kwargs.items()
         if k in SeismogramTransformer.__dataclass_fields__}
    )
    return SeismogramTransformer(**args)


_HEAD_DPK = dict(head_type="dpk", head_out_channels=3)
_HEAD_PMP = dict(head_type="cls", head_num_classes=2)


def _head_reg(scale: float) -> dict:
    return dict(head_type="reg", head_scale=scale)


# Per-task drop-rate overrides mirror the registered ctors
# (ref: seist.py:940-1170).
@register_model
def seist_s_dpk(**kw):
    """Detection and phase picking (small)."""
    return _build("s", _HEAD_DPK, {}, **kw)


@register_model
def seist_m_dpk(**kw):
    """Detection and phase picking (medium)."""
    return _build("m", _HEAD_DPK, _drops(0.2), **kw)


@register_model
def seist_l_dpk(**kw):
    """Detection and phase picking (large)."""
    return _build("l", _HEAD_DPK, _drops(0.3), **kw)


@register_model
def seist_s_pmp(**kw):
    """First-motion polarity classification (small)."""
    return _build("s", _HEAD_PMP, _drops(0.2), **kw)


@register_model
def seist_m_pmp(**kw):
    """First-motion polarity classification (medium)."""
    return _build("m", _HEAD_PMP, _drops(0.25), **kw)


@register_model
def seist_l_pmp(**kw):
    """First-motion polarity classification (large)."""
    return _build("l", _HEAD_PMP, _drops(0.3), **kw)


@register_model
def seist_s_emg(**kw):
    """Magnitude estimation (small): sigmoid x 8."""
    return _build("s", _head_reg(8.0), {}, **kw)


@register_model
def seist_m_emg(**kw):
    """Magnitude estimation (medium)."""
    return _build("m", _head_reg(8.0), {}, **kw)


@register_model
def seist_l_emg(**kw):
    """Magnitude estimation (large)."""
    return _build("l", _head_reg(8.0), {}, **kw)


@register_model
def seist_s_baz(**kw):
    """Back-azimuth estimation (small): sigmoid x 360."""
    return _build("s", _head_reg(360.0), {}, **kw)


@register_model
def seist_m_baz(**kw):
    """Back-azimuth estimation (medium)."""
    return _build("m", _head_reg(360.0), {}, **kw)


@register_model
def seist_l_baz(**kw):
    """Back-azimuth estimation (large)."""
    return _build("l", _head_reg(360.0), {}, **kw)


@register_model
def seist_s_dis(**kw):
    """Epicentral distance estimation (small): sigmoid x 500."""
    return _build("s", _head_reg(500.0), {}, **kw)


@register_model
def seist_m_dis(**kw):
    """Epicentral distance estimation (medium)."""
    return _build("m", _head_reg(500.0), {}, **kw)


@register_model
def seist_l_dis(**kw):
    """Epicentral distance estimation (large)."""
    return _build("l", _head_reg(500.0), {}, **kw)
