"""NemotronH: a hybrid Mamba-2 / mixture-of-experts / attention language
model (``model_type: nemotron_h``, e.g. NVIDIA-Nemotron-3-Nano-30B-A3B).

Block ``i`` is one mixer alone under a pre-norm residual,
``x <- x + Mixer_i(RMSNorm(x))``, the mixer chosen by the ``pattern``'s
character: ``M`` Mamba-2 (chunked scan, ``ops/ssd.py``), ``E`` a mixture of
experts with a shared expert (``ops/moe.py``), ``*`` causal grouped-query
attention without rotary embedding (``ops/causal_attention.py``). After the
last block a final RMSNorm and an untied head. No linear map has a bias; the
Mamba convolution has one.

One class holds every size as a field, so the CPU-sized preset of the tests
and the published one are the same code. What a chip holds of a deployment
is said by sizes too: ``experts_held`` (first id, count) of the
``n_routed_experts`` the router scores, ``vocab_size`` rows of the
vocabulary, the first ``len(pattern)`` blocks. The router's correction bias
``b`` is no trained parameter: it lives in no collection, it is the
constant zero (config.json gives no balancing rule and none is invented).

Precision under ``--dtype bf16`` (train/precision.py casts parameters and
activations): matrix products in bf16 with float32 accumulation; the norms'
statistics, the router, the softmax, the scan's decay and state, and the
loss in float32.

Every block is rematerialised (``nn.remat``): the backward pass keeps the
block's input and computes the rest again. The counts a step's host side
wants (token-slots on experts held, rows beyond the buffer, load skew) are
sown into the ``aux`` collection once, at the top, from what the blocks
return — nothing is sown inside a rematerialised module.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from seist_tpu.ops import moe
from seist_tpu.ops.causal_attention import causal_gqa_attention
from seist_tpu.ops.ssd import ssd_chunked
from seist_tpu.registry import register_model


def _normal(std: float):
    return nn.initializers.normal(stddev=std)


def rms_norm(x, scale, eps):
    """RMSNorm with float32 statistics, output in ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _dot(x, w):
    return jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32
                   ).astype(x.dtype)


def _dt_bias_init(dt_min, dt_max, dt_floor):
    def init(key, shape, dtype=jnp.float32):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (np.log(dt_max) - np.log(dt_min)) + np.log(dt_min))
        dt = jnp.maximum(dt, dt_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus^-1

    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


class Mamba2Mixer(nn.Module):
    hidden: int
    heads: int
    head_dim: int
    groups: int
    state: int
    conv_kernel: int
    chunk: int
    eps: float
    out_std: float
    dt_limits: Tuple[float, float, float]

    @nn.compact
    def __call__(self, x):
        d_inner = self.heads * self.head_dim
        d_bc = self.groups * self.state
        d_conv = d_inner + 2 * d_bc
        p = self.param
        w_in = p("in_proj", _normal(0.02), (self.hidden, d_inner + d_conv + self.heads))
        conv_w = p("conv_kernel", _normal(self.conv_kernel ** -0.5),
                   (self.conv_kernel, d_conv))
        conv_b = p("conv_bias", _normal(0.02), (d_conv,))
        dt_bias = p("dt_bias", _dt_bias_init(*self.dt_limits), (self.heads,))
        a_log = p("A_log", _a_log_init, (self.heads,))
        d_skip = p("D", nn.initializers.ones, (self.heads,))
        gate_scale = p("gate_norm_scale", nn.initializers.ones, (d_inner,))
        w_out = p("out_proj", _normal(self.out_std), (d_inner, self.hidden))

        bsz, length, _ = x.shape
        with jax.named_scope("ssm_proj"):
            zxbcdt = _dot(x, w_in)
            z, xbc, dt = jnp.split(zxbcdt, [d_inner, d_inner + d_conv], axis=-1)
            # Causal depthwise convolution as shifted products: position t
            # reads t - K + 1 .. t.
            padded = jnp.pad(xbc, ((0, 0), (self.conv_kernel - 1, 0), (0, 0)))
            conv = conv_b.astype(xbc.dtype)
            for k in range(self.conv_kernel):
                conv = conv + padded[:, k:k + length] * conv_w[k].astype(xbc.dtype)
            xbc = jax.nn.silu(conv)
            xs, b, c = jnp.split(xbc, [d_inner, d_inner + d_bc], axis=-1)
            dt = jax.nn.softplus(
                dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
        with jax.named_scope("ssm_scan"):
            xh = xs.reshape(bsz, length, self.heads, self.head_dim)
            y, _ = ssd_chunked(
                xh, dt, -jnp.exp(a_log.astype(jnp.float32)),
                b.reshape(bsz, length, self.groups, self.state),
                c.reshape(bsz, length, self.groups, self.state),
                chunk=self.chunk,
            )
            y = y + xh * d_skip.astype(xh.dtype)[:, None]
        with jax.named_scope("ssm_proj"):
            y = y.reshape(bsz, length, d_inner) * jax.nn.silu(z)
            # RMSNorm over each of the groups' d_inner / groups channels
            yg = y.reshape(bsz, length, self.groups, d_inner // self.groups)
            yg = rms_norm(yg, jnp.ones((), jnp.float32), self.eps)
            y = yg.reshape(bsz, length, d_inner) * gate_scale.astype(y.dtype)
            return _dot(y, w_out)


class AttentionMixer(nn.Module):
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    out_std: float

    @nn.compact
    def __call__(self, x):
        p = self.param
        wq = p("q_proj", _normal(0.02), (self.hidden, self.heads * self.head_dim))
        wk = p("k_proj", _normal(0.02), (self.hidden, self.kv_heads * self.head_dim))
        wv = p("v_proj", _normal(0.02), (self.hidden, self.kv_heads * self.head_dim))
        wo = p("o_proj", _normal(self.out_std), (self.heads * self.head_dim, self.hidden))
        bsz, length, _ = x.shape
        with jax.named_scope("attn_path"):
            q = _dot(x, wq).reshape(bsz, length, self.heads, self.head_dim)
            k = _dot(x, wk).reshape(bsz, length, self.kv_heads, self.head_dim)
            v = _dot(x, wv).reshape(bsz, length, self.kv_heads, self.head_dim)
            o = causal_gqa_attention(q, k, v, scale=self.head_dim ** -0.5)
            return _dot(o.reshape(bsz, length, -1), wo)


#: The experts' row buffer, as a multiple of the rows an even router would
#: send to the experts held (tokens x top_k x held / all). With no balancing
#: rule a fresh router is not even: over 12 initialisations x 16 shares x 4
#: layers at the published widths and 8192 Zipf ids a share took 0.27-2.44
#: times its expected rows (PERF.md, PR 29), so twice overflows and four
#: times has not; rows beyond the buffer are counted, never dropped in
#: silence, and fail the benchmark's run.
MOE_BUFFER_OVER_EXPECTED = 4


class MoEMixer(nn.Module):
    hidden: int
    n_routed_experts: int
    experts_held: Tuple[int, int]
    top_k: int
    expert_width: int
    shared_width: int
    scaling: float
    out_std: float

    @nn.compact
    def __call__(self, x):
        first, count = self.experts_held
        p = self.param
        w_router = p("router", _normal(0.02), (self.hidden, self.n_routed_experts))
        w_up = p("experts_up", _normal(0.02), (count, self.hidden, self.expert_width))
        w_down = p("experts_down", _normal(self.out_std),
                   (count, self.expert_width, self.hidden))
        s_up = p("shared_up", _normal(0.02), (self.hidden, self.shared_width))
        s_down = p("shared_down", _normal(self.out_std), (self.shared_width, self.hidden))

        bsz, length, _ = x.shape
        flat = x.reshape(bsz * length, self.hidden)
        with jax.named_scope("moe_router"):
            ids, weights = moe.route(
                flat, w_router, jnp.zeros((self.n_routed_experts,), jnp.float32),
                top_k=self.top_k, scaling=self.scaling,
            )
        with jax.named_scope("moe_experts"):
            slots = bsz * length * self.top_k
            capacity = min(slots, MOE_BUFFER_OVER_EXPECTED * moe.expected_rows(
                bsz * length, self.top_k, count, self.n_routed_experts))
            routed, stats = moe.local_experts(
                flat, ids, weights, w_up, w_down, first=first,
                capacity=capacity)
        with jax.named_scope("moe_shared"):
            shared = _dot(jnp.square(jax.nn.relu(_dot(flat, s_up))), s_down)
        out = (routed + shared).reshape(bsz, length, self.hidden)
        return out, jnp.stack([
            stats["slots_local"].astype(jnp.float32),
            stats["overflow_rows"].astype(jnp.float32),
            stats["load_max_over_mean"],
        ])


class Block(nn.Module):
    kind: str
    cfg: "NemotronHConfig"

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        scale = self.param("norm_scale", nn.initializers.ones, (c.hidden_size,))
        h = rms_norm(x, scale, c.norm_eps)
        out_std = 0.02 / np.sqrt(len(c.pattern))  # rescale_prenorm_residual
        stats = jnp.zeros((3,), jnp.float32)
        if self.kind == "M":
            y = Mamba2Mixer(
                hidden=c.hidden_size, heads=c.mamba_num_heads,
                head_dim=c.mamba_head_dim, groups=c.n_groups,
                state=c.ssm_state_size, conv_kernel=c.conv_kernel,
                chunk=c.chunk_size, eps=c.norm_eps, out_std=out_std,
                dt_limits=(c.time_step_min, c.time_step_max, c.time_step_floor),
                name="mixer")(h)
        elif self.kind == "*":
            y = AttentionMixer(
                hidden=c.hidden_size, heads=c.num_attention_heads,
                kv_heads=c.num_key_value_heads, head_dim=c.head_dim,
                out_std=out_std, name="mixer")(h)
        elif self.kind == "E":
            y, stats = MoEMixer(
                hidden=c.hidden_size, n_routed_experts=c.n_routed_experts,
                experts_held=c.experts_held, top_k=c.num_experts_per_tok,
                expert_width=c.moe_intermediate_size,
                shared_width=c.moe_shared_expert_intermediate_size,
                scaling=c.routed_scaling_factor,
                out_std=out_std,
                name="mixer")(h)
        else:
            raise ValueError(f"unknown block kind '{self.kind}'")
        return x + y, stats


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """Every size of the model; the defaults are the published ones of
    NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 but for ``pattern`` (its first nine
    of 52 characters)."""

    pattern: str = "MEMEM*EME"
    hidden_size: int = 2688
    vocab_size: int = 131072
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # experts
    n_routed_experts: int = 128
    experts_held: Tuple[int, int] = (0, 128)
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_eps: float = 1e-5


class NemotronH(nn.Module):
    """Token ids (B, L) int -> logits (B, L, vocab_size)."""

    cfg: NemotronHConfig = NemotronHConfig()

    input_kind = "tokens"  # models/api.py builds integer example inputs

    @nn.compact
    def __call__(self, ids, train: bool = False):
        from seist_tpu.train.precision import policy_param_dtype

        c = self.cfg
        cdtype = policy_param_dtype()
        with jax.named_scope("embed"):
            table = self.param(
                "embed", _normal(0.02), (c.vocab_size, c.hidden_size))
            x = table.astype(cdtype)[ids]
        block_cls = nn.remat(Block)
        stats = []
        for i, kind in enumerate(c.pattern):
            x, s = block_cls(kind=kind, cfg=c, name=f"block_{i}")(x)
            if kind == "E":
                stats.append(s)
        with jax.named_scope("lm_head"):
            scale = self.param(
                "final_norm_scale", nn.initializers.ones, (c.hidden_size,))
            head = self.param(
                "lm_head", _normal(0.02), (c.hidden_size, c.vocab_size))
            logits = _dot(rms_norm(x, scale, c.norm_eps), head)
        if stats:
            total = jnp.stack(stats)
            self.sow("aux", "moe_slots_local", total[:, 0].sum())
            self.sow("aux", "moe_overflow_rows", total[:, 1].sum())
            self.sow("aux", "moe_load_max_over_mean", total[:, 2].mean())
        self.sow("aux", "tokens", jnp.float32(ids.shape[0] * ids.shape[1]))
        return logits


@register_model
def nemotron3_nano_ep16(in_channels: int = 1, in_samples: int = 8192, **kwargs):
    """One chip's share of a 16-way expert-parallel job over
    NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 at its published widths: the
    pattern's first nine blocks, 8 of the 128 routed experts (ids 0-7) and
    the shared expert, an eighth of the vocabulary (docs/TOKEN_TASK.md)."""
    del in_channels, in_samples  # a token model: no channels, any length
    return NemotronH(cfg=NemotronHConfig(**{
        "pattern": "MEMEM*EME", "vocab_size": 16384, "experts_held": (0, 8),
        **kwargs}))


#: The CPU-sized preset of the tests and of the benchmark's rehearsal: every
#: block kind, 4 of 16 experts held, chunk 32.
TINY = dict(
    pattern="MEMEM*EME", hidden_size=64, vocab_size=256,
    mamba_num_heads=8, mamba_head_dim=16, n_groups=2, ssm_state_size=16,
    chunk_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    n_routed_experts=16, experts_held=(0, 4), num_experts_per_tok=3,
    moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
)


@register_model
def nemotron3_tiny(in_channels: int = 1, in_samples: int = 256, **kwargs):
    del in_channels, in_samples
    return NemotronH(cfg=NemotronHConfig(**{**TINY, **kwargs}))
