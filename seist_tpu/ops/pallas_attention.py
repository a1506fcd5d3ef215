"""Pallas TPU kernel: fused pooled-KV attention, with in-kernel dropout.

The SeisT encoder's attention keeps full-length Q but pools K/V by
``attn_aggr_ratio`` (ref seist.py:321-393), so scores are (L x M) with
M = L/r. XLA's unfused path materializes the (N, H, L, M) probability
tensor in HBM — at the reference training shape (batch 500, stage 1:
L=1024, M=128) that is ~0.5 GB of HBM traffic per layer per direction.
This kernel fuses qk-matmul + softmax + (dropout) + pv-matmul in VMEM
(one grid step per batch element, heads unrolled in-kernel over the
feature axis; L, M and H*E are small enough that a whole batch element's
Q/K/V fit on-chip), writing only the (L, H*E) output. Q/K/V enter as
(N, L, H*E) — exactly the layout the Dense projections produce — so no
head transpose is ever materialized in HBM (the (N,L,H,E)->(N,H,L,E)
copies were ~2 ms/step in the round-2 seist_l profile).

Training works through a custom VJP whose backward is a second fused
kernel (recompute-p flash-style backward), so no probability tensor is
ever materialized in either direction.

Attention-probability dropout (ref seist.py:383-388 applies
``attn_drop`` after softmax) is generated *inside* the kernel from a
counter-based hash PRNG written in plain jnp ops, so the exact same
mask math runs in three places: the compiled TPU kernel, the Pallas
interpreter (CPU tests), and the XLA einsum fallback. The backward
kernel regenerates the identical mask from the saved seed, so no mask
tensor is materialized either.

``fused_pooled_attention`` is numerically identical (fp32) to the
einsum path for the same seed. On the TPU backend the kernel is used and a
compiler refusal is the run's error; non-TPU backends (and the explicit
``SEIST_ATTN_IMPL=einsum``) take the einsum, and ``interpret=True`` drives
the same kernels through the Pallas interpreter in tests only.
"""

from __future__ import annotations

import math
import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

def _wrap_i32(v: int) -> np.int32:
    """Python int -> int32 scalar with explicit two's-complement wrap.

    ``jnp.int32(big)`` raises under numpy>=2; the counter math here wraps
    mod 2^32 by design (long-context L*M can exceed 2^31 — the hash mixes
    the wrapped bits the same way on every path).

    Returns a NUMPY scalar, not a jnp array: numpy scalars trace as inline
    jaxpr literals, while jnp arrays become captured constants — which
    Mosaic's pallas_call rejects outright ("captures constants ... pass
    them as inputs", observed live on TPU 2026-08-02). The arithmetic is
    identical either way, so the kernel, the interpreter, and the XLA
    einsum fallback keep bit-identical mask math.
    """
    return np.int32(np.uint32(int(v) & 0xFFFFFFFF))


def _mix_to_uniform(x, seed) -> jnp.ndarray:
    """murmur3-finalizer hash of int32 counter array ``x`` -> U[0,1).

    int32 throughout (Mosaic lacks uint32<->float casts): multiplies wrap
    two's-complement — identical low 32 bits to the uint32 murmur mix —
    and shifts are explicit logical shifts.
    """

    def c(u):  # uint32 constant as wrapped int32 (numpy scalar: traces as
        # an inline literal — a jnp constant would be a captured const,
        # which pallas_call rejects; see _wrap_i32)
        return np.int32(np.uint32(u))

    shr = lambda x, n: lax.shift_right_logical(x, np.int32(n))
    x = x ^ (seed.astype(jnp.int32) * c(0x9E3779B9))
    x = x ^ shr(x, 16)
    x = x * c(0x85EBCA6B)
    x = x ^ shr(x, 13)
    x = x * c(0xC2B2AE35)
    x = x ^ shr(x, 16)
    return shr(x, 8).astype(jnp.float32) * (1.0 / (1 << 24))


def _uniform01(seed, pid, l: int, m: int) -> jnp.ndarray:
    """Deterministic (L, M) uniforms in [0, 1) for batch-head slice ``pid``.

    Counter-based (murmur3-finalizer over a linear element index), pure jnp
    — runs identically inside a Pallas kernel, under the interpreter, and in
    the XLA fallback, so all three paths agree bit-for-bit on the mask.
    The ring-attention path generates the same stream blockwise via
    ``_uniform01_block``.
    """
    row = lax.broadcasted_iota(jnp.int32, (l, m), 0)
    col = lax.broadcasted_iota(jnp.int32, (l, m), 1)
    x = pid.astype(jnp.int32) * _wrap_i32(l * m) + row * _wrap_i32(m) + col
    return _mix_to_uniform(x, seed)


def _apply_dropout(p, seed, pid, rate: float):
    """Zero entries where u < rate; scale survivors by 1/(1-rate)."""
    l, m = p.shape[-2], p.shape[-1]
    u = _uniform01(seed, pid, l, m)
    keep = u >= np.float32(rate)
    return jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)


def _einsum_attention(q, k, v, scale, dropout_rate=0.0, dropout_seed=None):
    s = jnp.einsum("nlhe,nmhe->nhlm", q * scale, k)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate > 0.0:
        n, h, l, m = p.shape
        pid = lax.broadcasted_iota(jnp.int32, (n * h, 1, 1), 0)
        u = jax.vmap(
            lambda i: _uniform01(dropout_seed[0], i.reshape(()), l, m)
        )(pid.reshape(n * h))
        keep = u.reshape(n, h, l, m) >= jnp.float32(dropout_rate)
        p = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
    return jnp.einsum("nhlm,nmhe->nlhe", p, v)


# -- kernels (operate on one (batch*head) slice in VMEM) ---------------------


def _softmax_rows(q, k, scale):
    s = jnp.dot(q * scale, k.T, preferred_element_type=jnp.float32)  # (L, M)
    s = s - s.max(axis=-1, keepdims=True)
    p = jnp.exp(s)
    return p / p.sum(axis=-1, keepdims=True)


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, *, scale, rate, heads):
    from jax.experimental import pallas as pl

    q = q_ref[0].astype(jnp.float32)  # (L, H*E)
    k = k_ref[0].astype(jnp.float32)  # (M, H*E)
    v = v_ref[0].astype(jnp.float32)  # (M, H*E)
    e = q.shape[-1] // heads
    for h in range(heads):
        sl = slice(h * e, (h + 1) * e)
        p = _softmax_rows(q[:, sl], k[:, sl], scale)
        if rate > 0.0:
            pid = (seed_ref[1] + pl.program_id(0)) * heads + h
            p = _apply_dropout(p, seed_ref[0], pid, rate)
        o_ref[0, :, sl] = jnp.dot(
            p, v[:, sl], preferred_element_type=jnp.float32
        ).astype(o_ref.dtype)


def _bwd_kernel(
    seed_ref,
    q_ref,
    k_ref,
    v_ref,
    g_ref,
    dq_ref,
    dk_ref,
    dv_ref,
    *,
    scale,
    rate,
    heads,
):
    from jax.experimental import pallas as pl

    qa = q_ref[0].astype(jnp.float32)  # (L, H*E)
    ka = k_ref[0].astype(jnp.float32)  # (M, H*E)
    va = v_ref[0].astype(jnp.float32)
    ga = g_ref[0].astype(jnp.float32)  # (L, H*E) upstream grad
    e = qa.shape[-1] // heads
    for h in range(heads):
        sl = slice(h * e, (h + 1) * e)
        q, k, v, g = qa[:, sl], ka[:, sl], va[:, sl], ga[:, sl]
        pid = (seed_ref[1] + pl.program_id(0)) * heads + h
        p = _softmax_rows(q, k, scale)  # recomputed probs (L, M)
        if rate > 0.0:
            pd = _apply_dropout(p, seed_ref[0], pid, rate)
        else:
            pd = p
        dv = jnp.dot(pd.T, g, preferred_element_type=jnp.float32)
        dpd = jnp.dot(g, v.T, preferred_element_type=jnp.float32)  # (L, M)
        if rate > 0.0:
            # d(dropout)/dp is the same keep/scale mask; reuse via pd =
            # mask*p/kp: where p > 0, mask*inv_keep = pd / p. Regenerate
            # instead (exact, avoids 0/0): same counter stream.
            dp = _apply_dropout(dpd, seed_ref[0], pid, rate)
        else:
            dp = dpd
        ds = p * (dp - (dp * p).sum(axis=-1, keepdims=True))  # softmax vjp
        dq = jnp.dot(ds, k, preferred_element_type=jnp.float32) * scale
        dk = jnp.dot(ds.T, q, preferred_element_type=jnp.float32) * scale
        dq_ref[0, :, sl] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, sl] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, sl] = dv.astype(dv_ref.dtype)


def _fold_heads(x):
    """(N, L, H, E) -> (N, L, H*E): a pure bitcast reshape (no transpose —
    the heads stay interleaved on the feature axis exactly as the q/k/v
    Dense projections produce them; the kernel slices per head in VMEM)."""
    n, l, h, e = x.shape
    return x.reshape(n, l, h * e)


def _call_fused(kernel, out_shapes, seed, inputs, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nh = inputs[0].shape[0]

    def spec(x):
        return pl.BlockSpec((1,) + x.shape[1:], lambda i, s: (i, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nh,),
        in_specs=[spec(x) for x in inputs],
        out_specs=(
            [spec(o) for o in out_shapes]
            if isinstance(out_shapes, (list, tuple))
            else spec(out_shapes)
        ),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
    )(seed, *inputs)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _fused(q3, k3, v3, seed, scale, rate, heads, interpret):
    o = _call_fused(
        partial(_fwd_kernel, scale=scale, rate=rate, heads=heads),
        jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        seed,
        (q3, k3, v3),
        interpret,
    )
    return o


def _fused_fwd(q3, k3, v3, seed, scale, rate, heads, interpret):
    return (
        _fused(q3, k3, v3, seed, scale, rate, heads, interpret),
        (q3, k3, v3, seed),
    )


def _fused_bwd(scale, rate, heads, interpret, res, g):
    q3, k3, v3, seed = res
    dq, dk, dv = _call_fused(
        partial(_bwd_kernel, scale=scale, rate=rate, heads=heads),
        (
            jax.ShapeDtypeStruct(q3.shape, q3.dtype),
            jax.ShapeDtypeStruct(k3.shape, k3.dtype),
            jax.ShapeDtypeStruct(v3.shape, v3.dtype),
        ),
        seed,
        (q3, k3, v3, g),
        interpret,
    )
    return dq, dk, dv, np.zeros(seed.shape, dtype=jax.dtypes.float0)


_fused.defvjp(_fused_fwd, _fused_bwd)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _fused_rows(q3, k3, v3, seed, scale, rate, heads, interpret):
    """The kernel over the batch rows, laid out as the step being traced
    lays them out (``parallel.mesh.active_mesh``, scoped by train.step's
    jit wrappers around the function whose shardings name that mesh).

    No data-parallel mesh: one call on the whole batch. With one: Mosaic
    kernels cannot be partitioned automatically (XLA refuses the step), so
    each device runs the kernel on ITS rows inside a shard_map. Either way
    the second scalar of the seed is the global index of the first row the
    kernel sees, so the dropout masks are the ones a single device draws
    for the whole batch."""
    from jax.sharding import PartitionSpec as P

    from seist_tpu.parallel import mesh as mesh_lib

    static = (scale, rate, heads, interpret)
    mesh = mesh_lib.active_mesh()
    if mesh is None or mesh.shape.get(mesh_lib.AXIS_DATA, 1) == 1:
        seed2 = jnp.concatenate([seed, jnp.zeros((1,), jnp.int32)])
        return _fused(q3, k3, v3, seed2, *static)

    def local(q3, k3, v3, seed):
        row0 = lax.axis_index(mesh_lib.AXIS_DATA) * q3.shape[0]
        seed2 = jnp.stack([seed[0], row0.astype(jnp.int32)])
        return _fused(q3, k3, v3, seed2, *static)

    rows = P(mesh_lib.AXIS_DATA)
    return jax.shard_map(
        local, mesh=mesh, in_specs=(rows, rows, rows, P()), out_specs=rows,
        check_vma=False,
    )(q3, k3, v3, seed)


def fused_pooled_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: Optional[float] = None,
    *,
    dropout_rate: float = 0.0,
    dropout_seed: Optional[jnp.ndarray] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused attention for ``q (N, L, H, E)``, ``k/v (N, M, H, E)``.

    Uses the Pallas kernel on TPU (``interpret`` runs it through the Pallas
    interpreter, for tests); otherwise the XLA einsum path — both compute
    identical fp32 math, including the dropout mask (same counter-based PRNG
    in both).

    ``dropout_rate`` > 0 applies post-softmax probability dropout (ref
    seist.py:383-388) and requires ``dropout_seed``, an int32 array of
    shape (1,) — derive it per step from the flax 'dropout' rng stream.
    """
    e = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(e)
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    if dropout_seed is None:
        dropout_seed = jnp.zeros((1,), jnp.int32)
    dropout_seed = dropout_seed.astype(jnp.int32)
    # The kernel on TPU, the einsum elsewhere; SEIST_ATTN_IMPL=einsum is the
    # explicit choice of the identical-math XLA path on TPU. A compiler
    # refusal of the kernel is the run's error — nothing routes around it.
    env_impl = os.environ.get("SEIST_ATTN_IMPL")
    if env_impl not in (None, "", "einsum"):
        raise ValueError(
            f"unknown SEIST_ATTN_IMPL {env_impl!r} (unset it, or einsum)"
        )
    if not (interpret or (env_impl != "einsum" and _on_tpu())):
        return _einsum_attention(q, k, v, scale, dropout_rate, dropout_seed)
    o3 = _fused_rows(
        _fold_heads(q), _fold_heads(k), _fold_heads(v), dropout_seed,
        scale, float(dropout_rate), q.shape[2], interpret,
    )
    return o3.reshape(q.shape)
