"""Causal grouped-query attention through a blocked kernel.

On the TPU backend this is the flash-attention Pallas kernel that ships with
jax (``jax.experimental.pallas.ops.tpu.flash_attention``): scores never
exist beyond a block, forward or backward, and a compiler refusal is the
run's error — there is no fallback to materialised scores there (at 8192
positions and 32 heads they would be 17 GB). Other backends (the CPU tests,
at lengths of a few hundred) take the plain einsum; the same rule as
``ops/pallas_attention.py``. Softmax is in float32 on both paths.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _einsum_attention(q, k, v, scale):
    length = q.shape[2]
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    mask = jnp.tril(jnp.ones((length, length), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


def causal_gqa_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, scale: float
) -> jax.Array:
    """``q`` (B, L, Hq, E); ``k``, ``v`` (B, L, Hkv, E) with ``Hq % Hkv ==
    0``: query head ``h`` reads key-value head ``h // (Hq / Hkv)``. Position
    ``t`` attends to positions ``<= t``. Returns (B, L, Hq, E)."""
    hq, hkv = q.shape[2], k.shape[2]
    if hq % hkv:
        raise ValueError(f"{hq} query heads over {hkv} key-value heads")
    rep = hq // hkv
    q = q.transpose(0, 2, 1, 3)
    k = jnp.repeat(k.transpose(0, 2, 1, 3), rep, axis=1)
    v = jnp.repeat(v.transpose(0, 2, 1, 3), rep, axis=1)
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu import flash_attention as fa

        block = min(512, q.shape[2])
        sizes = fa.BlockSizes(
            block_q=block, block_k_major=block, block_k=block, block_b=1,
            block_q_major_dkv=block, block_k_major_dkv=block,
            block_k_dkv=block, block_q_dkv=block,
            block_k_major_dq=block, block_k_dq=block, block_q_dq=block,
        )
        out = fa.flash_attention(
            q, k, v, causal=True, sm_scale=scale, block_sizes=sizes
        )
    else:
        out = _einsum_attention(q, k, v, scale)
    return out.transpose(0, 2, 1, 3)
