"""Ring attention: sequence-parallel exact attention over the ``seq`` mesh axis.

The reference has no long-context support at all (SURVEY.md §5: sequence
length is a flag and the whole window lives on one device). This module is
the TPU-native capability the mesh's ``seq`` axis exists for: shard the
sequence over devices, keep Q blocks resident, and rotate K/V blocks around
the ring with ``lax.ppermute`` while accumulating the softmax online
(flash-attention style running max/denominator), so attention over a
sequence of length L uses O(L/D) memory per device and the K/V transfers
ride ICI neighbor links.

Math: per-block scores s_i = q k_i^T * scale; with running (o, m, l):
    m' = max(m, max_j s_ij);  corr = exp(m - m')
    l' = l * corr + sum_j exp(s_ij - m')
    o' = o * corr + exp(s_i - m') v_i
and o / l at the end equals exact softmax attention — every device sees
every K/V block after axis_size rotations, so no approximation is made.

Post-softmax probability dropout (ref seist.py:383-388) is exact under the
online accumulation too: dense applies ``mask/(1-rate)`` to the softmax
probabilities p_ij = exp(s_ij - m_final)/l_final and then multiplies by V.
Masking is linear in the numerator and the softmax denominator is built
from the *unmasked* probabilities, so the ring applies the mask (with the
survivor scale) to each block's exp-numerator contribution to ``o`` while
``l`` keeps accumulating unmasked — ``o/l`` then equals dense-with-dropout
numerically up to fp reassociation of the online sums (the tests assert
rtol/atol ~2e-5..2e-4), with the *same* dropout mask. The mask comes
from the same counter-based
PRNG the fused/einsum paths share (pallas_attention._mix_to_uniform),
indexed by *global* (batch, head, row, col) so every device regenerates
exactly its slice of the dense mask.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from seist_tpu.parallel.mesh import AXIS_SEQ


def _rotate(x, axis_name: str, axis_size: int):
    """Send this device's block to the next ring neighbor."""
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    return lax.ppermute(x, axis_name, perm)


def _block_dropout_mult(
    seed,
    rate: float,
    n: int,
    h: int,
    lq: int,
    mk: int,
    n0,
    row0,
    col0,
    l_total: int,
    m_total: int,
):
    """(n, h, lq, mk) multiplier — 0 where dropped, 1/(1-rate) where kept —
    equal to the dense path's mask slice at global offsets (n0, row0, col0).

    Dense (_einsum_attention) hashes x = (n*H + h)·(L·M) + row·M + col in
    wrapping int32; regenerating with global indices reproduces it exactly
    (heads are never sharded here — the mesh's model axis is size 1 by
    design — so the local ``h`` is the global head count).
    """
    from seist_tpu.ops.pallas_attention import _mix_to_uniform, _wrap_i32

    ni = lax.broadcasted_iota(jnp.int32, (n, h, lq, mk), 0) + n0
    hi = lax.broadcasted_iota(jnp.int32, (n, h, lq, mk), 1)
    ri = lax.broadcasted_iota(jnp.int32, (n, h, lq, mk), 2) + row0
    ci = lax.broadcasted_iota(jnp.int32, (n, h, lq, mk), 3) + col0
    # _wrap_i32: counters wrap mod 2^32 identically to the dense path even
    # when global L*M exceeds int32 (long-context --seq-shards runs).
    x = (
        (ni * _wrap_i32(h) + hi) * _wrap_i32(l_total * m_total)
        + ri * _wrap_i32(m_total)
        + ci
    )
    u = _mix_to_uniform(x, seed)
    keep = u >= jnp.float32(rate)
    return jnp.where(keep, jnp.float32(1.0 / (1.0 - rate)), 0.0)


def ring_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = AXIS_SEQ,
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_seed: Optional[jnp.ndarray] = None,
    batch_axis: Optional[str] = None,
) -> jnp.ndarray:
    """Per-device body (call inside ``shard_map``): local blocks
    ``q (N, Lq, H, E)``, ``k/v (N, Lk, H, E)`` sharded on the sequence axis.

    Returns the local ``(N, Lq, H, E)`` output block of exact attention over
    the *global* sequence. ``dropout_rate`` > 0 applies the dense path's
    post-softmax probability dropout exactly (see module docstring);
    ``batch_axis`` must name the batch-sharding mesh axis (or None) so the
    global batch index offsets the mask stream.
    """
    n, lq, h, e = q.shape
    mk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(e)
    axis_size = lax.psum(1, axis_name)
    if dropout_rate > 0.0:
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")
        seq_idx = lax.axis_index(axis_name)
        n0 = (
            lax.axis_index(batch_axis) * n
            if batch_axis is not None
            else jnp.int32(0)
        )
        row0 = seq_idx * lq
        l_total = lq * axis_size
        m_total = mk * axis_size

    def accumulate(o, m, l, k_blk, v_blk, src_idx):
        s = jnp.einsum(
            "nlhe,nmhe->nhlm", q * scale, k_blk, preferred_element_type=jnp.float32
        )
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        if dropout_rate > 0.0:
            # Mask the numerator contribution only; `l` stays unmasked —
            # post-softmax dropout divides by the full softmax denominator.
            mult = _block_dropout_mult(
                dropout_seed[0],
                float(dropout_rate),
                n,
                h,
                lq,
                mk,
                n0,
                row0,
                src_idx * mk,
                l_total,
                m_total,
            )
            p = p * mult
        o_new = o * corr[..., None] + jnp.einsum(
            "nhlm,nmhe->nhle", p, v_blk, preferred_element_type=jnp.float32
        )
        return o_new, m_new, l_new

    o = jnp.zeros((n, h, lq, e), dtype=jnp.float32)
    m = jnp.full((n, h, lq), -jnp.inf, dtype=jnp.float32)
    l = jnp.zeros((n, h, lq), dtype=jnp.float32)
    # shard_map tracks varying-axis types through scan: the carry becomes
    # seq-varying after one step, so the initial values must be marked
    # varying too.
    o, m, l = (lax.pcast(t, (axis_name,), to="varying") for t in (o, m, l))

    # Peel the first (local-block) step so the scan rotates BEFORE each
    # accumulation — axis_size-1 rotations total, none wasted on a block
    # that would be discarded.
    my_idx = lax.axis_index(axis_name)
    o, m, l = accumulate(
        o, m, l, k.astype(jnp.float32), v.astype(jnp.float32), my_idx
    )

    def body(carry, t):
        o, m, l, k_blk, v_blk = carry
        k_blk = _rotate(k_blk, axis_name, axis_size)
        v_blk = _rotate(v_blk, axis_name, axis_size)
        # After t forward rotations this device holds the block that
        # originated at ring position (my_idx - t) mod axis_size.
        src_idx = lax.rem(my_idx - t + axis_size, axis_size)
        o, m, l = accumulate(o, m, l, k_blk, v_blk, src_idx)
        return (o, m, l, k_blk, v_blk), None

    if axis_size > 1:
        (o, m, l, _, _), _ = lax.scan(
            body,
            (o, m, l, k.astype(jnp.float32), v.astype(jnp.float32)),
            jnp.arange(1, axis_size, dtype=jnp.int32),
        )
    out = o / l[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    seq_axis: str = AXIS_SEQ,
    batch_axis: Optional[str] = None,
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_seed: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Exact attention with Q/K/V ``(N, L, H, E)`` sequence-sharded over
    ``mesh[seq_axis]``. Global L (and K/V's M) must divide evenly by the
    axis size. ``batch_axis`` additionally shards the batch dim — pass
    ``'data'`` when calling inside a data-parallel jitted step so the
    shard_map composes with DP instead of gathering the batch.

    ``dropout_rate`` > 0 applies post-softmax probability dropout with
    semantics (and the exact mask) of the dense/fused paths — pass the same
    (1,) int32 ``dropout_seed`` the fused kernel takes."""
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    if dropout_seed is None:
        dropout_seed = jnp.zeros((1,), jnp.int32)
    dropout_seed = dropout_seed.astype(jnp.int32)
    spec = P(batch_axis, seq_axis, None, None)
    seed_spec = P()  # replicated
    body = partial(
        ring_attention_local,
        axis_name=seq_axis,
        scale=scale,
        dropout_rate=float(dropout_rate),
        batch_axis=batch_axis,
    )

    def wrapped(q, k, v, seed):
        return body(q, k, v, dropout_seed=seed)

    in_specs = (spec, spec, spec, seed_spec)
    fn = jax.shard_map(wrapped, mesh=mesh, in_specs=in_specs, out_specs=spec)
    return fn(q, k, v, dropout_seed)


def dense_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Single-device reference: plain softmax attention over (N, L, H, E).
    Shared implementation — see pallas_attention._einsum_attention."""
    from seist_tpu.ops.pallas_attention import _einsum_attention

    e = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(e)
    return _einsum_attention(q, k, v, scale)
