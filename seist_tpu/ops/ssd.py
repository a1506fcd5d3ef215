"""Chunked state-space scan (SSD, the Mamba-2 recurrence) in plain einsums.

Per head ``h`` with state ``S`` of shape (P, N):

    S_t = exp(dt_t * A_h) * S_{t-1} + dt_t * x_t B_t^T        y_t = S_t C_t

``B`` and ``C`` are shared by the ``H / G`` heads of a group. The sequence
is cut into chunks of ``chunk`` positions (Dao & Gu 2024, "Transformers are
SSMs", section 6): inside a chunk the output is one decay-masked
``(C B^T) x`` product, across chunks the state ``S`` is carried by a
``lax.scan`` over the chunks. JAX differentiates through this chunked form;
there is no hand-written backward and no kernel here (a later perf PR starts
from this one's trace).

Precision: the decay (``dt * A``, its cumulative sums and their
exponentials) and the carried state are float32 whatever the inputs are;
the matrix products take their operands in the inputs' dtype and accumulate
in float32. A length that is no multiple of ``chunk`` is padded with
``dt = 0`` positions, which leave the state as it is and are cut off again.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def ssd_chunked(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    chunk: int,
    initial_state: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """``x`` (B, L, H, P), ``dt`` (B, L, H) after softplus, ``a`` (H,)
    negative, ``b`` and ``c`` (B, L, G, N) with ``H % G == 0``. Returns
    ``y`` (B, L, H, P) in ``x``'s dtype and the final state (B, H, P, N) in
    float32. ``initial_state`` defaults to zeros."""
    bsz, length, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    if heads % groups:
        raise ValueError(f"{heads} heads do not divide into {groups} groups")
    per_group = heads // groups
    cdtype = x.dtype
    pad = (-length) % chunk
    if pad:
        x, dt, b, c = (
            jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
            for t in (x, dt, b, c)
        )
    nc = (length + pad) // chunk

    # (B, nc, G, R, Q, ...): heads as (group, head in group), so that a
    # group's B and C meet its heads without being repeated in memory, and
    # the chunk's positions innermost, where the products contract them.
    x = x.reshape(bsz, nc, chunk, groups, per_group, p).transpose(0, 1, 3, 4, 2, 5)
    dt = dt.astype(jnp.float32).reshape(
        bsz, nc, chunk, groups, per_group).transpose(0, 1, 3, 4, 2)
    a = a.astype(jnp.float32).reshape(groups, per_group, 1)
    b = b.reshape(bsz, nc, chunk, groups, n).transpose(0, 1, 3, 2, 4)
    c = c.reshape(bsz, nc, chunk, groups, n).transpose(0, 1, 3, 2, 4)

    da = dt * a  # (B, nc, G, R, Q), <= 0
    cum = jnp.cumsum(da, axis=-1)  # decay from the chunk's start to t, in log
    total = cum[..., -1]  # (B, nc, G, R): a whole chunk's decay, in log

    # Inside a chunk: y_i += sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j
    cb = jnp.einsum(
        "bcgin,bcgjn->bcgij", c, b, preferred_element_type=jnp.float32
    )
    seg = cum[..., :, None] - cum[..., None, :]  # (B, nc, G, R, Qi, Qj)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    scores = (cb[:, :, :, None] * decay * dt[..., None, :]).astype(cdtype)
    y = jnp.einsum(
        "bcgrij,bcgrjp->bcgrip", scores, x, preferred_element_type=jnp.float32
    )

    # What a chunk adds to the state by its end:
    # sum_j exp(total - cum_j) dt_j x_j B_j^T
    to_end = (jnp.exp(total[..., None] - cum) * dt).astype(cdtype)
    chunk_state = jnp.einsum(
        "bcgrj,bcgrjp,bcgjn->bcgrpn", to_end, x, b,
        preferred_element_type=jnp.float32,
    )

    # Across chunks: carry S; ``entering[c]`` is the state before chunk c.
    s0 = (
        jnp.zeros((bsz, groups, per_group, p, n), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32).reshape(
            bsz, groups, per_group, p, n)
    )

    def carry(s, inp):
        log_decay, add = inp
        return jnp.exp(log_decay)[..., None, None] * s + add, s

    final, entering = jax.lax.scan(
        carry, s0,
        (jnp.moveaxis(total, 1, 0), jnp.moveaxis(chunk_state, 1, 0)),
    )
    entering = jnp.moveaxis(entering, 0, 1)  # (B, nc, G, R, P, N)

    # What the entering state gives position i: exp(cum_i) C_i . S
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bcgin,bcgrpn->bcgrip", c, entering.astype(cdtype),
        preferred_element_type=jnp.float32,
    )
    y = y.transpose(0, 1, 4, 2, 3, 5)  # (B, nc, Q, G, R, P)
    y = y.reshape(bsz, nc * chunk, heads, p)[:, :length].astype(cdtype)
    return y, final.reshape(bsz, heads, p, n)


def ssd_recurrent(x, dt, a, b, c, initial_state=None):
    """The same map by the literal recurrence, one position at a time, in
    float32: what :func:`ssd_chunked` is tested against."""
    bsz, _, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    rep = heads // groups
    f32 = jnp.float32
    x, dt, a = x.astype(f32), dt.astype(f32), a.astype(f32)
    b = jnp.repeat(b.astype(f32), rep, axis=2)
    c = jnp.repeat(c.astype(f32), rep, axis=2)
    s0 = (jnp.zeros((bsz, heads, p, n), f32) if initial_state is None
          else initial_state.astype(f32))

    def step(s, inp):
        xt, dtt, bt, ct = inp  # (B,H,P) (B,H) (B,H,N) (B,H,N)
        s = jnp.exp(dtt * a)[..., None, None] * s + (
            dtt[..., None, None] * xt[..., :, None] * bt[..., None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, ct)

    final, ys = jax.lax.scan(
        step, s0, tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1), final
