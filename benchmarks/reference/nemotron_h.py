"""Plain reference of NemotronH (``model_type: nemotron_h``): float32
``jax.numpy``, literal lowerings, nothing of the program.

Block ``i``: ``x <- x + Mixer_i(RMSNorm(x))`` (eps ``norm_eps``), the mixer by
the pattern's character; then a final RMSNorm and the untied head. No linear
map has a bias, the Mamba convolution has one.

* ``M`` Mamba-2, as the literal recurrence, one position at a time:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``
  per head, no chunks. (The scan over time is nested — an outer scan over
  segments, each rematerialised — so that its gradient fits the chip; that
  changes what is stored, not what is computed.)
* ``*`` causal grouped-query attention with materialised scores, one block
  of queries at a time; no rotary embedding (``nemotron_h`` applies none).
* ``E`` sigmoid router in float32 over all ``n_routed_experts``, the
  ``num_experts_per_tok`` largest of ``s + b`` (``b`` the correction bias,
  zero and no parameter), weights the chosen ``s`` over their sum times
  ``routed_scaling_factor``; the experts as a loop over the experts HELD
  (``experts_held``: first id, count) with a mask, each
  ``W_down relu(W_up x)^2``; what the absent experts would add is left out.
  The shared expert, the same form, is added for every token.

Departures from the published model, stated: the chip's share of a
deployment (experts held, vocabulary rows, depth) comes in through
``config["architecture"]``; ``b`` stays zero (config.json gives no balancing
rule); the loss is the mean next-token cross-entropy over the vocabulary
held, no auxiliary term.

``init(key, config)`` gives ``{"params": ...}`` under the program's
parameter names; ``forward(variables, ids, config)`` the logits (B, L, V);
``loss(variables, ids, config)`` the mean of ``-log p(ids[t+1] | ids[..t])``
over ``t < L - 1`` and the batch; ``q`` (the control's hook) rounds the
operands of every matrix product the configuration computes in its compute
dtype, and is the identity otherwise. Rows of the batch are computed one after
the other (``lax.map``) and every block is rematerialised, so that the whole
fits beside nothing else on a chip at 2 x 8192. Callers set
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def arch(config):
    return config["architecture"]


# ---------------------------------------------------------------------- init
def init(key, config):
    a = arch(config)
    d, n_layers = a["hidden_size"], len(a["pattern"])
    std, out_std = 0.02, 0.02 / math.sqrt(n_layers)
    heads, hd = a["mamba_num_heads"], a["mamba_head_dim"]
    d_inner = heads * hd
    d_conv = d_inner + 2 * a["n_groups"] * a["ssm_state_size"]
    k_conv = a["conv_kernel"]
    count = a["experts_held"][1]
    keys = iter(jax.random.split(key, 16 * (n_layers + 1)))

    def normal(shape, s):
        return s * jax.random.normal(next(keys), shape, F32)

    params = {
        "embed": normal((a["vocab_size"], d), std),
        "final_norm_scale": jnp.ones((d,), F32),
        "lm_head": normal((d, a["vocab_size"]), std),
    }
    for i, kind in enumerate(a["pattern"]):
        if kind == "M":
            u = jax.random.uniform(next(keys), (heads,), F32)
            dt = jnp.exp(u * (math.log(a["time_step_max"]) - math.log(a["time_step_min"]))
                         + math.log(a["time_step_min"]))
            dt = jnp.maximum(dt, a["time_step_floor"])
            mixer = {
                "in_proj": normal((d, d_inner + d_conv + heads), std),
                "conv_kernel": normal((k_conv, d_conv), k_conv ** -0.5),
                "conv_bias": normal((d_conv,), std),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(next(keys), (heads,), F32, 1.0, 16.0)),
                "D": jnp.ones((heads,), F32),
                "gate_norm_scale": jnp.ones((d_inner,), F32),
                "out_proj": normal((d_inner, d), out_std),
            }
        elif kind == "*":
            hq, hkv, e = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
            mixer = {
                "q_proj": normal((d, hq * e), std),
                "k_proj": normal((d, hkv * e), std),
                "v_proj": normal((d, hkv * e), std),
                "o_proj": normal((hq * e, d), out_std),
            }
        elif kind == "E":
            f, fs = a["moe_intermediate_size"], a["moe_shared_expert_intermediate_size"]
            mixer = {
                "router": normal((d, a["n_routed_experts"]), std),
                "experts_up": normal((count, d, f), std),
                "experts_down": normal((count, f, d), out_std),
                "shared_up": normal((d, fs), std),
                "shared_down": normal((fs, d), out_std),
            }
        else:
            raise ValueError(f"unknown block kind '{kind}'")
        params[f"block_{i}"] = {"norm_scale": jnp.ones((d,), F32), "mixer": mixer}
    return {"params": params}


# ------------------------------------------------------------------- layers
def exact(a):
    return a


def mm(a, b, q):
    """A matrix product whose operands go through ``q`` first: the identity
    everywhere but in the control (``benchmarks/tools/control_lm.py``),
    which rounds them to the precision below the configuration's."""
    return q(a) @ q(b)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _segment(length, most=128):
    return max(s for s in range(1, most + 1) if length % s == 0)


def ssm_recurrence(x, dt, a_neg, b, c, q=exact):
    """x (L, H, P), dt (L, H), a_neg (H,), b and c (L, H, N) -> y (L, H, P):
    ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t``."""
    length, heads, p = x.shape
    n = b.shape[-1]
    seg = _segment(length)

    def one(s, inp):
        xt, dtt, bt, ct = inp
        s = jnp.exp(dtt * a_neg)[:, None, None] * s + jnp.einsum(
            "hp,hn->hpn", q(dtt[:, None] * xt), q(bt))
        return s, jnp.einsum("hpn,hn->hp", q(s), q(ct))

    @jax.checkpoint
    def segment(s, inp):
        return jax.lax.scan(one, s, inp)

    cut = lambda t: t.reshape((length // seg, seg) + t.shape[1:])  # noqa: E731
    _, ys = jax.lax.scan(
        segment, jnp.zeros((heads, p, n), F32), (cut(x), cut(dt), cut(b), cut(c)))
    return ys.reshape(length, heads, p)


def mamba_mixer(m, x, a, q=exact):
    """One row: x (L, D)."""
    heads, hd = a["mamba_num_heads"], a["mamba_head_dim"]
    groups, n = a["n_groups"], a["ssm_state_size"]
    d_inner, d_bc = heads * hd, groups * n
    k_conv = a["conv_kernel"]
    length = x.shape[0]
    zxbcdt = mm(x, m["in_proj"], q)
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:2 * d_inner + 2 * d_bc]
    dt = zxbcdt[:, 2 * d_inner + 2 * d_bc:]
    padded = jnp.concatenate([jnp.zeros((k_conv - 1, xbc.shape[1]), F32), xbc])
    conv = m["conv_bias"] + sum(
        padded[k:k + length] * m["conv_kernel"][k] for k in range(k_conv))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d_inner].reshape(length, heads, hd)
    b = xbc[:, d_inner:d_inner + d_bc].reshape(length, groups, n)
    c = xbc[:, d_inner + d_bc:].reshape(length, groups, n)
    rep = heads // groups  # the heads of a group share its B and C
    dt = jax.nn.softplus(dt + m["dt_bias"])
    y = ssm_recurrence(
        xs, dt, -jnp.exp(m["A_log"]),
        jnp.repeat(b, rep, axis=1), jnp.repeat(c, rep, axis=1), q)
    y = y + m["D"][:, None] * xs
    y = y.reshape(length, d_inner) * jax.nn.silu(z)
    yg = y.reshape(length, groups, d_inner // groups)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + a["norm_eps"])
    return mm(yg.reshape(length, d_inner) * m["gate_norm_scale"], m["out_proj"], q)


def attention_mixer(m, x, a, q=exact, q_block=1024):
    hq, hkv, e = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    length = x.shape[0]
    qs = mm(x, m["q_proj"], q).reshape(length, hq, e)
    k = jnp.repeat(mm(x, m["k_proj"], q).reshape(length, hkv, e), hq // hkv, axis=1)
    v = jnp.repeat(mm(x, m["v_proj"], q).reshape(length, hkv, e), hq // hkv, axis=1)
    qb = _segment(length, q_block)
    pos = jnp.arange(length)

    @jax.checkpoint
    def block(args):
        qi, start = args  # (qb, H, E), first position of the block
        s = jnp.einsum("qhe,khe->hqk", q(qi), q(k)) * (e ** -0.5)
        seen = pos[None, :] <= (start + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khe->qhe", q(p), q(v))

    o = jax.lax.map(
        block, (qs.reshape(length // qb, qb, hq, e), jnp.arange(0, length, qb)))
    return mm(o.reshape(length, hq * e), m["o_proj"], q)


def relu2_mlp(x, w_up, w_down, q=exact):
    return mm(jnp.square(jax.nn.relu(mm(x, w_up, q))), w_down, q)


def route(m, x, a):
    """ids (T, k) and weights (T, k) over ALL experts."""
    s = jax.nn.sigmoid(x @ m["router"])
    bias = jnp.zeros((a["n_routed_experts"],), F32)  # e_score_correction_bias
    _, ids = jax.lax.top_k(s + bias, a["num_experts_per_tok"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    return ids, w / jnp.sum(w, axis=-1, keepdims=True) * a["routed_scaling_factor"]


def routed_part(m, x, a, ids, weights, q=exact):
    """What the experts held add: a loop over them, each over every token,
    masked by whether the token chose it."""
    first, count = a["experts_held"]
    out = jnp.zeros_like(x)
    for j in range(count):
        gate = jnp.sum(jnp.where(ids == first + j, weights, 0.0), axis=-1)
        out = out + gate[:, None] * relu2_mlp(
            x, m["experts_up"][j], m["experts_down"][j], q)
    return out


def moe_mixer(m, x, a, q=exact):
    ids, weights = route(m, x, a)  # float32 in the configuration: never q
    return routed_part(m, x, a, ids, weights, q) + relu2_mlp(
        x, m["shared_up"], m["shared_down"], q)


MIXERS = {"M": mamba_mixer, "*": attention_mixer, "E": moe_mixer}


def hidden_states(params, ids_row, config, q=exact):
    """One row of ids (L,) -> the last block's output (L, D)."""
    a = arch(config)
    x = params["embed"][ids_row]
    for i, kind in enumerate(a["pattern"]):
        blk = params[f"block_{i}"]

        @jax.checkpoint
        def block(x, blk=blk, kind=kind):
            return x + MIXERS[kind](
                blk["mixer"], rms_norm(x, blk["norm_scale"], a["norm_eps"]), a, q)

        x = block(x)
    return x


def row_logits(params, ids_row, config, q=exact):
    a = arch(config)
    x = hidden_states(params, ids_row, config, q)
    return mm(rms_norm(x, params["final_norm_scale"], a["norm_eps"]),
              params["lm_head"], q)


def forward(variables, ids, config, q=exact):
    """ids (B, L) -> logits (B, L, V), float32."""
    params = jax.tree.map(lambda p: p.astype(F32), variables["params"])
    return jax.lax.map(lambda row: row_logits(params, row, config, q), ids)


def row_loss_sum(params, ids_row, config, q=exact):
    """Sum over t < L - 1 of -log p(ids[t+1] | ids[..t]) of one row."""
    logits = jax.checkpoint(lambda p, r: row_logits(p, r, config, q))(params, ids_row)
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, ids_row[1:, None], axis=-1))


def loss(variables, ids, config, q=exact):
    params = jax.tree.map(lambda p: p.astype(F32), variables["params"])
    sums = jax.lax.map(lambda row: row_loss_sum(params, row, config, q), ids)
    return jnp.sum(sums) / (ids.shape[0] * (ids.shape[1] - 1))
