"""Plain reference of the Seismogram Transformer (Li et al., IEEE TGRS 2024;
senli1073/SeisT ``models/seist.py``) with the detection + phase-picking
head: four stem blocks of three depthwise-separable paths, four stages of
local-aware aggregation, multi-scale mixed grouped convolutions and
multi-path transformer layers (attention over pooled keys beside a grouped
convolution), and an interpolate-and-convolve ladder back to the input
length. Input (N, L, 3) -> probabilities (N, L, 3).

Every lowering is the literal one: ``feature_group_count`` convolutions,
three separate stem paths, attention as two einsums and a softmax.
Departures from the paper's training graph: dropout and stochastic depth are
left out (this is the eval-mode function; the train mode, on batch
statistics, only estimates BatchNorm statistics for seeded weights).
The parameter tree carries the names of the program's checkpoint so that
the same weights can be served by both.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from . import ops


def make_divisible(v: int, divisor: int) -> int:
    new_v = max(divisor, int(v + divisor / 2) // divisor * divisor)
    if new_v < 0.9 * v:
        new_v += divisor
    return new_v


def _arch(config: Dict) -> Dict:
    a = dict(config["architecture"])
    a["in_channels"] = int(config.get("in_channels", 3))
    return a


def _msmc_dims(io_dim: int, group_size: int, n: int) -> List[int]:
    dims: List[int] = []
    for _ in range(n):
        dims.append(make_divisible((io_dim - sum(dims)) // (n - len(dims)), group_size))
    return dims


def _head_plan(a: Dict) -> Tuple[List[int], List[int]]:
    chans, kerns = [], []
    for c, k, s in zip(
        [a["in_channels"]] + list(a["stem_channels"]) + list(a["layer_channels"][:-1]),
        list(a["stem_kernel_sizes"]) + [max(a["msmc_kernel_sizes"])] * len(a["layer_channels"]),
        list(a["stem_strides"]) + list(a["stage_aggr_ratios"]),
    ):
        if s > 1:
            chans.insert(0, c)
            kerns.insert(0, k)
    return chans, kerns


def _blocks(a: Dict):
    """(stage, block, kind) in order; kind 'msmc' or 'mptl'."""
    for i, nb in enumerate(a["layer_blocks"]):
        for j in range(nb):
            yield i, j, ("mptl" if j >= nb - a["attn_blocks"][i] else "msmc")


def _attn_dims(a: Dict, i: int) -> Tuple[int, int]:
    lc, hd = a["layer_channels"][i], a["head_dims"][i]
    attn = make_divisible(int(lc * a["attn_ratio"]), hd) if a["attn_ratio"] > 0 else 0
    return attn, max(lc - attn, 0)


def shapes(config: Dict):
    a = _arch(config)
    params: Dict = {}
    stats: Dict = {}
    K, B = "kernel", "bias"

    def norm(path, c):
        p, s = ops.norm_shapes(c)
        ops.put(params, path, p)
        ops.put(stats, path, s)

    def lin(path, cin, cout, bias):
        d = {K: ((cin, cout), K)}
        if bias:
            d[B] = ((cout,), B)
        ops.put(params, path, d)

    def mlp(path, dim):
        hidden = int(dim * a["mlp_ratio"])
        lin(path + ("lin0",), dim, hidden, True)
        lin(path + ("lin1",), hidden, dim, True)

    def gconv(path, dim, groups, k):
        ops.put(params, path + ("conv",), {K: ((k, dim // groups, dim), K)})
        norm(path + ("norm0",), dim)
        lin(path + ("proj",), dim, dim, False)
        norm(path + ("norm1",), dim)
        mlp(path + ("mlp",), dim)

    stem_in = [a["in_channels"]] + list(a["stem_channels"][:-1])
    for i, (inc, outc, k) in enumerate(zip(stem_in, a["stem_channels"], a["stem_kernel_sizes"])):
        for dk in range(3):
            pth = (f"stem{i}", f"conv{dk}")
            lin(pth + ("in_proj",), inc, inc, False)
            ops.put(params, pth + ("dconv",), {K: ((k + 4 * dk, 1, inc), K)})
            lin(pth + ("pconv",), inc, outc, False)
            norm(pth + ("norm",), outc)
        lin((f"stem{i}", "out_proj"), 3 * outc, outc, False)
        norm((f"stem{i}", "norm"), outc)

    prev = a["stem_channels"][-1]
    for i, lc in enumerate(a["layer_channels"]):
        lin((f"stage{i}_aggr", "proj"), prev, lc, False)
        norm((f"stage{i}_aggr", "norm"), lc)
        prev = lc
    for i, j, kind in _blocks(a):
        lc, hd = a["layer_channels"][i], a["head_dims"][i]
        blk = (f"stage{i}_block{j}",)
        if kind == "msmc":
            dims = _msmc_dims(lc, hd, len(a["msmc_kernel_sizes"]))
            for m, (dim, k) in enumerate(zip(dims, a["msmc_kernel_sizes"])):
                lin(blk + (f"proj{m}",), lc, dim, False)
                norm(blk + (f"norm{m}",), dim)
                gconv(blk + (f"conv{m}",), dim, dim // hd, k)
            norm(blk + ("out_norm",), lc)
        else:
            attn, conv = _attn_dims(a, i)
            lin(blk + ("attn_proj",), lc, attn, False)
            norm(blk + ("norm0",), attn)
            at = blk + ("attention",)
            lin(at + ("q_proj",), attn, attn, True)
            if a["attn_aggr_ratios"][i] > 1:
                lin(at + ("aggr", "proj"), attn, attn, False)
                norm(at + ("aggr", "norm"), attn)
                norm(at + ("norm",), attn)
            lin(at + ("k_proj",), attn, attn, True)
            lin(at + ("v_proj",), attn, attn, True)
            lin(at + ("out_proj",), attn, attn, True)
            if conv > 0:
                lin(blk + ("conv_proj",), lc, conv, False)
                norm(blk + ("norm1",), conv)
                gconv(blk + ("gconv",), conv, conv // hd, 3)
            norm(blk + ("norm2",), lc)
            mlp(blk + ("mlp",), lc)

    chans, kerns = _head_plan(a)
    outs = chans[:-1] + [a["head_out_channels"] * 2]
    cin = a["layer_channels"][-1]
    for i, (outc, k) in enumerate(zip(outs, kerns)):
        ops.put(params, ("out_head", f"conv{i}"), {K: ((k, cin, outc), K), B: ((outc,), B)})
        norm(("out_head", f"norm{i}"), outc)
        cin = outc
    ops.put(params, ("out_head", "out_conv"),
            {K: ((7, cin, a["head_out_channels"]), K), B: ((a["head_out_channels"],), B)})
    return params, stats


def init(key, config: Dict) -> Dict:
    p, s = shapes(config)
    k1, k2 = jax.random.split(key)
    return {"params": ops.make_weights(k1, p), "batch_stats": ops.make_weights(k2, s)}


def forward(variables: Dict, x, config: Dict, train: bool = False, q=ops.identity):
    a = _arch(config)
    P = variables["params"]
    bn = ops.Norms(variables.get("batch_stats", {}), train)
    x_input = x

    def norm(h, path):
        return bn(h, ops.get(P, path), path)

    def lin(h, path):
        return ops.dense(h, ops.get(P, path), q)

    def mlp(h, path):
        return lin(ops.gelu(lin(h, path + ("lin0",))), path + ("lin1",))

    def aggregate(h, path, k):
        return norm(lin(ops.pool_ceil(h, k), path + ("proj",)), path + ("norm",))

    def gconv(h, path, groups, k):
        h1 = ops.conv1d(ops.auto_pad(h, k), ops.get(P, path + ("conv", "kernel")), q, groups=groups)
        h1 = lin(ops.gelu(norm(h1, path + ("norm0",))), path + ("proj",))
        h = h + h1
        return h + mlp(norm(h, path + ("norm1",)), path + ("mlp",))

    def attention(h, path, head_dim, ratio):
        n, length, c = h.shape
        heads = c // head_dim
        qq = lin(h, path + ("q_proj",)).reshape(n, length, heads, head_dim)
        if ratio > 1:
            h = norm(aggregate(h, path + ("aggr",), ratio), path + ("norm",))
        m = h.shape[1]
        kk = lin(h, path + ("k_proj",)).reshape(n, m, heads, head_dim)
        vv = lin(h, path + ("v_proj",)).reshape(n, m, heads, head_dim)
        scores = jnp.einsum("nlhe,nmhe->nhlm", q(qq), q(kk)) / math.sqrt(head_dim)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("nhlm,nmhe->nlhe", q(probs), q(vv)).reshape(n, length, c)
        return lin(out, path + ("out_proj",))

    # stem: three depthwise-separable paths per block
    for i, (k, s) in enumerate(zip(a["stem_kernel_sizes"], a["stem_strides"])):
        outs = []
        for dk in range(3):
            pth = (f"stem{i}", f"conv{dk}")
            h = lin(x, pth + ("in_proj",))
            h = ops.conv1d(ops.auto_pad(h, k + 4 * dk, s),
                           ops.get(P, pth + ("dconv", "kernel")), q,
                           stride=s, groups=h.shape[-1])
            h = lin(h, pth + ("pconv",))
            outs.append(ops.gelu(norm(h, pth + ("norm",))))
        x = lin(jnp.concatenate(outs, axis=-1), (f"stem{i}", "out_proj"))
        x = norm(x, (f"stem{i}", "norm"))

    for i, j, kind in _blocks(a):
        lc, hd = a["layer_channels"][i], a["head_dims"][i]
        if j == 0:
            x = aggregate(x, (f"stage{i}_aggr",), a["stage_aggr_ratios"][i])
        blk = (f"stage{i}_block{j}",)
        if kind == "msmc":
            dims = _msmc_dims(lc, hd, len(a["msmc_kernel_sizes"]))
            outs = []
            for m, (dim, k) in enumerate(zip(dims, a["msmc_kernel_sizes"])):
                xi = norm(lin(x, blk + (f"proj{m}",)), blk + (f"norm{m}",))
                outs.append(xi + gconv(xi, blk + (f"conv{m}",), dim // hd, k))
            x = norm(jnp.concatenate(outs, axis=-1), blk + ("out_norm",))
        else:
            attn, conv = _attn_dims(a, i)
            x1 = norm(lin(x, blk + ("attn_proj",)), blk + ("norm0",))
            x1 = x1 + attention(x1, blk + ("attention",), hd, a["attn_aggr_ratios"][i])
            paths = [x1]
            if conv > 0:
                x2 = norm(lin(x, blk + ("conv_proj",)), blk + ("norm1",))
                paths.append(x2 + gconv(x2, blk + ("gconv",), conv // hd, 3))
            x = norm(jnp.concatenate(paths, axis=-1), blk + ("norm2",))
            x = x + mlp(x, blk + ("mlp",))

    # head: interpolate + conv ladder back to the input length
    chans, kerns = _head_plan(a)
    depth = len(chans)
    sizes = [x_input.shape[1]] * depth
    factor = (x_input.shape[1] / x.shape[1]) ** (1 / depth)
    for i in range(depth - 2, -1, -1):
        sizes[i] = int(sizes[i + 1] / factor)
    for i, k in enumerate(kerns):
        x = ops.interpolate_linear(x, sizes[i])
        c = ops.get(P, ("out_head", f"conv{i}"))
        x = ops.conv1d(ops.auto_pad(x, k), c["kernel"], q) + c["bias"]
        x = ops.gelu(norm(x, ("out_head", f"norm{i}")))
    c = ops.get(P, ("out_head", "out_conv"))
    x = ops.conv1d(jnp.pad(x, ((0, 0), (3, 3), (0, 0))), c["kernel"], q) + c["bias"]
    out = jax.nn.sigmoid(x)
    return (out, bn.new) if train else out
