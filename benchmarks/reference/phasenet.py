"""Plain reference of PhaseNet (Zhu & Beroza 2019) as the SeisT repository
trains it: 1-D U-Net, stride-4 down and up x4, skip concatenation with the
transposed convolution's overhang cropped, softmax over three classes.
Input (N, L, 3) -> probabilities (N, L, 3). Dropout is left out: the
reference is the network's eval-mode function, and its train mode (batch
statistics) serves only to estimate BatchNorm statistics for seeded weights.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from . import ops


def _arch(config: Dict) -> Dict:
    a = config.get("architecture") or {}
    return {"k": a.get("kernel_size", 7), "s": a.get("stride", 4),
            "ch": list(a.get("conv_channels", (8, 16, 32, 64, 128)))}


def _plan(config: Dict):
    a = _arch(config)
    ch, depth = a["ch"], len(a["ch"])
    down_in = ch[:1] + ch[:-1]
    up_in = ch[::-1]
    up_out = ch[-2::-1] + [ch[0]]
    rev = list(range(depth))[::-1]
    return a, ch, depth, down_in, up_in, up_out, rev


def shapes(config: Dict):
    a, ch, depth, down_in, up_in, up_out, rev = _plan(config)
    k, cin = a["k"], int(config.get("in_channels", 3))
    params: Dict = {"conv_in": {"kernel": ((k, cin, ch[0]), "kernel"), "bias": ((ch[0],), "bias")}}
    stats: Dict = {}

    def norm(path, c):
        p, s = ops.norm_shapes(c)
        ops.put(params, path, p)
        ops.put(stats, path, s)

    norm(("bn_in",), ch[0])
    for i in range(depth):
        if i != 0:
            params.setdefault(f"down{i}", {})["conv0"] = {"kernel": ((k, down_in[i], down_in[i]), "kernel")}
            norm((f"down{i}", "bn0"), down_in[i])
        params.setdefault(f"down{i}", {})["conv1"] = {"kernel": ((k, down_in[i], ch[i]), "kernel")}
        norm((f"down{i}", "bn1"), ch[i])
    c_now = ch[-1]
    for j in range(depth):
        same, trans = rev[j] < depth - 1, rev[j] > 0
        if same:
            params.setdefault(f"up{j}", {})["conv0"] = {"kernel": ((k, c_now, up_in[j]), "kernel")}
            norm((f"up{j}", "bn0"), up_in[j])
            c_now = up_in[j]
        if trans:
            params.setdefault(f"up{j}", {})["convt"] = {"kernel": ((k, c_now, up_out[j]), "kernel")}
            norm((f"up{j}", "bn1"), up_out[j])
            c_now = up_out[j] + ch[depth - 2 - j]  # + the skip
    params["conv_out"] = {"kernel": ((1, c_now, 3), "kernel"), "bias": ((3,), "bias")}
    return params, stats


def init(key, config: Dict) -> Dict:
    p, s = shapes(config)
    k1, k2 = jax.random.split(key)
    return {"params": ops.make_weights(k1, p), "batch_stats": ops.make_weights(k2, s)}


def forward(variables: Dict, x, config: Dict, train: bool = False, q=ops.identity):
    a, ch, depth, down_in, up_in, up_out, rev = _plan(config)
    k, s = a["k"], a["s"]
    p = variables["params"]
    bn = ops.Norms(variables.get("batch_stats", {}), train)
    relu = jax.nn.relu

    x = ops.conv1d(ops.same_pad(x, k), p["conv_in"]["kernel"], q) + p["conv_in"]["bias"]
    x = relu(bn(x, p["bn_in"], ("bn_in",)))
    shortcuts = []
    for i in range(depth):
        d = p[f"down{i}"]
        if i != 0:
            x = ops.conv1d(ops.auto_pad(x, k, s), d["conv0"]["kernel"], q, stride=s)
            x = relu(bn(x, d["bn0"], (f"down{i}", "bn0")))
        x = ops.conv1d(ops.same_pad(x, k), d["conv1"]["kernel"], q)
        x = relu(bn(x, d["bn1"], (f"down{i}", "bn1")))
        if i < depth - 1:
            shortcuts.append(x)
    for j in range(depth):
        u = p[f"up{j}"]
        if rev[j] < depth - 1:
            x = ops.conv1d(ops.same_pad(x, k), u["conv0"]["kernel"], q)
            x = relu(bn(x, u["bn0"], (f"up{j}", "bn0")))
        if rev[j] > 0:
            x = ops.conv1d_transpose(x, u["convt"]["kernel"], q, s)
            x = relu(bn(x, u["bn1"], (f"up{j}", "bn1")))
            short = shortcuts[-(j + 1)]
            pds = (s - short.shape[1] % s) % s + k - s
            lp, rp = pds // 2, pds - pds // 2
            x = jnp.concatenate([short, x[:, lp: x.shape[1] - rp, :]], axis=-1)
    x = ops.conv1d(x, p["conv_out"]["kernel"], q) + p["conv_out"]["bias"]
    out = jax.nn.softmax(x, axis=-1)
    return (out, bn.new) if train else out
