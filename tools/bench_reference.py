"""Measure the torch reference's training throughput (host CPU).

The reference repo publishes no benchmark numbers and this
environment has no GPU, so the comparison baseline for bench.py is the
reference's own training step (forward + BCE loss + backward + Adam) timed on
this host's CPU. The reference code is *imported* from /root/reference at
runtime (never copied); its `timm` dependency is satisfied with a minimal
stub since only `timm.models.layers.DropPath` is used (reference
models/seist.py:7).

Writes tools/reference_baseline.json consumed by bench.py (per_model
entries keyed by model name; each stamped with its session's host/torch).

Usage: python tools/bench_reference.py \
    [--models seist_l_dpk,phasenet,...] [--batch 16] [--steps 4]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

REFERENCE = "/root/reference"


def _install_timm_stub() -> None:
    import torch.nn as nn

    class DropPath(nn.Module):
        """Stochastic depth (per-sample residual drop), the standard
        implementation every library ships.

        ``DropPath.inject`` (class attribute) mirrors seist_tpu's
        droppath_mask_injection (models/common.py): when set to
        ``{"uniforms": (max_calls, batch) tensor, "i": 0}``, each
        train-mode call consumes the next row as its uniform draws —
        identical rows in identical call order on both frameworks make
        the dropped residual paths identical (tools/train_dynamics.py
        dropout-on lane)."""

        inject = None  # class-level: one shared stream per forward

        def __init__(self, drop_prob: float = 0.0):
            super().__init__()
            self.drop_prob = float(drop_prob)

        def forward(self, x):
            if self.drop_prob == 0.0 or not self.training:
                return x
            keep = 1.0 - self.drop_prob
            shape = (x.shape[0],) + (1,) * (x.ndim - 1)
            if DropPath.inject is not None:
                inj = DropPath.inject
                u = inj["uniforms"][inj["i"]]
                inj["i"] += 1
                mask = (u < keep).to(x.dtype).view(shape)
            else:
                mask = x.new_empty(shape).bernoulli_(keep)
            return x * mask / keep

    timm = types.ModuleType("timm")
    models = types.ModuleType("timm.models")
    layers = types.ModuleType("timm.models.layers")
    layers.DropPath = DropPath
    models.layers = layers
    timm.models = models
    sys.modules["timm"] = timm
    sys.modules["timm.models"] = models
    sys.modules["timm.models.layers"] = layers


def _dpk_loss(torch, batch, in_samples):
    """BCE on probability outputs, dpk weights (ref config.py:138)."""
    y = torch.zeros(batch, 3, in_samples)
    y[:, 0, :] = 1.0
    y[:, 1, in_samples // 4] = 1.0
    y[:, 2, in_samples // 2] = 1.0
    w = torch.tensor([[0.5], [1.0], [1.0]])
    eps = 1e-6

    def loss_fn(out):
        loss = -(y * torch.log(out + eps) + (1 - y) * torch.log(1 - out + eps))
        return (loss * w).mean()

    return loss_fn


def _ce_loss(torch, batch, in_samples):
    """CE on softmax outputs (phasenet, ref config.py:68-71)."""
    y = torch.zeros(batch, 3, in_samples)
    y[:, 0, :] = 1.0
    eps = 1e-6
    return lambda out: -(y * torch.log(out + eps)).mean()


def _tuple_bce_loss(torch, out, batch, in_samples):
    """Per-output BCE mean (eqtransformer's (det, p, s) triple — surrogate
    with the same tensor structure/shapes as ref CombinationLoss)."""
    ys = [torch.zeros_like(o) for o in out]
    eps = 1e-6

    def loss_fn(out):
        total = 0.0
        for o, y in zip(out, ys):
            total = total + (
                -(y * torch.log(o + eps) + (1 - y) * torch.log(1 - o + eps))
            ).mean()
        return total / len(out)

    return loss_fn


def _measure(model_name: str, batch: int, steps: int, in_samples: int) -> dict:
    import torch

    from models import create_model  # reference models/_factory.py

    model = create_model(model_name, in_channels=3, in_samples=in_samples)
    model.train()
    opt = torch.optim.Adam(model.parameters(), lr=1e-3)
    x = torch.randn(batch, 3, in_samples)

    with torch.no_grad():  # structure probe only — keep no autograd graph
        out0 = model(x)
    if isinstance(out0, (tuple, list)):
        loss_fn = _tuple_bce_loss(torch, out0, batch, in_samples)
    elif model_name == "phasenet":
        loss_fn = _ce_loss(torch, batch, in_samples)
    else:
        loss_fn = _dpk_loss(torch, batch, in_samples)
    del out0

    def step():
        opt.zero_grad()
        out = model(x)
        loss = loss_fn(out)
        loss.backward()
        opt.step()

    step()  # warmup
    t0 = time.perf_counter()
    for _ in range(steps):
        step()
    dt = time.perf_counter() - t0
    return {
        "waveforms_per_sec": round(batch * steps / dt, 2),
        "batch": batch,
        "steps": steps,
        "in_samples": in_samples,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="seist_l_dpk",
                    help="comma-separated reference model names")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--in-samples", type=int, default=8192)
    args = ap.parse_args()

    import torch

    _install_timm_stub()
    sys.path.insert(0, REFERENCE)

    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "reference_baseline.json")
    payload = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            payload = json.load(f)
    # Overwritten each run; per-entry stamps below are the durable record
    # (a later session on different hardware must not masquerade as the
    # one that measured the other entries).
    hardware = f"host CPU ({os.cpu_count()} cores), torch {torch.__version__}"
    session = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    payload["hardware"] = hardware
    payload["note"] = (
        "torch reference train step timed on host CPU (no GPU in env; "
        "reference publishes no numbers); compare per_model entries only "
        "within one hardware/session stamp"
    )
    per_model = payload.setdefault("per_model", {})
    for name in args.models.split(","):
        entry = _measure(name, args.batch, args.steps, args.in_samples)
        entry["hardware"] = hardware
        entry["session"] = session
        per_model[name] = entry
        print(name, json.dumps(entry), flush=True)
        with open(out_path, "w") as f:  # persist incrementally
            json.dump(payload, f, indent=2)


if __name__ == "__main__":
    main()
