"""ICI collective payload of the dp=N train step at a given config.

Compiles (does NOT run) the full jitted train step for --model at --batch
over an N-device virtual CPU mesh and prints the per-step collective
payload read off the optimized HLO (seist_tpu.parallel.collectives).
Evidence for the multi-chip scaling argument: the DP payload is
batch-independent (gradient all-reduce = param bytes + BN batch-stats +
loss scalars), so a CPU compile at the reference batch documents exactly
what would ride the ICI links on a real v4-8/v5e-8 slice.

    python tools/collective_report.py [--model seist_l_dpk] [--batch 512]
        [--in-samples 8192] [--devices 8]

Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def attribute_collectives(ops, param_shapes, batch: int, devices: int) -> dict:
    """Bucket per-op collective payloads.

    Gradient reductions are all-reduces of param-shaped tensors inside
    the backward pass (op_name carries XLA's "transpose(jvp(...))"
    marker). Param-shaped all-reduces WITHOUT that marker land in
    ``unattributed`` (XLA's combiner can drop/merge metadata — silently
    misfiling them under bn_stat would claim ~0 gradient traffic);
    ``warn_unattributed`` is True when that bucket is nonzero while zero
    gradient ops were found, i.e. the unattributed bytes ARE the
    gradients. Batch-leading-dim collectives are activation traffic.
    """
    param_shapes = {tuple(s) for s in param_shapes}
    grad_bytes = grad_ops = act_bytes = act_ops = other_bytes = 0
    unattr_bytes = unattr_ops = 0
    per_shard_batch = batch // devices
    for op in ops:
        dims = op["shape_dims"]
        is_param_shaped_ar = op["kind"] == "all-reduce" and any(
            tuple(d) in param_shapes for d in dims
        )
        if is_param_shaped_ar and "transpose(jvp" in op["op_name"]:
            grad_bytes += op["bytes"]
            grad_ops += 1
        elif is_param_shaped_ar:
            # Checked BEFORE the batch-leading-dim heuristic so a param
            # with a batch-sized leading dim can't shadow it.
            unattr_bytes += op["bytes"]
            unattr_ops += 1
            other_bytes += op["bytes"]
        elif any(
            d and d[0] in (batch, per_shard_batch) and len(d) >= 2
            for d in dims
        ):
            act_bytes += op["bytes"]
            act_ops += 1
        else:
            other_bytes += op["bytes"]
    return {
        "grad_bytes": grad_bytes,
        "grad_ops": grad_ops,
        "act_bytes": act_bytes,
        "act_ops": act_ops,
        "other_bytes": other_bytes,
        "unattr_bytes": unattr_bytes,
        "unattr_ops": unattr_ops,
        "warn_unattributed": bool(grad_ops == 0 and unattr_bytes),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="seist_l_dpk")
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--in-samples", type=int, default=8192)
    ap.add_argument("--devices", type=int, default=8)
    args = ap.parse_args()

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np

    import seist_tpu
    from seist_tpu import taskspec
    from seist_tpu.models import api
    from seist_tpu.parallel import (
        collective_stats,
        make_mesh,
    )
    from seist_tpu.train import (
        build_optimizer,
        create_train_state,
        jit_step,
        make_train_step,
    )

    seist_tpu.load_all()
    mesh = make_mesh(data=args.devices)
    model = api.create_model(args.model, in_samples=args.in_samples)
    variables = api.init_variables(
        model, in_samples=args.in_samples, batch_size=2
    )
    state = create_train_state(
        model, variables, build_optimizer("adam", 1e-3)
    )
    n_params = sum(
        x.size for x in jax.tree.leaves(state.params)
    )

    spec = taskspec.get_task_spec(args.model)
    loss_fn = taskspec.make_loss(args.model)
    step = jit_step(make_train_step(spec, loss_fn), mesh=mesh)

    # Abstract lowering: ShapeDtypeStructs — no batch-sized buffers exist.
    x_s = jax.ShapeDtypeStruct(
        (args.batch, args.in_samples, len(spec.inputs[0])
         if isinstance(spec.inputs[0], (list, tuple)) else 3),
        jnp.float32,
    )
    # Label struct mirrors the train batch the worker builds.
    y_shape = jax.eval_shape(
        lambda v, x: model.apply(v, x, train=False), variables,
        jax.ShapeDtypeStruct((args.batch, args.in_samples, 3), jnp.float32),
    )
    y_s = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), y_shape
    )
    rng_s = jax.ShapeDtypeStruct((2,), jnp.uint32)
    # state is tiny (<1M params) — lower with the concrete pytree; only the
    # batch-sized inputs need to stay abstract.

    t0 = time.time()
    compiled = step.lower(state, x_s, y_s, rng_s).compile()
    from seist_tpu.parallel.collectives import collective_ops

    hlo = compiled.as_text()
    stats = collective_stats(hlo)
    ops = collective_ops(hlo)
    total = sum(s["bytes"] for s in stats.values())
    n = args.devices

    # Attribute the bytes (make it self-evident which ops
    # carry the gradient bytes). Gradient reductions are all-reduces of
    # param-shaped tensors INSIDE the backward pass (op_name metadata
    # carries XLA's "transpose(jvp(...))" marker); BN cross-replica
    # batch-stat sums are also (C,)-shaped all-reduces — same shapes as
    # BN scale/bias params — but sit in the forward, so the op_name test
    # keeps them out of the gradient bucket. Collectives with a
    # batch-sized leading dim are activation traffic and scale WITH
    # batch; the rest is BN batch-stats + loss scalars.
    param_shapes = {
        tuple(np.shape(x)) for x in jax.tree.leaves(state.params)
    }
    buckets = attribute_collectives(ops, param_shapes, args.batch, n)
    grad_bytes, grad_ops = buckets["grad_bytes"], buckets["grad_ops"]
    act_bytes, act_ops = buckets["act_bytes"], buckets["act_ops"]
    other_bytes = buckets["other_bytes"]
    unattr_bytes, unattr_ops = buckets["unattr_bytes"], buckets["unattr_ops"]
    if buckets["warn_unattributed"]:
        print(
            "WARNING: no all-reduce carries the transpose(jvp) gradient "
            f"marker, but {unattr_ops} param-shaped all-reduce op(s) "
            f"({unattr_bytes / 1e6:.3f} MB) exist — XLA likely dropped "
            "op_name metadata when combining; treat unattributed_allreduce "
            "as the gradient bucket.",
            file=sys.stderr,
        )
    print(
        json.dumps(
            {
                "metric": "dp_train_step_collective_payload",
                "value": round(total / 1e6, 3),
                "unit": "MB/step payload",
                "model": args.model,
                "batch": args.batch,
                "in_samples": args.in_samples,
                "devices": n,
                "per_kind": stats,
                "param_bytes_mb": round(n_params * 4 / 1e6, 3),
                "gradient_allreduce": {
                    "ops": grad_ops,
                    "mb": round(grad_bytes / 1e6, 3),
                    "note": (
                        "backward-pass (transpose(jvp)) all-reduce ops "
                        "with param-shaped tuple elements == the fp32 "
                        "gradient bytes; batch-independent"
                    ),
                },
                "activation_collectives": {
                    "ops": act_ops,
                    "mb": round(act_bytes / 1e6, 3),
                    "note": (
                        "batch-leading-dim buffers (backward-pass "
                        "activation gathers); scales WITH batch"
                    ),
                },
                "bn_stat_and_scalar_collectives_mb": round(
                    other_bytes / 1e6, 3
                ),
                "unattributed_allreduce": {
                    "ops": unattr_ops,
                    "mb": round(unattr_bytes / 1e6, 3),
                    "note": (
                        "param-shaped all-reduces WITHOUT the "
                        "transpose(jvp) marker (also included in the "
                        "bn_stat bucket); nonzero while gradient ops==0 "
                        "means XLA dropped combiner metadata and these "
                        "ARE the gradient bytes"
                    ),
                },
                "ring_allreduce_link_traffic_mb": round(
                    total * 2 * (n - 1) / n / 1e6, 3
                ),
                "compile_s": round(time.time() - t0, 1),
                "note": (
                    "payload bytes from optimized HLO (static counts; DP "
                    "step has no loop-carried collectives). Link traffic "
                    "per chip for ring all-reduce = 2(N-1)/N x payload."
                ),
            }
        )
    )


if __name__ == "__main__":
    main()
