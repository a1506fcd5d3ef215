"""Operations and bytes from shapes — the benchmark's own count, so that a
lowering which wastes work cannot raise its own utilisation.

``dot_flops`` / ``conv_flops`` count one multiply-add as two operations on
the equation's shapes (the counting of ``seist_tpu/obs/attribution.py``,
copied, not imported); ``count_jaxpr`` sums them over a jaxpr, scans
multiplied by their length. ``reference_flops_per_waveform`` applies it to
the configuration's plain reference (``reference/<config>.py``): the
literal architecture, no composed, fused or block-diagonal-dense lowering.
``attention_cost`` is the pooled-attention kernel's count.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple


def dot_flops(eqn) -> int:
    (lhs_c, rhs_c), (lhs_b, rhs_b) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = math.prod(lhs[i] for i in lhs_b) if lhs_b else 1
    k = math.prod(lhs[i] for i in lhs_c) if lhs_c else 1
    m = math.prod(d for i, d in enumerate(lhs) if i not in lhs_c and i not in lhs_b)
    n = math.prod(d for i, d in enumerate(rhs) if i not in rhs_c and i not in rhs_b)
    return 2 * batch * m * n * k


def conv_flops(eqn) -> int:
    out = eqn.outvars[0].aval.shape
    kernel = eqn.invars[1].aval.shape
    dnums = eqn.params["dimension_numbers"]
    # MACs = (prod(out) / out_channels) * prod(kernel): the kernel's
    # in-channel extent is already in_channels / groups.
    out_ch = kernel[dnums.rhs_spec[0]]
    batch_groups = eqn.params.get("batch_group_count", 1) or 1
    return 2 * (math.prod(out) // max(out_ch, 1)) * math.prod(kernel) // batch_groups


def count_jaxpr(jaxpr) -> int:
    inner = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    total = 0
    for eqn in inner.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += dot_flops(eqn)
        elif name == "conv_general_dilated":
            total += conv_flops(eqn)
        elif name == "scan":
            total += int(eqn.params.get("length", 1)) * count_jaxpr(eqn.params["jaxpr"])
        elif name == "cond":
            total += max(count_jaxpr(b) for b in eqn.params["branches"])
        else:
            for v in eqn.params.values():
                if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                    total += count_jaxpr(v)
    return total


def reference_flops_per_waveform(reference: Any, config: Dict[str, Any]) -> Dict[str, int]:
    """Forward, and forward + backward (gradient with respect to the
    parameters), per waveform, on the plain reference at batch 1."""
    import jax
    import jax.numpy as jnp

    n = int(config["in_samples"])
    variables = jax.eval_shape(lambda: reference.init(jax.random.PRNGKey(0), config))
    x = jax.ShapeDtypeStruct((1, n, int(config["in_channels"])), jnp.float32)

    def fwd(v, x):
        return reference.forward(v, x, config, train=False)

    def loss(params, stats, x):
        out, _ = reference.forward(
            {"params": params, "batch_stats": stats}, x, config, train=True
        )
        return jnp.sum(out)

    forward = count_jaxpr(jax.make_jaxpr(fwd)(variables, x))
    train = count_jaxpr(jax.make_jaxpr(jax.grad(loss))(
        variables["params"], variables["batch_stats"], x
    ))
    return {"forward": int(forward), "train": int(train)}


def attention_cost(*, batch: int, L: int, M: int, H: int, E: int,
                   dtype_bytes: int, backward: bool) -> Tuple[float, float]:
    """(operations, bytes) of one pooled-attention kernel pass over a batch:
    L queries against M pooled keys, H heads of E channels.

    Forward: q.k^T and p.v, 2 x 2 x L x M x E per head; reads q, k, v and
    writes the output. Backward recomputes the scores and makes dv, dp, dq,
    dk: five such products; reads q, k, v and the output's gradient, writes
    dq, dk, dv."""
    product = 2.0 * batch * H * L * M * E
    q_bytes = batch * L * H * E * dtype_bytes
    kv_bytes = batch * M * H * E * dtype_bytes
    if backward:
        return 5.0 * product, 3.0 * q_bytes + 4.0 * kv_bytes
    return 2.0 * product, 2.0 * q_bytes + 2.0 * kv_bytes
