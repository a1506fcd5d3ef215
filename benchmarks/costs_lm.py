"""Operations and bytes of a token model's step, from shapes — the
benchmark's own count for ``mfu.train`` and for the two new kernels' shares
of their roofline (``readers/region_roofline.py``).

``sequence_flops`` is what ``flops_per_waveform`` of a token configuration
stores (a "waveform" of such a cell is one ``in_samples``-token sequence):
``flops.count_jaxpr`` (it multiplies a scan by its length) over the plain
reference's forward with the routed experts taken out, less the masked half
of the attention scores the reference materialises, plus the routed experts
from shapes at the EXPECTED load of ``top_k * held / n_experts`` experts a
token — the reference's masked loop runs every token through every expert
held and would inflate the count eightfold. ``train`` is three times
``forward``: a product's backward pass is two products of its size, and
rematerialisation does not count.

``ssd_cost`` and ``moe_experts_cost`` are the least a step needs in the
regions ``ssm_scan`` and ``moe_experts``, whatever implements them: forward
+ backward + the recomputed forward (the blocks are rematerialised; that IS
work the region does each step). The scan's is the recurrence's own count
(state update and read-out, 4 H P N a token and layer) — the chunked form's
extra products are the implementation's, not the algorithm's — and the
bytes of its operands and result once per pass; the experts' is two
products per row over the rows actually routed to experts held (the
counter ``moe_slots_local``), and the held weights read once per pass.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import flops
from checks.eval_reference import load_reference

#: forward, recomputed forward, backward (two products per product)
PASSES = 4


def masked_half_of_scores(a: Dict[str, Any], length: int) -> int:
    """Operations the reference spends on positions a causal model never
    reads: of its L x L scores and probabilities, those above the diagonal."""
    per_pair = 2 * 2 * a["num_attention_heads"] * a["head_dim"]  # qk and pv
    return a["pattern"].count("*") * per_pair * (length * (length - 1) // 2)


def routed_flops(a: Dict[str, Any], rows: float) -> float:
    """Two products per routed row: up to ``moe_intermediate_size``, down."""
    return rows * 2 * 2 * a["hidden_size"] * a["moe_intermediate_size"]


def expected_local_rows(a: Dict[str, Any], tokens: int) -> float:
    """Token-slots on experts held, over all expert layers."""
    return (a["pattern"].count("E") * tokens * a["num_experts_per_tok"]
            * a["experts_held"][1] / a["n_routed_experts"])


def sequence_flops(config: Dict[str, Any]) -> Dict[str, int]:
    import jax
    import jax.numpy as jnp

    reference = load_reference(config)
    a = config["architecture"]
    length = int(config["in_samples"])
    bare = {**config, "architecture": {
        **a, "experts_held": [a["experts_held"][0], 0]}}
    variables = jax.eval_shape(
        lambda: reference.init(jax.random.PRNGKey(0), bare))
    ids = jax.ShapeDtypeStruct((1, length), jnp.int32)
    counted = flops.count_jaxpr(jax.make_jaxpr(
        lambda v, i: reference.forward(v, i, bare))(variables, ids))
    forward = (counted - masked_half_of_scores(a, length)
               + routed_flops(a, expected_local_rows(a, length)))
    return {"forward": int(forward), "train": int(3 * forward)}


def _tokens_per_step(config: Dict[str, Any]) -> int:
    return int(config["batch"]) * int(config["in_samples"])


def ssd_cost(config: Dict[str, Any], record: Dict[str, Any]) -> Tuple[float, float]:
    a = config["architecture"]
    tokens, layers = _tokens_per_step(config), a["pattern"].count("M")
    h, p = a["mamba_num_heads"], a["mamba_head_dim"]
    g, n = a["n_groups"], a["ssm_state_size"]
    ops = PASSES * layers * tokens * 4 * h * p * n
    # a pass reads x, B, C (compute dtype, 2 bytes) and dt (float32) and
    # writes y; the backward pass reads those and dy and writes four
    # gradients: twice a forward pass's traffic
    per_token = 2 * (h * p + 2 * g * n + h * p) + 4 * h
    return float(ops), float(PASSES * layers * tokens * per_token)


def moe_experts_cost(config: Dict[str, Any], record: Dict[str, Any]
                     ) -> Tuple[float, float]:
    a = config["architecture"]
    rows = local_rows_per_step(record)
    if rows is None:
        rows = expected_local_rows(a, _tokens_per_step(config))
    layers = a["pattern"].count("E")
    weights = (layers * a["experts_held"][1] * 2
               * a["hidden_size"] * a["moe_intermediate_size"])
    # rows in and out at 2 bytes an element, the held weights once a pass
    nbytes = PASSES * (2 * weights + rows * 2 * 2 * a["hidden_size"])
    return float(PASSES * routed_flops(a, rows)), float(nbytes)


def local_rows_per_step(record: Dict[str, Any]):
    """``moe_slots_local`` counted over the window, per optimizer step."""
    opened = record.get("counters_open") or {}
    closed = record.get("counters_close") or {}
    steps = record.get("attempted")
    if "moe_slots_local" not in closed or not steps:
        return None
    return (closed["moe_slots_local"] - opened.get("moe_slots_local", 0.0)) / steps
