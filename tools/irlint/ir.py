"""IR analysis primitives: dtype-aware matmul FLOP accounting, host
transfer detection, donation-aliasing and sharding extraction from
lowered StableHLO.

FLOP formulas are obs/attribution.py's exact ``dot_general`` /
``conv_general_dilated`` accounting (imported, not duplicated) — the
same numbers the BENCH ``step_breakdown`` reports, so an irlint coverage
fraction and a bench MFU decomposition agree about what a matmul costs.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from seist_tpu.obs.attribution import (
    conv_flops as _conv_flops,
    dot_flops as _dot_flops,
    inner_jaxpr as _inner,
    sub_jaxprs as _sub_jaxprs,
)

#: Primitives that move data across the device<->host boundary inside a
#: program. Matched by exact name OR by the ``callback`` substring so a
#: jax version rename (pure_callback -> ...) fails loud, not silent.
HOST_TRANSFER_PRIMS = frozenset(
    (
        "pure_callback",
        "io_callback",
        "debug_callback",
        "host_callback_call",
        "outside_call",
        "infeed",
        "outfeed",
    )
)


def _is_host_transfer(prim_name: str) -> bool:
    return prim_name in HOST_TRANSFER_PRIMS or "callback" in prim_name


def _shape_str(v) -> str:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None:
        return "?"
    return f"{dtype}[{','.join(str(d) for d in shape)}]"


# ------------------------------------------------------------- jaxpr walks
def matmul_dtype_table(closed_jaxpr) -> List[Dict[str, Any]]:
    """Per-(primitive, operand-dtypes) matmul FLOP records, scan bodies
    multiplied by trip count, cond branches summed (conservative: a
    branch's f32 matmul counts even if the other branch is hotter).

    Returns records ``{"op", "dtypes": (lhs, rhs), "flops", "count",
    "example"}`` sorted by descending FLOPs.
    """
    acc: Dict[Tuple[str, Tuple[str, str]], Dict[str, Any]] = {}

    def walk(jaxpr, scale: int) -> None:
        for eqn in _inner(jaxpr).eqns:
            subs = _sub_jaxprs(eqn)
            if subs:
                for sub, mult, _excl in subs:
                    walk(sub, scale * mult)
                continue
            name = eqn.primitive.name
            try:
                if name == "dot_general":
                    flops = _dot_flops(eqn)
                elif name == "conv_general_dilated":
                    flops = _conv_flops(eqn)
                else:
                    continue
            except (AttributeError, KeyError, TypeError, IndexError):
                continue  # unmodeled layout: skip rather than die
            dts = tuple(str(v.aval.dtype) for v in eqn.invars[:2])
            rec = acc.setdefault(
                (name, dts),
                {
                    "op": name,
                    "dtypes": dts,
                    "flops": 0,
                    "count": 0,
                    "example": " x ".join(
                        _shape_str(v) for v in eqn.invars[:2]
                    ),
                },
            )
            rec["flops"] += flops * scale
            rec["count"] += scale

    walk(closed_jaxpr, 1)
    return sorted(acc.values(), key=lambda r: -r["flops"])


def matmul_coverage(table: Sequence[Dict[str, Any]], dtype: str) -> Dict[str, Any]:
    """Fraction of matmul FLOPs whose BOTH operands are ``dtype`` —
    the precision campaign's per-program coverage number."""
    total = sum(r["flops"] for r in table)
    covered = sum(
        r["flops"] for r in table if all(d == dtype for d in r["dtypes"])
    )
    return {
        "matmul_flops_total": int(total),
        "matmul_flops_covered": int(covered),
        "coverage": (covered / total) if total else None,
        "by_dtype": [
            {
                "op": r["op"],
                "dtypes": list(r["dtypes"]),
                "flops": int(r["flops"]),
                "count": int(r["count"]),
                "example": r["example"],
            }
            for r in table
        ],
    }


def host_transfers(closed_jaxpr) -> List[Dict[str, Any]]:
    """Host-boundary primitives inside the program (callbacks, infeed,
    outfeed), scan-scaled. The IR-level truth jaxlint's AST host-sync
    pass can only approximate: anything here executes a device->host
    round trip INSIDE the compiled program, per call."""
    acc: Dict[str, Dict[str, Any]] = {}

    def walk(jaxpr, scale: int) -> None:
        for eqn in _inner(jaxpr).eqns:
            subs = _sub_jaxprs(eqn)
            if subs:
                for sub, mult, _excl in subs:
                    walk(sub, scale * mult)
                continue
            name = eqn.primitive.name
            if _is_host_transfer(name):
                rec = acc.setdefault(
                    name,
                    {
                        "prim": name,
                        "count": 0,
                        "example": " ".join(
                            _shape_str(v) for v in eqn.invars[:2]
                        ),
                    },
                )
                rec["count"] += scale

    walk(closed_jaxpr, 1)
    return sorted(acc.values(), key=lambda r: -r["count"])


def total_flops_bytes(closed_jaxpr) -> Tuple[int, int]:
    """(analytic FLOPs, analytic bytes) via obs/attribution's full walk."""
    from seist_tpu.obs.attribution import jaxpr_op_costs

    ops = jaxpr_op_costs(closed_jaxpr)
    return (
        int(sum(r["flops"] for r in ops)),
        int(sum(r["bytes"] for r in ops)),
    )


# -------------------------------------------------------- stablehlo parses
_MAIN_RE = re.compile(
    r"func\.func\s+public\s+@main\((?P<args>.*?)\)\s*->", re.DOTALL
)
_ARG_HEAD_RE = re.compile(r"%arg(?P<idx>\d+):\s*tensor<(?P<ty>[^>]*)>")
_ALIAS_RE = re.compile(r"tf\.aliasing_output\s*=\s*(\d+)")
# Shardy (the sharding dialect jax lowers to): per-dimension axis lists,
# e.g. ``sdy.sharding = #sdy.sharding<@mesh, [{"data"}, {}]>``.
_SHARD_RE = re.compile(r"sdy\.sharding\s*=\s*#sdy\.sharding<@\w+,\s*(\[[^\]]*\])")


def parse_main_args(stablehlo_text: str) -> List[Dict[str, Any]]:
    """Flat entry-arg records from a lowered module's ``@main`` signature:
    ``{"index", "type", "aliased_output": int|None, "buffer_donor": bool,
    "sharding": str|None}`` — ``sharding`` is the Shardy per-dimension
    axis list (``'[{"data"}, {}]'``); a dimension naming no axis is not
    split.

    Donation shows up two ways depending on how the program was lowered:
    a plain jit emits ``tf.aliasing_output = N`` on every donated arg it
    could pair with an output AT LOWERING TIME; a sharded (mesh) lowering
    instead emits ``jax.buffer_donor = true`` and defers the actual
    aliasing decision to XLA's compile. A declared-donated arg carrying
    NEITHER marker was dropped by the lowering (the "Some donated buffers
    were not usable" warning) — the distinction the audit pins.

    Parsing splits on ``%argN:`` boundaries instead of matching the attr
    brace block — attribute values legally contain nested braces
    (``sdy.sharding = #sdy.sharding<@mesh, [{"data"}, {}]>``), which
    brace-matching regexes silently truncate.
    """
    m = _MAIN_RE.search(stablehlo_text)
    if not m:
        return []
    args: List[Dict[str, Any]] = []
    for part in re.split(r"(?=%arg\d+:)", m.group("args")):
        head = _ARG_HEAD_RE.match(part.strip())
        if not head:
            continue
        alias = _ALIAS_RE.search(part)
        shard = _SHARD_RE.search(part)
        args.append(
            {
                "index": int(head.group("idx")),
                "type": head.group("ty"),
                "aliased_output": int(alias.group(1)) if alias else None,
                "buffer_donor": "jax.buffer_donor" in part,
                "sharding": shard.group(1) if shard else None,
            }
        )
    return args


def flat_arg_ranges(arg_structs: Sequence[Any]) -> List[Tuple[int, int]]:
    """[start, end) flat-leaf index range of each positional argument —
    maps a jit argnum to the contiguous ``%argN`` block it flattens to
    in the lowered module's ``@main`` signature."""
    import jax

    ranges: List[Tuple[int, int]] = []
    off = 0
    for a in arg_structs:
        n = len(jax.tree_util.tree_leaves(a))
        ranges.append((off, off + n))
        off += n
    return ranges


def _lowered_positions(
    flat_indices: Sequence[int], kept: Optional[Sequence[int]]
) -> Dict[int, Optional[int]]:
    """Map original flat-arg indices to their ``%argN`` position in the
    lowered module. ``kept`` is the lowering's kept_var_idx (sorted);
    a pruned index maps to None. ``kept=None`` = identity (nothing
    pruned, or the lowering doesn't report)."""
    if kept is None:
        return {i: i for i in flat_indices}
    pos = {orig: n for n, orig in enumerate(kept)}
    return {i: pos.get(i) for i in flat_indices}


def donation_audit(
    stablehlo_text: str,
    arg_structs: Sequence[Any],
    donate_argnums: Sequence[int],
    kept: Optional[Sequence[int]] = None,
) -> Dict[str, Any]:
    """Declared donation vs what the lowering actually did with it.

    Returns ``{"declared_argnums", "donated_leaves", "aliased_leaves",
    "deferred_leaves", "unaliased": [{"index", "type"}...],
    "stray_aliases": [...]}``:

    * ``aliased_leaves`` — donated buffers paired to an output at
      LOWERING time (``tf.aliasing_output``, plain-jit lowerings);
    * ``deferred_leaves`` — donated buffers marked ``jax.buffer_donor``
      (sharded lowerings): donation accepted, the input->output pairing
      happens inside XLA's compile;
    * ``unaliased`` — declared-donated buffers carrying NEITHER marker:
      the lowering dropped them ("Some donated buffers were not
      usable"), so they free HBM only after the program finishes.
    """
    args = parse_main_args(stablehlo_text)
    by_pos = {a["index"]: a for a in args}
    ranges = flat_arg_ranges(arg_structs)
    donated: List[int] = []
    for argnum in donate_argnums:
        if 0 <= argnum < len(ranges):
            start, end = ranges[argnum]
            donated.extend(range(start, end))
    positions = _lowered_positions(donated, kept)
    pruned = [i for i in donated if positions[i] is None]
    recs = [
        by_pos[positions[i]]
        for i in donated
        if positions[i] is not None and positions[i] in by_pos
    ]
    unaliased = [
        {"index": a["index"], "type": a["type"]}
        for a in recs
        if a["aliased_output"] is None and not a["buffer_donor"]
    ]
    aliased = [a for a in recs if a["aliased_output"] is not None]
    deferred = [
        a
        for a in recs
        if a["buffer_donor"] and a["aliased_output"] is None
    ]
    # Aliases the lowering claims outside the declared donation would be
    # a jax-level invariant violation; surface them rather than hide.
    donated_pos = {
        positions[i] for i in donated if positions[i] is not None
    }
    stray = [
        a["index"]
        for a in args
        if (a["aliased_output"] is not None or a["buffer_donor"])
        and a["index"] not in donated_pos
    ]
    return {
        "declared_argnums": list(donate_argnums),
        "donated_leaves": len(donated),
        "aliased_leaves": len(aliased),
        "deferred_leaves": len(deferred),
        "pruned_leaves": len(pruned),
        "unaliased": unaliased,
        "stray_aliases": stray,
    }


def sharding_audit(
    stablehlo_text: str,
    arg_structs: Sequence[Any],
    data_argnums: Sequence[int],
    kept: Optional[Sequence[int]] = None,
) -> Dict[str, Any]:
    """Entry-arg sharding of a mesh-lowered program: for each declared
    DATA argument (expected batch-sharded), report whether the lowered
    module actually annotates it with a device split. ``replicated``
    lists data-arg buffers whose dimensions name no mesh axis (or that
    carry no sharding at all) — each one is a full copy of the global batch on
    every device. Args the lowering pruned (unused) are skipped."""
    args = parse_main_args(stablehlo_text)
    by_pos = {a["index"]: a for a in args}
    ranges = flat_arg_ranges(arg_structs)
    flat: List[int] = []
    for argnum in data_argnums:
        if 0 <= argnum < len(ranges):
            start, end = ranges[argnum]
            flat.extend(range(start, end))
    positions = _lowered_positions(flat, kept)
    replicated: List[Dict[str, Any]] = []
    sharded = 0
    total = 0
    pruned = 0
    for i in flat:
        pos = positions[i]
        if pos is None:
            pruned += 1
            continue
        a = by_pos.get(pos)
        if a is None:
            continue
        total += 1
        s = a["sharding"]
        if s is not None and '"' in s:  # some dimension names an axis
            sharded += 1
        else:
            replicated.append(
                {"index": pos, "type": a["type"], "sharding": s}
            )
    return {
        "data_argnums": list(data_argnums),
        "data_leaves": total,
        "sharded_leaves": sharded,
        "pruned_leaves": pruned,
        "replicated": replicated,
    }
