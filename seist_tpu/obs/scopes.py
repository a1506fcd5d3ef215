"""Region scopes: from a device op to the part of the step that owns it.

A profiler trace names a device op by its HLO instruction
(``%fusion.24020``); the program knows which module produced it — Flax puts
every module call under a ``jax.named_scope``, the step's own phases
(augmentation, store gather, loss, optimizer) carry scopes of their own, and
the compiled HLO keeps the whole path as ``metadata={op_name="..."}``. This
module reads that path back from the **optimized** HLO of the executable
that runs and sorts it into a small, ordered vocabulary of regions.

* :data:`REGIONS` — ``(region, pattern over op_name)``, first match wins.
  The patterns are over scope names the program sets (module instance
  names, the named scopes of ``train/step.py``, ``data/device_aug.py`` and
  ``models/seist.py``); ``tests/test_scopes.py`` holds every registered
  model family to them, so a renamed module fails a test instead of
  silently turning ``unowned``.
* :func:`classify` — ``op_name -> (region, pass)``; forward or backward is
  read from the op_name itself (``transpose(`` / ``jvp(``).
* :func:`parse_hlo` — ``{instruction: {region, pass, op_name}}`` for every
  instruction of an HLO text that can run as an op of its own.
* :func:`scope_map` — the same for a step built by any ``jit_*`` factory of
  ``train/step.py``, on demand: nothing here runs unless somebody asks.

An op with no op_name (a layout copy, a scan carry's slice) or with one no
region claims is ``unowned``: reported as such, never spread over the
others.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, Optional, Tuple

UNOWNED = "unowned"

#: Ordered: the step's own phases first (they wrap nothing of the model),
#: then the model's blocks from the most specific scope outwards, then
#: whatever else ran inside the model.
REGIONS: Tuple[Tuple[str, str], ...] = (
    ("device_aug", r"(^|[/(])device_aug[)/]"),
    ("cache_gather", r"(^|[/(])cache_gather[)/]"),
    ("loss", r"(^|[/(])loss[)/]"),
    ("optimizer", r"(^|[/(])optimizer[)/]"),
    # SeisT (models/seist.py)
    ("stem", r"/stem\d+/"),
    ("attention", r"/attn_path[)/]"),
    ("msmc", r"/(msmc|gconv_path)[)/]"),
    ("mlp", r"/mlp_path[)/]"),
    ("stage_aggr", r"/stage\d+_aggr/"),
    ("head", r"/out_head/"),
    # NemotronH (models/nemotron_h.py): its attention mixer is an
    # ``attn_path`` too and falls under ``attention`` above
    ("ssm_scan", r"(^|[/(])ssm_scan[)/]"),
    ("ssm_proj", r"(^|[/(])ssm_proj[)/]"),
    ("moe_router", r"(^|[/(])moe_router[)/]"),
    # (the TPU compiler names the grouped product it builds from
    # ``ragged_dot`` ``ragged-dot-*`` and drops the scope path)
    ("moe_experts", r"(^|[/(])moe_experts[)/]|^ragged-dot-"),
    ("moe_shared", r"(^|[/(])moe_shared[)/]"),
    ("embed", r"(^|[/(])embed[)/]"),
    ("lm_head", r"(^|[/(])lm_head[)/]"),
    # PhaseNet (models/phasenet.py)
    ("conv_down", r"/down\d+/"),
    ("conv_up", r"/up\d+/"),
    # EQTransformer, MagNet (models/common.py LSTM / BiLSTM)
    ("lstm", r"/(bilstm|lstm)/|LSTMCell"),
    ("model_other", r"(^|[/(])model[)/]"),
)
_COMPILED = tuple((name, re.compile(pat)) for name, pat in REGIONS)


def classify(op_name: str) -> Tuple[str, str]:
    """``(region, pass)`` of one op_name. ``pass`` is ``"bwd"`` under a
    transposed JVP, ``"fwd"`` under a JVP, ``""`` outside differentiation
    (augmentation, optimizer, an eval step)."""
    if not op_name:
        return UNOWNED, ""
    which = "bwd" if "transpose(" in op_name else (
        "fwd" if "jvp(" in op_name else "")
    for region, pat in _COMPILED:
        if pat.search(op_name):
            return region, which
    return UNOWNED, which


_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[^\s(]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?(%[^\s=]+) = (.*)$")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=(%[^\s,}]+)")
_TO_APPLY = re.compile(r"\bto_apply=(%[^\s,}]+)")
#: The computations a control-flow op runs as ops of their own.
_BODIES = re.compile(
    r"\b(?:condition|body|true_computation|false_computation)=(%[^\s,}]+)"
    r"|\bbranch_computations=\{([^}]*)\}"
)
#: Opcodes that stand for no work of their own on any backend.
_NOT_OPS = frozenset(
    ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")
)


def parse_hlo(text: str) -> Dict[str, Dict[str, str]]:
    """``{instruction name: {"region", "pass", "op_name"}}`` for the
    instructions of an HLO module's text that run as ops of their own: the
    names a trace event carries before its `` = ``. Left out are the bodies
    of fused computations and of reducers (their instructions run inside
    the fusion or reduce that calls them) and opcodes that are no work
    (parameters, constants, tuple plumbing).

    A fusion is charged to the region of its own metadata; one that the
    compiler gave none takes the op_name of its fused computation's root.
    An op without an op_name inside the body of a loop, branch or call
    that a region owns (the carry copies of a dropout mask's RNG loop, of
    the augmentation's per-row loops) belongs to that region: containment,
    not apportioning. Everything else without an op_name is ``unowned``."""
    computations: Dict[str, Dict[str, Dict[str, str]]] = {}  # in text order
    roots: Dict[str, str] = {}  # computation -> op_name of its root
    bodies: Dict[str, list] = {}  # control-flow instruction -> computations
    inlined = set()  # computations that run inside the op that names them
    current: Optional[str] = None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = m.group(1)
                computations.setdefault(current, {})
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        is_root, name, rest = m.groups()
        op = _OPCODE.search(" " + rest)
        opcode = op.group(1) if op else ""
        found = _OP_NAME.search(rest)
        op_name = found.group(1) if found else ""
        if opcode == "fusion":
            fused = _CALLS.findall(rest)
            inlined.update(fused)
            if not op_name and fused:
                op_name = roots.get(fused[0], "")
        elif opcode == "call":
            bodies[name] = _TO_APPLY.findall(rest)
        else:
            inlined.update(_TO_APPLY.findall(rest))
            called = [a or b for a, b in _BODIES.findall(rest)]
            if called:
                bodies[name] = [
                    c.strip() for part in called for c in part.split(",")
                ]
        # The root's op_name, or the first one in the computation where
        # the root has none.
        if op_name and (is_root or current not in roots):
            roots[current] = op_name
        if opcode in _NOT_OPS:
            continue
        region, which = classify(op_name)
        computations[current][name] = {
            "region": region, "pass": which, "op_name": op_name,
        }
    # Callers are printed after what they call: walking the text backwards,
    # a body's owner is settled before the body is read.
    out: Dict[str, Dict[str, str]] = {}
    owner: Dict[str, Tuple[str, str]] = {}
    for comp in reversed(list(computations)):
        if comp in inlined:
            continue
        inherit = owner.get(comp)
        for name, entry in computations[comp].items():
            if inherit and not entry["op_name"]:
                entry["region"], entry["pass"] = inherit
            if entry["region"] != UNOWNED:
                for body in bodies.get(name, ()):
                    owner[body] = (entry["region"], entry["pass"])
            out[name] = entry
    return out


def abstract_call(args: tuple, kwargs: dict) -> Tuple[tuple, dict]:
    """The types of one call (shape, dtype, weak type, and the sharding of
    a committed array) with none of its buffers: what lowering the same
    program again needs once the call's donated arguments are gone."""
    import jax

    def describe(a: Any) -> Any:
        if not isinstance(a, jax.Array):
            return a
        sharding = a.sharding if getattr(a, "committed", True) else None
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding, weak_type=a.weak_type
        )

    return jax.tree.map(describe, (args, kwargs))


def hlo_text(step: Callable) -> Optional[str]:
    """Optimized HLO of the executable behind a ``jit_*`` step that has
    been called once, or None (never called, or not such a step). After a
    call of the same types, lowering and compiling again comes out of
    JAX's in-memory caches; the cost is printing the module."""
    jitted = getattr(step, "jitted", None)
    types = getattr(step, "first_call_types", None)
    if jitted is None or types is None:
        return None
    args, kwargs = types
    return jitted.lower(*args, **kwargs).compile().as_text()


def scope_map(step: Callable) -> Optional[Dict[str, Dict[str, str]]]:
    """:func:`parse_hlo` of :func:`hlo_text`; None where there is no text."""
    text = hlo_text(step)
    return None if text is None else parse_hlo(text)
