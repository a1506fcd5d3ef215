"""irlint rule catalog — each rule audits one ProgramInfo and returns
engine Findings anchored at the program's registration site, while
filling the program's machine-readable report entry (irlint_report.json)
as a side effect. Rules must stay device-free: everything here reads
jaxprs and lowered StableHLO text, never runs a program.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List

from tools.irlint import ir
from tools.irlint.manifest import ProgramInfo
from tools.jaxlint.engine import Finding


@dataclass(frozen=True)
class IrRule:
    name: str
    summary: str
    hint: str
    check: Callable[[ProgramInfo], List[Finding]]

    # The shared --list-rules printer reads .name/.summary/.hint like the
    # AST analyzers' Rule objects.


def _finding(prog: ProgramInfo, rule: str, message: str, hint: str) -> Finding:
    site = prog.spec.site
    return Finding(
        file=site.file,
        line=site.line,
        col=0,
        rule=rule,
        message=f"[{prog.spec.key}] {message}",
        hint=hint,
        text=site.text,
    )


# ------------------------------------------------- f32 matmuls under bf16
_COVERAGE_HINT = (
    "trace the offending module under the bf16 policy "
    "(train/precision.py) — a single fp32 operand (an fp32 carry, a "
    "policy-blind module dtype) promotes the matmul and everything "
    "downstream; deliberately-fp32 math needs an `# irlint: disable` "
    "with a rationale at the program's registration site"
)


def check_precision(prog: ProgramInfo) -> List[Finding]:
    table = ir.matmul_dtype_table(prog.jaxpr)
    cov = ir.matmul_coverage(table, "bfloat16")
    if prog.spec.policy == "bf16":
        prog.report["matmul"] = cov
    else:
        # fp32/int8 programs: record totals, no coverage judgment.
        prog.report["matmul"] = {
            "matmul_flops_total": cov["matmul_flops_total"],
            "coverage": None,
        }
        return []
    frac = cov["coverage"]
    if frac is None or frac >= prog.spec.coverage_min:
        return []
    offenders = [
        f"{r['op']}{list(r['dtypes'])} {r['flops']:.3g} flops ({r['example']})"
        for r in cov["by_dtype"]
        if not all(d == "bfloat16" for d in r["dtypes"])
    ][:3]
    return [
        _finding(
            prog,
            "f32-matmul-under-bf16-policy",
            (
                f"bf16 matmul-FLOPs coverage {frac:.3f} < "
                f"{prog.spec.coverage_min:.2f} under the declared bf16 "
                f"policy; non-bf16: {'; '.join(offenders)}"
            ),
            _COVERAGE_HINT,
        )
    ]


# ------------------------------------------------------- donation aliasing
_DONATE_HINT = (
    "a donated buffer the lowering could not alias frees HBM only after "
    "the program finishes — match the donated leaf's (shape, dtype) to an "
    "output or drop it from donate_argnums"
)


def check_donation(prog: ProgramInfo) -> List[Finding]:
    spec = prog.spec
    if not spec.donate:
        return []
    audit = ir.donation_audit(
        prog.stablehlo, spec.args, spec.donate, kept=prog.kept_var_idx
    )
    prog.report["donation"] = audit
    out: List[Finding] = []
    if audit["unaliased"]:
        ex = ", ".join(u["type"] for u in audit["unaliased"][:3])
        out.append(
            _finding(
                prog,
                "donation-alias-audit",
                (
                    f"{len(audit['unaliased'])} of "
                    f"{audit['donated_leaves']} donated buffer(s) were NOT "
                    f"aliased to an output by the lowering (e.g. {ex})"
                ),
                _DONATE_HINT,
            )
        )
    if audit["stray_aliases"]:
        out.append(
            _finding(
                prog,
                "donation-alias-audit",
                (
                    f"lowering aliased {len(audit['stray_aliases'])} "
                    "buffer(s) OUTSIDE the declared donate_argnums "
                    f"(entry indices {audit['stray_aliases'][:5]})"
                ),
                "an alias jax did not get from donate_argnums means the "
                "declared donation table and the lowered program disagree "
                "— audit the jit wrapper",
            )
        )
    return out


# ------------------------------------------------------------ host transfer
_HOST_HINT = (
    "a callback/infeed/outfeed inside a compiled program is a synchronous "
    "device<->host round trip PER CALL — hoist it out of the program, or "
    "suppress with a rationale if the transfer is the program's purpose"
)


def check_host_transfer(prog: ProgramInfo) -> List[Finding]:
    transfers = ir.host_transfers(prog.jaxpr)
    prog.report["host_transfers"] = transfers
    if not transfers:
        return []
    desc = ", ".join(f"{t['prim']} x{t['count']}" for t in transfers)
    return [
        _finding(
            prog,
            "host-transfer-in-program",
            f"host-boundary primitive(s) inside the lowered program: {desc}",
            _HOST_HINT,
        )
    ]


# ------------------------------------------------------------ padding waste
_PAD_HINT = (
    "a request landing just above a bucket boundary pays the whole gap as "
    "padded FLOPs — tighten the bucket ladder (serve --buckets) so no gap "
    "exceeds 2x, or accept the waste with a rationale'd suppression"
)


def check_padding(prog: ProgramInfo) -> List[Finding]:
    spec = prog.spec
    if spec.kind != "serve" or not spec.bucket or not spec.ladder:
        return []
    flops, _ = ir.total_flops_bytes(prog.jaxpr)
    below = [b for b in spec.ladder if b < spec.bucket]
    worst_occupancy = (max(below) if below else 0) + 1
    waste_worst = 1.0 - worst_occupancy / spec.bucket
    prog.report["padding"] = {
        "bucket": spec.bucket,
        "ladder": list(spec.ladder),
        "flops_total": flops,
        "flops_per_row": flops // max(spec.bucket, 1),
        "worst_occupancy": worst_occupancy,
        "waste_frac_worst": round(waste_worst, 4),
    }
    if waste_worst <= 0.5:
        return []
    return [
        _finding(
            prog,
            "padding-waste",
            (
                f"bucket {spec.bucket} with ladder {list(spec.ladder)}: a "
                f"{worst_occupancy}-row flush pads {waste_worst:.0%} of "
                f"{flops:.3g} FLOPs"
            ),
            _PAD_HINT,
        )
    ]


# ------------------------------------------------------- replication audit
_REPL_HINT = (
    "declare the batch axis in in_shardings (jit_step/jit_multi_step/"
    "jit_cached_call do this; a bare jax.jit under a mesh does not) — a "
    "replicated data arg uploads the full global batch to EVERY device"
)


def check_replication(prog: ProgramInfo) -> List[Finding]:
    spec = prog.spec
    if spec.mesh_size <= 1 or not spec.data_argnums:
        return []
    audit = ir.sharding_audit(
        prog.stablehlo, spec.args, spec.data_argnums,
        kept=prog.kept_var_idx,
    )
    prog.report["sharding"] = audit
    if not audit["replicated"]:
        return []
    ex = ", ".join(r["type"] for r in audit["replicated"][:3])
    return [
        _finding(
            prog,
            "replication-audit",
            (
                f"{len(audit['replicated'])} of {audit['data_leaves']} "
                f"data-arg buffer(s) lowered REPLICATED on a "
                f"{spec.mesh_size}-device mesh (e.g. {ex})"
            ),
            _REPL_HINT,
        )
    ]


RULES = (
    IrRule(
        name="f32-matmul-under-bf16-policy",
        summary=(
            "matmul FLOPs still running in fp32 in a program whose "
            "declared compute policy is bf16 (per-program coverage "
            "fraction below the manifest's threshold)"
        ),
        hint=_COVERAGE_HINT,
        check=check_precision,
    ),
    IrRule(
        name="donation-alias-audit",
        summary=(
            "declared donate_argnums vs the input_output aliases the "
            "lowering actually established: donated-but-unaliased and "
            "stray-aliased buffers"
        ),
        hint=_DONATE_HINT,
        check=check_donation,
    ),
    IrRule(
        name="host-transfer-in-program",
        summary=(
            "callback/infeed/outfeed primitives inside a compiled "
            "program — synchronous host round trips per call"
        ),
        hint=_HOST_HINT,
        check=check_host_transfer,
    ),
    IrRule(
        name="padding-waste",
        summary=(
            "worst-case FLOPs fraction burned padding a partial flush up "
            "to its serve bucket, per bucket ladder"
        ),
        hint=_PAD_HINT,
        check=check_padding,
    ),
    IrRule(
        name="replication-audit",
        summary=(
            "data arguments of mesh-lowered programs that the lowering "
            "left replicated (full global batch on every device)"
        ),
        hint=_REPL_HINT,
        check=check_replication,
    ),
)

RULES_BY_NAME: Dict[str, IrRule] = {r.name: r for r in RULES}


def lint_programs(
    programs,
    rules=None,
) -> List[ProgramInfo]:
    """Run the catalog over ProgramSpecs; returns the ProgramInfos with
    ``.findings`` attached (suppression/baseline handling is the
    frontend's job, like the AST analyzers)."""
    infos: List[ProgramInfo] = []
    for spec in programs:
        info = ProgramInfo(spec)
        findings: List[Finding] = []
        for rule in rules if rules is not None else RULES:
            findings.extend(rule.check(info))
        info.findings = findings
        infos.append(info)
    return infos
