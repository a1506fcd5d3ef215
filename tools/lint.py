"""Combined static-analysis gate: jaxlint + threadlint + detlint +
irlint in ONE interpreter invocation (``make lint``).

The four analyzers share the engine frontend (tools/jaxlint/__main__.py
``run``); this runner additionally shares the FILE WALK — every source
file under the AST analyzers' paths is read exactly once into a source
cache all three AST passes consume — and combines the exit codes (worst
wins, usage errors beat findings). irlint's manifest walk happens once
as well; its extra flags keep their defaults here (use ``python -m
tools.irlint`` to vary them).

    python -m tools.lint              # the full gate
    python -m tools.lint --skip-ir    # no program lowering (fast loop)
    python -m tools.lint --skip-det   # skip the determinism catalog

Exit codes: 0 all clean, 1 new findings in any analyzer, 2 usage/parse/
lowering error in any analyzer.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Dict, List, Optional, Sequence

# irlint lowers real programs: the backend must be pinned BEFORE the
# first jax import (a lint gate must never take the chip).
from tools.irlint.manifest import ensure_cpu_backend

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (analyzer, lint paths) — the same path sets the standalone gates use.
AST_ANALYZERS = (
    ("jaxlint", ("seist_tpu",)),
    ("threadlint", ("seist_tpu", "tools")),
    ("detlint", ("seist_tpu", "tools")),
)


def _prewalk(paths: Sequence[str]) -> Dict[str, str]:
    """ONE os.walk + read over the union of all analyzers' paths."""
    from tools.jaxlint.engine import iter_python_files

    cache: Dict[str, str] = {}
    for p in iter_python_files(sorted(set(paths)), _REPO_ROOT):
        ap = os.path.abspath(p)
        with open(ap, encoding="utf-8") as f:
            cache[ap] = f.read()
    return cache


def main(argv: Optional[Sequence[str]] = None) -> int:
    ensure_cpu_backend()
    ap = argparse.ArgumentParser(
        prog="python -m tools.lint",
        description=__doc__.splitlines()[0],
    )
    ap.add_argument(
        "--skip-ir",
        action="store_true",
        help="run only the AST analyzers (no program lowering)",
    )
    ap.add_argument(
        "--skip-det",
        action="store_true",
        help="skip the determinism catalog (detlint)",
    )
    args = ap.parse_args(argv)

    from tools.jaxlint.__main__ import run
    from tools.jaxlint.rules import RULES as JAX_RULES
    from tools.jaxlint.rules import RULES_BY_NAME as JAX_BY_NAME
    from tools.threadlint.rules import RULES as THREAD_RULES
    from tools.threadlint.rules import RULES_BY_NAME as THREAD_BY_NAME
    from tools.detlint.rules import RULES as DET_RULES
    from tools.detlint.rules import RULES_BY_NAME as DET_BY_NAME

    ast_analyzers = tuple(
        (tag, paths)
        for tag, paths in AST_ANALYZERS
        if not (tag == "detlint" and args.skip_det)
    )
    all_paths: List[str] = []
    for _tag, paths in ast_analyzers:
        all_paths.extend(paths)
    cache = _prewalk(all_paths)

    rcs: Dict[str, int] = {}
    print("== jaxlint ==")
    rcs["jaxlint"] = run(
        list(AST_ANALYZERS[0][1]),
        tag="jaxlint",
        catalog=JAX_RULES,
        rules_by_name=JAX_BY_NAME,
        default_baseline=os.path.join(
            _REPO_ROOT, "tools", "jaxlint_baseline.json"
        ),
        docs="docs/STATIC_ANALYSIS.md",
        source_cache=cache,
    )
    print("== threadlint ==")
    rcs["threadlint"] = run(
        list(AST_ANALYZERS[1][1]),
        tag="threadlint",
        catalog=THREAD_RULES,
        rules_by_name=THREAD_BY_NAME,
        default_baseline=os.path.join(
            _REPO_ROOT, "tools", "threadlint_baseline.json"
        ),
        docs="docs/STATIC_ANALYSIS.md",
        source_cache=cache,
    )
    if not args.skip_det:
        print("== detlint ==")
        rcs["detlint"] = run(
            list(AST_ANALYZERS[2][1]),
            tag="detlint",
            catalog=DET_RULES,
            rules_by_name=DET_BY_NAME,
            default_baseline=os.path.join(
                _REPO_ROOT, "tools", "detlint_baseline.json"
            ),
            docs="docs/STATIC_ANALYSIS.md",
            refuse_empty_baseline_update=True,
            source_cache=cache,
        )
    if not args.skip_ir:
        print("== irlint ==")
        from tools.irlint.__main__ import main as irlint_main

        rcs["irlint"] = irlint_main([])

    # Usage/lowering errors (2) dominate findings (1) dominate clean (0).
    worst = max(rcs.values())
    summary = ", ".join(f"{tag}={rc}" for tag, rc in rcs.items())
    print(f"lint: {summary} -> exit {worst}")
    return worst


if __name__ == "__main__":
    sys.exit(main())
