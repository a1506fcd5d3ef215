"""Replay-divergence smoke: the whole det-critical pipeline, twice,
under perturbation — every digest pinned byte-identical.

The ``make replay-smoke`` lane (docs/STATIC_ANALYSIS.md "Determinism
analysis"): detlint's static rules hunt the PATTERNS that break
byte-identity on a different machine; this lane proves the CONTRACTS
hold under the perturbations those patterns are sensitive to. Each
child subprocess runs the full pipeline under one perturbation tuple:

* ``PYTHONHASHSEED`` — set/dict hash order (the axis
  `set-or-dict-order-dependence` guards);
* pack/repick worker count — reduction pairing + shard scheduling (the
  `float-reduction-order` axis, and PR 14/15's N-worker contracts);
* shuffled directory inode order via the ``relink_tree`` shim — readdir
  order (the `unsorted-dir-enumeration` axis), exercised on BOTH the
  pack-resume sidecar scan and the journal-restore directory scan (the
  reversed-listdir regression).

Per child: pack a synthetic archive -> delete the last sidecar +
meta.json and RESUME (digests must not move) -> repick the archive to a
catalog -> write per-station journals in hash-order (deliberately) and
restore them from a reversed-relink copy -> append + replay an alert
WAL. The parent cross-compares every digest across children and prints
ONE JSON verdict line; exit 0 iff all byte-identical.

    python -m tools.replay_smoke                # the make lane (2 children)
    python -m tools.replay_smoke --full         # full 2x2 matrix
    python -m tools.replay_smoke --skip-repick  # no model work (fast loop)
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional

from tools.detlint.runtime import combine, digest_file, digest_tree, relink_tree

# Same geometry as tools/repick_smoke.py ON PURPOSE: the repick phase
# lowers the same programs, so the persistent XLA compile cache is warm
# for every child after the first.
N_EVENTS = 44
TRACE = 256
SPS = 16
BATCH = 4
BPC = 2
COMMIT = 1

#: (PYTHONHASHSEED, workers, reversed-relink) per child. The default
#: diagonal covers both hash seeds, both worker counts, and the
#: reversed-listdir regression; --full runs the whole matrix.
VARIANTS = ((0, 1, False), (1, 2, True))
VARIANTS_FULL = ((0, 1, False), (0, 2, True), (1, 1, True), (1, 2, False))


# --------------------------------------------------------------- child phases
def _pack(archive: str, workers: int):
    from seist_tpu.data.packed import PackSource, pack_sources

    pack_sources(
        [PackSource(
            name="synthetic",
            dataset_kwargs={
                "num_events": N_EVENTS, "trace_samples": TRACE,
                "cache": False,
            },
        )],
        archive,
        num_workers=workers,
        samples_per_shard=SPS,
    )


def _resume_exercise(archive: str, workers: int, relink: bool) -> bool:
    """Delete the pack commit point (meta.json) plus the LAST shard's
    sidecar, then resume — optionally inside a reversed-relink copy of
    the archive, so the resume scan walks a different readdir order.
    Returns whether the resumed tree digests identical to the original."""
    before = digest_tree(archive)
    target = archive
    if relink:
        target = archive + "_rev"
        relink_tree(archive, target)
    os.remove(os.path.join(target, "meta.json"))
    last_sidecar = sorted(
        f for f in os.listdir(target) if f.endswith(".idx.npz")
    )[-1]
    os.remove(os.path.join(target, last_sidecar))
    _pack(target, workers)
    return digest_tree(target) == before


def _repick(archive: str, out: str, workers: int) -> str:
    from tools.repick_archive import main as repick_main

    base = [
        "--archive", archive, "--out", out, "--model", "phasenet",
        "--batch-size", str(BATCH), "--batches-per-call", str(BPC),
        "--commit-every", str(COMMIT),
    ]
    if workers <= 1:
        rc = repick_main(base)
        assert rc == 0, f"serial repick rc={rc}"
    else:
        # Multi-worker children ride the FLEET path (lease + fencing
        # token, batch/fleet.py) so the divergence grid also proves the
        # lease plane costs zero bytes: worker 0 work-steals every unit,
        # worker 1 joins late and finds only done markers — the merge
        # audits each segment's fence sidecar against the done ledger.
        lease_dir = os.path.join(out, "leases")
        for i in range(workers):
            rc = repick_main(base + [
                "--fleet", "--lease-dir", lease_dir, "--lease-store", "dir",
                "--worker-index", str(i), "--worker-id", f"w{i}",
                "--no-merge",
            ])
            assert rc == 0, f"fleet repick worker {i} rc={rc}"
        rc = repick_main([
            "--archive", archive, "--out", out, "--merge-only",
            "--lease-dir", lease_dir,
        ])
        assert rc == 0, f"repick merge rc={rc}"
    return digest_file(os.path.join(out, "catalog.jsonl"))


def _journal_digest(root: str) -> str:
    """Digest of the RESTORED pick-stream state: station enumeration
    order + every deserialized snapshot, not the npz container bytes
    (compression is an implementation detail; the restored state is the
    contract)."""
    from seist_tpu.stream.journal import StationJournal

    j = StationJournal(root, model="replay")
    h = hashlib.sha256()
    for sid in j.station_ids():
        state = j.load(sid)
        h.update(sid.encode())
        h.update(json.dumps(state["meta"], sort_keys=True).encode())
        for k in sorted(state["arrays"]):
            a = state["arrays"][k]
            h.update(f"{k}:{a.dtype}:{a.shape}".encode())
            h.update(a.tobytes())
    return h.hexdigest()


def _journal_exercise(out: str) -> Dict[str, object]:
    import numpy as np

    from seist_tpu.stream.journal import AlertWAL, StationJournal

    jroot = os.path.join(out, "journal")
    j = StationJournal(jroot, model="replay")
    # Deliberate perturbation: write order is SET-ITERATION order, i.e.
    # it varies with this child's PYTHONHASHSEED — the journal contract
    # must erase write order entirely.
    # detlint: disable=set-or-dict-order-dependence -- the hash-order
    # write sequence IS the perturbation under test; per-station content
    # below is a pure function of the station id.
    for sid in {f"ST{i:02d}" for i in range(8)}:
        idx = int(sid[2:])
        j.write(sid, {
            "meta": {"station": sid, "seq": idx * 7, "sps": 100},
            "arrays": {
                "ring": (np.linspace(0.0, 1.0, 64) + idx).astype(np.float32),
                "watermark": np.array([idx * 100], np.int64),
            },
        })
    restored = _journal_digest(jroot)
    # Reversed-listdir regression for the journal dir scan.
    jrev = jroot + "_rev"
    relink_tree(jroot, jrev)
    rev_identical = _journal_digest(jrev) == restored

    wal = AlertWAL(os.path.join(out, "alerts.jsonl"))
    for i in range(6):
        wal.append({"event_id": f"evt_{i:03d}", "t0": i * 1.5, "n_sta": i + 3})
    replayed = wal.replay()
    wal_digest = hashlib.sha256(
        json.dumps(replayed, sort_keys=True).encode()
    ).hexdigest()
    return {
        "journal": restored,
        "journal_rev_identical": rev_identical,
        "wal": wal_digest,
    }


def _child(args) -> int:
    import seist_tpu

    seist_tpu.load_all()
    t0 = time.monotonic()
    out = args.out
    archive = os.path.join(out, "archive")
    _pack(archive, args.workers)
    pack_digests = digest_tree(archive)
    resume_identical = _resume_exercise(archive, args.workers, args.relink)

    catalog: Optional[str] = None
    if not args.skip_repick:
        catalog = _repick(archive, os.path.join(out, "repick"), args.workers)

    result = {
        "role": "child",
        "hashseed": os.environ.get("PYTHONHASHSEED", ""),
        "workers": args.workers,
        "relink": bool(args.relink),
        "pack": combine(pack_digests),
        "pack_files": len(pack_digests),
        "resume_identical": bool(resume_identical),
        "catalog": catalog,
        "wall_s": None,  # filled below so the key order stays stable
    }
    result.update(_journal_exercise(out))
    result["wall_s"] = round(time.monotonic() - t0, 1)
    print(json.dumps(result))
    ok = resume_identical and result["journal_rev_identical"]
    return 0 if ok else 1


# -------------------------------------------------------------------- parent
def _last_json_line(text: str) -> dict:
    for line in reversed(text.strip().splitlines()):
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if d.get("role") == "child":
            return d
    raise SystemExit(f"no child verdict in output: {text[-400:]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.replay_smoke",
        description=__doc__.splitlines()[0],
    )
    ap.add_argument("--full", action="store_true",
                    help="run the full 2x2 perturbation matrix")
    ap.add_argument("--skip-repick", action="store_true",
                    help="pack/journal phases only (no model, fast loop)")
    ap.add_argument("--keep", action="store_true",
                    help="keep the scratch directory for inspection")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--workers", type=int, default=1, help=argparse.SUPPRESS)
    ap.add_argument("--out", default="", help=argparse.SUPPRESS)
    ap.add_argument("--relink", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child:
        return _child(args)

    t0 = time.monotonic()
    root = tempfile.mkdtemp(prefix="replay_smoke_")
    variants = VARIANTS_FULL if args.full else VARIANTS
    children: List[dict] = []
    try:
        # Sequential on purpose: the repick phase is compile-heavy and
        # the host budget is one core (ROADMAP gotchas).
        for hashseed, workers, relink in variants:
            out = os.path.join(root, f"h{hashseed}_w{workers}")
            os.makedirs(out, exist_ok=True)
            cmd = [
                sys.executable, "-m", "tools.replay_smoke", "--child",
                "--workers", str(workers), "--out", out,
            ]
            if relink:
                cmd.append("--relink")
            if args.skip_repick:
                cmd.append("--skip-repick")
            env = dict(os.environ, PYTHONHASHSEED=str(hashseed))
            proc = subprocess.run(
                cmd, env=env, stdout=subprocess.PIPE, text=True,
                timeout=1800,
            )
            if proc.returncode != 0:
                print(proc.stdout[-2000:], file=sys.stderr)
                raise SystemExit(
                    f"child h{hashseed}/w{workers} rc={proc.returncode}"
                )
            children.append(_last_json_line(proc.stdout))

        ref = children[0]
        axes = ("pack", "catalog", "journal", "wal")
        identical = {
            axis: all(c[axis] == ref[axis] for c in children)
            for axis in axes
        }
        resumes = all(c["resume_identical"] for c in children)
        rev = all(c["journal_rev_identical"] for c in children)
        verdict = {
            "ok": bool(all(identical.values()) and resumes and rev),
            "perturbations": [
                {"hashseed": h, "workers": w, "relink": r}
                for h, w, r in variants
            ],
            "identical": identical,
            "resume_identical": resumes,
            "reversed_listdir_identical": rev,
            "digests": {axis: ref[axis] for axis in axes},
            "pack_files": ref["pack_files"],
            "wall_s": round(time.monotonic() - t0, 1),
        }
        print(json.dumps(verdict))
        return 0 if verdict["ok"] else 1
    finally:
        if not args.keep:
            shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
