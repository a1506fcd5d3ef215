"""What a train run must show whatever the model: the data path the cell
names is the one that ran, nothing compiled inside the window, and the first
call of the timed step (``steps_per_call`` optimizer steps from the seeded
weights) lost, moved and saw gradients as sound runs of this configuration
do. No reference follows the step (dropout and the on-device augmentation
draw from the program's own streams, PERF.md section 2), so these are
invariants, each held to limits read from sound runs; the limits of a
configuration sit in its own file (``limits.train_invariants``).

Numbers compared (each with its limit, printed in every run):

* ``compiles_in_window``: exact, limit 0.
* ``device_aug_mode``: the resolved mode logged by the trainer equals the
  traffic file's (``cached`` falls back silently to ``step``/``off`` when
  the store exceeds half of HBM).
* ``first_call_loss``: mean loss of the call's steps, inside ``loss``
  [low, high]: through augmentation, labels, forward and loss on every row.
* ``moved_share``: share of float parameter leaves whose values changed;
  limit 1.0, exact. A frozen leaf or a zeroed gradient (Adam then moves
  nothing) reads under 1; a step that returns its state unchanged reads 0.
* ``update_ratio_min_leaf`` / ``update_ratio_max_leaf``: for every leaf,
  ||after - before|| / (steps x ``lr_low`` x sqrt(size)); Adam moves an
  element by about the learning rate a step, by less where the gradient's
  sign flips from step to step. The smallest and the largest leaf against
  ``update_ratio`` [low, high]: a wrong learning rate or a leaf that gets
  noise for a gradient shows in its own leaf, not in a sum over all. The
  smallest is taken over the leaves that have a gradient: a bias in front
  of a normalisation has none but rounding, Adam's epsilon then sets how
  far it moves (2.6e-4 read for one such leaf of ``seist_l_dpk``), and that
  would hold a sound change of rounding to a limit. A leaf has a gradient
  where its root-mean-square per element, from Adam's second moment, is at
  least ``grad_floor_eps`` (10) x ``adam_eps``; the smallest over all
  leaves is logged beside it.
* ``grad_norm`` / ``grad_norm_median_leaf``: the gradient as the optimizer
  got it, worked out from Adam's second moment after the call (bias
  corrected, so the root of the weighted mean of the steps' squared
  gradients), summed over all leaves and for the median leaf, against
  ``grad_norm`` and ``grad_norm_median_leaf`` [low, high]. The update under
  Adam hardly depends on the gradient's size; this does. Left out where
  the optimizer keeps no second moment.
"""

from __future__ import annotations

import math
import re
import statistics
from typing import Any, Dict, Iterator, List, Optional, Tuple


def _band(name: str, value: float, limits) -> Dict[str, Any]:
    if not limits:  # a number without a limit is not correct
        return {"name": name, "value": value, "limit": None, "ok": False}
    lo, hi = float(limits[0]), float(limits[1])
    return {"name": name, "value": value, "limit": [lo, hi],
            "ok": math.isfinite(value) and lo <= value <= hi}


def _adam_moments(opt_state: Any) -> Optional[Tuple[int, Any]]:
    """(steps taken, second-moment pytree) of the first Adam-like state in
    an optax state, found by its fields and not by its place in a chain."""
    import jax

    found = jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "nu") and hasattr(x, "count")
    )
    for s in found:
        if hasattr(s, "nu") and hasattr(s, "count"):
            return int(jax.device_get(s.count)), jax.device_get(s.nu)
    return None


def check(record: Dict[str, Any], args: Dict[str, Any], ctx: Any,
          log_text: str) -> Iterator[Dict[str, Any]]:
    import jax
    import numpy as np

    n = len(record["compiles"].between(record["t_open"], record["t_close"]))
    yield {"name": "compiles_in_window", "value": n, "limit": 0, "ok": n == 0}

    want = args.get("device_aug_mode")
    if want:
        if re.search(r"device-aug cached: \d+ epoch samples resident", log_text):
            got = "cached"
        elif "device-aug step:" in log_text:
            got = "step"
        else:
            got = "off"
        yield {"name": "device_aug_mode", "value": got, "limit": want,
               "ok": got == want}

    tap = next(t for t in record["taps"].values() if not t.is_eval)
    first = tap.first
    loss = float(np.asarray(jax.device_get(first["loss"])))
    yield _band("first_call_loss", loss, args.get("loss"))

    before = jax.tree_util.tree_leaves_with_path(jax.device_get(first["before"]))
    after = jax.tree.leaves(jax.device_get(first["after"]))
    steps = record["steps_per_call"]
    ratios: List[Tuple[float, str]] = []
    for (path, b), a in zip(before, after):
        d = np.asarray(a, np.float64) - np.asarray(b, np.float64)
        ratios.append((
            float(np.sqrt(np.sum(d * d)))
            / (steps * float(args["lr_low"]) * math.sqrt(d.size)),
            jax.tree_util.keystr(path),
        ))
    share = sum(r > 0.0 for r, _ in ratios) / max(len(ratios), 1)
    yield {"name": "moved_share", "value": share, "limit": 1.0,
           "ok": share == 1.0}
    moments = _adam_moments(first["opt_state"])
    norms: List[float] = []
    with_gradient = ratios
    if moments is not None:
        count, nu = moments
        unbias = 1.0 - float(args.get("adam_b2", 0.999)) ** max(count, 1)
        sums = [float(np.sum(np.asarray(v, np.float64))) / unbias
                for v in jax.tree.leaves(nu)]
        norms = [math.sqrt(v) for v in sums]
        floor = float(args.get("grad_floor_eps", 10.0)) * float(
            args.get("adam_eps", 1e-8))
        with_gradient = [
            r for r, v, (_p, b) in zip(ratios, sums, before)
            if math.sqrt(v / np.asarray(b).size) >= floor
        ] or ratios
    (rall, pall), (rmin, pmin), (rmax, pmax) = (
        min(ratios), min(with_gradient), max(ratios))
    ctx.log(f"update ratio over {len(ratios)} leaves, {len(with_gradient)} "
            f"with a gradient: smallest of those {pmin} {rmin:.4g}, smallest "
            f"of all {pall} {rall:.4g}, largest {pmax} {rmax:.4g}")
    yield _band("update_ratio_min_leaf", rmin, args.get("update_ratio"))
    yield _band("update_ratio_max_leaf", rmax, args.get("update_ratio"))
    if not norms:
        return
    yield _band("grad_norm", math.sqrt(sum(g * g for g in norms)),
                args.get("grad_norm"))
    yield _band("grad_norm_median_leaf", statistics.median(norms),
                args.get("grad_norm_median_leaf"))
