"""The timed programs of a token cell against the plain reference: logits,
loss and gradient, at the timed sizes, after the window.

Both compiled programs the trainer built — tapped by the driver when it
built them — are called once more, on weights the BENCHMARK made from
``--seed`` (``reference/<config>.py``'s ``init``) and on seeded ids (Zipf,
exponent 1, over the vocabulary held), with the trainer's own state gone
from the device by then:

(a) the eval step: its logits against the reference's (``eval_rms_gap``,
    ``eval_p999_gap`` over every logit of the batch; ``logits_range`` says
    the output depends on the input), and ``eval_recompiled`` 0 — it is the
    timed program, not one built for the check;
(b) the train step, once, from those weights and a zero optimizer state:
    its loss against the reference's (``train_loss_gap``), and the gradient
    AS THE OPTIMIZER GOT IT — Adam's first moment after one step from zero
    is ``(1 - b1)`` x the gradient — against ``jax.grad`` of the reference's
    loss, leaf by leaf: ``grad_cos_gap_max_leaf`` (1 - cosine, the worst
    leaf), ``grad_cos_gap_all`` (all leaves as one vector) and
    ``grad_norm_gap_max_leaf`` (the worst leaf's |ln| of its norm over the
    reference's); and the update it made of it — per leaf, the norm of the
    parameters' change over the norm of Adam's first update from the
    REFERENCE's gradient at the run's learning rate
    (``update_norm_ratio_min_leaf`` / ``_max_leaf``: a wrong rate, a leaf
    left behind or updated twice reads away from 1);
    ``train_recompiled`` 0; ``step_overflow_rows`` 0 (that step dropped no
    token-slot) and ``moe_overflow_rows`` 0 over the whole run (the bus
    counter): a drop is a failed run.

The plain reference is float32 with ``jax.default_matmul_precision
("highest")``, one row of the batch at a time, every block rematerialised.
Order of work, so that each fits a 16 GB chip beside nothing else: the
program's calls first (its state of 8 GB is donated to the train step and
deleted after), results to the host, then the reference. Limits sit in the
configuration's file (``limits.lm_reference``), set from chip readings and
from the control one precision below (``tools/control_lm.py``, which hands
its numbers to the same ``compare_logits`` / ``compare_step``; PERF.md
section 2).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np

from checks.eval_reference import load_reference  # noqa: F401 - also control_lm's


def seeded_ids(seed: int, batch: int, length: int, vocab: int) -> np.ndarray:
    """(batch, length) int32 ids, Zipf with exponent 1 over ``vocab``."""
    rng = np.random.default_rng(seed)
    cdf = np.cumsum(1.0 / np.arange(1, vocab + 1, dtype=np.float64))
    ids = np.searchsorted(cdf / cdf[-1], rng.random((batch, length)))
    return np.minimum(ids, vocab - 1).astype(np.int32)


def next_ids(ids: np.ndarray) -> np.ndarray:
    """The label of a window: itself shifted by one, -1 where it ends."""
    return np.concatenate(
        [ids[:, 1:], np.full((ids.shape[0], 1), -1, np.int32)], axis=1)


def logit_gaps(a: np.ndarray, b: np.ndarray) -> Dict[str, float]:
    """rms and 99.9th percentile of |a - b| (float32 arrays of a gigabyte:
    no float64 copy of the whole)."""
    d = np.abs(a - b).ravel()
    sq = 0.0
    for part in np.array_split(d, max(1, d.size // (1 << 24))):
        sq += float(np.sum(part.astype(np.float64) ** 2))
    k = min(d.size - 1, int(math.ceil(0.999 * d.size)))
    return {"rms": math.sqrt(sq / d.size), "p999": float(np.partition(d, k)[k]),
            "max": float(d.max())}


def grad_gaps(prog: Any, ref: Any) -> Dict[str, Any]:
    """Per-leaf 1 - cosine and norm ratio of two gradient pytrees (host)."""
    import jax

    rows: List[Tuple[str, float, float]] = []
    dot_all = pp_all = rr_all = 0.0
    ref_leaves = jax.tree.leaves(ref)
    for (path, p), r in zip(jax.tree_util.tree_leaves_with_path(prog), ref_leaves):
        p64 = np.asarray(p, np.float64).ravel()
        r64 = np.asarray(r, np.float64).ravel()
        pr, pp, rr = float(p64 @ r64), float(p64 @ p64), float(r64 @ r64)
        dot_all, pp_all, rr_all = dot_all + pr, pp_all + pp, rr_all + rr
        cos = pr / math.sqrt(pp * rr) if pp > 0 and rr > 0 else 0.0
        ratio = math.sqrt(pp / rr) if rr > 0 else math.inf
        rows.append((jax.tree_util.keystr(path), 1.0 - cos, ratio))
    return {
        "rows": rows,
        "cos_gap_all": 1.0 - dot_all / math.sqrt(pp_all * rr_all),
        "norm_ratio_all": math.sqrt(pp_all / rr_all),
    }


def to_host(tree: Any) -> Any:
    """numpy on the host, one leaf after the other (a ``device_get`` of a
    whole tree starts every transfer at once; the staging stays)."""
    import jax

    return jax.tree.map(np.asarray, tree)


def _place(value: Any, like: Any) -> Any:
    import jax
    import jax.numpy as jnp

    return jax.device_put(jnp.asarray(value, like.dtype), like.sharding)


def _zeros(s: Any) -> Any:
    import jax
    import jax.numpy as jnp

    if not isinstance(s, jax.ShapeDtypeStruct):
        return s
    if s.weak_type:  # e.g. the step counter, born a Python int
        z = jnp.broadcast_to(jnp.asarray(s.dtype.type(0).item()), s.shape)
    else:
        z = jnp.zeros(s.shape, s.dtype)
    return jax.device_put(z, s.sharding)


def program_state(t_state: Any, params: Any) -> Any:
    """The program's own state layout with ``params`` in it (placed as the
    program places them) and every other leaf zero."""
    import jax

    def leaf(path, s):
        v = params
        for k in path:
            v = v[getattr(k, "key", getattr(k, "name", None))]
        if tuple(v.shape) != tuple(s.shape):
            raise ValueError(f"{jax.tree_util.keystr(path)}: reference "
                             f"{v.shape} vs program {s.shape}")
        return _place(v, s)

    placed = jax.tree_util.tree_map_with_path(leaf, t_state.params)
    state = jax.tree.map(_zeros, t_state.replace(params=None))
    return state.replace(params=placed)


def adam_first_moment(opt_state: Any) -> Any:
    import jax

    for s in jax.tree.leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu") and hasattr(x, "nu")):
        if hasattr(s, "mu"):
            return s.mu
    raise ValueError("the optimizer keeps no first moment")


def _band(name: str, value: float, limit: float, *, low: bool = False) -> Dict:
    ok = math.isfinite(value) and (value >= limit if low else value <= limit)
    return {"name": name, "value": value, "limit": limit, "ok": ok}


def change_norms(before: Any, after: Any) -> List[float]:
    """Per leaf, the norm of ``after - before`` (``after`` on the device,
    fetched one leaf at a time and dropped)."""
    import jax

    return [
        float(np.linalg.norm(np.asarray(a, np.float64) - np.asarray(b, np.float64)))
        for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before))
    ]


def adam_first_update_norms(grads: Any, lr: float, eps: float) -> List[float]:
    """Per leaf, the norm of Adam's first update from a zero state: both
    moments bias-corrected are the gradient's own, so an element moves by
    ``lr * g / (|g| + eps)``."""
    import jax

    out = []
    for g in jax.tree.leaves(grads):
        g64 = np.asarray(g, np.float64)
        out.append(lr * float(np.linalg.norm(g64 / (np.abs(g64) + eps))))
    return out


def compare_logits(prog: np.ndarray, ref: np.ndarray, args: Dict[str, Any],
                   log: Any) -> Iterator[Dict[str, Any]]:
    spread = float(ref.max() - ref.min())
    yield _band("logits_range", spread, float(args["range_min"]), low=True)
    g = logit_gaps(prog, ref)
    for k in ("rms", "p999"):
        yield _band(f"eval_{k}_gap", g[k], float(args[f"{k}_max"]))
    log(f"logit gap max {g['max']:.6g} (not compared: a widest gap swings)")


def compare_step(prog_loss: float, prog_grads: Any, ref_loss: float,
                 ref_grads: Any, args: Dict[str, Any], log: Any
                 ) -> Iterator[Dict[str, Any]]:
    yield _band("train_loss_gap", abs(prog_loss - ref_loss),
                float(args["loss_gap_max"]))
    log(f"train step loss {prog_loss:.6f}, reference {ref_loss:.6f}")
    gg = grad_gaps(prog_grads, ref_grads)
    rows = gg["rows"]
    worst = max(rows, key=lambda r: r[1])
    lo, hi = min(rows, key=lambda r: r[2]), max(rows, key=lambda r: r[2])
    log(f"gradient over {len(rows)} leaves: worst cosine gap {worst[0]} "
        f"{worst[1]:.4g}; norm ratio smallest {lo[0]} {lo[2]:.4f}, largest "
        f"{hi[0]} {hi[2]:.4f}; all leaves as one: cosine gap "
        f"{gg['cos_gap_all']:.4g}, norm ratio {gg['norm_ratio_all']:.4f}")
    yield _band("grad_cos_gap_max_leaf", worst[1], float(args["grad_cos_gap_max"]))
    yield _band("grad_cos_gap_all", gg["cos_gap_all"],
                float(args["grad_cos_gap_all_max"]))
    off = max(abs(math.log(r[2])) if 0 < r[2] < math.inf else math.inf
              for r in rows)
    yield _band("grad_norm_gap_max_leaf", off, float(args["grad_norm_gap_max"]))


def check(record: Dict[str, Any], args: Dict[str, Any], ctx: Any,
          log_text: str) -> Iterator[Dict[str, Any]]:
    import jax

    taps = record["taps"].values()
    eval_tap = next((t for t in taps if t.is_eval), None)
    train_tap = next((t for t in taps if not t.is_eval), None)
    if (eval_tap is None or eval_tap.first_args is None
            or train_tap is None or train_tap.first_args is None):
        yield {"name": "programs", "value": "never ran", "limit": "ran", "ok": False}
        return
    config = ctx.config
    reference = load_reference(config)
    a = config["architecture"]
    t_state, t_ids, t_targets, t_mask = eval_tap.first_args
    batch, length = (int(d) for d in t_ids.shape)
    ids = seeded_ids(ctx.seed, batch, length, int(a["vocab_size"]))
    targets = next_ids(ids)
    key = jax.random.PRNGKey(ctx.seed % (2**31 - 1))
    b1 = float(args.get("adam_b1", 0.9))

    with jax.default_matmul_precision("highest"):
        variables = jax.jit(lambda k: reference.init(k, config))(key)
    params_host = to_host(variables["params"])
    state = program_state(t_state, variables["params"])
    del variables
    ids_dev, targets_dev = _place(ids, t_ids), _place(targets, t_targets)
    mask = _place(np.ones(t_mask.shape), t_mask)

    compiled = record["compiles"].compiled
    before = len(compiled)
    _loss, logits = eval_tap.fn(state, ids_dev, targets_dev, mask)
    prog_logits = np.asarray(jax.device_get(logits), np.float32)
    del logits
    n_eval = sum("eval_step" in c[1] for c in compiled[before:])
    yield _band("eval_recompiled", n_eval, 0)

    before = len(compiled)
    rng = _place(jax.random.PRNGKey(0), train_tap.first_args[3])
    out = train_tap.fn(state, ids_dev, targets_dev, rng)
    prog_loss = float(jax.device_get(out[1]))
    overflow = float(jax.device_get(out[2].get("moe_overflow_rows", 0.0)))
    moment = to_host(adam_first_moment(out[0].opt_state))
    moved = change_norms(params_host, out[0].params)
    del out, state
    n_train = sum("train_step" in c[1] for c in compiled[before:])
    yield _band("train_recompiled", n_train, 0)
    yield _band("step_overflow_rows", overflow, 0)
    run_overflow = float(
        (record.get("counters_close") or {}).get("moe_overflow_rows", 0.0))
    yield _band("moe_overflow_rows", run_overflow, 0)
    prog_grads = jax.tree.map(lambda m: np.asarray(m) / (1.0 - b1), moment)
    del moment

    with jax.default_matmul_precision("highest"):
        params = jax.device_put(params_host)
        ref_logits = np.asarray(jax.jit(
            lambda p, i: reference.forward({"params": p}, i, config)
        )(params, ids), np.float32)
        yield from compare_logits(prog_logits, ref_logits, args, ctx.log)
        del prog_logits, ref_logits

        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p, i: reference.loss({"params": p}, i, config)))(params, ids)
        ref_loss = float(ref_loss)
        ref_grads = to_host(ref_grads)
        del params
    yield from compare_step(prog_loss, prog_grads, ref_loss, ref_grads, args, ctx.log)
    due = adam_first_update_norms(
        ref_grads, float(args["lr"]), float(args.get("adam_eps", 1e-8)))
    names = [jax.tree_util.keystr(path)
             for path, _ in jax.tree_util.tree_leaves_with_path(ref_grads)]
    ratios = [(m / d, n) for m, d, n in zip(moved, due, names) if d > 0]
    least, most = min(ratios), max(ratios)
    ctx.log(f"parameters' change over Adam's first update from the reference's "
            f"gradient: smallest {least[1]} {least[0]:.4f}, largest {most[1]} "
            f"{most[0]:.4f}")
    lo, hi = (float(v) for v in args["update_norm_ratio"])
    yield _band("update_norm_ratio_min_leaf", least[0], lo, low=True)
    yield _band("update_norm_ratio_max_leaf", most[0], hi)
