"""The timed eval program against the plain reference.

The trainer's compiled eval step — the program its validation passes drive
inside the window, tapped when the trainer built it — is called once more,
after the window, at its own batch: on weights the BENCHMARK made from
``--seed`` (``reference/<config>.py``'s ``init``, then BatchNorm statistics
estimated by one train-mode pass of the reference, so that the output
depends on the input: with never-estimated statistics ``seist_l_dpk``
answers a constant 0.5) and on a batch of seeded waveforms. The plain
reference (float32, ``jax.default_matmul_precision("highest")``, literal
lowerings, nothing of the program) computes the same batch in blocks of
rows. Numbers compared, each with its limit (set in the traffic file from
chip readings, PERF.md section 2):

* ``eval_recompiled``: programs compiled by that extra call; limit 0 — it
  is the timed program, not one built for the check.
* ``eval_output_range``: max - min of the reference's output; must exceed
  ``range_min`` (the output depends on the input).
* ``eval_rms_gap`` / ``eval_p999_gap``: root-mean-square and 99.9th
  percentile of |program - reference| over every output of the batch.

What this does not cover is said in PERF.md: the backward pass and the
optimizer (dropout 0.3 draws its masks from the program's own streams, so
no independent reference can follow a train step).
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, Iterator

import numpy as np


def seeded_waveforms(seed: int, batch: int, n: int, channels: int) -> np.ndarray:
    """Noise plus one damped two-phase wavelet per row, z-normalised per
    channel: the shape of the pool's events, made here from the seed."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(batch, n, channels)).astype(np.float32)
    w = max(n // 6, 8)
    t = np.arange(w) / 50.0
    env = t * np.exp(-3.0 * t)
    env /= env.max()
    for r in range(batch):
        p = int(rng.integers(n // 10, n // 2))
        s = min(p + int(rng.integers(n // 20, n // 4)), n - w)
        amp = rng.uniform(5.0, 20.0)
        for c in range(channels):
            x[r, p:p + w, c] += amp * env * np.sin(
                2 * np.pi * rng.uniform(4, 8) * t + rng.uniform(0, 2 * np.pi))
            x[r, s:s + w, c] += 1.6 * amp * env * np.sin(
                2 * np.pi * rng.uniform(1.5, 4) * t + rng.uniform(0, 2 * np.pi))
    x -= x.mean(axis=1, keepdims=True)
    x /= x.std(axis=1, keepdims=True) + 1e-9
    return x


def make_variables(reference: Any, config: Dict, seed: int, x_cal) -> Dict:
    """Weights from the seed, BatchNorm statistics from one train-mode pass
    of the reference over ``x_cal`` — one jitted call, on the device."""
    import jax

    @jax.jit
    def make(key, x):
        v = reference.init(key, config)
        _, stats = reference.forward(v, x, config, train=True)
        return {"params": v["params"], "batch_stats": stats}

    with jax.default_matmul_precision("highest"):
        return make(jax.random.PRNGKey(seed % (2**31 - 1)), x_cal)


def reference_outputs(reference: Any, config: Dict, variables: Dict, x: np.ndarray,
                      rows: int, q=None) -> np.ndarray:
    """The reference over ``x`` in blocks of ``rows`` (eval mode)."""
    import jax

    kw = {} if q is None else {"q": q}

    @jax.jit
    def fwd(v, xb):
        return reference.forward(v, xb, config, train=False, **kw)

    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, x.shape[0], rows):
            out.append(np.asarray(fwd(variables, x[i:i + rows])))
    return np.concatenate(out, axis=0)


def gap_stats(a: np.ndarray, b: np.ndarray) -> Dict[str, float]:
    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return {"rms": float(np.sqrt(np.mean(d * d))),
            "p999": float(np.quantile(d, 0.999)), "max": float(d.max())}


def load_reference(config: Dict) -> Any:
    name = config["reference"].rsplit("/", 1)[-1].removesuffix(".py")
    return importlib.import_module(f"reference.{name}")


def program_outputs(tap: Any, variables: Dict, x: np.ndarray) -> np.ndarray:
    """Drive the tapped eval program once: its own state layout, with the
    benchmark's weights in it; every other leaf (optimizer state) zero."""
    import jax
    import jax.numpy as jnp

    t_state, t_inputs, t_targets, t_mask = tap.first_args

    def zeros(s):
        if not isinstance(s, jax.ShapeDtypeStruct):
            return s
        if s.weak_type:  # e.g. the step counter, born a Python int
            z = jnp.broadcast_to(jnp.asarray(s.dtype.type(0).item()), s.shape)
        else:
            z = jnp.zeros(s.shape, s.dtype)
        return jax.device_put(z, s.sharding)

    def fill(tree, mine):
        def leaf(path, s):
            v = mine
            for k in path:
                v = v[getattr(k, "key", getattr(k, "name", None))]
            if tuple(v.shape) != tuple(s.shape):
                raise ValueError(f"{jax.tree_util.keystr(path)}: reference "
                                 f"{v.shape} vs program {s.shape}")
            return jax.device_put(jnp.asarray(v, s.dtype), s.sharding)
        return jax.tree_util.tree_map_with_path(leaf, tree)

    state = jax.tree.map(zeros, t_state)
    state = state.replace(
        params=fill(t_state.params, variables["params"]),
        batch_stats=fill(t_state.batch_stats, variables["batch_stats"]),
    )
    # Placed as the trainer's own batches are: the mesh is part of the type.
    inputs = jax.tree.map(
        lambda s: jax.device_put(jnp.asarray(x, s.dtype), s.sharding), t_inputs)
    targets = jax.tree.map(zeros, t_targets)
    mask = jax.tree.map(
        lambda s: jax.device_put(jnp.ones(s.shape, s.dtype), s.sharding), t_mask)
    _loss, outputs = tap.fn(state, inputs, targets, mask)
    return np.asarray(jax.device_get(outputs), np.float32)


def check(record: Dict[str, Any], args: Dict[str, Any], ctx: Any,
          log_text: str) -> Iterator[Dict[str, Any]]:
    import jax.numpy as jnp

    tap = next((t for t in record["taps"].values() if t.is_eval), None)
    if tap is None or tap.first_args is None:
        yield {"name": "eval_program", "value": "never ran", "limit": "ran", "ok": False}
        return
    config = ctx.config
    reference = load_reference(config)
    batch = int(tap.first_args[3].shape[0])
    n, c = int(config["in_samples"]), int(config["in_channels"])
    x = seeded_waveforms(ctx.seed, batch, n, c)
    rows = min(int(args.get("rows_per_block", 32)), batch)
    variables = make_variables(reference, config, ctx.seed, jnp.asarray(x[:rows]))

    before = len(record["compiles"].compiled)
    prog = program_outputs(tap, variables, x)
    after_names = [c[1] for c in record["compiles"].compiled[before:]]
    n_new = sum(1 for name in after_names if "eval_step" in name)
    yield {"name": "eval_recompiled", "value": n_new, "limit": 0, "ok": n_new == 0}

    ref = reference_outputs(reference, config, variables, x, rows)
    spread = float(ref.max() - ref.min())
    lo = float(args["range_min"])
    yield {"name": "eval_output_range", "value": spread, "limit": lo, "ok": spread > lo}
    g = gap_stats(prog, ref)
    for key in ("rms", "p999"):
        limit = float(args[f"{key}_max"])
        yield {"name": f"eval_{key}_gap", "value": g[key], "limit": limit,
               "ok": g[key] <= limit}
    ctx.log(f"eval gap max {g['max']:.6g} (not compared: a widest gap swings)")
