"""Compile-budget regression on the REAL jitted train step (jaxlint
runtime audit lane, docs/STATIC_ANALYSIS.md).

The invariant ROADMAP's "as fast as the hardware allows" depends on:
the train step compiles once per shape bucket, then every identical-shape
step is a pure cache hit. A retrace on identical shapes (fresh jit wrap
per step, non-hashable static, weak-type churn) silently turns a ~100 ms
step into a multi-second one — here it turns into a failing assertion.

Kept out of the pure-unit smoke lane (model compiles dominate); runs in
tier-1 (`-m 'not slow'`). CompileBudget mechanics on tiny programs are
covered in tests/test_jaxlint.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import seist_tpu
from seist_tpu import taskspec
from seist_tpu.models import api
from seist_tpu.train import (
    build_optimizer,
    create_train_state,
    jit_step,
    make_train_step,
)

# repo root is put on sys.path by tests/conftest.py
from tools.jaxlint.runtime import CompileBudget  # noqa: E402

seist_tpu.load_all()

L = 256
BATCH = 4


def _setup():
    model = api.create_model("phasenet", in_samples=L)
    variables = api.init_variables(model, in_samples=L, batch_size=BATCH)
    tx = build_optimizer("adam", 1e-3)
    state = create_train_state(model, variables, tx)
    spec = taskspec.get_task_spec("phasenet")
    return state, spec, taskspec.make_loss("phasenet")


def _batch(rng):
    x = rng.standard_normal((BATCH, L, 3)).astype(np.float32)
    ppk = np.zeros((BATCH, L), np.float32)
    ppk[:, 64] = 1.0
    spk = np.zeros((BATCH, L), np.float32)
    spk[:, 128] = 1.0
    y = np.stack([1.0 - ppk - spk, ppk, spk], axis=-1)
    return jnp.asarray(x), jnp.asarray(y)


def test_train_step_steady_state_never_recompiles(rng):
    """The first call compiles OUTSIDE the budget window (cold-cache
    first compiles can re-lower under load, which is noise, not the
    regression); the guarded property is steady state: once warm, steps
    of identical shape must trace exactly zero times."""
    state, spec, loss_fn = _setup()
    step = jit_step(make_train_step(spec, loss_fn))
    key = jax.random.PRNGKey(0)
    x, y = _batch(rng)
    state, loss, _ = step(state, x, y, key)  # warm-up compile
    jax.block_until_ready((state, loss))
    with CompileBudget() as budget:
        for _ in range(4):
            x, y = _batch(rng)  # fresh values, identical shapes/dtypes
            state, loss, _ = step(state, x, y, key)
            jax.block_until_ready((state, loss))
    # No identical-shape retrace, and at most one stray re-lowering
    # (observed once under heavy concurrent load; a real regression —
    # e.g. a fresh wrap per call — traces every step and trips both).
    assert budget.retraces("train_step") == [], budget.compiles
    assert budget.total("train_step") <= 1, budget.compiles
    assert int(state.step) == 5


def test_budget_fails_when_step_is_made_to_retrace(rng):
    """Negative control (the acceptance criterion): re-wrapping the step
    per call — the exact hazard jaxlint's jit-in-loop rule targets —
    must trip the budget's identical-shape retrace assertion."""
    state, spec, loss_fn = _setup()
    key = jax.random.PRNGKey(0)
    x, y = _batch(rng)
    with CompileBudget() as budget:
        for _ in range(2):
            step = jit_step(make_train_step(spec, loss_fn))  # fresh closure
            state, loss, _ = step(state, x, y, key)
        jax.block_until_ready((state, loss))
    assert budget.retraces("train_step"), "expected an identical-shape retrace"
    with pytest.raises(AssertionError, match="retrace on identical shapes"):
        budget.assert_compiles_once("train_step")
