"""State donation is kept, persistent compile cache or not.

An older jax (0.4.37, CPU) intermittently corrupted donated outputs of an
executable DESERIALIZED from the persistent compile cache, and the train
step used to drop donation in that configuration. On the installed jax the
hazard does not reproduce (80 deserialized chains over 16 processes, single
device and an 8-device mesh, none bad), so the gate is gone: these tests pin
that every shipped jit wrapper donates the state under the cache, and keep
the original repro chain as a regression test.
"""

import jax
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

seist_tpu.load_all()

L = 256
BATCH = 4


@pytest.fixture
def warm_cache_dir(tmp_path):
    """A fresh persistent compile cache with no compile-time threshold, so
    the test's small programs are serialized (and deserialized on a
    re-wrap) exactly like production-sized ones."""
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    cache = str(tmp_path / "xla_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    yield cache
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)


def _setup():
    model = api.create_model("phasenet", in_samples=L)
    variables = api.init_variables(model, in_samples=L, batch_size=BATCH)
    tx = build_optimizer("adam", 1e-3)
    state = create_train_state(model, variables, tx)
    spec = taskspec.get_task_spec("phasenet")
    return state, spec, taskspec.make_loss("phasenet")


def _batch(rng):
    import jax.numpy as jnp

    x = rng.standard_normal((BATCH, L, 3)).astype(np.float32)
    ppk = np.zeros((BATCH, L), np.float32)
    ppk[:, 64] = 1.0
    spk = np.zeros((BATCH, L), np.float32)
    spk[:, 128] = 1.0
    y = np.stack([1.0 - ppk - spk, ppk, spk], axis=-1)
    return jnp.asarray(x), jnp.asarray(y)


# ------------------------------------------------------- donation is kept
def _donated_leaves(jitted, *args) -> int:
    """Leaves of the lowered program that carry a donation marker."""
    text = jitted.__wrapped__.lower(*args).as_text()  # under the span wrap
    return text.count("tf.aliasing_output") + text.count("jax.buffer_donor")


@pytest.mark.parametrize("meshed", [False, True], ids=["plain", "mesh"])
def test_jit_step_donates_state_with_cache_on_cpu(warm_cache_dir, rng, meshed):
    from seist_tpu.parallel.mesh import make_mesh

    assert jax.default_backend() == "cpu"
    assert jax.config.jax_compilation_cache_dir == warm_cache_dir
    state, spec, loss_fn = _setup()
    x, y = _batch(rng)
    mesh = make_mesh(data=len(jax.devices())) if meshed else None
    if meshed:
        reps = -(-len(jax.devices()) // BATCH)
        x, y = (np.tile(np.asarray(a), (reps, 1, 1)) for a in (x, y))
    step = jit_step(make_train_step(spec, loss_fn), mesh)
    n = _donated_leaves(step, state, x, y, jax.random.PRNGKey(0))
    # every params + opt-state leaf has a same-shaped output to reuse
    assert n >= len(jax.tree.leaves(state.params))


def test_donate_state_false_donates_nothing(warm_cache_dir, rng):
    state, spec, loss_fn = _setup()
    x, y = _batch(rng)
    step = jit_step(make_train_step(spec, loss_fn), donate_state=False)
    assert _donated_leaves(step, state, x, y, jax.random.PRNGKey(0)) == 0


def test_donated_state_is_consumed(warm_cache_dir, rng):
    """The runtime honours the donation: the old state's buffers are gone
    after the step (the memory saving donation exists for)."""
    state, spec, loss_fn = _setup()
    x, y = _batch(rng)
    step = jit_step(make_train_step(spec, loss_fn))
    old_leaf = jax.tree.leaves(state.params)[0]
    new_state, loss, _ = step(state, x, y, jax.random.PRNGKey(0))
    jax.block_until_ready((new_state, loss))
    assert old_leaf.is_deleted()
    assert not jax.tree.leaves(new_state.params)[0].is_deleted()


# ------------------------------------------------------------- repro mirror
def test_deserialized_step_chain_is_correct(warm_cache_dir, rng):
    """The old hazard's repro, with donation ON: warm the disk cache,
    re-wrap the step so the next call DESERIALIZES the executable, then
    run 4 back-to-back unsynchronized donated steps. The chain's state
    must be exact (on jax 0.4.37 this flaked in 20-40% of processes)."""
    state, spec, loss_fn = _setup()
    key = jax.random.PRNGKey(0)
    x, y = _batch(rng)

    step1 = jit_step(make_train_step(spec, loss_fn))
    state, loss, _ = step1(state, x, y, key)
    jax.block_until_ready((state, loss))  # executable now in the disk cache

    # Fresh wrap of an identical program: lowering runs again, the
    # compile is a persistent-cache hit -> deserialization path.
    step2 = jit_step(make_train_step(spec, loss_fn))
    for _ in range(4):
        state, loss, _ = step2(state, x, y, key)
    # No pre-read synchronization on purpose (the repro's trigger).
    first_read = int(state.step)
    second_read = int(state.step)
    assert first_read == second_read == 5
    leaf = jax.tree.leaves(state.params)[0]
    np.testing.assert_array_equal(np.asarray(leaf), np.asarray(leaf))
    assert np.isfinite(float(loss))
