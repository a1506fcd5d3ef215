"""Training-engine tests: schedule parity vs torch, train step, checkpoint
round-trip, and data-parallel sharding on the 8-device virtual CPU mesh."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import seist_tpu
from seist_tpu import taskspec
from seist_tpu.models import api
from seist_tpu.parallel import make_mesh, replicate, shard_batch
from seist_tpu.train import (
    TrainState,
    build_optimizer,
    create_train_state,
    cyclic_lr,
    jit_multi_step,
    jit_step,
    load_checkpoint,
    make_accum_train_step,
    make_eval_step,
    make_multi_train_step,
    make_train_step,
    restore_into_state,
    save_checkpoint,
)

seist_tpu.load_all()

L = 256


# --------------------------------------------------------------------- schedule
@pytest.mark.parametrize("mode", ["triangular", "triangular2", "exp_range"])
def test_cyclic_lr_matches_torch(mode):
    torch = pytest.importorskip("torch")
    base_lr, max_lr, up, down, gamma = 8e-5, 1e-3, 7, 11, 0.999
    p = torch.nn.Parameter(torch.zeros(1))
    opt = torch.optim.SGD([p], lr=base_lr)
    sched = torch.optim.lr_scheduler.CyclicLR(
        opt,
        base_lr=base_lr,
        max_lr=max_lr,
        step_size_up=up,
        step_size_down=down,
        mode=mode,
        gamma=gamma,
        cycle_momentum=False,
    )
    ours = cyclic_lr(base_lr, max_lr, up, down, mode=mode, gamma=gamma)
    torch_lrs, our_lrs = [], []
    for step in range(50):
        torch_lrs.append(opt.param_groups[0]["lr"])
        our_lrs.append(float(ours(step)))
        opt.step()
        sched.step()
    np.testing.assert_allclose(our_lrs, torch_lrs, rtol=1e-5)


# ------------------------------------------------------------------- train step
def _setup(model_name="phasenet", batch=4):
    model = api.create_model(model_name, in_samples=L)
    variables = api.init_variables(model, in_samples=L, batch_size=batch)
    tx = build_optimizer("adam", 1e-3)
    state = create_train_state(model, variables, tx)
    spec = taskspec.get_task_spec(model_name)
    loss_fn = taskspec.make_loss(model_name)
    return state, spec, loss_fn


def _fake_dpk_batch(rng, batch=4):
    x = rng.standard_normal((batch, L, 3)).astype(np.float32)
    ppk = np.zeros((batch, L), np.float32)
    ppk[:, 64] = 1.0
    spk = np.zeros((batch, L), np.float32)
    spk[:, 128] = 1.0
    non = 1.0 - ppk - spk
    y = np.stack([non, ppk, spk], axis=-1)
    return jnp.asarray(x), jnp.asarray(y)


def test_train_step_reduces_loss(rng):
    state, spec, loss_fn = _setup()
    step = jit_step(make_train_step(spec, loss_fn))
    x, y = _fake_dpk_batch(rng)
    key = jax.random.PRNGKey(0)
    state, loss0, out = step(state, x, y, key)
    assert out.shape == (4, L, 3)
    for _ in range(10):
        state, loss, _ = step(state, x, y, key)
    assert float(loss) < float(loss0)
    assert int(state.step) == 11


def test_multi_train_step_matches_sequential(rng):
    """k scanned micro-steps == k sequential single steps (same per-step
    RNG folding via state.step; see train/step.py make_multi_train_step).
    SGD keeps the comparison linear in the gradients, so the only residue
    is XLA fusion reassociation (Adam's m/sqrt(v) normalization would
    amplify ULP noise to +/-lr on step 1)."""
    k = 3
    batches = [_fake_dpk_batch(rng) for _ in range(k)]
    xs = jnp.stack([b[0] for b in batches])
    ys = jnp.stack([b[1] for b in batches])
    key = jax.random.PRNGKey(7)

    def sgd_setup():
        model = api.create_model("phasenet", in_samples=L)
        variables = api.init_variables(model, in_samples=L, batch_size=4)
        tx = build_optimizer("sgd", 1e-2)
        state = create_train_state(model, variables, tx)
        spec = taskspec.get_task_spec("phasenet")
        return state, spec, taskspec.make_loss("phasenet")

    state, spec, loss_fn = sgd_setup()
    single = jax.jit(make_train_step(spec, loss_fn))
    losses = []
    for i in range(k):
        state, loss, _ = single(state, xs[i], ys[i], key)
        losses.append(float(loss))

    state2, _, _ = sgd_setup()
    multi = jax.jit(make_multi_train_step(spec, loss_fn, steps_per_call=k))
    state2, mean_loss, _ = multi(state2, xs, ys, key)

    assert int(state2.step) == k
    np.testing.assert_allclose(float(mean_loss), np.mean(losses), rtol=1e-6)
    for a, b in zip(
        jax.tree.leaves(state.params), jax.tree.leaves(state2.params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        )


def test_train_step_updates_batch_stats(rng):
    state, spec, loss_fn = _setup()
    step = jit_step(make_train_step(spec, loss_fn), donate_state=False)
    x, y = _fake_dpk_batch(rng)
    new_state, _, _ = step(state, x, y, jax.random.PRNGKey(0))
    before = jax.tree_util.tree_leaves(state.batch_stats)
    after = jax.tree_util.tree_leaves(new_state.batch_stats)
    assert any(
        not np.allclose(np.asarray(b), np.asarray(a)) for b, a in zip(before, after)
    )


def test_eval_step_is_deterministic(rng):
    state, spec, loss_fn = _setup()
    estep = jax.jit(make_eval_step(spec, loss_fn))
    x, y = _fake_dpk_batch(rng)
    mask = np.ones(x.shape[0], dtype=np.float32)
    l1, o1 = estep(state, x, y, mask)
    l2, o2 = estep(state, x, y, mask)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    assert float(l1) == float(l2)


def test_train_step_with_transforms(rng):
    # baz_network uses targets->(cos,sin) transform + CombinationLoss.
    state, spec, loss_fn = _setup("baz_network", batch=2)
    step = jit_step(make_train_step(spec, loss_fn))
    x = jnp.asarray(rng.standard_normal((2, L, 3)), jnp.float32)
    baz = jnp.asarray([[45.0], [270.0]], jnp.float32)
    state, loss, outputs = step(state, x, baz, jax.random.PRNGKey(0))
    assert np.isfinite(float(loss))


# ------------------------------------------------------- scoped L1 (eqt hooks)
def test_eqt_l1_mask_scopes_to_ref_hooked_convs():
    """l1_param_mask selects exactly the encoder ConvBlock / decoder
    Upsampling convs the reference hooks (ref eqtransformer.py:43-51,
    388-396) — not LSTM/attention/ff/resconv params."""
    from seist_tpu.models.eqtransformer import l1_param_mask

    model = api.create_model("eqtransformer", in_samples=L)
    shapes = api.param_shapes(model, in_samples=L)["params"]
    kmask = l1_param_mask(shapes, "kernel")
    flat = jax.tree_util.tree_leaves_with_path(kmask)
    selected = {
        "/".join(str(getattr(k, "key", k)) for k in p)
        for p, v in flat
        if v
    }
    assert any(s.startswith("encoder/conv0/") for s in selected)
    assert any(s.startswith("decoder0/up0/") for s in selected)
    assert all("bilstm" not in s and "transformer" not in s for s in selected)
    assert all("resconv" not in s and "conv_out" not in s for s in selected)
    assert all(s.endswith("/kernel") for s in selected)
    # 7 encoder convs + 3 decoders x 7 ups = 28 hooked kernels.
    assert len(selected) == 28, sorted(selected)


def test_build_optimizer_applies_scoped_l1():
    from seist_tpu.models.eqtransformer import l1_param_mask

    model = api.create_model("eqtransformer", in_samples=L)
    variables = api.init_variables(model, in_samples=L, batch_size=1)
    params = variables["params"]
    alpha = 0.125
    tx0 = build_optimizer("sgd", 1.0, momentum=0.0)
    tx1 = build_optimizer(
        "sgd", 1.0, momentum=0.0,
        l1_kernel_alpha=alpha, l1_mask_fn=l1_param_mask,
    )
    grads = jax.tree.map(jnp.zeros_like, params)
    u0, _ = tx0.update(grads, tx0.init(params), params)
    u1, _ = tx1.update(grads, tx1.init(params), params)
    kmask = l1_param_mask(params, "kernel")
    diffs = jax.tree.map(
        lambda a, b, m, p: np.allclose(
            np.asarray(b - a), (alpha if m else 0.0) * -np.sign(np.asarray(p))
        ),
        u0, u1, kmask, params,
    )
    assert all(jax.tree_util.tree_leaves(diffs))


# -------------------------------------------------------------- mixed precision
@pytest.mark.parametrize(
    "model_name",
    [
        "phasenet",
        pytest.param("seist_s_dpk", marks=pytest.mark.slow),  # 2 heavy compiles
    ],
)
def test_bf16_train_step_tracks_fp32(rng, model_name):
    """bf16 compute dtype: loss close to fp32, params/stats stay fp32, and
    several steps still reduce the loss."""
    x, y = _fake_dpk_batch(rng)
    key = jax.random.PRNGKey(0)

    state32, spec, loss_fn = _setup(model_name)
    state16, _, _ = _setup(model_name)
    step32 = jit_step(make_train_step(spec, loss_fn), donate_state=False)
    step16 = jit_step(
        make_train_step(spec, loss_fn, compute_dtype="bf16"),
        donate_state=False,
    )

    s32, l32, o32 = step32(state32, x, y, key)
    s16, l16, o16 = step16(state16, x, y, key)
    # Outputs come back fp32 regardless of compute dtype.
    assert o16.dtype == jnp.float32
    # Same init => loss matches to bf16 tolerance.
    np.testing.assert_allclose(float(l16), float(l32), rtol=0.05, atol=5e-3)
    # Master params / optimizer / BN stats remain fp32.
    for leaf in jax.tree_util.tree_leaves(s16.params):
        assert leaf.dtype == jnp.float32
    for leaf in jax.tree_util.tree_leaves(s16.batch_stats):
        assert leaf.dtype == jnp.float32

    loss0 = float(l16)
    for _ in range(10):
        s16, l16, _ = step16(s16, x, y, key)
    assert float(l16) < loss0


def test_bf16_eval_step_close_to_fp32(rng):
    state, spec, loss_fn = _setup("seist_s_dpk")
    x, y = _fake_dpk_batch(rng)
    mask = np.ones(x.shape[0], dtype=np.float32)
    e32 = jax.jit(make_eval_step(spec, loss_fn))
    e16 = jax.jit(make_eval_step(spec, loss_fn, compute_dtype="bf16"))
    l32, o32 = e32(state, x, y, mask)
    l16, o16 = e16(state, x, y, mask)
    assert o16.dtype == jnp.float32
    np.testing.assert_allclose(float(l16), float(l32), rtol=0.05, atol=5e-3)
    # dpk outputs are probabilities; bf16 forward should stay within a few
    # probability points of fp32.
    assert float(jnp.abs(o16 - o32).max()) < 0.05


def test_resolve_dtype():
    from seist_tpu.train.precision import resolve_dtype

    assert resolve_dtype(None) is None
    assert resolve_dtype("fp32") is None
    assert resolve_dtype("bf16") == jnp.bfloat16
    with pytest.raises(ValueError):
        resolve_dtype("fp16")


# ------------------------------------------------------------------ parallelism
def test_dp_sharded_step_matches_single_device(rng):
    assert jax.device_count() >= 8, "conftest must provide 8 virtual devices"
    # SGD (linear in grads) so the comparison tests sharding semantics, not
    # Adam's g/sqrt(v) amplification of float reassociation noise.
    model = api.create_model("phasenet", in_samples=L)
    variables = api.init_variables(model, in_samples=L, batch_size=8)
    state = create_train_state(model, variables, build_optimizer("sgd", 1e-2))
    spec = taskspec.get_task_spec("phasenet")
    loss_fn = taskspec.make_loss("phasenet")
    x, y = _fake_dpk_batch(rng, batch=8)
    key = jax.random.PRNGKey(0)

    single = jit_step(make_train_step(spec, loss_fn), donate_state=False)
    s1, loss1, _ = single(state, x, y, key)

    mesh = make_mesh(data=8)
    state_r = replicate(mesh, state)
    xb, yb = shard_batch(mesh, (x, y))
    sharded = jit_step(make_train_step(spec, loss_fn), mesh=mesh, donate_state=False)
    s2, loss2, _ = sharded(state_r, xb, yb, key)

    np.testing.assert_allclose(float(loss1), float(loss2), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(s1.params), jax.tree_util.tree_leaves(s2.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


def test_multi_step_sharded_matches_single_device(rng):
    """jit_multi_step shards the BATCH axis (axis 1), not the micro-step
    axis: a dp-sharded 2-step call must equal the single-device one."""
    assert jax.device_count() >= 8
    model = api.create_model("phasenet", in_samples=L)
    variables = api.init_variables(model, in_samples=L, batch_size=8)
    state = create_train_state(model, variables, build_optimizer("sgd", 1e-2))
    spec = taskspec.get_task_spec("phasenet")
    loss_fn = taskspec.make_loss("phasenet")
    batches = [_fake_dpk_batch(rng, batch=8) for _ in range(2)]
    xs = jnp.stack([b[0] for b in batches])
    ys = jnp.stack([b[1] for b in batches])
    key = jax.random.PRNGKey(0)
    multi = make_multi_train_step(spec, loss_fn, steps_per_call=2)

    s1, loss1, _ = jit_multi_step(multi, donate_state=False)(state, xs, ys, key)

    mesh = make_mesh(data=8)
    state_r = replicate(mesh, state)
    from seist_tpu.parallel import shard_stacked_batch

    xb, yb = shard_stacked_batch(mesh, (xs, ys))
    assert xb.sharding.spec == (None, "data")
    s2, loss2, _ = jit_multi_step(multi, mesh=mesh, donate_state=False)(
        state_r, xb, yb, key
    )

    np.testing.assert_allclose(float(loss1), float(loss2), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(s1.params), jax.tree_util.tree_leaves(s2.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


def test_worker_runs_the_attention_kernel_on_a_data_mesh(tmp_path, monkeypatch):
    """The trainer's own call order on several chips: init at batch 1, then
    the jitted steps on a data=4 mesh. On the TPU backend the attention is
    the Pallas kernel (driven here through the interpreter), which has to
    run per batch shard in the steps and on the whole (batch-1) input at
    init — the mesh a kernel call sees is the one its jit wrapper names,
    never a process-wide one."""
    from seist_tpu.ops import pallas_attention as pa
    from seist_tpu.train import worker
    from seist_tpu.utils.logger import logger
    from tests.test_fault_tolerance_e2e import make_args

    seen = []
    kernel = pa._fused

    def interpreted(q3, k3, v3, seed, scale, rate, heads, interpret):
        seen.append(q3.shape[0])
        return kernel(q3, k3, v3, seed, scale, rate, heads, True)

    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    monkeypatch.setattr(pa, "_fused", interpreted)
    monkeypatch.setattr(
        worker.mesh_lib, "make_mesh",
        lambda seq=1, _make=worker.mesh_lib.make_mesh: _make(
            data=4, seq=seq, devices=jax.devices()[:4]
        ),
    )
    logger.set_logdir(str(tmp_path))
    ckpt = worker.train_worker(make_args(model_name="seist_s_dpk", batch_size=8))
    assert ckpt and os.path.isdir(ckpt)
    losses = np.load(os.path.join(str(tmp_path), "train_losses.npy"))
    assert losses.size == 4 and np.isfinite(losses).all()
    # batch 1 at init (no mesh), 8 / 4 = 2 rows per device in the steps
    assert set(seen) == {1, 2}, sorted(set(seen))


@pytest.mark.parametrize("seq", [1, 2])
def test_jit_step_scopes_its_mesh_around_the_trace(seq):
    """The model's mesh-aware paths follow the mesh jit_step was given, for
    the trace of that step only: a seq-sharded mesh routes SeisT attention
    through the ring (collective permutes in the lowered step), and no mesh
    is left active once the trace is done."""
    from seist_tpu.parallel import mesh as mesh_lib

    name, n = "seist_s_dpk", 512
    model = api.create_model(name, in_samples=n)
    variables = api.init_variables(model, in_samples=n)
    state = create_train_state(model, variables, build_optimizer("sgd", 1e-2))
    step_fn = make_train_step(taskspec.get_task_spec(name), taskspec.make_loss(name))
    mesh = make_mesh(seq=seq, devices=jax.devices()[:4])
    x = np.zeros((4, n, 3), np.float32)
    text = (
        jit_step(step_fn, mesh).__wrapped__
        .lower(replicate(mesh, state), x, x, jax.random.PRNGKey(0)).as_text()
    )
    assert ("collective_permute" in text) == (seq > 1)
    assert mesh_lib.active_mesh() is None


def test_mesh_axes():
    mesh = make_mesh()
    assert mesh.axis_names == ("data", "model", "seq")
    assert mesh.devices.size == jax.device_count()


# ------------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip(tmp_path, rng):
    state, spec, loss_fn = _setup()
    step = jit_step(make_train_step(spec, loss_fn), donate_state=False)
    x, y = _fake_dpk_batch(rng)
    state, loss, _ = step(state, x, y, jax.random.PRNGKey(0))

    path = save_checkpoint(str(tmp_path / "ckpts"), state, epoch=3, loss=float(loss))
    fresh, _, _ = _setup()
    restored = load_checkpoint(path, fresh)
    assert restored["meta"]["epoch"] == 3
    resumed = restore_into_state(fresh, restored)
    assert int(resumed.step) == 1
    for a, b in zip(
        jax.tree_util.tree_leaves(state.params),
        jax.tree_util.tree_leaves(resumed.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------- l1 decay
def test_l1_sign_decay_adds_sign_to_grads():
    import optax
    from seist_tpu.train import l1_sign_decay

    params = {"a": jnp.asarray([1.0, -2.0, 0.0]), "b": jnp.asarray([3.0])}
    grads = {"a": jnp.asarray([0.1, 0.1, 0.1]), "b": jnp.asarray([0.1])}
    tx = l1_sign_decay(0.5, mask=lambda p: {"a": True, "b": False})
    updates, _ = tx.update(grads, tx.init(params), params)
    np.testing.assert_allclose(np.asarray(updates["a"]), [0.6, -0.4, 0.1])
    np.testing.assert_allclose(np.asarray(updates["b"]), [0.1])


def test_jit_eval_step_preserves_state(rng):
    from seist_tpu.train import jit_eval_step

    state, spec, loss_fn = _setup()
    estep = jit_eval_step(make_eval_step(spec, loss_fn))
    x, y = _fake_dpk_batch(rng)
    estep(state, x, y, np.ones(x.shape[0], dtype=np.float32))
    # state must remain usable (no donation)
    tstep = jit_step(make_train_step(spec, loss_fn), donate_state=False)
    tstep(state, x, y, jax.random.PRNGKey(0))


# ---------------------------------------------------------- grad accumulation
def test_accum_step_matches_big_batch(rng):
    """k accumulated micro-batch gradients == ONE big-batch gradient, for a
    BN-free model with a mean-reduced loss (make_accum_train_step's exact
    regime — with BatchNorm the stats couple samples, so accumulation
    matches small-batch BN semantics instead, covered by the smoke test
    below)."""
    from flax import linen as nn

    k, b = 4, 2

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            h = nn.gelu(nn.Dense(8)(x))
            return jax.nn.softmax(nn.Dense(3)(h), axis=-1)

    model = Tiny()
    variables = model.init(jax.random.PRNGKey(3), jnp.zeros((1, L, 3)))
    spec = taskspec.get_task_spec("phasenet")  # CE on (N, L, 3) probs
    loss_fn = taskspec.make_loss("phasenet")
    xs, ys = [], []
    for _ in range(k):
        x, y = _fake_dpk_batch(rng, batch=b)
        xs.append(x)
        ys.append(y)
    key = jax.random.PRNGKey(0)

    def fresh_state():
        return create_train_state(
            model, {"params": variables["params"]}, build_optimizer("sgd", 1e-2)
        )

    big = jax.jit(make_train_step(spec, loss_fn))
    s1, loss1, _ = big(
        fresh_state(), jnp.concatenate(xs), jnp.concatenate(ys), key
    )

    accum = jax.jit(make_accum_train_step(spec, loss_fn, accum_steps=k))
    s2, loss2, _ = accum(fresh_state(), jnp.stack(xs), jnp.stack(ys), key)

    assert int(s2.step) == 1  # ONE optimizer update
    np.testing.assert_allclose(float(loss1), float(loss2), rtol=1e-6)
    for a, c in zip(
        jax.tree_util.tree_leaves(s1.params), jax.tree_util.tree_leaves(s2.params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(c), rtol=1e-5, atol=1e-7
        )


def test_accum_step_bn_smoke(rng):
    """With a BatchNorm model: accumulation chains running stats through the
    micro-steps (as k separate forwards) and applies one update."""
    state, spec, loss_fn = _setup()
    stats0 = jax.tree_util.tree_leaves(state.batch_stats)
    batches = [_fake_dpk_batch(rng) for _ in range(2)]
    xs = jnp.stack([b[0] for b in batches])
    ys = jnp.stack([b[1] for b in batches])
    accum = jax.jit(make_accum_train_step(spec, loss_fn, accum_steps=2))
    state, loss, _ = accum(state, xs, ys, jax.random.PRNGKey(0))
    assert np.isfinite(float(loss))
    assert int(state.step) == 1
    changed = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(stats0, jax.tree_util.tree_leaves(state.batch_stats))
    )
    assert changed


def test_accum_one_is_plain_step():
    spec = taskspec.get_task_spec("phasenet")
    loss_fn = taskspec.make_loss("phasenet")
    fn = make_accum_train_step(spec, loss_fn, accum_steps=1)
    # accum_steps=1 falls back to the plain single-batch step signature.
    assert fn.__name__ == "train_step"


def test_accum_step_sharded_matches_single_device(rng):
    """jit_multi_step's stacked-batch sharding (P(None, 'data')) applies to
    the accumulation step too: a dp-sharded accumulated update must equal
    the single-device one."""
    assert jax.device_count() >= 8
    model = api.create_model("phasenet", in_samples=L)
    variables = api.init_variables(model, in_samples=L, batch_size=8)
    state = create_train_state(model, variables, build_optimizer("sgd", 1e-2))
    spec = taskspec.get_task_spec("phasenet")
    loss_fn = taskspec.make_loss("phasenet")
    batches = [_fake_dpk_batch(rng, batch=8) for _ in range(2)]
    xs = jnp.stack([b[0] for b in batches])
    ys = jnp.stack([b[1] for b in batches])
    key = jax.random.PRNGKey(0)
    accum = make_accum_train_step(spec, loss_fn, accum_steps=2)

    s1, loss1, _ = jit_multi_step(accum, donate_state=False)(state, xs, ys, key)

    mesh = make_mesh(data=8)
    state_r = replicate(mesh, state)
    from seist_tpu.parallel import shard_stacked_batch

    xb, yb = shard_stacked_batch(mesh, (xs, ys))
    s2, loss2, _ = jit_multi_step(accum, mesh=mesh, donate_state=False)(
        state_r, xb, yb, key
    )

    np.testing.assert_allclose(float(loss1), float(loss2), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(s1.params), jax.tree_util.tree_leaves(s2.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)
