"""Fused pooled-KV attention kernel == einsum attention (Pallas interpreter
on CPU; the same kernel compiles for TPU)."""

import jax
import numpy as np
import pytest

from seist_tpu.ops.pallas_attention import (
    _einsum_attention,
    fused_pooled_attention,
)


def _qkv(rng, n=2, l=64, m=16, h=2, e=8):
    q = rng.normal(size=(n, l, h, e)).astype(np.float32)
    k = rng.normal(size=(n, m, h, e)).astype(np.float32)
    v = rng.normal(size=(n, m, h, e)).astype(np.float32)
    return q, k, v


@pytest.mark.parametrize("h", [1, 2, 3])
def test_forward_matches_einsum(rng, h):
    # h=3, e=8 is the real SeisT stage-0 attention shape; the in-kernel
    # head unroll slices the folded (L, H*E) feature axis per head.
    q, k, v = _qkv(rng, h=h)
    want = np.asarray(_einsum_attention(q, k, v, 1.0 / np.sqrt(q.shape[-1])))
    got = np.asarray(fused_pooled_attention(q, k, v, interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_forward_pooled_shapes(rng):
    # L != M (pooled K/V) and E not a lane multiple.
    q, k, v = _qkv(rng, l=128, m=16, e=24)
    want = np.asarray(_einsum_attention(q, k, v, 1.0 / np.sqrt(24)))
    got = np.asarray(fused_pooled_attention(q, k, v, interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_extreme_logits(rng):
    q, k, v = _qkv(rng)
    q *= 40.0
    want = np.asarray(_einsum_attention(q, k, v, 1.0 / np.sqrt(q.shape[-1])))
    got = np.asarray(fused_pooled_attention(q, k, v, interpret=True))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_custom_vjp_matches_einsum_grads(rng):
    q, k, v = _qkv(rng, n=1, l=32, m=8)
    scale = 1.0 / np.sqrt(q.shape[-1])

    def loss_fused(q, k, v):
        return (fused_pooled_attention(q, k, v, interpret=True) ** 2).sum()

    def loss_einsum(q, k, v):
        return (_einsum_attention(q, k, v, scale) ** 2).sum()

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
    ge = jax.grad(loss_einsum, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, ge, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
            err_msg=f"d{name}",
        )


def test_cpu_fallback_is_einsum(rng):
    # Off the TPU backend (and without interpret) the public API is the einsum.
    q, k, v = _qkv(rng)
    got = np.asarray(fused_pooled_attention(q, k, v))
    want = np.asarray(_einsum_attention(q, k, v, 1.0 / np.sqrt(q.shape[-1])))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# -- kernel or failure: nothing routes around a compiler refusal ------------


def _refusing_pallas_call(*args, **kwargs):
    raise RuntimeError("Mosaic failed to compile TPU kernel: refused")


def test_tpu_path_takes_the_kernel(rng, monkeypatch):
    # On the TPU backend the public API goes straight to the kernel.
    from seist_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    called = {}

    def spy(q3, k3, v3, seed, scale, rate, h, interpret):
        called["interpret"] = interpret
        return q3

    monkeypatch.setattr(pa, "_fused", spy)
    q, k, v = _qkv(rng)
    out = fused_pooled_attention(q, k, v)
    assert called == {"interpret": False}  # the compiled kernel, not interpret
    assert out.shape == q.shape


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
def test_kernel_compile_error_raises(rng, monkeypatch, jitted):
    # A compiler refusal of the kernel is the run's error — eagerly and
    # under an enclosing jit (the train-step case) alike. It must never
    # come back as the einsum result.
    from jax.experimental import pallas as pl

    from seist_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    monkeypatch.setattr(pl, "pallas_call", _refusing_pallas_call)
    q, k, v = _qkv(rng)
    fn = lambda q, k, v: fused_pooled_attention(q, k, v)  # noqa: E731
    if jitted:
        fn = jax.jit(fn)
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        fn(q, k, v)


def test_kernel_compile_error_raises_in_the_backward(rng, monkeypatch):
    # The backward kernel is held to the same rule as the forward.
    from jax.experimental import pallas as pl

    from seist_tpu.ops import pallas_attention as pa

    q, k, v = _qkv(rng, n=1, l=32, m=8)
    real = pl.pallas_call
    calls = {"n": 0}

    def second_call_refuses(*args, **kwargs):
        calls["n"] += 1
        if calls["n"] > 1:  # 1st = forward kernel, 2nd = backward kernel
            return _refusing_pallas_call()
        return real(*args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", second_call_refuses)

    def loss(q, k, v):
        return (fused_pooled_attention(q, k, v, interpret=True) ** 2).sum()

    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        jax.grad(loss)(q, k, v)
    assert calls["n"] == 2


def test_kernel_compile_error_raises_out_of_the_model_call(monkeypatch):
    # The acceptance criterion: through a registered SeisT model, a kernel
    # refusal on the TPU path raises out of model.apply instead of
    # returning the einsum result.
    from jax.experimental import pallas as pl

    import seist_tpu
    from seist_tpu.models import api
    from seist_tpu.ops import pallas_attention as pa

    seist_tpu.load_all()
    model = api.create_model("seist_s_dpk", in_samples=512)
    variables = api.init_variables(model, in_samples=512, batch_size=1)
    x = np.zeros((1, 512, 3), np.float32)
    apply = jax.jit(lambda v, x: model.apply(v, x, train=False))
    assert np.isfinite(np.asarray(apply(variables, x))).all()  # einsum, CPU

    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    monkeypatch.setattr(pl, "pallas_call", _refusing_pallas_call)
    refused = jax.jit(lambda v, x: model.apply(v, x, train=False))
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        refused(variables, x)


def test_a_real_refusal_on_this_host_raises(rng, monkeypatch):
    # No mock: pretend the backend is TPU on this CPU host, so the genuine
    # TPU kernel is handed to a compiler that cannot take it. The error
    # must surface; the old probe turned exactly this into an einsum.
    from seist_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    q, k, v = _qkv(rng)
    with pytest.raises(Exception) as exc:
        jax.block_until_ready(fused_pooled_attention(q, k, v))
    assert not isinstance(exc.value, AssertionError)


def test_no_probe_or_status_machinery_left():
    from seist_tpu.ops import pallas_attention as pa

    for name in (
        "_kernel_usable", "_probe_kernel", "kernel_status_summary",
        "_KERNEL_STATUS", "_KERNEL_EVENTS", "_is_transient",
    ):
        assert not hasattr(pa, name), name


# -- in-kernel dropout -------------------------------------------------------


import jax.numpy as jnp


def _seed(v=1234):
    return jnp.asarray([v], jnp.int32)


def test_dropout_zero_rate_is_noop(rng):
    q, k, v = _qkv(rng)
    base = np.asarray(fused_pooled_attention(q, k, v, interpret=True))
    got = np.asarray(
        fused_pooled_attention(
            q, k, v, dropout_rate=0.0, dropout_seed=_seed(), interpret=True
        )
    )
    np.testing.assert_array_equal(got, base)


def test_dropout_mask_statistics(rng):
    # With h=1 and v = identity (M == E), the output IS the dropped
    # probability matrix — check drop fraction and survivor scaling.
    n, l, m, rate = 2, 128, 16, 0.25
    q = rng.normal(size=(n, l, 1, m)).astype(np.float32)
    k = rng.normal(size=(n, m, 1, m)).astype(np.float32)
    v = np.eye(m, dtype=np.float32)[None, :, None, :].repeat(n, axis=0)
    p = np.asarray(fused_pooled_attention(q, k, v, interpret=True))
    pd = np.asarray(
        fused_pooled_attention(
            q, k, v, dropout_rate=rate, dropout_seed=_seed(), interpret=True
        )
    )
    dropped = pd == 0.0
    frac = dropped.mean()
    assert abs(frac - rate) < 0.02, frac
    surv = ~dropped
    np.testing.assert_allclose(
        pd[surv], p[surv] / (1.0 - rate), rtol=1e-5, atol=1e-6
    )


def test_dropout_deterministic_per_seed(rng):
    q, k, v = _qkv(rng)
    a = np.asarray(
        fused_pooled_attention(
            q, k, v, dropout_rate=0.2, dropout_seed=_seed(7), interpret=True
        )
    )
    b = np.asarray(
        fused_pooled_attention(
            q, k, v, dropout_rate=0.2, dropout_seed=_seed(7), interpret=True
        )
    )
    c = np.asarray(
        fused_pooled_attention(
            q, k, v, dropout_rate=0.2, dropout_seed=_seed(8), interpret=True
        )
    )
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


@pytest.mark.parametrize("h", [1, 3])
def test_dropout_kernel_matches_einsum_fallback(rng, h):
    # Kernel (interpret) and XLA fallback share the counter-based PRNG, so
    # outputs agree including which entries were dropped — per head: the
    # kernel's in-kernel pid is program_id*H + h, matching the fallback's
    # flattened (n, h) order.
    q, k, v = _qkv(rng, l=32, m=8, h=h)
    scale = 1.0 / np.sqrt(q.shape[-1])
    want = np.asarray(
        _einsum_attention(q, k, v, scale, dropout_rate=0.3, dropout_seed=_seed())
    )
    got = np.asarray(
        fused_pooled_attention(
            q, k, v, scale, dropout_rate=0.3, dropout_seed=_seed(),
            interpret=True,
        )
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("h", [1, 3])
def test_dropout_custom_vjp_matches_einsum_grads(rng, h):
    q, k, v = _qkv(rng, n=1, l=32, m=8, h=h)
    scale = 1.0 / np.sqrt(q.shape[-1])

    def loss_fused(q, k, v):
        o = fused_pooled_attention(
            q, k, v, scale, dropout_rate=0.3, dropout_seed=_seed(),
            interpret=True,
        )
        return (o ** 2).sum()

    def loss_einsum(q, k, v):
        o = _einsum_attention(
            q, k, v, scale, dropout_rate=0.3, dropout_seed=_seed()
        )
        return (o ** 2).sum()

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
    ge = jax.grad(loss_einsum, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, ge, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
            err_msg=f"d{name}",
        )


# -- data-parallel meshes: the kernel runs per batch shard -------------------


@pytest.mark.parametrize("rate", [0.0, 0.3], ids=["nodrop", "drop0.3"])
def test_kernel_under_a_data_mesh_matches_one_device(rng, rate):
    """Under an active data-parallel mesh the kernel is shard_mapped over
    the batch rows (Mosaic kernels cannot be partitioned automatically);
    forward, gradients and the dropout masks are exactly the ones a single
    device computes for the whole batch."""
    from seist_tpu.parallel import mesh as mesh_lib

    n_dev = min(4, len(jax.devices()))
    if n_dev < 2:
        pytest.skip("needs >= 2 devices")
    q, k, v = _qkv(rng, n=2 * n_dev, l=32, m=8, h=3)
    seed = _seed() if rate else None

    def loss(q, k, v):
        o = fused_pooled_attention(
            q, k, v, dropout_rate=rate, dropout_seed=seed, interpret=True
        )
        return (o**2).sum(), o

    f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))
    (want_l, want_o), want_g = f(q, k, v)
    mesh = mesh_lib.make_mesh(data=n_dev, devices=jax.devices()[:n_dev])
    with mesh_lib.use_mesh(mesh):
        g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))
        (got_l, got_o), got_g = g(*mesh_lib.shard_batch(mesh, (q, k, v)))
    assert len(got_o.sharding.device_set) == n_dev  # stays laid over them
    np.testing.assert_allclose(
        np.asarray(got_o), np.asarray(want_o), rtol=1e-6, atol=1e-6
    )
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5
        )


# --------------------------------------------------- env surface (ISSUE 10)
class TestEnvSurface:
    """SEIST_ATTN_IMPL routing — the env contract of the dispatch."""

    def test_unknown_impl_value_rejected(self, rng, monkeypatch):
        monkeypatch.setenv("SEIST_ATTN_IMPL", "turbo")
        q, k, v = _qkv(rng)
        with pytest.raises(ValueError, match="unknown SEIST_ATTN_IMPL"):
            fused_pooled_attention(q, k, v)

    def test_einsum_forces_xla_path(self, rng, monkeypatch):
        # =einsum must bypass the kernel entirely, even where the kernel
        # would be chosen: a booby-trapped _fused proves it is not called.
        from seist_tpu.ops import pallas_attention as pa

        monkeypatch.setenv("SEIST_ATTN_IMPL", "einsum")
        monkeypatch.setattr(
            pa, "_fused",
            lambda *a, **k: (_ for _ in ()).throw(
                AssertionError("kernel path taken under =einsum")
            ),
        )
        q, k, v = _qkv(rng)
        want = np.asarray(
            _einsum_attention(q, k, v, 1.0 / np.sqrt(q.shape[-1]))
        )
        got = np.asarray(fused_pooled_attention(q, k, v))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    def test_einsum_yields_to_explicit_kernel_request(self, rng, monkeypatch):
        # A test's interpret=True beats the ambient env var.
        from seist_tpu.ops import pallas_attention as pa

        monkeypatch.setenv("SEIST_ATTN_IMPL", "einsum")
        called = {}

        def spy(q3, k3, v3, seed, scale, rate, h, interpret):
            called["interpret"] = interpret
            return pa._einsum_attention(
                q3.reshape(q3.shape[0], q3.shape[1], h, -1),
                k3.reshape(k3.shape[0], k3.shape[1], h, -1),
                v3.reshape(v3.shape[0], v3.shape[1], h, -1),
                scale,
            ).reshape(q3.shape)

        monkeypatch.setattr(pa, "_fused", spy)
        q, k, v = _qkv(rng)
        fused_pooled_attention(q, k, v, interpret=True)
        assert called == {"interpret": True}

    @pytest.mark.parametrize("on_tpu", [False, True])
    def test_unset_is_the_kernel_on_tpu_only(self, rng, monkeypatch, on_tpu):
        # No value: the kernel on TPU, the einsum elsewhere (nothing forces
        # a TPU kernel onto another backend). The old spelling of the
        # default, =fused, is gone with the probe it used to override.
        from seist_tpu.ops import pallas_attention as pa

        monkeypatch.delenv("SEIST_ATTN_IMPL", raising=False)
        monkeypatch.setattr(pa, "_on_tpu", lambda: on_tpu)
        called = {}

        def spy(q3, k3, v3, seed, scale, rate, h, interpret):
            called["hit"] = True
            return q3

        monkeypatch.setattr(pa, "_fused", spy)
        q, k, v = _qkv(rng)
        out = fused_pooled_attention(q, k, v)
        assert called == ({"hit": True} if on_tpu else {})
        assert out.shape == q.shape
        monkeypatch.setenv("SEIST_ATTN_IMPL", "fused")
        with pytest.raises(ValueError, match="unknown SEIST_ATTN_IMPL"):
            fused_pooled_attention(q, k, v)

    def test_einsum_is_honoured_on_tpu(self, rng, monkeypatch):
        # The explicit =einsum choice holds on the TPU backend too.
        from seist_tpu.ops import pallas_attention as pa

        monkeypatch.setenv("SEIST_ATTN_IMPL", "einsum")
        monkeypatch.setattr(pa, "_on_tpu", lambda: True)
        monkeypatch.setattr(
            pa, "_fused",
            lambda *a, **k: (_ for _ in ()).throw(
                AssertionError("kernel path taken under =einsum")
            ),
        )
        q, k, v = _qkv(rng)
        want = np.asarray(
            _einsum_attention(q, k, v, 1.0 / np.sqrt(q.shape[-1]))
        )
        got = np.asarray(fused_pooled_attention(q, k, v))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
