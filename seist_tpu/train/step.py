"""Jitted train / eval steps.

The reference's per-batch hot loop (/root/reference/training/train.py:75-177)
is: H2D copy -> forward -> loss -> backward -> optimizer -> NCCL allreduce.
Here the entire step is ONE jitted XLA program: forward + backward + update
fuse, and when the batch is sharded over the mesh's ``data`` axis the gradient
all-reduce is emitted by XLA over ICI — there is no DDP wrapper and no
explicit collective call.

Loss/target transforms come from the TaskSpec
(seist_tpu/taskspec.py; ref config.py:88-135), applied inside the jitted
program so e.g. the baz (cos,sin) encoding costs nothing extra.
"""

from __future__ import annotations

from functools import partial, wraps
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from seist_tpu.parallel import mesh as mesh_lib
from seist_tpu.taskspec import TaskSpec
from seist_tpu.train.precision import (
    cast_floating,
    cast_to_float32,
    precision_policy,
    resolve_dtype,
)
from seist_tpu.train.state import TrainState


def _first_call_span(jitted: Callable, name: str) -> Callable:
    """Record the jitted function's FIRST invocation as a bus span
    (``jit_first_call_ms{fn=...}``, obs/bus.py) — on a fresh process that
    call IS the compile (minutes for the big models), historically
    invisible outside stderr. Steady-state cost: one truthiness check per
    call.

    The first call runs to completion inside its span, which so also owns
    the wait for the call's inputs (the cached store's upload outlasts
    ``setup_store`` by a quarter of a minute) and one execution: a returned
    first call means a device that has run the program, to the loop and to
    whoever watches the run from outside (PERF.md, PR 35).

    The wrapper keeps what ``obs.scopes.scope_map`` needs to read the
    executable's HLO back later, on demand: the jitted function
    (``.jitted``) and the first call's types (``.first_call_types``:
    shapes, dtypes and shardings, no buffers — the state is donated)."""

    @wraps(jitted)
    def call(*args, **kwargs):
        if call.first_call_types is not None:
            return jitted(*args, **kwargs)
        from seist_tpu.obs.bus import BUS
        from seist_tpu.obs.scopes import abstract_call

        types = abstract_call(args, kwargs)
        with BUS.span("jit_first_call", fn=name):
            out = jitted(*args, **kwargs)
            jax.block_until_ready(out)
        call.first_call_types = types
        return out

    call.jitted = jitted
    call.first_call_types = None
    return call


def _apply_transforms(spec: TaskSpec, outputs, targets):
    if spec.targets_transform_for_loss is not None:
        targets = spec.targets_transform_for_loss(targets)
    if spec.outputs_transform_for_loss is not None:
        outputs = spec.outputs_transform_for_loss(outputs)
    return outputs, targets


def _forward_loss(spec: TaskSpec, loss_fn: Callable, cdtype, apply_fn) -> Callable:
    """Shared train-mode forward+loss body for the single-step and
    gradient-accumulation paths: cast params/inputs to the compute dtype,
    apply with mutable BN stats, cast outputs back to fp32, apply the task
    transforms. Returns ``compute(params, stats, inputs, targets, key) ->
    (loss, (outputs, new_stats))`` — differentiable in ``params`` (arg 0).

    A token task (``spec.tokens``) gets the scalars its model sowed into the
    ``aux`` collection in place of ``outputs``: the counts the host reads
    with the epoch's losses, where the logits would be a gigabyte nobody
    reads.
    """
    tokens = bool(getattr(spec, "tokens", False))

    def compute(params, stats, inputs, targets, key):
        has_stats = stats is not None
        # Named scopes are metadata only: they name the region a device op
        # belongs to (obs/scopes.py REGIONS) and change no generated code.
        with jax.named_scope("model"):
            variables = {"params": cast_floating(params, cdtype)}
            if has_stats:
                variables["batch_stats"] = stats
            with precision_policy(cdtype):
                out = apply_fn(
                    variables,
                    cast_floating(inputs, cdtype),
                    train=True,
                    mutable=(["batch_stats"] if has_stats else [])
                    + (["aux"] if tokens else []),
                    rngs={"dropout": key},
                )
            outputs, mutated = out if (has_stats or tokens) else (out[0], {})
            outputs = cast_to_float32(outputs)
        with jax.named_scope("loss"):
            o, t = _apply_transforms(spec, outputs, targets)
            loss = loss_fn(o, t)
        if tokens:  # sow keeps a tuple per name: the one value of this call
            outputs = {k: v[-1] for k, v in mutated.get("aux", {}).items()}
        return loss, (outputs, mutated.get("batch_stats"))

    return compute


def _apply_update(state: TrainState, grads, new_stats):
    """The optimizer's update and the new BN statistics."""
    state = state.apply_gradients(grads=grads)
    if new_stats is not None:
        state = state.replace(batch_stats=cast_to_float32(new_stats))
    return state


# Under the ``optimizer`` region scope (obs/scopes.py; metadata only).
_update = jax.named_scope("optimizer")(_apply_update)


@jax.named_scope("optimizer")
def _guarded_update(state: TrainState, grads, loss, new_stats):
    """Apply the gradient update only when loss AND global grad-norm are
    finite; otherwise return ``state`` unchanged (params, opt_state, BN
    stats, and ``step`` all keep their pre-update values, so a skipped
    step does not advance the LR schedule).

    Multi-host agreement: by the time this runs, ``grads`` have already
    been all-reduced over the mesh's ``data`` axis (XLA emits the
    collective for the batch-sharded backward), so the finite flag is
    computed from values that are bit-identical on every host — the
    gradient all-reduce IS the cross-host agreement, and no worker can
    take the skip branch while another applies the update.

    Returns ``(state, diag)`` with ``diag = {"applied": i32 0/1,
    "grad_norm": f32}``.
    """
    grad_norm = optax.global_norm(grads)
    finite = jnp.isfinite(loss) & jnp.isfinite(grad_norm)
    updated = _apply_update(state, grads, new_stats)
    # NaN grads make NaN optimizer moments; jnp.where discards the whole
    # poisoned update in one pass over the state pytree.
    state = jax.tree.map(
        lambda n, o: jnp.where(finite, n, o), updated, state
    )
    return state, {"applied": finite.astype(jnp.int32), "grad_norm": grad_norm}


def make_train_step(
    spec: TaskSpec,
    loss_fn: Callable,
    compute_dtype: Optional[str] = None,
    guard: bool = False,
) -> Callable:
    """Build ``train_step(state, inputs, targets, rng) -> (state, loss, outputs)``.

    ``rng`` is a base key; the global step is folded in so every step gets
    fresh dropout/droppath noise while the traced program stays static.

    ``compute_dtype`` 'bf16' runs the forward/backward in bfloat16 (fp32
    master params, optimizer, BN stats, softmax, loss — see
    train/precision.py); gradients flow through the cast back to the fp32
    params, so the optimizer update is full precision.

    ``guard=True`` adds the bad-update guard (:func:`_guarded_update`):
    the step then returns ``(state, loss, outputs, diag)`` where a
    non-finite loss or gradient norm leaves the state untouched and
    ``diag["applied"] == 0``. The returned ``loss`` is the raw (possibly
    non-finite) value so callers can log what happened.
    """
    cdtype = resolve_dtype(compute_dtype)

    def train_step(state: TrainState, inputs, targets, rng):
        with jax.named_scope("model"):  # the dropout masks' key
            step_rng = jax.random.fold_in(rng, state.step)
        fwd = _forward_loss(spec, loss_fn, cdtype, state.apply_fn)
        (loss, (outputs, new_stats)), grads = jax.value_and_grad(
            fwd, has_aux=True
        )(state.params, state.batch_stats, inputs, targets, step_rng)
        if guard:
            state, diag = _guarded_update(state, grads, loss, new_stats)
            return state, loss, outputs, diag
        return _update(state, grads, new_stats), loss, outputs

    return train_step


def make_multi_train_step(
    spec: TaskSpec,
    loss_fn: Callable,
    compute_dtype: Optional[str] = None,
    steps_per_call: int = 1,
    guard: bool = False,
) -> Callable:
    """Build a step that runs ``steps_per_call`` optimizer updates inside ONE
    jitted program via ``lax.scan`` over stacked micro-batches.

    ``multi_step(state, inputs_k, targets_k, rng) -> (state, mean_loss, None)``
    where every leaf of ``inputs_k``/``targets_k`` has a leading
    ``steps_per_call`` axis (k distinct batches — this is k REAL sequential
    training steps, not gradient accumulation).

    Why: each jit dispatch costs a fixed host->device round trip; for small
    steps that cost can rival the compute itself (not measured on this
    installation). Scanning k steps amortizes it k-fold. The
    per-step RNG folding uses ``state.step`` exactly like the single-step
    path, so dropout/droppath noise matches a loop of k single steps.

    The reference has no analogue (its loop is host-driven per batch,
    ref train.py:75-177). Trade-offs: per-micro-step outputs are not
    returned (train-loop metrics sample the steps that fall on the
    single-step path) and k batches must be resident at once.

    Sharding caveat: the batch axis here is axis 1, not axis 0 — do NOT
    pass this through :func:`jit_step`, whose data sharding targets the
    leading axis (it would shard the k micro-step axis across devices,
    silently computing something other than k sequential global-batch
    updates). Under a mesh, jit it directly with
    ``in_shardings=(replicated, P(None, 'data'), P(None, 'data'),
    replicated)``.
    """
    if steps_per_call <= 1:
        return make_train_step(spec, loss_fn, compute_dtype, guard=guard)
    base = make_train_step(spec, loss_fn, compute_dtype, guard=guard)

    if guard:
        # Each scanned micro-update carries its own finite check; the call
        # reports the per-micro-step applied MASK (ordered — the worker's
        # consecutive-bad tracking needs to know whether skips were
        # trailing), and the mean loss is taken over the finite
        # micro-steps only (all-skipped -> NaN, which the worker logs but
        # never feeds back into params).
        def guarded_multi_step(state: TrainState, inputs_k, targets_k, rng):
            def body(st, batch):
                x, y = batch
                st, loss, _, diag = base(st, x, y, rng)
                return st, (loss, diag["applied"])

            state, (losses, applied) = jax.lax.scan(
                body, state, (inputs_k, targets_k)
            )
            return state, _finite_mean(losses, applied), None, {
                "applied": applied
            }

        return guarded_multi_step

    def multi_step(state: TrainState, inputs_k, targets_k, rng):
        def body(st, batch):
            x, y = batch
            st, loss, _ = base(st, x, y, rng)
            return st, loss

        state, losses = jax.lax.scan(body, state, (inputs_k, targets_k))
        return state, losses.mean(), None

    return multi_step


def _finite_mean(losses, applied):
    """Mean loss over the applied (finite) micro-steps of a scanned call;
    NaN when every step was skipped (callers log it but never feed it
    back into params)."""
    n_ok = applied.sum()
    return jnp.where(
        n_ok > 0,
        jnp.where(applied > 0, losses, 0.0).sum()
        / jnp.maximum(n_ok, 1).astype(losses.dtype),
        jnp.float32(jnp.nan),
    )


def make_device_aug_train_step(
    spec: TaskSpec,
    loss_fn: Callable,
    process_rows: Callable,
    compute_dtype: Optional[str] = None,
    guard: bool = False,
) -> Callable:
    """Build the augment-inside-the-step variant (``--device-aug step``):

    ``step(state, rows, idx, aug, epoch, rng)`` where ``rows`` is a raw
    sample-row pytree (data/pipeline.RawStore batch), ``idx`` the (B,)
    global epoch indices keying the augmentation PRNG, ``aug`` the (B,)
    augment flags. ``process_rows`` (data/device_aug.make_row_processor)
    turns them into (inputs, targets) INSIDE the jitted program — the
    host never runs per-sample numpy augmentation, label synthesis, or
    Python stacking; it only gathers raw rows. Jit with
    :func:`jit_device_aug_step`.

    Returns ``(state, loss, None[, diag])`` — per-step model outputs are
    not exposed (the device path has no host-side metrics targets to
    score them against, and returning them would force a cross-device
    gather under the replicated out_shardings).
    """
    base = make_train_step(spec, loss_fn, compute_dtype, guard=guard)

    def device_aug_step(state: TrainState, rows, idx, aug, epoch, rng):
        inputs, targets = process_rows(rows, idx, aug, epoch)
        ret = base(state, inputs, targets, rng)
        if guard:
            st, loss, _, diag = ret
            return st, loss, None, diag
        st, loss, _ = ret
        return st, loss, None

    return device_aug_step


def _under_mesh(step_fn: Callable, mesh: Mesh) -> Callable:
    """``step_fn``, traced with ``mesh`` as the active mesh: the model's
    mesh-aware paths (ring attention over ``seq``, the attention kernel per
    ``data`` shard) follow the mesh the jit's shardings name."""

    @wraps(step_fn)
    def traced(*args):
        with mesh_lib.use_mesh(mesh):
            return step_fn(*args)

    return traced


def jit_device_aug_step(step_fn: Callable, mesh: Optional[Mesh]) -> Callable:
    """Jit a :func:`make_device_aug_train_step` function: rows/idx/aug
    batch-sharded on ``data``; state/epoch/rng replicated. Outputs are
    pinned replicated — without the pin GSPMD is free to hand back
    data-sharded state leaves, which then clash with the replicated
    in_shardings of the next consumer (the eval step)."""
    donate = (0,)
    if mesh is None:
        return _first_call_span(
            jax.jit(step_fn, donate_argnums=donate), "device_aug_step"
        )
    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("data"))
    return _first_call_span(
        jax.jit(
            _under_mesh(step_fn, mesh),
            in_shardings=(repl, data, data, data, repl, repl),
            out_shardings=repl,
            donate_argnums=donate,
        ),
        "device_aug_step",
    )


def make_cached_train_call(
    spec: TaskSpec,
    loss_fn: Callable,
    process_cache: Callable,
    steps_per_call: int = 1,
    compute_dtype: Optional[str] = None,
    guard: bool = False,
) -> Callable:
    """Build the scan-based epoch executor over an HBM-resident raw cache
    (``--device-aug cached``):

    ``call(state, cache, idx_k, epoch, rng) -> (state, mean_loss, None
    [, diag])`` runs ``steps_per_call`` optimizer updates inside ONE
    jitted program; each scanned step gathers its raw rows from
    ``cache`` by the (k, B) ``idx_k`` slice, augments + synthesizes
    labels on device (``process_cache`` =
    data/device_aug.make_cache_processor), and updates. The only
    per-call host->device traffic is the k*B int32 indices — per-step
    host stacking is zero, which is the whole point.

    Guarded calls return the ordered per-micro-step applied mask exactly
    like :func:`make_multi_train_step`. Jit via :func:`jit_cached_call`.
    """
    base = make_train_step(spec, loss_fn, compute_dtype, guard=guard)

    if guard:
        def guarded_call(state: TrainState, cache, idx_k, epoch, rng):
            def body(st, idx):
                x, y = process_cache(cache, idx, epoch)
                st, loss, _, diag = base(st, x, y, rng)
                return st, (loss, diag["applied"])

            state, (losses, applied) = jax.lax.scan(body, state, idx_k)
            return state, _finite_mean(losses, applied), None, {
                "applied": applied
            }

        return guarded_call

    def call(state: TrainState, cache, idx_k, epoch, rng):
        def body(st, idx):
            x, y = process_cache(cache, idx, epoch)
            st, loss, _ = base(st, x, y, rng)
            return st, loss

        state, losses = jax.lax.scan(body, state, idx_k)
        return state, losses.mean(), None

    return call


def jit_cached_call(call_fn: Callable, mesh: Optional[Mesh], cache) -> Callable:
    """Jit a :func:`make_cached_train_call` function. The cache pytree is
    sharded on its sample axis over ``data`` (matching
    pipeline.DeviceEpochCache's upload placement); the (k, B) index array
    shards its batch axis; state/epoch/rng replicate. ``cache`` is only
    consulted for its pytree structure."""
    donate = (0,)
    if mesh is None:
        return _first_call_span(
            jax.jit(call_fn, donate_argnums=donate), "cached_call"
        )
    import jax.tree_util as jtu

    repl = NamedSharding(mesh, P())
    row_sh = jtu.tree_map(lambda _: NamedSharding(mesh, P("data")), cache)
    idx_sh = NamedSharding(mesh, P(None, "data"))
    return _first_call_span(
        jax.jit(
            _under_mesh(call_fn, mesh),
            in_shardings=(repl, row_sh, idx_sh, repl, repl),
            # Replicated outputs: GSPMD would otherwise be free to hand
            # back data-sharded state leaves that clash with the eval
            # step's replicated in_shardings (observed live on the 8-dev
            # CPU mesh).
            out_shardings=NamedSharding(mesh, P()),
            donate_argnums=donate,
        ),
        "cached_call",
    )


def make_accum_train_step(
    spec: TaskSpec,
    loss_fn: Callable,
    compute_dtype: Optional[str] = None,
    accum_steps: int = 1,
    guard: bool = False,
) -> Callable:
    """Build ONE optimizer update from ``accum_steps`` micro-batch
    gradients, scanned inside a single jitted program.

    ``accum_step(state, inputs_k, targets_k, rng) -> (state, mean_loss, None)``
    where every leaf of ``inputs_k``/``targets_k`` has a leading
    ``accum_steps`` axis (same stacked layout as
    :func:`make_multi_train_step` — jit under a mesh with
    :func:`jit_multi_step`). The scan carries a running gradient sum, so
    peak memory is ONE micro-batch's activations plus one gradient pytree:
    this is how the reference's batch-500 training config
    (ref main.py:119-149) fits a memory-tight chip without changing the
    effective batch. The reference itself has no gradient accumulation
    (SURVEY.md §2.4: absent).

    Semantics vs one big-batch step:

    * gradients — mean over micro-batches == big-batch gradient for
      mean-reduced losses and equal micro sizes (exact for BN-free
      models; with BatchNorm the batch statistics couple samples, so the
      gradient matches SMALL-batch BN semantics, like torch DDP
      accumulation loops).
    * BatchNorm running stats — chained through the micro-steps, exactly
      as if the micro-batches had been separate forward passes.
    * dropout/droppath — each micro-batch folds its index into the step
      key, so noise differs per micro-batch.
    * ``state.step`` advances by ONE per call (one update), so LR
      schedules see update counts, not micro-step counts.
    """
    if accum_steps <= 1:
        return make_train_step(spec, loss_fn, compute_dtype, guard=guard)
    cdtype = resolve_dtype(compute_dtype)

    def accum_step(state: TrainState, inputs_k, targets_k, rng):
        step_rng = jax.random.fold_in(rng, state.step)
        has_stats = state.batch_stats is not None
        grad_fn = jax.value_and_grad(
            _forward_loss(spec, loss_fn, cdtype, state.apply_fn), has_aux=True
        )

        def body(carry, batch):
            grads_sum, stats, loss_sum, i = carry
            x, y = batch
            key = jax.random.fold_in(step_rng, i)
            (loss, (_, new_stats)), grads = grad_fn(state.params, stats, x, y, key)
            grads_sum = jax.tree.map(jnp.add, grads_sum, grads)
            if has_stats:
                stats = cast_to_float32(new_stats)
            return (grads_sum, stats, loss_sum + loss, i + 1), None

        zeros = jax.tree.map(jnp.zeros_like, state.params)
        carry0 = (zeros, state.batch_stats, jnp.zeros(()), jnp.zeros((), jnp.int32))
        (grads_sum, stats, loss_sum, _), _ = jax.lax.scan(
            body, carry0, (inputs_k, targets_k)
        )
        grads = jax.tree.map(lambda g: g / accum_steps, grads_sum)
        mean_loss = loss_sum / accum_steps
        if guard:
            # One NaN micro-batch poisons the summed gradient (and the
            # chained BN stats), so the finite check on the mean covers
            # every micro-step: skip the whole accumulated update.
            state, diag = _guarded_update(
                state, grads, mean_loss, stats if has_stats else None
            )
            return state, mean_loss, None, diag
        state = _update(state, grads, stats if has_stats else None)
        return state, mean_loss, None

    return accum_step


def make_eval_step(
    spec: TaskSpec, loss_fn: Callable, compute_dtype: Optional[str] = None
) -> Callable:
    """Build ``eval_step(state, inputs, targets, mask) -> (loss, outputs)``
    (the reference's no-grad validate body, validate.py:54-127).

    ``mask`` (float, shape (N,)) zeroes padded tail rows: the input pipeline
    pads the final eval batch to keep jit shapes static, so the loss is
    recombined from *per-sample* losses (vmap over batch-of-1 slices) —
    a mask-weighted mean for mean-reduced losses, a masked sum for
    sum-reduced ones (``loss_fn.reduction == 'sum'``, e.g. MousaviLoss).
    """
    sum_reduced = getattr(loss_fn, "reduction", "mean") == "sum"
    cdtype = resolve_dtype(compute_dtype)

    def eval_step(state: TrainState, inputs, targets, mask):
        with jax.named_scope("model"):
            variables = {"params": cast_floating(state.params, cdtype)}
            if state.batch_stats is not None:
                variables["batch_stats"] = state.batch_stats
            with precision_policy(cdtype):
                outputs = state.apply_fn(
                    variables, cast_floating(inputs, cdtype), train=False
                )
            outputs = cast_to_float32(outputs)

        def one(o1, t1):
            ob = jax.tree.map(lambda a: a[None], o1)
            tb = jax.tree.map(lambda a: a[None], t1)
            return loss_fn(ob, tb)

        with jax.named_scope("loss"):
            o, t = _apply_transforms(spec, outputs, targets)
            per_sample = jax.vmap(one)(o, t)
            w = mask.astype(per_sample.dtype)
            masked = (per_sample * w).sum()
            loss = (
                masked if sum_reduced else masked / jnp.maximum(w.sum(), 1.0)
            )
        return loss, outputs

    return eval_step


def jit_step(
    step_fn: Callable,
    mesh: Optional[Mesh] = None,
    donate_state: bool = True,
    n_batch_args: int = 2,
    n_extra_args: int = 1,
    span_name: str = "train_step",
) -> Callable:
    """Jit a step function with mesh shardings. Defaults fit the *train* step
    ``(state, inputs, targets, rng)``; for eval steps use :func:`jit_eval_step`.

    State (arg 0) is replicated; the next ``n_batch_args`` args (inputs,
    targets pytrees) are sharded on ``data``; the remaining ``n_extra_args``
    (rng, ...) are replicated. Without a mesh this is a plain jit (single
    device).
    """
    donate = (0,) if donate_state else ()
    if mesh is None:
        return _first_call_span(
            jax.jit(step_fn, donate_argnums=donate), span_name
        )
    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("data"))
    in_shardings = (repl,) + (data,) * n_batch_args + (repl,) * n_extra_args
    return _first_call_span(
        jax.jit(
            _under_mesh(step_fn, mesh),
            in_shardings=in_shardings,
            donate_argnums=donate,
        ),
        span_name,
    )


def jit_multi_step(
    step_fn: Callable, mesh: Optional[Mesh] = None, donate_state: bool = True
) -> Callable:
    """Jit a :func:`make_multi_train_step` function under a mesh.

    The stacked batches carry the micro-step axis FIRST and the batch axis
    SECOND, so the data sharding is ``P(None, 'data')`` — :func:`jit_step`
    would wrongly shard the micro-step axis (see make_multi_train_step's
    sharding caveat).
    """
    donate = (0,) if donate_state else ()
    if mesh is None:
        return _first_call_span(
            jax.jit(step_fn, donate_argnums=donate), "multi_step"
        )
    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P(None, "data"))
    return _first_call_span(
        jax.jit(
            _under_mesh(step_fn, mesh),
            in_shardings=(repl, data, data, repl),
            donate_argnums=donate,
        ),
        "multi_step",
    )


def jit_eval_step(step_fn: Callable, mesh: Optional[Mesh] = None) -> Callable:
    """Jit an eval step ``(state, inputs, targets, mask) -> (loss, outputs)``.

    Never donates the state (eval does not return one — donating would
    invalidate the live TrainState); inputs, targets and mask are all
    batch-sharded on ``data``.
    """
    return jit_step(
        step_fn, mesh=mesh, donate_state=False, n_batch_args=3,
        n_extra_args=0, span_name="eval_step",
    )


def fold_rngs(rng: jax.Array, epoch: int) -> jax.Array:
    """Per-epoch base key (the reference reshuffles samplers per epoch,
    train.py:381-382; here the same idea reseeds augmentation/dropout)."""
    return jax.random.fold_in(rng, epoch)
