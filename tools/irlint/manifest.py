"""The irlint program manifest: every jit boundary the repo ships,
lowered from ``eval_shape``-derived avals.

No checkpoints, no weights, no device execution: model variables come
from ``models/api.param_shapes`` (an ``eval_shape`` of flax init),
optimizer state from an ``eval_shape`` of ``create_train_state``, and
batch/target avals from ONE synthetic-dataset sample lifted to a batch
of ShapeDtypeStructs. Lowering is then pure tracing — the exact programs
XLA would compile, at zero device cost.

Programs enumerated (the serve table mirrors ``ModelPool.warmup``; the
train table the worker's dispatch in ``train/worker.py``):

* ``train/step.py`` — ``jit_step`` / ``jit_multi_step`` /
  ``jit_device_aug_step`` / ``jit_cached_call``, lowered through the
  REAL jit wrappers (state donation included, so the donation audit
  sees what actually ships);
* ``serve/aot.py`` — the AOT executable table: single-task full
  forwards and group trunk + per-task head programs, per warm bucket x
  variant, with variant weight transforms applied at the aval level
  (bf16 leaves / int8+scale packing) so the analyzed program holds the
  same weights-at-rest as the shipped executable;
* ``ops/stream.py`` — the ``annotate`` device chain
  (stitch + pick + detect).

Findings anchor to each program's REGISTRATION SITE (the ``def`` line of
the jit wrapper / warm-up builder that ships it), so suppressions and
baseline keys live in real source files like the sibling analyzers'.
"""

from __future__ import annotations

import inspect
import os
import sys
from dataclasses import dataclass, field
from functools import cached_property
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def ensure_cpu_backend() -> None:
    """Pin the CPU backend for analysis runs: lowering needs no
    accelerator, and a lint gate must not take the chip from the process
    that owns it. Must run BEFORE the first jax import; a no-op when jax
    is already imported (pytest's conftest owns the config there)."""
    if "jax" in sys.modules:
        return
    # FORCE-assign, don't setdefault: an exported JAX_PLATFORMS=tpu would
    # otherwise route the lint gate onto the device. An explicit
    # SEIST_IRLINT_BACKEND wins for anyone who really wants on-device
    # lowering.
    os.environ["JAX_PLATFORMS"] = os.environ.get(
        "SEIST_IRLINT_BACKEND", "cpu"
    )
    if os.environ["JAX_PLATFORMS"] == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            # A multi-device mesh is what the replication audit audits.
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()


# ------------------------------------------------------------------- sites
@dataclass(frozen=True)
class SiteRef:
    """Where a program is registered in source — the finding anchor."""

    file: str  # posix relpath from repo root
    line: int
    text: str  # stripped def line (the baseline identity)


def site_of(obj: Any) -> SiteRef:
    src = inspect.getsourcefile(obj)
    lines, lineno = inspect.getsourcelines(obj)
    rel = os.path.relpath(os.path.abspath(src), _REPO_ROOT).replace(
        os.sep, "/"
    )
    text = ""
    for ln in lines:
        s = ln.strip()
        if s.startswith(("def ", "class ")):
            text = s
            break
    return SiteRef(file=rel, line=lineno, text=text or lines[0].strip())


# ---------------------------------------------------------------- programs
@dataclass
class ProgramSpec:
    """One manifest entry: a traceable fn + its abstract args + the
    metadata the rule catalog keys on."""

    key: str  # e.g. "serve/seist_s/trunk/b4/bf16"
    kind: str  # "train" | "serve" | "stream"
    site: SiteRef
    fn: Callable  # unjitted body (jaxpr walks)
    args: Tuple[Any, ...]  # ShapeDtypeStruct pytrees, one per positional
    policy: str = "fp32"  # declared compute dtype of the matmul FLOPs
    coverage_min: float = 0.9
    donate: Tuple[int, ...] = ()  # the wrapper's donate_argnums
    jitted: Optional[Callable] = None  # shipped jit wrapper (for .lower)
    mesh_size: int = 1
    data_argnums: Tuple[int, ...] = ()  # args expected batch-sharded
    bucket: Optional[int] = None  # serve batch bucket
    ladder: Optional[Tuple[int, ...]] = None  # full bucket ladder
    notes: Dict[str, Any] = field(default_factory=dict)


class ProgramInfo:
    """A ProgramSpec plus lazily-computed IR views. Tracing happens at
    most twice per program (jaxpr walk + stablehlo lowering), and only
    for the views a rule actually requests."""

    def __init__(self, spec: ProgramSpec):
        self.spec = spec
        self.report: Dict[str, Any] = {
            "kind": spec.kind,
            "policy": spec.policy,
            "site": f"{spec.site.file}:{spec.site.line}",
        }

    @cached_property
    def jaxpr(self):
        import jax

        return jax.make_jaxpr(self.spec.fn)(*self.spec.args)

    @cached_property
    def lowered(self):
        import jax

        jitted = self.spec.jitted
        if jitted is not None:
            # train/step.py wrappers hide the jit behind _first_call_span;
            # @wraps exposes it as __wrapped__ — unwrap until something
            # lowerable appears, so the analysis keeps the SHIPPED
            # donate/in_shardings configuration. (A raw jax.jit function
            # also has __wrapped__ — the original python fn — so unwrap
            # only while .lower is missing.)
            while not hasattr(jitted, "lower") and hasattr(
                jitted, "__wrapped__"
            ):
                jitted = jitted.__wrapped__
        else:
            jitted = jax.jit(
                self.spec.fn, donate_argnums=self.spec.donate
            )
        return jitted.lower(*self.spec.args)

    @cached_property
    def stablehlo(self) -> str:
        return self.lowered.as_text()

    @property
    def kept_var_idx(self) -> Optional[List[int]]:
        """Original flat-arg indices the lowering KEPT (jit prunes unused
        args by default, shifting every ``%argN`` after a pruned one) —
        the alignment key for mapping declared argnums onto the lowered
        ``@main`` signature. None = unknown, assume nothing pruned."""
        try:
            kept = self.lowered._lowering.compile_args.get("kept_var_idx")
        except AttributeError:
            return None
        return sorted(kept) if kept is not None else None


# ------------------------------------------------------------ struct utils
def _lift_batch(sample: Any, batch: int):
    """One host sample pytree -> a batch of ShapeDtypeStructs, with the
    x64 host dtypes narrowed exactly like ``jnp.asarray`` under the
    default x64-disabled config."""
    import jax
    import numpy as np

    def lift(x):
        a = np.asarray(x)
        dt = {
            np.dtype(np.float64): np.dtype(np.float32),
            np.dtype(np.int64): np.dtype(np.int32),
        }.get(a.dtype, a.dtype)
        return jax.ShapeDtypeStruct((batch,) + a.shape, dt)

    return jax.tree.map(lift, sample)


def _structs_of(tree: Any):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree
    )


def variant_structs(var_structs: Any, variant: str):
    """Aval-level mirror of serve/aot.py's weight transforms: the
    analyzed program must hold the same weights-at-rest as the shipped
    executable (bf16 leaves for the bf16 variant; int8 + per-out-channel
    scale packing for int8)."""
    import jax
    import jax.numpy as jnp

    from seist_tpu.serve import aot

    if variant == "fp32":
        return var_structs
    if variant == "bf16":
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
            if jnp.issubdtype(s.dtype, jnp.floating)
            else s,
            var_structs,
        )
    if variant == "int8":
        from typing import Mapping

        def pack(tree):
            if isinstance(tree, Mapping):
                return {k: pack(v) for k, v in tree.items()}
            if (
                jnp.issubdtype(tree.dtype, jnp.floating)
                and len(tree.shape) >= 2
            ):
                return {
                    aot._INT8_MARK: jax.ShapeDtypeStruct(
                        tree.shape, jnp.int8
                    ),
                    "scale": jax.ShapeDtypeStruct(
                        tree.shape[-1:], jnp.float32
                    ),
                }
            return tree

        return pack(var_structs)
    raise ValueError(f"unknown variant {variant!r}")


def _rng_struct():
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct((2,), jnp.uint32)


def _scalar(dtype):
    import jax

    return jax.ShapeDtypeStruct((), dtype)


# ------------------------------------------------------------ model pieces
class _ModelCtx:
    """Shared per-model construction: model object, variable avals,
    abstract train state, one synthetic (inputs, targets) sample."""

    def __init__(self, model_name: str, window: int):
        from seist_tpu import taskspec
        from seist_tpu.models import api

        self.name = model_name
        self.window = int(window)
        self.spec = taskspec.get_task_spec(model_name)
        self.loss_fn = taskspec.make_loss(model_name)
        self.in_channels = taskspec.get_num_inchannels(model_name)
        self.model = api.create_model(
            model_name, in_channels=self.in_channels, in_samples=self.window
        )
        self.var_structs = api.param_shapes(
            self.model, in_samples=self.window, in_channels=self.in_channels
        )

    @cached_property
    def state_structs(self):
        import jax

        from seist_tpu.train import build_optimizer
        from seist_tpu.train.state import create_train_state

        tx = build_optimizer("adam", 1e-3)
        return jax.eval_shape(
            lambda v: create_train_state(self.model, v, tx),
            self.var_structs,
        )

    @cached_property
    def _sample(self):
        from seist_tpu.data import pipeline as pl

        sds = pl.from_task_spec(
            self.spec,
            "synthetic",
            "train",
            seed=0,
            in_samples=self.window,
            augmentation=False,
            data_split=False,
            shuffle=False,
            dataset_kwargs={
                "num_events": 2,
                "trace_samples": max(self.window + 64, 256),
            },
        )
        inputs, targets, _, _ = sds[0]
        return inputs, targets

    def batch_structs(self, batch: int):
        inputs, targets = self._sample
        return _lift_batch(inputs, batch), _lift_batch(targets, batch)

    def x_struct(self, batch: int):
        import jax
        import jax.numpy as jnp

        return jax.ShapeDtypeStruct(
            (batch, self.window, self.in_channels), jnp.float32
        )


# -------------------------------------------------------- train programs
def _mesh():
    from seist_tpu.parallel import mesh as mesh_lib

    return mesh_lib.make_mesh()


def train_programs(
    model_name: str = "phasenet",
    *,
    compute_dtype: Optional[str] = None,
    window: int = 512,
    batch: int = 8,  # divisible by the analysis mesh's data axis
    steps_per_call: int = 2,
    include: Sequence[str] = ("step", "multi_step"),
    guard: bool = True,
) -> List[ProgramSpec]:
    """``jit_step`` / ``jit_multi_step`` programs for one model at one
    compute dtype, lowered through the shipped wrappers (mesh shardings
    and donation resolution exactly as ``train/worker.py`` builds them).
    """
    import seist_tpu
    from seist_tpu.train import step as step_mod

    seist_tpu.load_all()
    ctx = _ModelCtx(model_name, window)
    mesh = _mesh()
    xi, yt = ctx.batch_structs(batch)
    policy = "bf16" if compute_dtype == "bf16" else "fp32"
    donate = (0,)  # what jit_step & co. declare
    out: List[ProgramSpec] = []
    tag = compute_dtype or "fp32"

    if "step" in include:
        fn = step_mod.make_train_step(
            ctx.spec, ctx.loss_fn, compute_dtype=compute_dtype, guard=guard
        )
        out.append(
            ProgramSpec(
                key=f"train/jit_step/{model_name}/{tag}",
                kind="train",
                site=site_of(step_mod.jit_step),
                fn=fn,
                args=(ctx.state_structs, xi, yt, _rng_struct()),
                policy=policy,
                donate=donate,
                jitted=step_mod.jit_step(fn, mesh),
                mesh_size=int(mesh.devices.size),
                data_argnums=(1, 2),
            )
        )
    if "multi_step" in include and steps_per_call > 1:
        fn = step_mod.make_multi_train_step(
            ctx.spec,
            ctx.loss_fn,
            compute_dtype=compute_dtype,
            steps_per_call=steps_per_call,
            guard=guard,
        )
        import jax

        stack = lambda s: jax.tree.map(  # noqa: E731
            lambda a: type(a)((steps_per_call,) + a.shape, a.dtype), s
        )
        out.append(
            ProgramSpec(
                key=(
                    f"train/jit_multi_step/{model_name}/{tag}"
                    f"/k{steps_per_call}"
                ),
                kind="train",
                site=site_of(step_mod.jit_multi_step),
                fn=fn,
                args=(ctx.state_structs, stack(xi), stack(yt), _rng_struct()),
                policy=policy,
                donate=donate,
                jitted=step_mod.jit_multi_step(fn, mesh),
                mesh_size=int(mesh.devices.size),
                data_argnums=(1, 2),
            )
        )
    return out


def device_aug_programs(
    model_name: str = "phasenet",
    *,
    compute_dtype: Optional[str] = None,
    window: int = 128,
    batch: int = 8,  # divisible by the analysis mesh's data axis
    steps_per_call: int = 2,
    num_events: int = 8,
    guard: bool = True,
) -> List[ProgramSpec]:
    """``jit_device_aug_step`` + ``jit_cached_call`` programs. A tiny
    synthetic RawStore supplies the row-pytree STRUCTURE (decode of
    ``num_events`` miniature traces — host work, no device compute); the
    actual rows/cache enter the analysis as avals only."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import seist_tpu
    from seist_tpu.data import device_aug as da
    from seist_tpu.data import pipeline as pl
    from seist_tpu.train import step as step_mod

    seist_tpu.load_all()
    ctx = _ModelCtx(model_name, window)
    mesh = _mesh()
    sds = pl.from_task_spec(
        ctx.spec,
        "synthetic",
        "train",
        seed=0,
        in_samples=window,
        augmentation=True,
        data_split=False,
        shuffle=True,
        # Real augmentation rates: a rate-0 config makes the traced
        # program drop the aug flags entirely (python-level gates), which
        # is NOT the program the worker ships.
        shift_event_rate=0.5,
        add_noise_rate=0.5,
        add_gap_rate=0.5,
        drop_channel_rate=0.5,
        scale_amplitude_rate=0.5,
        pre_emphasis_rate=0.5,
        generate_noise_rate=0.1,
        add_event_rate=0.5,
        max_event_num=2,
        dataset_kwargs={
            "num_events": num_events,
            "trace_samples": max(window + 64, 256),
        },
    )
    store = pl.RawStore.build(sds)
    cfg = da.AugConfig.from_preprocessor(
        sds.preprocessor,
        seed=0,
        raw_len=store.raw_len,
        phase_slots=store.phase_slots,
    )
    policy = "bf16" if compute_dtype == "bf16" else "fp32"
    donate = (0,)  # what jit_step & co. declare
    tag = compute_dtype or "fp32"
    rows_struct = _structs_of(
        jax.tree.map(np.asarray, store.row_batch(np.arange(batch)))
    )
    out: List[ProgramSpec] = []

    aug_fn = step_mod.make_device_aug_train_step(
        ctx.spec,
        ctx.loss_fn,
        da.make_row_processor(cfg, sds.input_names, sds.label_names),
        compute_dtype=compute_dtype,
        guard=guard,
    )
    out.append(
        ProgramSpec(
            key=f"train/jit_device_aug_step/{model_name}/{tag}",
            kind="train",
            site=site_of(step_mod.jit_device_aug_step),
            fn=aug_fn,
            args=(
                ctx.state_structs,
                rows_struct,
                jax.ShapeDtypeStruct((batch,), jnp.int32),
                jax.ShapeDtypeStruct((batch,), jnp.bool_),
                _scalar(jnp.int32),
                _rng_struct(),
            ),
            policy=policy,
            donate=donate,
            jitted=step_mod.jit_device_aug_step(aug_fn, mesh),
            mesh_size=int(mesh.devices.size),
            data_argnums=(1, 2, 3),
        )
    )

    cache_struct = _structs_of(jax.tree.map(np.asarray, store.arrays))
    call_fn = step_mod.make_cached_train_call(
        ctx.spec,
        ctx.loss_fn,
        da.make_cache_processor(
            cfg,
            sds.input_names,
            sds.label_names,
            n_raw=store.n_raw,
            augmentation=store.augmentation,
        ),
        steps_per_call=steps_per_call,
        compute_dtype=compute_dtype,
        guard=guard,
    )
    out.append(
        ProgramSpec(
            key=(
                f"train/jit_cached_call/{model_name}/{tag}/k{steps_per_call}"
            ),
            kind="train",
            site=site_of(step_mod.jit_cached_call),
            fn=call_fn,
            args=(
                ctx.state_structs,
                cache_struct,
                jax.ShapeDtypeStruct((steps_per_call, batch), jnp.int32),
                _scalar(jnp.int32),
                _rng_struct(),
            ),
            policy=policy,
            donate=donate,
            jitted=step_mod.jit_cached_call(call_fn, mesh, cache_struct),
            mesh_size=int(mesh.devices.size),
            data_argnums=(2,),
        )
    )
    return out


# --------------------------------------------------------- serve programs
# The in-trace variant conventions are NOT re-implemented here: the
# manifest lowers aot.variant_compute / aot.head_variant_compute — the
# exact builders serve/pool.py ships — over aval-level variables
# (variant_structs), so the audited program cannot drift from the
# shipped executable.
def _serve_full_fn(model, variant: str):
    from seist_tpu.serve import aot

    return aot.variant_compute(
        lambda v, x: model.apply(v, x, train=False), variant
    )


def _trunk_fn(model, variant: str):
    from seist_tpu.models.seist import backbone_apply
    from seist_tpu.serve import aot

    # cast_outputs=False: bf16 features flow to bf16 heads.
    return aot.variant_compute(
        lambda v, x: backbone_apply(model, v, x), variant,
        cast_outputs=False,
    )


def _head_fn(model, variant: str):
    from seist_tpu.serve import aot

    return aot.head_variant_compute(model, variant)


def serve_programs(
    model_name: str = "phasenet",
    *,
    buckets: Sequence[int] = (4,),
    ladder: Sequence[int] = (1, 2, 4),
    variants: Sequence[str] = ("fp32", "bf16"),
    window: int = 512,
) -> List[ProgramSpec]:
    """Single-task AOT programs: full forward per bucket x variant,
    anchored at ``ModelEntry.build_programs`` (the shipped warm-up)."""
    import seist_tpu
    from seist_tpu.serve.pool import ModelEntry

    seist_tpu.load_all()
    ctx = _ModelCtx(model_name, window)
    site = site_of(ModelEntry.build_programs)
    out: List[ProgramSpec] = []
    for variant in variants:
        vs = variant_structs(ctx.var_structs, variant)
        fn = _serve_full_fn(ctx.model, variant)
        for b in buckets:
            out.append(
                ProgramSpec(
                    key=f"serve/{model_name}/full/b{b}/{variant}",
                    kind="serve",
                    site=site,
                    fn=fn,
                    args=(vs, ctx.x_struct(b)),
                    policy="bf16" if variant == "bf16" else "fp32",
                    bucket=b,
                    ladder=tuple(ladder),
                    notes={"variant": variant},
                )
            )
    return out


def group_programs(
    group: str = "seist_s",
    tasks: Sequence[str] = ("dpk", "emg", "dis"),
    *,
    buckets: Sequence[int] = (4,),
    ladder: Sequence[int] = (1, 2, 4),
    variants: Sequence[str] = ("fp32", "bf16"),
    window: int = 512,
) -> List[ProgramSpec]:
    """Shared-trunk group AOT programs: trunk per bucket x variant plus
    each task head on the trunk's feature avals — the fan-out table
    ``MultiTaskEntry.build_programs`` compiles at replica load."""
    import jax

    import seist_tpu
    from seist_tpu.serve.pool import MultiTaskEntry

    seist_tpu.load_all()
    ctxs = {t: _ModelCtx(f"{group}_{t}", window) for t in tasks}
    first = ctxs[tasks[0]]
    site = site_of(MultiTaskEntry.build_programs)
    out: List[ProgramSpec] = []
    for variant in variants:
        policy = "bf16" if variant == "bf16" else "fp32"
        trunk_fn = _trunk_fn(first.model, variant)
        trunk_vs = variant_structs(first.var_structs, variant)
        for b in buckets:
            x = first.x_struct(b)
            out.append(
                ProgramSpec(
                    key=f"serve/{group}/trunk/b{b}/{variant}",
                    kind="serve",
                    site=site,
                    fn=trunk_fn,
                    args=(trunk_vs, x),
                    policy=policy,
                    bucket=b,
                    ladder=tuple(ladder),
                    notes={"variant": variant},
                )
            )
            feats = jax.eval_shape(trunk_fn, trunk_vs, x)
            for t in tasks:
                ctx = ctxs[t]
                out.append(
                    ProgramSpec(
                        key=f"serve/{group}/head:{t}/b{b}/{variant}",
                        kind="serve",
                        site=site,
                        fn=_head_fn(ctx.model, variant),
                        args=(
                            variant_structs(ctx.var_structs, variant),
                            feats,
                            x,
                        ),
                        policy=policy,
                        bucket=b,
                        ladder=tuple(ladder),
                        notes={"variant": variant},
                    )
                )
    return out


# --------------------------------------------------------- repick programs
def repick_programs(
    model_name: str = "phasenet",
    *,
    batch: int = 8,
    window: int = 512,
    variants: Sequence[str] = ("int8",),
) -> List[ProgramSpec]:
    """The batch repick engine's int8-shards program (ISSUE 18): int8
    rows + per-row per-channel scales enter the device program AS
    STORED; the dequant (``engine.dequant_rows``) is fused ahead of the
    z-score prep and the variant forward — the exact per-micro-batch
    step body ``RepickEngine._step_fn`` builds (the shipped executable
    ``lax.map``s it over batches_per_call). Lowering it here keeps the
    host-transfer and matmul-coverage audits on the path forever: the
    widening must happen IN-program, never before the device boundary."""
    import jax
    import jax.numpy as jnp

    import seist_tpu
    from seist_tpu.batch import engine as engine_mod
    from seist_tpu.serve import aot

    seist_tpu.load_all()
    ctx = _ModelCtx(model_name, window)
    site = site_of(engine_mod.RepickEngine._step_fn)
    out: List[ProgramSpec] = []
    for variant in variants:
        vs = variant_structs(ctx.var_structs, variant)
        compute = aot.variant_compute(
            lambda v, x: ctx.model.apply(v, x, train=False), variant
        )

        def step(v, q, scale, _compute=compute):
            x = engine_mod.normalize_transpose(
                engine_mod.dequant_rows(q, scale)
            )
            return _compute(v, x)

        out.append(
            ProgramSpec(
                key=f"repick/{model_name}/b{batch}/{variant}+i8shards",
                kind="serve",
                site=site,
                fn=step,
                args=(
                    vs,
                    jax.ShapeDtypeStruct(
                        (batch, ctx.in_channels, window), jnp.int8
                    ),
                    jax.ShapeDtypeStruct(
                        (batch, ctx.in_channels), jnp.float32
                    ),
                ),
                policy="bf16" if variant == "bf16" else "fp32",
                bucket=batch,
                notes={"variant": variant, "shards": "int8"},
            )
        )
    return out


# --------------------------------------------------------- stream program
def stream_program(
    *, window: int = 512, n_windows: int = 15, record_len: int = 4096
) -> ProgramSpec:
    """The ``ops/stream.annotate`` device chain downstream of the model
    forward: stitch overlapping window probabilities, pick phases,
    detect intervals — one program chain, one final host transfer."""
    import jax
    import jax.numpy as jnp

    from seist_tpu.ops import stream
    from seist_tpu.ops.postprocess import detect_events, pick_peaks

    def chain(probs, offsets):
        curve = stream.stitch_probs(probs, offsets, record_len)
        ppk = pick_peaks(curve[None, :, 1], 0.3, 50, 64)
        spk = pick_peaks(curve[None, :, 2], 0.3, 50, 64)
        det = detect_events(1.0 - curve[:, 0][None, :], 0.5, 64)
        return ppk, spk, det

    return ProgramSpec(
        key="stream/annotate/stitch_pick_detect",
        kind="stream",
        site=site_of(stream.annotate),
        fn=chain,
        args=(
            jax.ShapeDtypeStruct((n_windows, window, 3), jnp.float32),
            jax.ShapeDtypeStruct((n_windows,), jnp.int32),
        ),
        notes={"record_len": record_len, "n_windows": n_windows},
    )


# -------------------------------------------------------- default manifest
def default_manifest(
    *,
    window: int = 512,
    batch: int = 8,  # divisible by the analysis mesh's data axis
    buckets: Sequence[int] = (4,),
    ladder: Sequence[int] = (1, 2, 4),
    variants: Sequence[str] = ("fp32", "bf16"),
    serve_group: str = "seist_s",
    group_tasks: Sequence[str] = ("dpk", "emg", "dis"),
    match: Optional[Callable[[str], bool]] = None,
) -> List[ProgramSpec]:
    """The gate manifest: every shipped jit boundary, sized to lower in
    about a minute on the CPU backend. Tests build narrower manifests
    directly from the builders above (and wider ones — all five heads,
    seist_l — where a number must be pinned).

    ``match(key) -> bool`` prunes at the SECTION level before any model
    is even constructed — candidate keys are deterministic strings, so a
    subset run (``python -m tools.irlint 'serve/phasenet/*'``) never pays
    for building the programs it is not going to lint."""
    keep = match or (lambda _k: True)

    def _keys_train(model: str, tag: str, include, k: int) -> List[str]:
        out = []
        if "step" in include:
            out.append(f"train/jit_step/{model}/{tag}")
        if "multi_step" in include:
            out.append(f"train/jit_multi_step/{model}/{tag}/k{k}")
        return out

    programs: List[ProgramSpec] = []
    sections = [
        (
            _keys_train("phasenet", "fp32", ("step",), 2),
            lambda: train_programs(
                "phasenet", compute_dtype=None, window=window, batch=batch,
                include=("step",),
            ),
        ),
        (
            _keys_train(
                "seist_s_dpk", "bf16", ("step", "multi_step"), 2
            ),
            lambda: train_programs(
                "seist_s_dpk", compute_dtype="bf16", window=window,
                batch=batch, include=("step", "multi_step"),
            ),
        ),
        (
            [
                "train/jit_device_aug_step/phasenet/fp32",
                "train/jit_cached_call/phasenet/fp32/k2",
            ],
            lambda: device_aug_programs(
                "phasenet", compute_dtype=None, window=min(window, 128),
                batch=batch,
            ),
        ),
        (
            [
                f"serve/phasenet/full/b{b}/{v}"
                for b in buckets
                for v in variants
            ],
            lambda: serve_programs(
                "phasenet", buckets=buckets, ladder=ladder,
                variants=variants, window=window,
            ),
        ),
        (
            [
                f"serve/{serve_group}/{part}/b{b}/{v}"
                for b in buckets
                for v in variants
                for part in ["trunk"] + [f"head:{t}" for t in group_tasks]
            ],
            lambda: group_programs(
                serve_group, group_tasks, buckets=buckets, ladder=ladder,
                variants=variants, window=window,
            ),
        ),
        (
            [f"repick/phasenet/b{batch}/int8+i8shards"],
            lambda: repick_programs(
                "phasenet", batch=batch, window=window, variants=("int8",)
            ),
        ),
        (
            ["stream/annotate/stitch_pick_detect"],
            lambda: [stream_program(window=window)],
        ),
    ]
    for keys, build in sections:
        if not any(keep(k) for k in keys):
            continue
        programs.extend(p for p in build() if keep(p.key))
    return programs
