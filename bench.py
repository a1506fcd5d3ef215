"""Benchmark: SeisT-L dpk training throughput (waveforms/sec/chip).

Runs the full jitted training step (forward + BCE loss + backward + Adam +
BatchNorm stat update) of the flagship ``seist_l_dpk`` model on synthetic
8192-sample 3-channel waveforms — the north-star metric of BASELINE.json
(DiTing waveforms/sec/chip; reference training shape `main.py:119-149`
batch 500 x 8192).

Prints ONE JSON line on stdout:
  {"metric", "value", "unit", "vs_baseline", ...diagnostics}
Diagnostic extras: step_time_ms, mfu, flops_per_waveform, dtype, device,
batch. Progress/diagnostics go to stderr so stdout stays one parseable line
even on failure (value=0 + "error" key instead of a traceback).

A run that cannot measure (no backend, a compiler refusal, a device kind
missing from the peak table) prints its error and exits non-zero; nothing
is replayed from an earlier run.

``vs_baseline`` (train mode) = measured wf/s divided by the FROZEN
analytical A100 anchor: one A100 (312 TFLOP/s bf16) assumed to reach 3%
MFU on this workload (an assumption, not a measurement). The frozen
denominator makes the ratio move linearly with our measured throughput.
Diagnostics: ``a100_analytical_wfs`` = what one A100 would do at OUR
measured MFU (equal-MFU construction, reduces to the peak-FLOPs ratio);
``vs_torch_cpu_1core`` = ratio vs the torch reference timed on this
host's single CPU core (tools/reference_baseline.json) — a magnitude
sanity check, NOT a chip-class comparison. Missing comparators are
``null``.

Env knobs: BENCH_MODEL, BENCH_BATCH, BENCH_SAMPLES, BENCH_STEPS,
BENCH_DTYPE (fp32|bf16), BENCH_MODE (train|eval|loader|stream;
stream = ops/stream.py continuous-record annotate, record-seconds/sec,
knobs BENCH_RECORD_SECONDS/BENCH_STRIDE), BENCH_STEPS_PER_CALL
(k>1 scans k optimizer updates inside one jitted call — dispatch
amortization; see train/step.py make_multi_train_step), BENCH_DONATE,
BENCH_BREAKDOWN(=0 disables the step_breakdown section)/
BENCH_BREAKDOWN_TOPK.

Every payload carries a top-level ``schema_version`` and names the device
it ran on (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Optional

_REPO = os.path.dirname(os.path.abspath(__file__))

# BENCH JSON schema version, stamped top-level on every payload. Bump when
# a consumer-visible field changes shape.
# v3: no cached/kernel_status/degraded fields, no regression section.
_SCHEMA_VERSION = 3

# Frozen analytical A100 anchor (see module docstring): 312 TFLOP/s bf16
# at an assumed 3% MFU on this workload. Frozen so vs_baseline scales with
# OUR measurement.
_A100_ANCHOR_FLOPS = 0.03 * 312e12

# bf16 dense peak FLOP/s per chip, keyed by substring of device_kind
# (Google Cloud TPU documentation, per-chip figures). A device kind that
# matches no key is an error (_peak_flops), never a default.
_PEAK_BF16 = {
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6": 918e12,
}

# HBM bandwidth per chip (bytes/s), same keys. Used for the roofline
# context: ridge intensity = peak_flops / bw; a program whose
# arithmetic intensity sits below the ridge is memory-bound and its MFU
# ceiling is intensity/ridge, not 1.0.
_HBM_BW = {
    "v4": 1.2e12,
    "v5 lite": 0.82e12,
    "v5e": 0.82e12,
    "v5p": 2.77e12,
    "v6": 1.64e12,
}


def _eprint(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def _emit(payload: dict) -> None:
    payload.setdefault("schema_version", _SCHEMA_VERSION)
    print(json.dumps(payload), flush=True)


def env_config() -> dict:
    """The benchmark configuration from the BENCH_* env knobs — the ONE
    place defaults live.

    Batch default 512: closest power of 2 to the reference's headline
    batch 500 (ref main.py:119-149). Dtype default bf16 since round 2's
    dense conv lowerings: with the grouped convs lowered as
    block-diagonal-dense/shift-FMA matmul work, bf16 compute (fp32
    params/BN-stats/loss — train/precision.py) measured +46% over fp32 in
    a same-session A/B on an earlier installation (not measured on this
    one). The torch reference trains fp32 with at most a TF32
    matmul hint (ref main.py:224-226); bf16-compute training is this
    framework's mixed-precision lever (tolerance-tested in
    tests/test_train.py::test_bf16_train_step_tracks_fp32).
    """
    return {
        "model": os.environ.get("BENCH_MODEL", "seist_l_dpk"),
        "dtype": os.environ.get("BENCH_DTYPE", "bf16"),
        "batch": int(os.environ.get("BENCH_BATCH", 512)),
        "in_samples": int(os.environ.get("BENCH_SAMPLES", 8192)),
        # Micro-steps scanned inside one jitted call (amortizes
        # per-dispatch cost; see train/step.py make_multi_train_step).
        "steps_per_call": int(os.environ.get("BENCH_STEPS_PER_CALL", 1)),
        # Active kernel-lowering overrides (SEIST_GCONV_IMPL,
        # SEIST_CHANNEL_PAD, ...), so a payload says which program it
        # timed. Empty dict for a plain default run.
        "lowering_overrides": _lowering_overrides(),
    }


def _lowering_overrides() -> dict:
    """Every SEIST_* env knob that changes the compiled program."""
    return {
        k: os.environ[k]
        for k in sorted(os.environ)
        if k.startswith("SEIST_") and os.environ[k] != ""
    }


def stream_config() -> dict:
    """Stream-mode knobs (BENCH_MODE=stream)."""
    cfg = env_config()
    window = cfg["in_samples"]
    return {
        "batch": cfg["batch"],
        "in_samples": window,
        "stride": int(os.environ.get("BENCH_STRIDE", window // 2)),
        "record_seconds": int(os.environ.get("BENCH_RECORD_SECONDS", 600)),
        "lowering_overrides": _lowering_overrides(),
    }


def _peak_flops(device_kind: str) -> float:
    dk = device_kind.lower()
    for key, peak in _PEAK_BF16.items():
        if key in dk:
            return peak
    raise KeyError(
        f"device kind {device_kind!r} is not in bench.py's peak table "
        f"({sorted(_PEAK_BF16)}): add its published peak, do not assume one"
    )


def _vs_baseline(
    wfs: float,
    model_name: Optional[str] = None,
    in_samples: Optional[int] = None,
) -> float:
    """Ratio vs the torch reference's CPU-measured number for the SAME
    model when available (tools/bench_reference.py --models ... writes
    per_model entries), else the legacy flagship number. wf/s scales
    inversely with sequence length, so a baseline recorded at a different
    in_samples is NOT comparable -> 0.0 (batch may differ: throughput is
    already per-waveform)."""
    path = os.path.join(_REPO, "tools", "reference_baseline.json")
    if os.path.exists(path):
        with open(path) as f:
            ref = json.load(f)
        entry = ref.get("per_model", {}).get(model_name) if model_name else None
        if entry is None:
            entry = ref  # legacy flat layout
        ref_wfs = entry.get("waveforms_per_sec", 0.0)
        ref_len = entry.get("in_samples")
        if ref_wfs and (
            in_samples is None or ref_len is None or ref_len == in_samples
        ):
            return round(wfs / ref_wfs, 3)
    return 0.0


def _synthetic_batch(spec, batch: int, in_samples: int, k: int = 1):
    """(inputs, loss_targets) via the real input pipeline on the synthetic
    dataset, so every registered model config benches with its true label
    shapes (dpk soft curves, pmp one-hot, emg/baz/dis values...).

    ``k > 1`` returns ``k`` distinct batches stacked on a leading axis (for
    the multi-step scan path).
    """
    import jax
    import numpy as np
    from seist_tpu.data.pipeline import Loader, from_task_spec

    ds = from_task_spec(
        spec,
        "synthetic",
        "train",
        seed=0,
        in_samples=in_samples,
        augmentation=False,
        data_split=False,
        dataset_kwargs={
            "num_events": batch * k,
            "trace_samples": max(12_000, in_samples + in_samples // 2),
        },
    )
    loader = Loader(ds, batch_size=batch, shuffle=False, num_workers=1)
    try:
        batches = []
        for b in loader:
            batches.append((b.inputs, b.loss_targets))
            if len(batches) == k:
                break
    finally:
        loader.close()
    if k == 1:
        stacked = batches[0]
    else:
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *batches)
    return jax.tree.map(jax.device_put, stacked)


def _cost_analysis(step) -> tuple:
    """(flops, bytes_accessed) of a compiled executable (best-effort;
    zeros if the backend doesn't expose cost analysis)."""
    try:
        cost = step.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return (
            float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
        )
    except Exception as e:  # noqa: BLE001 - cost analysis is best-effort
        _eprint(f"cost_analysis unavailable: {e!r}")
        return 0.0, 0.0


def _roofline(flops: float, bytes_accessed: float, device_kind: str):
    """Roofline context for the compiled step: the program's arithmetic
    intensity, from the bytes-accessed XLA's cost analysis exposes.

    Returns None when either input is unavailable. ``mfu_bound`` is the
    ceiling the MEMORY system imposes: intensity/ridge, capped at 1.0 —
    measured MFU far below it means the gap is overhead (layout copies,
    dispatch, serialization), not bandwidth."""
    peak = _peak_flops(device_kind)
    dk = device_kind.lower()
    bw = next((v for k, v in _HBM_BW.items() if k in dk), None)
    if not (flops and bytes_accessed and bw):
        return None
    intensity = flops / bytes_accessed
    ridge = peak / bw
    return {
        "bytes_accessed": round(bytes_accessed),
        "arithmetic_intensity": round(intensity, 2),
        "ridge_intensity": round(ridge, 2),
        "memory_bound": intensity < ridge,
        "mfu_bound": round(min(1.0, intensity / ridge), 4),
    }


def _setup_model(cfg: dict, tx=None):
    """Shared bench scaffolding: registry load, task spec, model, and an
    initialized TrainState at the benchmark batch shape. ``tx`` defaults
    to plain Adam (fine for eval, where the optimizer is never applied);
    bench_train passes its cyclic-schedule optimizer so the LR-schedule
    cost stays inside the timed step like production."""
    import seist_tpu
    from seist_tpu import taskspec
    from seist_tpu.models import api
    from seist_tpu.train import build_optimizer, create_train_state

    seist_tpu.load_all()
    model_name, in_samples = cfg["model"], cfg["in_samples"]
    spec = taskspec.get_task_spec(model_name)
    loss_fn = taskspec.make_loss(model_name)
    in_channels = taskspec.get_num_inchannels(model_name)
    model = api.create_model(
        model_name, in_channels=in_channels, in_samples=in_samples
    )
    variables = api.init_variables(
        model,
        in_samples=in_samples,
        in_channels=in_channels,
        batch_size=cfg["batch"],
    )
    state = create_train_state(
        model, variables, tx if tx is not None else build_optimizer("adam", 1e-3)
    )
    return spec, loss_fn, state


def measure_input_split(spec, loss_fn, cfg: dict, steps: int) -> dict:
    """Host-wait vs device-compute split of the training input pipeline,
    measured BOTH ways in the same run (BENCH_PIPELINE_STEPS knob):

    * ``host_path`` — the classic loop: per-sample numpy augmentation +
      Python stacking on the host, ``device_put``, then the jitted step.
      ``host_wait`` is everything before the device can start.
    * ``device_aug_cached`` — raw epochs resident on device
      (data/pipeline.DeviceEpochCache), augmentation + label synthesis
      inside the jitted step; the only per-step host work is handing over
      a (1, B) int32 index array.

    The per-path ``input_bound_fraction`` (utils/profiling.StepTimeSplit)
    is the input-bound→compute-bound evidence:
    host_path ~1 and cached ~0 means the chip was idling behind the input
    pipeline and no longer is.
    """
    from seist_tpu.utils.logger import logger as _logger

    # Dataset/loader construction logs to the console handler, which
    # writes to stdout — keep the BENCH stdout contract (one JSON line)
    # from picking up more noise than it already tolerates.
    _logger.enable_console(False)
    try:
        return _measure_input_split(spec, loss_fn, cfg, steps)
    finally:
        _logger.enable_console(True)


def _measure_input_split(spec, loss_fn, cfg: dict, steps: int) -> dict:
    import jax
    import jax.numpy as jnp

    from seist_tpu import taskspec as _ts
    from seist_tpu.data import device_aug as da
    from seist_tpu.data import pipeline as pl
    from seist_tpu.train import make_cached_train_call, make_train_step
    from seist_tpu.utils.profiling import StepTimeSplit

    batch, in_samples = cfg["batch"], cfg["in_samples"]
    dtype = cfg["dtype"]
    label_kinds = {
        _ts.get_kind(n) for n in _ts.flatten_io_names(spec.labels)
    }
    aug_rates = dict(
        shift_event_rate=0.2,
        add_noise_rate=0.4,
        add_gap_rate=0.4,
        drop_channel_rate=0.4,
        scale_amplitude_rate=0.4,
        pre_emphasis_rate=0.4,
        # generate_noise clears VALUE/ONEHOT labels (host path crashes,
        # device path refuses) — only enable it for soft-label specs.
        generate_noise_rate=(
            0.05 if label_kinds == {_ts.SOFT} else 0.0
        ),
    )
    n_events = max(batch, batch * (steps + 2) // 2)
    sds = pl.from_task_spec(
        spec,
        "synthetic",
        "train",
        seed=0,
        in_samples=in_samples,
        augmentation=True,
        data_split=False,
        shuffle=True,
        dataset_kwargs={
            "num_events": n_events,
            "trace_samples": in_samples + in_samples // 2,
        },
        **aug_rates,
    )
    key = jax.random.PRNGKey(0)

    def fresh_state():
        # Same construction as the headline bench (_setup_model), so the
        # split measures the program the bench actually times.
        return _setup_model(cfg)[2]

    # -- host path --------------------------------------------------------
    split_host = StepTimeSplit(skip_first=1)
    state = fresh_state()
    step = jax.jit(make_train_step(spec, loss_fn, compute_dtype=dtype))
    loader = pl.Loader(
        sds, batch_size=batch, shuffle=True, drop_last=True, num_workers=8
    )
    try:
        it, epoch = iter(loader), 0
        # Timing via StepTimeSplit's span helpers (the obs stopwatch —
        # the ONE interval clock, satellite dedup): host() covers
        # fetch/stack/stage, device() dispatch→block.
        for _ in range(steps + 1):
            with split_host.host():
                b = next(it, None)
                if b is None:
                    epoch += 1
                    loader.set_epoch(epoch)
                    it = iter(loader)
                    b = next(it)
                x = jax.device_put(b.inputs)
                y = jax.device_put(b.loss_targets)
                jax.block_until_ready((x, y))
            with split_host.device():
                state, loss, _ = step(state, x, y, key)
                jax.block_until_ready(loss)
    finally:
        loader.close()

    # -- cached device-aug path -------------------------------------------
    store = pl.RawStore.build(sds)
    cache = pl.DeviceEpochCache(store)
    acfg = da.AugConfig.from_preprocessor(
        sds.preprocessor,
        seed=0,
        raw_len=store.raw_len,
        phase_slots=store.phase_slots,
    )
    proc = da.make_cache_processor(
        acfg, sds.input_names, sds.label_names,
        n_raw=store.n_raw, augmentation=store.augmentation,
    )
    call = jax.jit(
        make_cached_train_call(
            spec, loss_fn, proc, steps_per_call=1, compute_dtype=dtype
        )
    )
    split_cached = StepTimeSplit(skip_first=1)
    state = fresh_state()

    def chunk_stream():
        epoch = 0
        while True:
            yield from (
                (epoch, c)
                for c in cache.epoch_index_chunks(
                    epoch, seed=0, shuffle=True,
                    batch_size=batch, steps_per_call=1,
                )
            )
            epoch += 1

    chunks = chunk_stream()
    for _ in range(steps + 1):
        with split_cached.host():
            epoch, idx = next(chunks)
            idx_dev = jax.block_until_ready(jnp.asarray(idx))
        with split_cached.device():
            state, loss, _ = call(
                state, cache.arrays, idx_dev, jnp.int32(epoch), key
            )
            jax.block_until_ready(loss)

    host = split_host.summary()
    cached = split_cached.summary()
    return {
        "steps": steps,
        "batch": batch,
        "cache_mib": round(cache.nbytes / 2**20, 1),
        "host_path": host,
        "device_aug_cached": cached,
        # The tentpole claim, decided from numbers measured in THIS run.
        "host_stack_removed": (
            (host["host_wait_ms_per_step"] or 0.0)
            > (cached["host_wait_ms_per_step"] or 0.0)
        ),
    }


def measure_data_plane(spec, cfg: dict, batches: int) -> dict:
    """Clean-path overhead of the data-plane I/O guard (data/io_guard.py):
    the same per-sample pipeline is timed with the guard active (retry
    wrapper + ingest validation + quarantine bookkeeping) and bypassed
    (``io_guard.disabled()``), on an already-warm synthetic dataset so
    both passes price the *pipeline*, not wavelet synthesis. Reported as
    per-batch loader-stage medians + ``overhead_frac`` — the BENCH
    evidence that self-healing reads cost a negligible slice of loader
    stage time on the fault-free path. Counters ride along so a bench run
    that DID hit faults (retries/quarantines > 0) is self-describing."""
    from seist_tpu.utils.logger import logger as _logger

    _logger.enable_console(False)
    try:
        return _measure_data_plane(spec, cfg, batches)
    finally:
        _logger.enable_console(True)


def _measure_data_plane(spec, cfg: dict, passes: int) -> dict:
    from seist_tpu.data import io_guard
    from seist_tpu.data import pipeline as pl

    in_samples = cfg["in_samples"]
    batch = min(cfg["batch"], 32)  # stage cost is per-sample; keep it cheap

    def make_sds(cache: bool):
        return pl.from_task_spec(
            spec,
            "synthetic",
            "train",
            seed=0,
            in_samples=in_samples,
            augmentation=True,
            data_split=False,
            shuffle=True,
            dataset_kwargs={
                "num_events": 2 * batch,
                "trace_samples": in_samples + in_samples // 2,
                "cache": cache,
            },
        )

    # Stage denominator: UNCACHED events — a cached synthetic "read" is a
    # ~10 us memcpy, two orders cheaper than any real dataset's decode
    # (data/base.py profile: ~1 ms/sample read stage), which would
    # overstate the guard's share of stage time ~100x; wavelet synthesis
    # is the same order as a real read and stands in for it.
    sds = make_sds(cache=False)
    n = len(sds)
    for i in range(n):  # one warm pass (page cache, numpy internals)
        sds[i]

    def full_pass_s() -> float:
        t0 = time.perf_counter()
        for i in range(n):
            sds[i]
        return time.perf_counter() - t0

    # min-of-passes, order alternated per round: a full pipeline pass is
    # hundreds of us/sample with >10% run-to-run scheduler noise, and
    # back-to-back passes over the same objects warm CPU caches for
    # whichever runs second — min() over alternated rounds strips both
    # biases and compares the two paths at their respective best.
    on_s, off_s = [], []
    for r in range(max(passes, 2)):
        if r % 2 == 0:
            on_s.append(full_pass_s())
            with io_guard.disabled():
                off_s.append(full_pass_s())
        else:
            with io_guard.disabled():
                off_s.append(full_pass_s())
            on_s.append(full_pass_s())
    stage_us = min(off_s) / n * 1e6

    # The guard delta itself, measured directly (read+validate vs bare
    # read, no preprocessing in the loop) on a CACHED clone — cheap
    # (~10 us) raw reads make the subtraction precise, where an uncached
    # read's synthesis noise would drown a microsecond-scale delta. This
    # is the number the <2%-of-stage claim rests on.
    micro = make_sds(cache=True)
    for i in range(micro.raw_size):
        micro._fetch_event(i, idx=i)  # warm: event cache + guard internals

    def micro_pass_us(guarded: bool) -> float:
        t0 = time.perf_counter()
        if guarded:
            for i in range(micro.raw_size):
                micro._fetch_event(i, idx=i)
        else:
            for i in range(micro.raw_size):
                micro._dataset[i]
        return (time.perf_counter() - t0) / micro.raw_size * 1e6

    # min over alternating rounds, same reasoning as the stage passes: a
    # single pass over a small dataset is one scheduler hiccup away from
    # a 5x-overstated delta.
    g_us, r_us = [], []
    for r in range(8):
        if r % 2 == 0:
            g_us.append(micro_pass_us(True))
            r_us.append(micro_pass_us(False))
        else:
            r_us.append(micro_pass_us(False))
            g_us.append(micro_pass_us(True))
    guard_us = max(min(g_us) - min(r_us), 0.0)

    return {
        "passes": max(passes, 2),
        "samples_per_pass": n,
        "stage_us_per_sample": round(stage_us, 1),
        "guard_us_per_sample": round(guard_us, 2),
        "overhead_frac_of_stage": round(guard_us / max(stage_us, 1e-9), 4),
        # Whole-pipeline cross-check (min-of-passes); negative = below
        # measurement noise. The claim is "small", not "exactly zero".
        "pass_overhead_frac": round(
            (min(on_s) - min(off_s)) / max(min(off_s), 1e-9), 4
        ),
        "counters": io_guard.COUNTERS.snapshot(),
    }


def measure_telemetry_overhead(step_ms: float) -> dict:
    """Clean-path cost of the per-step telemetry the train worker runs
    (two spans + a flight-recorder record + two gauge sets), measured the
    same way the io-guard overhead is (PR 5): min over repeated passes so
    a scheduler hiccup can't overstate a microsecond-scale number. The
    <1%-of-step-time acceptance figure comes from here."""
    from seist_tpu.obs.bus import MetricsBus
    from seist_tpu.obs.flight import FlightRecorder

    bus = MetricsBus()
    rec = FlightRecorder(capacity=256)
    bus.add_span_sink(rec.on_span)
    g_step = bus.gauge("global_step")
    g_loss = bus.gauge("train_loss")
    n = 2000

    def one_pass_us() -> float:
        t0 = time.perf_counter()
        for i in range(n):
            with bus.span("host_wait"):
                pass
            with bus.span("step_dispatch"):
                pass
            rec.record_step(i)
            g_step.set(i)
            g_loss.set(0.5)
        return (time.perf_counter() - t0) / n * 1e6

    one_pass_us()  # warm (dict entries, deque, histogram buckets)
    us = min(one_pass_us() for _ in range(5))
    return {
        "us_per_step": round(us, 2),
        "frac_of_step": (
            round(us / (step_ms * 1e3), 6) if step_ms else None
        ),
    }


def measure_step_breakdown(
    step_fn,
    example_args: tuple,
    device_kind: str,
    call_ms: float,
    compiled=None,
    updates_per_call: int = 1,
) -> dict:
    """The BENCH ``step_breakdown`` section (ISSUE 6 tentpole): per-op
    attribution of the measured step time.

    * analytic jaxpr walk (obs/attribution.py): top-k ops by
      roofline-modeled time with exact dot/conv FLOPs, bytes moved, and
      the per-class MFU decomposition;
    * the compiled executable's ``cost_analysis()``/``memory_analysis()``
      for the XLA-side cross-check (``model_vs_xla_flops`` ~1 means the
      analytic model and XLA agree on the FLOP count);
    * measured telemetry overhead (must stay <1% of step time).

    ``call_ms`` is the wall time of ONE jitted call (= steps_per_call
    optimizer updates), matching what ``step_fn`` traces to.
    """
    from seist_tpu.obs.attribution import attribute_step

    peak = _peak_flops(device_kind)
    dk = device_kind.lower()
    bw = next((v for k, v in _HBM_BW.items() if k in dk), None)
    bd = attribute_step(
        step_fn,
        example_args,
        peak_flops=peak,
        hbm_bw=bw,
        measured_step_ms=call_ms,
        top_k=int(os.environ.get("BENCH_BREAKDOWN_TOPK", 8)),
    )
    bd["call_time_ms"] = round(call_ms, 3)

    if compiled is not None:
        flops_x, bytes_x = _cost_analysis(compiled)
        mem = {}
        try:
            ma = compiled.memory_analysis()
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
            ):
                v = getattr(ma, k, None)
                if v is not None:
                    mem[k] = int(v)
        except Exception as e:  # noqa: BLE001 - memory analysis is
            # backend-dependent diagnostics, like _cost_analysis
            _eprint(f"memory_analysis unavailable: {e!r}")
        bd["xla"] = {
            "flops": flops_x or None,
            "bytes_accessed": bytes_x or None,
            # XLA's cost_analysis counts a scan body ONCE regardless of
            # trip count (verified in bench_train's normalization note),
            # while the analytic walk multiplies by it — normalize the
            # model side back to one update so ~1 really means agreement
            # on the packed (steps_per_call > 1) path too.
            "model_vs_xla_flops": (
                round(
                    bd["flops_total"] / max(updates_per_call, 1) / flops_x, 3
                )
                if flops_x
                else None
            ),
            "memory_analysis": mem or None,
        }

    bd["telemetry"] = measure_telemetry_overhead(call_ms)
    return bd


def bench_train(device_kind: str) -> None:
    import jax

    from seist_tpu.utils.misc import enable_compile_cache

    enable_compile_cache(verbose=True)

    from seist_tpu.train import (
        build_cyclic_schedule,
        build_optimizer,
        make_multi_train_step,
        make_train_step,
    )

    cfg = env_config()
    model_name = cfg["model"]
    in_samples = cfg["in_samples"]
    batch = cfg["batch"]
    dtype = cfg["dtype"]
    spc = cfg["steps_per_call"]
    warmup_steps = 5
    bench_steps = int(os.environ.get("BENCH_STEPS", 30))
    metric = f"{model_name}_train_throughput"
    unit = "waveforms/sec/chip"

    sched = build_cyclic_schedule(8e-5, 1e-3, total_steps=10_000)
    spec, loss_fn, state = _setup_model(cfg, tx=build_optimizer("adam", sched))

    x, y = _synthetic_batch(spec, batch, in_samples, k=spc)
    step_fn = (
        make_multi_train_step(
            spec, loss_fn, compute_dtype=dtype, steps_per_call=spc
        )
        if spc > 1
        else make_train_step(spec, loss_fn, compute_dtype=dtype)
    )
    key = jax.random.PRNGKey(0)

    # AOT-compile ONCE; the same executable serves cost analysis (FLOPs for
    # MFU) and the timed loop. State
    # donation matches the production step (train/worker.py): the optimizer
    # update reuses the old state's HBM.
    donate = os.environ.get("BENCH_DONATE", "1") != "0"
    t0 = time.time()
    step = (
        jax.jit(step_fn, donate_argnums=(0,) if donate else ())
        .lower(state, x, y, key)
        .compile()
    )
    _eprint(f"compiled in {time.time() - t0:.1f}s (donate={donate})")
    flops_per_step, bytes_per_step = _cost_analysis(step)

    t0 = time.time()
    for _ in range(warmup_steps):
        state, loss, _ = step(state, x, y, key)
    jax.block_until_ready(state.params)
    _eprint(f"warmup done ({time.time() - t0:.1f}s), loss={float(loss):.4f}")

    t0 = time.perf_counter()
    for _ in range(bench_steps):
        state, loss, _ = step(state, x, y, key)
    jax.block_until_ready(state.params)
    dt = time.perf_counter() - t0

    # With steps_per_call > 1, each call is `spc` optimizer updates on
    # `spc` distinct micro-batches; normalize everything to ONE update.
    # XLA cost_analysis counts a scan body ONCE regardless of trip count
    # (verified: the k=8 program reports the same total flops as k=1), so
    # the per-waveform divisor is `batch`, not `batch * spc`.
    wfs = batch * spc * bench_steps / dt
    step_ms = dt / (bench_steps * spc) * 1e3
    flops_per_wf = flops_per_step / batch if flops_per_step else 0.0
    peak = _peak_flops(device_kind)
    mfu = wfs * flops_per_wf / peak if flops_per_wf else 0.0

    # Comparators.
    # vs_baseline = wfs / (frozen A100 anchor wf/s); the anchor's wf/s =
    # _A100_ANCHOR_FLOPS / flops_per_wf, so the ratio scales linearly
    # with measured throughput (a 10x regression shows as 10x here).
    # a100_analytical_wfs (diagnostic) = one A100 at OUR measured MFU —
    # the equal-MFU construction that reduces to the peak-FLOPs ratio.
    vs_anchor = (
        round(wfs * flops_per_wf / _A100_ANCHOR_FLOPS, 3)
        if flops_per_wf
        else None
    )
    a100_wfs = (
        mfu * 312e12 / flops_per_wf if flops_per_wf and mfu else None
    )
    # Input-pipeline split (BENCH_PIPELINE_STEPS=0 disables): host-path
    # vs cached-device-aug host-wait/device-time per step, measured in
    # THIS run so the input_bound_fraction claim is self-contained.
    split = None
    psteps = int(os.environ.get("BENCH_PIPELINE_STEPS", 4))
    if psteps > 0:
        t_split = time.time()
        try:
            split = measure_input_split(spec, loss_fn, cfg, psteps)
            _eprint(f"input-split measured in {time.time() - t_split:.1f}s")
        except Exception as e:  # noqa: BLE001 - split is diagnostics only
            _eprint(f"input-split measurement failed: {e!r}")

    # Data-plane guard overhead (BENCH_DATA_PLANE_BATCHES=0 disables):
    # guarded vs bypassed loader stage time, measured in THIS run.
    data_plane = None
    dp_batches = int(os.environ.get("BENCH_DATA_PLANE_BATCHES", 6))
    if dp_batches > 0:
        t_dp = time.time()
        try:
            data_plane = measure_data_plane(spec, cfg, dp_batches)
            _eprint(f"data-plane overhead measured in {time.time() - t_dp:.1f}s")
        except Exception as e:  # noqa: BLE001 - diagnostics only
            _eprint(f"data-plane measurement failed: {e!r}")

    # Per-op step-time attribution (BENCH_BREAKDOWN=0 disables): the
    # step_breakdown section — top-k ops, MFU decomposition, telemetry
    # overhead.
    breakdown = None
    if int(os.environ.get("BENCH_BREAKDOWN", "1")):
        t_bd = time.time()
        try:
            breakdown = measure_step_breakdown(
                step_fn,
                (state, x, y, key),
                device_kind,
                call_ms=step_ms * spc,
                compiled=step,
                updates_per_call=spc,
            )
            _eprint(f"step breakdown traced in {time.time() - t_bd:.1f}s")
        except Exception as e:  # noqa: BLE001 - diagnostics only
            _eprint(f"step-breakdown measurement failed: {e!r}")

    payload = {
        "metric": metric,
        "value": round(wfs, 2),
        "unit": unit,
        "input_pipeline": split,
        "data_plane": data_plane,
        "input_bound_fraction": (
            (split or {}).get("host_path", {}).get("input_bound_fraction")
        ),
        "vs_baseline": vs_anchor,  # null when cost analysis gave no FLOPs
        "baseline": (
            "one A100 at a frozen 3% MFU analytical anchor "
            "(312 TFLOP/s bf16; an assumption, not a measurement)"
        ),
        "a100_analytical_wfs": round(a100_wfs, 1) if a100_wfs else None,
        "vs_torch_cpu_1core": _vs_baseline(wfs, model_name, in_samples),
        "step_time_ms": round(step_ms, 2),
        "step_breakdown": breakdown,
        "mfu": round(mfu, 4),
        "mfu_note": "vs bf16 dense peak",
        "flops_per_waveform": round(flops_per_wf),
        "roofline": _roofline(flops_per_step, bytes_per_step, device_kind),
        "dtype": dtype,
        "device": device_kind,
        "batch": batch,
        "in_samples": in_samples,
        "steps_per_call": spc,
        "lowering_overrides": cfg["lowering_overrides"],
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    _emit(payload)


def bench_eval(device_kind: str) -> None:
    """Inference/eval throughput: the jitted no-grad eval step (forward +
    masked loss, running BN stats — train/step.py make_eval_step, the body
    the reference's validate.py:54-127 runs per batch). The deployment
    half of the story (tools/predict.py, demo_predict.py) runs this same
    forward; BENCH_MODE=eval gives it a measured number."""
    import jax

    from seist_tpu.utils.misc import enable_compile_cache

    enable_compile_cache(verbose=True)

    import jax.numpy as jnp

    from seist_tpu.train import make_eval_step

    cfg = env_config()
    model_name, in_samples = cfg["model"], cfg["in_samples"]
    batch, dtype = cfg["batch"], cfg["dtype"]
    warmup_steps = 5
    bench_steps = int(os.environ.get("BENCH_STEPS", 30))

    spec, loss_fn, state = _setup_model(cfg)
    x, y = _synthetic_batch(spec, batch, in_samples)
    mask = jnp.ones((batch,), jnp.float32)

    step_fn = make_eval_step(spec, loss_fn, compute_dtype=dtype)
    t0 = time.time()
    step = jax.jit(step_fn).lower(state, x, y, mask).compile()
    _eprint(f"compiled in {time.time() - t0:.1f}s")
    flops_per_step, bytes_per_step = _cost_analysis(step)

    for _ in range(warmup_steps):
        loss, _outputs = step(state, x, y, mask)
    jax.block_until_ready(loss)
    _eprint(f"warmup done, loss={float(loss):.4f}")

    t0 = time.perf_counter()
    for _ in range(bench_steps):
        loss, _outputs = step(state, x, y, mask)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    wfs = batch * bench_steps / dt
    flops_per_wf = flops_per_step / batch if flops_per_step else 0.0
    payload = {
            "metric": f"{model_name}_eval_throughput",
            "value": round(wfs, 2),
            "unit": "waveforms/sec/chip",
            # No comparator: tools/reference_baseline.json records train
            # throughput only.
            "vs_baseline": None,
            "step_time_ms": round(dt / bench_steps * 1e3, 2),
            "mfu": round(wfs * flops_per_wf / _peak_flops(device_kind), 4),
            "mfu_note": "vs bf16 dense peak",
            "flops_per_waveform": round(flops_per_wf),
            "roofline": _roofline(
                flops_per_step, bytes_per_step, device_kind
            ),
            "dtype": dtype,
            "device": device_kind,
            "batch": batch,
            "in_samples": in_samples,
            "lowering_overrides": cfg["lowering_overrides"],
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    _emit(payload)


def bench_stream(device_kind: str) -> None:
    """Continuous-record serving throughput: ops/stream.py
    ``annotate`` — sliding-window forward + on-device overlap stitch +
    fixed-shape peak picking — over a synthetic record, reported as
    record-seconds annotated per wall-second. The reference's deployment
    surface scores one fixed window at a time (demo_predict.py:59-97);
    this is the path a real deployment runs.

    Env: BENCH_MODEL (dpk family / phasenet), BENCH_RECORD_SECONDS (600),
    BENCH_STRIDE (window//2), BENCH_SAMPLES = window (8192).
    """
    import jax
    import numpy as np

    from seist_tpu.utils.misc import enable_compile_cache

    enable_compile_cache(verbose=True)

    import seist_tpu
    from seist_tpu import taskspec
    from seist_tpu.models import api
    from seist_tpu.ops.stream import annotate

    seist_tpu.load_all()
    cfg = env_config()
    scfg = stream_config()
    model_name, window = cfg["model"], scfg["in_samples"]
    batch = scfg["batch"]
    fs = 100
    rec_seconds = scfg["record_seconds"]
    stride = scfg["stride"]
    spec = taskspec.get_task_spec(model_name)
    channel0 = spec.labels[0][0]

    model = api.create_model(model_name, in_samples=window)
    variables = api.init_variables(
        model, in_samples=window, batch_size=batch
    )

    def apply_fn(x):
        return model.apply(variables, x, train=False)

    rng = np.random.default_rng(0)
    record = rng.standard_normal((rec_seconds * fs, 3)).astype(np.float32)

    kw = dict(
        window=window,
        stride=stride,
        batch_size=batch,
        sampling_rate=fs,
        channel0=channel0,
    )
    t0 = time.time()
    annotate(apply_fn, record, **kw)  # compile + warmup
    _eprint(f"stream warmup (incl. compile) {time.time() - t0:.1f}s")
    steps = int(os.environ.get("BENCH_STEPS", 3))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = annotate(apply_fn, record, **kw)
    dt = time.perf_counter() - t0
    rss = rec_seconds * steps / dt
    payload = {
            "metric": f"{model_name}_stream_throughput",
            "value": round(rss, 2),
            "unit": "record-seconds/sec",
            "vs_baseline": None,  # the reference has no continuous path
            "record_seconds": rec_seconds,
            "in_samples": window,
            "window": window,
            "stride": stride,
            "batch": batch,
            "sampling_rate_hz": fs,
            "n_picks": int(out["ppk"].size + out["spk"].size),
            "device": device_kind,
            "dtype": "fp32",
            "lowering_overrides": scfg["lowering_overrides"],
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    _emit(payload)


def bench_loader() -> None:
    """Input-pipeline-only throughput: full augmentation, no device."""
    from tools.bench_loader import run

    run()


def main() -> None:
    """Run one benchmark mode. Any failure — no backend, a compiler
    refusal, a device kind without a published peak — propagates: the
    traceback is the error and the exit code is non-zero."""
    mode = os.environ.get("BENCH_MODE", "train")
    if mode == "loader":
        bench_loader()
        return
    import jax

    kind = jax.devices()[0].device_kind
    _peak_flops(kind)  # refuse an unlisted device before compiling for it
    {"eval": bench_eval, "stream": bench_stream}.get(mode, bench_train)(kind)


if __name__ == "__main__":
    main()
