"""The one train loop (train/worker.py ``_train_epoch``) over every feed
(train/feed.py), driven with a stub step: no model, no compile. The real
feeds run over tiny stand-ins for the loader, the raw store and the epoch
cache, on the suite's CPU mesh, so the ``step`` and packed paths — which no
other tier-1 test reaches — run their own iterators here."""

import types

import jax
import numpy as np
import pytest

from seist_tpu.data import pipeline
from seist_tpu.obs.bus import BUS
from seist_tpu.parallel import mesh as mesh_lib
from seist_tpu.train import feed as feed_lib
from seist_tpu.train import worker

STEPS, BATCH, EPOCH, LAG = 11, 8, 1, 2
# (feed, batches a call): 11 batches leave a part-group behind at 2 and at 4
CASES = [
    ("cached", 1), ("cached", 2), ("cached", 4), ("step", 1),
    ("packed", 2), ("packed", 4), ("accum", 2), ("accum", 4), ("plain", 1),
]
HOST_FED = ("packed", "accum", "plain")
case = pytest.mark.parametrize("kind,k", CASES, ids=[f"{n}-k{k}" for n, k in CASES])


class FakeLoader:
    """The Loader as the loop and the host-fed feeds use it; batch ``b``'s
    inputs are all ``b``."""

    dataset = types.SimpleNamespace(sampling_rate=lambda: 100)

    def __init__(self):
        self.start, self.epochs_set, self.starts_set = 0, [], []

    def set_epoch(self, epoch):
        self.epochs_set.append(epoch)

    def set_start_batch(self, n):
        self.start = n
        self.starts_set.append(n)

    def __iter__(self):
        start, self.start = self.start, 0
        for b in range(start, STEPS):
            x = np.full((BATCH, 2), b, np.float32)
            yield pipeline.Batch(x, x + 0.5, {}, ["{}"] * BATCH, np.ones(BATCH))


class FakeStore:
    """A RawStore's surface for ``iter_raw_batches`` / ``epoch_index_chunks``."""

    n_raw, augmentation = STEPS * BATCH, False

    def __len__(self):
        return self.n_raw

    def row_batch(self, raw_idx):
        return {"data": np.asarray(raw_idx, np.float32)[:, None]}


class FakeCache:
    arrays = {"data": np.zeros(1, np.float32)}
    store = FakeStore()
    epoch_index_chunks = pipeline.DeviceEpochCache.epoch_index_chunks


class Loss:
    """A call's loss that notes how many calls had been dispatched when
    the host read it."""

    def __init__(self, rec, call):
        self.rec, self.call = rec, call

    def __float__(self):
        self.rec.loss_reads.append((self.call, len(self.rec.dispatched)))
        return float(self.call)


class Recorder:
    """Stands in for the fault injector, the profile window, the flight
    recorder, the checkpoint manager and the span sink, and is the step."""

    def __init__(self):
        self.dispatched, self.loss_reads, self.on_step = [], [], []
        self.corrupted, self.traced, self.saves, self.steps = [], [], [], []
        self.spans, self.args = [], []

    def train_step(self, state, *step_args):
        call = len(self.dispatched)
        self.dispatched.append(BUS.gauge("global_step").value)
        self.args.append(jax.tree.map(np.asarray, step_args[:-1]))
        return state + 1, Loss(self, call), None, {"applied": np.int32(1)}

    def corrupt_inputs(self, step, inputs, n_steps=1):
        self.corrupted.append((step, n_steps))
        return inputs

    def save(self, gstep, state, **meta):
        self.saves.append((gstep, meta["data_epoch"], meta["data_batch_offset"]))

    def __call__(self, span):
        self.spans.append(span.name)


def make_feed(kind, k, loader, mesh):
    order = dict(
        seed=3, shuffle=True, batch_size=BATCH, num_shards=1, shard_index=0,
        source_ids=None, mixture_temperature=0.0,
    )
    if kind == "cached":
        return feed_lib.CachedFeed(FakeCache(), None, mesh, order, k)
    if kind == "step":
        return feed_lib.StepFeed(FakeStore(), None, mesh, order)
    if kind == "plain":
        return feed_lib.PlainFeed(loader, mesh, STEPS)
    return feed_lib.PackedFeed(loader, mesh, STEPS, k, kind == "accum")


def drive(kind, k, *, save_every=0, start_batch=0, log_step=1):
    """One epoch (number ``EPOCH``) of the loop; what happened."""
    rec, loader = Recorder(), FakeLoader()
    feed = make_feed(kind, k, loader, mesh_lib.make_mesh())
    feed.attach(watchdog=None, on_death=None, faults=types.SimpleNamespace(
        corrupt_inputs=rec.corrupt_inputs))
    run = worker._Run(
        args=types.SimpleNamespace(
            log_step=log_step, batch_size=BATCH, model_name="stub", seed=0),
        spec=types.SimpleNamespace(eval=[], tokens=False),
        mesh=None, train_loader=loader, val_loader=None, resident_val=None,
        steps_per_epoch=STEPS, epochs=EPOCH + 1, state=0,
        start_epoch=EPOCH, start_batch=start_batch,
        feed=feed, train_step=rec.train_step, eval_step=None,
        writer=None,
        ckpt_mgr=types.SimpleNamespace(save=rec.save), save_every=save_every,
        faults=types.SimpleNamespace(
            on_step=lambda s, n_steps=1: rec.on_step.append((s, n_steps))),
        watchdog=None,
        telemetry=types.SimpleNamespace(
            recorder=types.SimpleNamespace(record_step=rec.steps.append)),
        monitor=worker._BadUpdateMonitor(3, lag=LAG),
        preempt=types.SimpleNamespace(triggered=False),
        profile=types.SimpleNamespace(
            step=lambda n, loss: rec.traced.append(n),
            end_epoch=lambda losses: None),
    )
    BUS.add_span_sink(rec)
    try:
        rec.losses = worker._train_epoch(run, EPOCH)[0]
    finally:
        BUS.remove_span_sink(rec)
    rec.run, rec.loader, rec.feed = run, loader, feed
    return rec


def firsts(k, skip=0):
    """The global step of each call's first batch."""
    return [EPOCH * STEPS + c * k for c in range(skip // k, STEPS // k)]


@case
def test_calls_and_batches_done_drop_the_trailing_part_group(kind, k):
    rec = drive(kind, k)
    calls = STEPS // k
    assert (rec.feed.batches_per_call, rec.feed.updates_per_call) == (
        k, 1 if kind == "accum" else k)
    assert len(rec.dispatched) == len(rec.losses) == calls
    assert rec.run.batches_done == calls * k
    assert rec.run.state == calls and rec.run.epoch == EPOCH
    assert rec.steps == firsts(k)  # the flight recorder's step tags
    assert rec.loader.epochs_set == [EPOCH] and rec.loader.starts_set == []


@case
def test_interval_saves_where_a_call_crosses_a_boundary(kind, k):
    save_every = 3  # a multiple of no k but 1
    rec = drive(kind, k, save_every=save_every)
    crossed = [
        done for done in range(k, STEPS // k * k + 1, k)
        if any(b % save_every == 0 for b in range(done - k + 1, done + 1))
    ]
    assert crossed and [g for g, _, _ in rec.saves] == [
        EPOCH * STEPS + done for done in crossed]
    # the data position saved is the next batch to consume
    assert [(e, off) for _, e, off in rec.saves] == [
        (EPOCH, done) if done < STEPS else (EPOCH + 1, 0) for done in crossed]


@case
def test_resume_offset_rounds_down_to_a_call_boundary(kind, k):
    whole, resumed = drive(kind, k), drive(kind, k, start_batch=3)
    skip = 3 // k * k  # 3 at k = 2 re-trains one batch
    assert resumed.loader.starts_set == ([skip] if skip else [])
    assert resumed.steps == firsts(k, skip)
    assert resumed.run.batches_done == STEPS // k * k
    # the same step arguments as the whole epoch's from that call on
    want = whole.args[skip // k:]
    assert len(resumed.args) == len(want)
    for got, exp in zip(resumed.args, want):
        assert jax.tree.structure(got) == jax.tree.structure(exp)
        assert all(np.array_equal(a, b) for a, b in zip(
            jax.tree.leaves(got), jax.tree.leaves(exp)))


@case
def test_faults_see_every_batch_of_a_call(kind, k):
    rec = drive(kind, k)
    assert rec.on_step == [(f, k) for f in firsts(k)]
    # NaN injection reaches host-fed inputs only (the resolution refuses
    # it on the device-aug paths)
    assert rec.corrupted == ([(f, k) for f in firsts(k)] if kind in HOST_FED else [])


@case
def test_profile_window_counts_optimizer_updates(kind, k):
    rec = drive(kind, k)
    per_call = 1 if kind == "accum" else k
    assert rec.traced == [c * per_call for c in range(STEPS // k)]


@case
def test_progress_line_reads_a_loss_lag_calls_late(kind, k):
    rec = drive(kind, k, log_step=2)
    calls = STEPS // k
    logged = list(range(0, calls, 2))
    assert [c for c, _ in rec.loss_reads] == logged
    # read once `LAG` more calls are queued behind it; the tail at the end
    assert [n for _, n in rec.loss_reads] == [
        min(c + LAG + 1, calls) for c in logged]
    assert BUS.gauge("train_loss").value == float(logged[-1])


@case
def test_one_dispatch_span_a_call_after_the_step_gauge(kind, k):
    rec = drive(kind, k)
    calls = STEPS // k
    assert rec.spans.count("step_dispatch") == calls
    assert rec.dispatched == firsts(k)  # global_step as the step saw it
    assert rec.spans.count("host_wait") == (0 if kind == "cached" else calls)
    assert rec.spans.count("checkpoint_save") == 0
