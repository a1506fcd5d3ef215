"""The resident validation pass (data/pipeline.py ResidentEvalPass): the
first whole pass of a training run streams from the host loader and keeps its
placed batches, every later pass replays them; a broken pass keeps nothing,
a pass that does not fit streams for the whole run, and ``test_worker``
neither fills nor replays."""

import jax
import numpy as np
import pytest

import seist_tpu
from seist_tpu import obs, taskspec
from seist_tpu.data import io_guard, pipeline
from seist_tpu.obs.bus import BUS
from seist_tpu.parallel import mesh as mesh_lib
from seist_tpu.utils.logger import logger
from tests.test_worker_e2e import make_args

seist_tpu.load_all()

N_VAL = 20  # 200 synthetic events x val_size 0.1
BATCH = 8  # three batches, four padded rows in the last


def _counts():
    snap = BUS.snapshot()
    return (
        snap["counters"].get("val_samples_streamed", 0.0),
        snap["counters"].get("val_samples_replayed", 0.0),
        snap["gauges"].get("val_resident_bytes", 0.0),
    )


@pytest.fixture(scope="module")
def rig(tmp_path_factory):
    """One fixed state, its compiled eval step and a validation loader, as
    ``train_worker`` builds them."""
    from seist_tpu.models import api
    from seist_tpu.train import (
        build_optimizer,
        create_train_state,
        jit_eval_step,
        make_eval_step,
    )
    from seist_tpu.train import worker

    logger.set_logdir(str(tmp_path_factory.mktemp("resident_logs")))
    args = make_args(
        in_samples=512, batch_size=BATCH,
        dataset_kwargs={"num_events": 200, "trace_samples": 1500},
    )
    spec = taskspec.get_task_spec(args.model_name)
    mesh = mesh_lib.make_mesh()
    model = api.create_model(args.model_name, in_channels=3, in_samples=512)
    variables = api.init_variables(
        model, seed=args.seed, in_samples=512, in_channels=3
    )
    state = mesh_lib.replicate(
        mesh, create_train_state(model, variables, build_optimizer("adam", 1e-3))
    )
    eval_step = jit_eval_step(make_eval_step(spec, spec.loss()), mesh)

    def val_pass(loader, **kwargs):
        loss, metrics = worker.validate(
            args, state, eval_step, spec, loader, mesh, **kwargs
        )
        counters = {
            task: {k: np.asarray(v) for k, v in m.counters.items()}
            for task, m in metrics.items()
        }
        return loss, counters

    def new_loader():
        return worker._build_loader(args, spec, "val")

    return val_pass, new_loader, mesh


def _host(batch):
    return jax.tree.map(
        np.asarray,
        (batch.inputs, batch.loss_targets, batch.metrics_targets, batch.mask),
    )


def test_replayed_pass_is_the_streamed_pass_bit_for_bit(rig):
    val_pass, new_loader, mesh = rig
    loader = new_loader()
    try:
        assert len(loader) == 3 and len(loader.dataset) == N_VAL
        plain = val_pass(loader)  # no memo: what the parent computed
        resident = pipeline.ResidentEvalPass()
        s0, r0, _ = _counts()
        first = val_pass(loader, resident=resident)
        assert resident.ready
        s1, r1, held = _counts()
        assert (s1 - s0, r1 - r0) == (N_VAL, 0)
        replayed = val_pass(loader, resident=resident)
        s2, r2, _ = _counts()
        assert (s2 - s1, r2 - r1) == (0, N_VAL)
        for loss, counters in (first, replayed):
            assert loss == plain[0]  # floats, bit for bit
            assert counters.keys() == plain[1].keys()
            for task, c in counters.items():
                assert c.keys() == plain[1][task].keys()
                for k, v in c.items():
                    np.testing.assert_array_equal(v, plain[1][task][k])

        streamed = list(pipeline.prefetch_to_device(iter(loader), mesh))
        again = list(pipeline.eval_batches(loader, mesh, resident=resident))
        assert len(again) == len(streamed) == 3
        for a, b in zip(again, streamed):
            for x, y in zip(jax.tree.leaves(_host(a)), jax.tree.leaves(_host(b))):
                assert x.dtype == y.dtype
                np.testing.assert_array_equal(x, y)
            assert a.inputs.sharding == b.inputs.sharding
            assert a.meta == b.meta
        np.testing.assert_array_equal(
            np.asarray(again[-1].mask), [1, 1, 1, 1, 0, 0, 0, 0]
        )
        # the gauge is what the memo holds on each of the mesh's 8 devices
        total = sum(x.nbytes for b in again for x in jax.tree.leaves(_host(b)))
        assert held == total / 8
    finally:
        loader.close()


@pytest.mark.parametrize("broken_by", ["loader_death", "consumer"])
def test_memo_engages_only_after_a_whole_pass(rig, broken_by):
    val_pass, new_loader, mesh = rig
    loader = new_loader()
    sds = loader.dataset
    orig = type(sds).__getitem__
    plan = {"die_at": 10 if broken_by == "loader_death" else None}

    def getitem(self, idx):
        if plan["die_at"] is not None and idx >= plan["die_at"]:
            raise RuntimeError("loader bug")
        return orig(self, idx)

    sds.__class__ = type("DyingSDS", (type(sds),), {"__getitem__": getitem})
    resident = pipeline.ResidentEvalPass()
    try:
        if broken_by == "loader_death":
            with pytest.raises(io_guard.LoaderDeathError):
                val_pass(loader, resident=resident)
        else:
            it = pipeline.eval_batches(loader, mesh, resident=resident)
            next(it)
            it.close()  # the consumer gave up after one batch
        assert not resident.ready
        plan["die_at"] = None
        s0, r0, _ = _counts()
        val_pass(loader, resident=resident)  # streams again, and fills
        assert resident.ready
        val_pass(loader, resident=resident)
        s1, r1, _ = _counts()
        assert (s1 - s0, r1 - r0) == (N_VAL, N_VAL)
    finally:
        loader.close()


def test_pass_larger_than_the_free_memory_streams_every_pass(rig, monkeypatch):
    val_pass, new_loader, mesh = rig
    loader = new_loader()
    asked, lines = [], []

    def free_bytes():
        asked.append(1)
        return 1000

    class Log:
        def info(self, msg):
            lines.append(msg)

    monkeypatch.setattr(pipeline, "logger", Log())
    resident = pipeline.ResidentEvalPass(free_bytes=free_bytes)
    try:
        s0, r0, held0 = _counts()
        losses = [val_pass(loader, resident=resident)[0] for _ in range(3)]
        s1, r1, held1 = _counts()
        assert not resident.ready
        assert (s1 - s0, r1 - r0) == (3 * N_VAL, 0)
        assert held1 == held0
        assert len(set(losses)) == 1
        assert len(asked) == 1  # decided once, for the run
        first = next(pipeline.prefetch_to_device(iter(loader), mesh))
        need = 3 * pipeline._per_device_bytes(first)
        assert lines == [
            "validation pass stays on the host loader: "
            f"{need} bytes a device to keep it resident, 1000 free"
        ]
    finally:
        loader.close()


def test_free_device_bytes_on_the_cpu_is_the_nominal_budget():
    from seist_tpu.data import device_aug

    assert pipeline.free_device_bytes() == device_aug.hbm_budget_bytes() == 4 << 30


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """A three-epoch training run, then ``test_worker`` on its checkpoint,
    with the memo's entry points spied on."""
    from seist_tpu.train.worker import test_worker, train_worker

    logger.set_logdir(str(tmp_path_factory.mktemp("resident_run")))
    calls = []
    hold, replay = pipeline.ResidentEvalPass.hold, pipeline.ResidentEvalPass.replay

    def spy_hold(self, *a, **k):
        calls.append("hold")
        return hold(self, *a, **k)

    def spy_replay(self):
        calls.append("replay")
        return replay(self)

    pipeline.ResidentEvalPass.hold = spy_hold
    pipeline.ResidentEvalPass.replay = spy_replay
    try:
        args = make_args(
            mode="train", epochs=3, in_samples=512, batch_size=BATCH,
            dataset_kwargs={"num_events": 200, "trace_samples": 1500},
        )
        before = _counts()
        ckpt = train_worker(args)
        trained = _counts()
        train_calls = list(calls)
        text = obs.render_prometheus(BUS)
        args.checkpoint = ckpt
        test_loss = test_worker(args)
        tested = _counts()
    finally:
        pipeline.ResidentEvalPass.hold = hold
        pipeline.ResidentEvalPass.replay = replay
    return dict(before=before, trained=trained, tested=tested, text=text,
                train_calls=train_calls, calls=calls, test_loss=test_loss)


def test_training_run_fills_once_and_replays_after(run):
    assert run["train_calls"] == ["hold", "replay", "replay"]
    streamed = run["trained"][0] - run["before"][0]
    replayed = run["trained"][1] - run["before"][1]
    assert (streamed, replayed) == (N_VAL, 2 * N_VAL)
    assert run["trained"][2] > 0  # val_resident_bytes
    for name in ("seist_val_samples_replayed_total",
                 "seist_val_samples_streamed_total", "seist_val_resident_bytes"):
        assert name in run["text"], name
    metrics = BUS.snapshot()  # what a flight-recorder dump carries
    assert "val_samples_replayed" in metrics["counters"]
    assert "val_resident_bytes" in metrics["gauges"]


def test_test_worker_never_fills_or_replays(run):
    assert run["calls"] == run["train_calls"]  # nothing since the training run
    assert np.isfinite(run["test_loss"])
    # its one pass streamed: 20 test events, none replayed
    assert run["tested"][0] - run["trained"][0] == N_VAL
    assert run["tested"][1] == run["trained"][1]
    assert run["tested"][2] == run["trained"][2]
