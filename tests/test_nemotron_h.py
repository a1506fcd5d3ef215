"""NemotronH on the training path (models/nemotron_h.py, ops/ssd.py,
ops/moe.py, ops/causal_attention.py) against the plain reference
(benchmarks/reference/nemotron_h.py), at the CPU-sized preset: hidden 64,
nine blocks ``MEMEM*EME``, 16 experts of which 4 held, vocabulary 256,
length 256 with chunk 32."""

import dataclasses
import glob
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import seist_tpu
from seist_tpu import taskspec
from seist_tpu.models import api
from seist_tpu.models import nemotron_h as nh
from seist_tpu.ops import moe
from seist_tpu.ops.ssd import ssd_chunked, ssd_recurrent

_BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")


def _load_reference():
    """By file, not through sys.path: ``benchmarks/tools`` is a package
    and would shadow the repo's ``tools/`` for every test module collected
    after this one."""
    spec = importlib.util.spec_from_file_location(
        "bench_reference_nemotron_h",
        os.path.join(_BENCH, "reference", "nemotron_h.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

seist_tpu.load_all()

CFG = nh.NemotronHConfig(**nh.TINY)
CONFIG = {"architecture": dataclasses.asdict(CFG)}
LENGTH = 256


def highest(fn):
    def run(*a, **k):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **k)
    return run


@pytest.fixture(scope="module")
def tiny():
    """Reference weights (the program's names), ids, and both sides'
    logits, loss and gradients in float32."""
    model = nh.NemotronH(cfg=CFG)
    variables = jax.jit(highest(lambda k: ref.init(k, CONFIG)))(jax.random.PRNGKey(3))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, LENGTH), 0, CFG.vocab_size)
    targets = jnp.concatenate([ids[:, 1:], -jnp.ones((2, 1), jnp.int32)], axis=1)
    loss_fn = taskspec.get_task_spec("nemotron3_tiny").loss()

    def prog_loss(p):
        return loss_fn(model.apply({"params": p}, ids, train=False), targets)

    out = {
        "model": model, "variables": variables, "ids": ids,
        "prog_logits": jax.jit(highest(
            lambda v: model.apply(v, ids, train=False)))(variables),
        "ref_logits": jax.jit(highest(
            lambda v: ref.forward(v, ids, CONFIG)))(variables),
    }
    out["prog_loss"], out["prog_grads"] = jax.jit(highest(
        jax.value_and_grad(prog_loss)))(variables["params"])
    out["ref_loss"], out["ref_grads"] = jax.jit(highest(jax.value_and_grad(
        lambda p: ref.loss({"params": p}, ids, CONFIG))))(variables["params"])
    for side in ("prog_grads", "ref_grads"):  # by leaf name, for the cases
        out[side] = {jax.tree_util.keystr(p): g for p, g in
                     jax.tree_util.tree_leaves_with_path(out[side])}
    return out


def test_program_and_reference_share_parameter_names(tiny):
    mine = api.param_shapes(tiny["model"], in_samples=LENGTH, in_channels=1)["params"]
    theirs = tiny["variables"]["params"]
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert jax.tree.map(lambda a: a.shape, mine) == jax.tree.map(
        lambda a: a.shape, theirs)
    assert {k[6] if k.startswith("block_") else k for k in mine} >= {"embed"}
    kinds = {CFG.pattern[int(k.split("_")[1])] for k in mine if k.startswith("block_")}
    assert kinds == {"M", "E", "*"}  # every block kind is in the preset


def test_logits_match_the_reference(tiny):
    """float32 on both sides: the gap is the order of summation (chunked
    against literal scan, sorted rows against a masked loop), a few ulp of
    logits of size 1."""
    gap = float(jnp.abs(tiny["prog_logits"] - tiny["ref_logits"]).max())
    assert float(jnp.abs(tiny["ref_logits"]).max()) > 0.3
    assert gap < 5e-6, gap


def test_loss_matches_the_reference(tiny):
    assert abs(float(tiny["prog_loss"]) - float(tiny["ref_loss"])) < 1e-5
    assert abs(float(tiny["ref_loss"]) - np.log(CFG.vocab_size)) < 0.1


_LEAVES = [
    jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(
        jax.eval_shape(lambda: ref.init(jax.random.PRNGKey(0), CONFIG))["params"])
]


@pytest.mark.parametrize("leaf", _LEAVES)
def test_gradient_of_every_leaf_matches_the_reference(tiny, leaf):
    """jax.grad of the program's loss against jax.grad of the reference's,
    leaf by leaf, in float32: 2e-5 of the leaf's norm (summation order)."""
    mine, theirs = tiny["prog_grads"][leaf], tiny["ref_grads"][leaf]
    norm = float(jnp.linalg.norm(theirs))
    assert norm > 0, "a leaf without a gradient would never train"
    assert float(jnp.linalg.norm(mine - theirs)) < 2e-5 * norm


@pytest.mark.parametrize("length", [64, 96, 50, 37, 5])
def test_chunked_scan_is_the_recurrence(length):
    """Lengths that are and are not multiples of the chunk (32), forward,
    final state and the gradient of every input."""
    b, h, p, g, n = 2, 4, 8, 2, 16
    k = jax.random.split(jax.random.PRNGKey(length), 5)
    x = jax.random.normal(k[0], (b, length, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, length, h)) - 1)
    a = -jnp.exp(0.5 * jax.random.normal(k[2], (h,)))
    bm = jax.random.normal(k[3], (b, length, g, n))
    cm = jax.random.normal(k[4], (b, length, g, n))
    args = (x, dt, a, bm, cm)
    chunked = jax.jit(highest(lambda *t: ssd_chunked(*t, chunk=32)))
    literal = jax.jit(highest(ssd_recurrent))
    (y1, s1), (y2, s2) = chunked(*args), literal(*args)
    scale = float(jnp.abs(y2).max())
    assert float(jnp.abs(y1 - y2).max()) < 2e-6 * scale
    assert float(jnp.abs(s1 - s2).max()) < 2e-6 * float(jnp.abs(s2).max())
    g1 = jax.jit(highest(jax.grad(
        lambda *t: jnp.sum(jnp.sin(ssd_chunked(*t, chunk=32)[0])),
        argnums=(0, 1, 2, 3, 4))))(*args)
    g2 = jax.jit(highest(jax.grad(
        lambda *t: jnp.sum(jnp.sin(ssd_recurrent(*t)[0])),
        argnums=(0, 1, 2, 3, 4))))(*args)
    for u, v in zip(g1, g2):  # sums of ~100 float32 terms, in another order
        assert float(jnp.abs(u - v).max()) < 1e-4 * float(jnp.abs(v).max())


def test_scan_carries_an_initial_state():
    b, length, h, p, g, n = 1, 64, 2, 4, 1, 8
    k = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(k[0], (b, length, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, length, h)))
    a = -jnp.ones((h,))
    bm, cm = (jax.random.normal(kk, (b, length, g, n)) for kk in k[2:4])
    whole, _ = highest(ssd_chunked)(x, dt, a, bm, cm, chunk=16)
    _, mid = highest(ssd_chunked)(
        x[:, :32], dt[:, :32], a, bm[:, :32], cm[:, :32], chunk=16)
    rest, _ = highest(ssd_chunked)(
        x[:, 32:], dt[:, 32:], a, bm[:, 32:], cm[:, 32:], chunk=16,
        initial_state=mid)
    assert float(jnp.abs(rest - whole[:, 32:]).max()) < 1e-5


# ------------------------------------------------------------- expert layer
def _moe_case(seed=0, tokens=192, skew=0.0):
    a = CONFIG["architecture"]
    d, e, f = a["hidden_size"], a["n_routed_experts"], a["moe_intermediate_size"]
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    m = {
        "router": 0.5 * jax.random.normal(k[0], (d, e)),
        "experts_up": 0.3 * jax.random.normal(k[1], (e, d, f)),
        "experts_down": 0.3 * jax.random.normal(k[2], (e, f, d)),
        "shared_up": 0.3 * jax.random.normal(k[3], (d, 2 * f)),
        "shared_down": 0.3 * jax.random.normal(k[4], (2 * f, d)),
    }
    x = jax.random.normal(k[5], (tokens, d))
    if skew:  # every token leans the same way: expert 1 takes most slots
        m["router"] = m["router"].at[:, 1].set(skew)
        x = x + 1.0
    return a, m, x


def test_shares_add_up_to_the_uncut_layer():
    """The share ties to the model: the routed parts of all 4 shares of an
    E block (4 of 16 experts each) plus the shared expert counted once are
    the uncut reference's block."""
    a, m, x = _moe_case()
    uncut = {**a, "experts_held": [0, a["n_routed_experts"]]}
    whole = highest(ref.moe_mixer)(m, x, uncut)
    ids, w = highest(moe.route)(
        x, m["router"], jnp.zeros((a["n_routed_experts"],)),
        top_k=a["num_experts_per_tok"], scaling=a["routed_scaling_factor"])
    total = highest(ref.relu2_mlp)(x, m["shared_up"], m["shared_down"])
    slots = 0
    for first in range(0, a["n_routed_experts"], 4):
        part, stats = highest(moe.local_experts)(
            x, ids, w, m["experts_up"][first:first + 4],
            m["experts_down"][first:first + 4], first=first,
            capacity=x.shape[0] * a["num_experts_per_tok"])
        # each share alone is the reference's share, too
        share = {**a, "experts_held": [first, 4]}
        mine = {**m, "experts_up": m["experts_up"][first:first + 4],
                "experts_down": m["experts_down"][first:first + 4]}
        rids, rw = highest(ref.route)(m, x, a)
        assert float(jnp.abs(
            part - highest(ref.routed_part)(mine, x, share, rids, rw)).max()) < 1e-4
        total = total + part
        slots += int(stats["slots_local"])
        assert int(stats["overflow_rows"]) == 0
    assert slots == x.shape[0] * a["num_experts_per_tok"]  # every slot, once
    assert float(jnp.abs(total - whole).max()) < 2e-4 * float(jnp.abs(whole).max())


@pytest.mark.parametrize("held", [(0, 4), (4, 4), (0, 16), (13, 3)])
def test_any_share_works_and_matches_the_reference(held):
    a, m, x = _moe_case(seed=held[0] + 1)
    first, count = held
    share = {**a, "experts_held": [first, count]}
    mine = {**m, "experts_up": m["experts_up"][first:first + count],
            "experts_down": m["experts_down"][first:first + count]}
    ids, w = highest(ref.route)(m, x, a)
    want = highest(ref.routed_part)(mine, x, share, ids, w)
    got, stats = highest(moe.local_experts)(
        x, ids, w, mine["experts_up"], mine["experts_down"], first=first,
        capacity=x.shape[0] * 3)
    assert int(stats["overflow_rows"]) == 0
    assert float(jnp.abs(got - want).max()) < 1e-4


def test_no_token_slot_is_dropped_under_a_skewed_router():
    """One expert held takes most slots: with room for every slot the
    outputs still match the reference and nothing overflows; with a buffer
    made too small on purpose ``overflow_rows`` counts exactly the rows
    beyond it."""
    a, m, x = _moe_case(skew=3.0)
    first, count = 0, 4
    share = {**a, "experts_held": [first, count]}
    mine = {**m, "experts_up": m["experts_up"][:count],
            "experts_down": m["experts_down"][:count]}
    ids, w = highest(ref.route)(m, x, a)
    on_hot = int(jnp.sum(ids == 1))
    assert on_hot > 0.9 * x.shape[0]  # the skew is real
    want = highest(ref.routed_part)(mine, x, share, ids, w)
    got, stats = highest(moe.local_experts)(
        x, ids, w, mine["experts_up"], mine["experts_down"], first=first,
        capacity=x.shape[0] * a["num_experts_per_tok"])
    local = int(jnp.sum((ids >= first) & (ids < first + count)))
    assert int(stats["slots_local"]) == local
    assert int(stats["overflow_rows"]) == 0
    assert float(stats["load_max_over_mean"]) > 2.0
    assert float(jnp.abs(got - want).max()) < 1e-4 * max(
        1.0, float(jnp.abs(want).max()))
    small = 64
    _, stats = highest(moe.local_experts)(
        x, ids, w, mine["experts_up"], mine["experts_down"], first=first,
        capacity=small)
    assert int(stats["overflow_rows"]) == local - small


def test_unwritten_buffer_rows_reach_neither_output_nor_gradient(monkeypatch):
    """On the TPU the grouped product leaves the rows past its groups
    unwritten, forward and backward (PERF.md, PR 29: a gradient 48000 times
    too large at the layer's input). Here the product is made to poison
    them, both ways: output and input gradient must not notice."""
    real = jax.lax.ragged_dot

    def poison(a, sizes):
        used = jnp.arange(a.shape[0])[:, None] < jnp.sum(sizes)
        return jnp.where(used, a, 1e30)

    @jax.custom_vjp
    def poisoned(lhs, rhs, sizes):
        return poison(real(lhs, rhs, sizes), sizes)

    def fwd(lhs, rhs, sizes):
        out, vjp = jax.vjp(lambda l, r: real(l, r, sizes), lhs, rhs)
        return poison(out, sizes), (vjp, sizes)

    def bwd(res, g):
        vjp, sizes = res
        dl, dr = vjp(g)
        return poison(dl, sizes), dr, None

    poisoned.defvjp(fwd, bwd)
    a, m, x = _moe_case(seed=3)
    ids, w = highest(ref.route)(m, x, a)
    mine = {**m, "experts_up": m["experts_up"][:4], "experts_down": m["experts_down"][:4]}
    share = {**a, "experts_held": [0, 4]}

    def prog(x):
        return jnp.sum(jnp.sin(moe.local_experts(
            x, ids, w, mine["experts_up"], mine["experts_down"], first=0,
            capacity=x.shape[0] * 3)[0]))

    def want(x):
        return jnp.sum(jnp.sin(ref.routed_part(mine, x, share, ids, w)))

    clean = highest(jax.grad(prog))(x)
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    dirty_out = highest(prog)(x)
    dirty = highest(jax.grad(prog))(x)
    assert np.isfinite(float(dirty_out))
    assert float(jnp.abs(dirty - clean).max()) == 0.0
    theirs = highest(jax.grad(want))(x)
    assert float(jnp.abs(dirty - theirs).max()) < 1e-4 * float(jnp.abs(theirs).max())


def test_the_model_buffer_is_a_stated_multiple_and_overflow_is_counted(monkeypatch):
    """The model gives the layer ``MOE_BUFFER_OVER_EXPECTED`` times the rows
    an even router would send to the experts held, and what a skewed router
    sends beyond that is counted at the top: one token everywhere puts a
    third of all slots on the busiest expert, which a share of that expert
    alone (1 of 16: 24 rows expected, a buffer of 96) cannot hold."""
    assert moe.expected_rows(16384, 6, 8, 128) == 6144
    seen = []
    real = moe.local_experts

    def spy(x, ids, *args, capacity, **kwargs):
        out, stats = real(x, ids, *args, capacity=capacity, **kwargs)
        jax.debug.callback(  # the block is rematerialised: traced values
            lambda n, chosen: seen.append(
                (capacity, int(n), np.bincount(chosen.ravel(), minlength=16))),
            stats["slots_local"], ids)
        return out, stats

    monkeypatch.setattr(nh.moe, "local_experts", spy)
    ids = jnp.zeros((2, 64), jnp.int32)

    def counts(held):
        model = nh.NemotronH(cfg=dataclasses.replace(CFG, experts_held=held))
        variables = api.init_variables(model, seed=1, in_samples=64, in_channels=1)
        seen.clear()
        _, aux = model.apply(
            {"params": variables["params"]}, ids, train=False, mutable=["aux"])
        return [float(aux["aux"][k][-1])
                for k in ("moe_slots_local", "moe_overflow_rows")], list(seen)

    (_, overflow), layers = counts((0, 16))  # the whole layer: every slot fits
    assert overflow == 0.0 and [c for c, _, _ in layers] == [2 * 64 * 3] * 4
    busiest = int(np.argmax(layers[0][2]))
    assert layers[0][2][busiest] > 96
    (slots, overflow), layers = counts((busiest, 1))
    assert [c for c, _, _ in layers] == [nh.MOE_BUFFER_OVER_EXPECTED * 24] * 4
    assert slots == sum(n for _, n, _ in layers)
    assert overflow == sum(max(0, n - 96) for _, n, _ in layers) > 0


def test_published_preset_sizes():
    """The published widths, the cut of the configuration's file, and the
    parameter count the memory reckoning rests on (667 M)."""
    model = api.create_model("nemotron3_nano_ep16")
    c = model.cfg
    assert (c.hidden_size, c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
            c.ssm_state_size, c.chunk_size) == (2688, 64, 64, 8, 128, 128)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (32, 2, 128)
    assert (c.n_routed_experts, c.experts_held, c.num_experts_per_tok,
            c.moe_intermediate_size, c.moe_shared_expert_intermediate_size
            ) == (128, (0, 8), 6, 1856, 3712)
    assert (c.pattern, c.vocab_size) == ("MEMEM*EME", 16384)
    shapes = api.param_shapes(model, in_samples=128, in_channels=1)["params"]
    assert api.count_params(shapes) == 666_962_944
    with open(os.path.join(_BENCH, "configs", "nemotron3_nano_ep16.json")) as f:
        stored = json.load(f)["architecture"]
    mine = dataclasses.asdict(c)
    mine["experts_held"] = list(mine["experts_held"])
    assert stored == mine


# --------------------------------------------------------------- token task
def test_token_task_spec():
    spec = taskspec.get_task_spec("nemotron3_nano_ep16")
    assert spec.tokens and spec.inputs == ("ids",) and spec.labels == ("next_ids",)
    assert taskspec.get_kind("ids") == taskspec.TOKENS
    assert taskspec.get_num_inchannels("nemotron3_tiny") == 1
    assert not taskspec.get_task_spec("phasenet").tokens


def test_token_loss_and_hits_leave_out_positions_without_a_target():
    from seist_tpu.models.losses import TokenCELoss, token_hits

    logits = jnp.log(jnp.asarray(
        [[[0.7, 0.2, 0.1], [0.1, 0.8, 0.1], [0.3, 0.3, 0.4]]]))
    targets = jnp.asarray([[0, 2, -1]])
    loss = TokenCELoss()(logits, targets)
    assert abs(float(loss) - (-(np.log(0.7) + np.log(0.1)) / 2)) < 1e-6
    hits, count = token_hits(logits, targets)
    assert (int(hits), int(count)) == (1, 2)
    # bf16 logits are scored in float32
    assert TokenCELoss()(logits.astype(jnp.bfloat16), targets).dtype == jnp.float32


def test_synthetic_tokens_are_seeded_zipf_integers():
    from seist_tpu.registry import DATASETS

    kw = dict(seed=0, mode="train", data_dir="", shuffle=False, data_split=False,
              num_events=6, trace_samples=4096, vocab_size=64)
    one, two = DATASETS.create("synthetic_tokens", **kw), DATASETS.create(
        "synthetic_tokens", **kw)
    a, b = one[2][0]["data"], two[2][0]["data"]
    assert a.dtype == np.int32 and a.shape == (1, 4096)
    assert np.array_equal(a, b) and not np.array_equal(a, one[3][0]["data"])
    assert 0 <= a.min() and a.max() < 64
    counts = np.bincount(np.concatenate([one[i][0]["data"][0] for i in range(6)]),
                         minlength=64)
    # exponent 1: id 0 about twice id 1 and four times id 3
    assert 1.6 < counts[0] / counts[1] < 2.5 and 3.0 < counts[0] / counts[3] < 5.5
    tiny = DATASETS.create("synthetic_tokens_tiny", **{
        k: v for k, v in kw.items() if k != "vocab_size"})
    assert tiny[0][0]["data"].max() < 256


def _token_pool(path, events=24, length=LENGTH, vocab=256):
    from seist_tpu.data.packed import PackSource, pack_sources

    pack_sources(
        [PackSource(name="synthetic_tokens", dataset_kwargs={
            "num_events": events, "trace_samples": length,
            "vocab_size": vocab, "cache": False})],
        path, num_workers=0,
    )
    return path


def test_packer_stores_integer_sequences_as_integers(tmp_path):
    from seist_tpu.registry import DATASETS

    pool = _token_pool(str(tmp_path / "pool"), events=5)
    with open(os.path.join(pool, "meta.json")) as f:
        meta = json.load(f)
    assert meta["dtype"] == "int32" and meta["channels"] == ["ids"]
    assert os.path.getsize(os.path.join(pool, "shard_00000.bin")) == 5 * LENGTH * 4
    kw = dict(seed=0, mode="train", shuffle=False, data_split=False)
    packed = DATASETS.create("packed", data_dir=pool, **kw)
    source = DATASETS.create(
        "synthetic_tokens", data_dir="", num_events=5, trace_samples=LENGTH,
        vocab_size=256, **kw)
    for i in range(5):
        got = packed[i][0]["data"]
        assert got.dtype == np.int32 and np.array_equal(got, source[i][0]["data"])


def test_loader_applies_nothing_to_token_sequences(tmp_path):
    """Through the packer and the loader: ids come out as they went in,
    the label is the input shifted by one, no augmentation doubles the
    epoch and no normalisation touches an id."""
    from seist_tpu.data import pipeline

    pool = _token_pool(str(tmp_path / "pool"), events=10)
    spec = taskspec.get_task_spec("nemotron3_tiny")
    sds = pipeline.from_task_spec(
        spec, "packed", "train", seed=0, data_dir=pool, in_samples=LENGTH,
        augmentation=False, shuffle=False, data_split=False, norm_mode="std")
    assert len(sds) == 10
    loader = pipeline.Loader(sds, batch_size=4, drop_last=True, num_workers=2)
    batch = next(iter(loader))
    assert batch.inputs.dtype == np.int32 and batch.inputs.shape == (4, LENGTH)
    assert batch.loss_targets.dtype == np.int32
    assert np.array_equal(batch.loss_targets[:, :-1], batch.inputs[:, 1:])
    assert (batch.loss_targets[:, -1] == -1).all()
    assert batch.metrics_targets == {}
    raw = sds.raw_event(0)[0]["data"][0]
    assert np.array_equal(batch.inputs[0], raw)
    loader.close()


# ------------------------------------------------------ the normal train path
@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """``main.py --mode train`` at the small preset for two epochs (batch 8:
    the suite's mesh has 8 data devices)."""
    from seist_tpu import cli
    from seist_tpu.obs.bus import BUS

    root = tmp_path_factory.mktemp("nemotron_train")
    pool = _token_pool(str(root / "pool"), events=40)
    spans = []
    sink = lambda s: spans.append(s.name)  # noqa: E731
    BUS.add_span_sink(sink)
    try:
        cli.main([
            "--mode", "train", "--model-name", "nemotron3_tiny",
            "--dataset-name", "packed", "--data", pool, "--dtype", "bf16",
            "--batch-size", "8", "--in-samples", str(LENGTH), "--seed", "5",
            "--epochs", "2", "--workers", "2", "--use-tensorboard", "false",
            "--log-step", "3", "--log-base", str(root / "logs"),
        ])
    finally:
        BUS.remove_span_sink(sink)
    (logdir,) = glob.glob(str(root / "logs" / "*"))
    return {"logdir": logdir, "spans": spans, "pool": pool}


def test_train_cli_loss_falls(trained):
    losses = np.load(os.path.join(trained["logdir"], "train_losses.npy"))
    assert len(losses) == 8  # 32 train sequences / 8, two epochs
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.05
    assert abs(losses[0] - np.log(256)) < 0.2
    val = np.load(os.path.join(trained["logdir"], "val_losses.npy"))
    assert len(val) == 2 and val[1] < val[0]


def test_train_cli_progress_lines_name_their_own_step(trained):
    """The host-fed loop reads a progress line's loss back two steps late
    (so that the device keeps a step queued); the line still carries the
    step it belongs to, and the epoch's tail is not lost."""
    import re

    losses = np.load(os.path.join(trained["logdir"], "train_losses.npy"))
    with open(glob.glob(os.path.join(trained["logdir"], "*train*.log"))[0]) as f:
        lines = re.findall(
            r"_train Epoch\[(\d)\] \[(\d)/4\]\s+loss (\S+) ", f.read())
    assert [(int(e), int(k)) for e, k, _ in lines] == [(0, 0), (0, 3), (1, 0), (1, 3)]
    for e, k, loss in lines:
        assert abs(float(loss) - losses[4 * int(e) + int(k)]) < 1e-3 * float(loss)


def test_train_cli_spans_and_counters(trained):
    from seist_tpu.obs.bus import BUS

    for name in ("train_epoch", "step_dispatch", "host_wait", "epoch_drain",
                 "validate", "val_step", "val_metrics", "checkpoint_save"):
        assert name in trained["spans"], name
    assert "val_postprocess" not in trained["spans"]  # nothing to pick
    tokens = BUS.counter("tokens_trained").value
    slots = BUS.counter("moe_slots_local").value
    assert tokens >= 8 * 8 * LENGTH
    # 4 expert layers x 3 of 16 experts a token x 4 held: near 4 x 3 / 4
    assert 0.5 * 3 < slots / tokens < 1.5 * 3
    assert BUS.counter("moe_overflow_rows").value == 0
    assert BUS.gauge("moe_load_max_over_mean").value >= 1.0
    assert 0.0 <= BUS.gauge("val_token_accuracy").value <= 1.0
    with open(glob.glob(os.path.join(trained["logdir"], "*train*.log"))[0]) as f:
        text = f.read()
    assert "tokens: loss" in text and "accuracy" in text


def test_train_cli_checkpoint_restores(trained):
    from seist_tpu.train import load_checkpoint

    steps = sorted(glob.glob(os.path.join(trained["logdir"], "checkpoints", "model_*")))
    assert steps
    restored = load_checkpoint(steps[-1])
    shapes = api.param_shapes(
        api.create_model("nemotron3_tiny"), in_samples=LENGTH, in_channels=1)["params"]
    assert jax.tree.map(lambda a: tuple(a.shape), restored["params"]) == jax.tree.map(
        lambda a: tuple(a.shape), dict(shapes))
    moved = np.asarray(restored["params"]["block_1"]["mixer"]["experts_up"])
    assert np.isfinite(moved).all() and moved.std() > 0
    assert int(restored["meta"]["data_epoch"]) >= 1


def test_scope_map_of_the_step_owns_the_new_regions():
    """The regions of obs/scopes.py over the token step: every new region
    is there, and of the instructions that carry an op_name nearly all
    fall into a region."""
    import optax

    from seist_tpu.obs import scopes
    from seist_tpu.train import create_train_state, jit_step, make_train_step

    model = api.create_model("nemotron3_tiny")
    variables = api.init_variables(model, seed=0, in_samples=LENGTH, in_channels=1)
    spec = taskspec.get_task_spec("nemotron3_tiny")
    state = create_train_state(model, variables, optax.adam(1e-3))
    step = jit_step(
        make_train_step(spec, spec.loss(), compute_dtype="bf16", guard=True),
        donate_state=False)
    ids = jax.ShapeDtypeStruct((2, LENGTH), jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    text = step.jitted.lower(state, ids, ids, key).compile().as_text()
    m = scopes.parse_hlo(text)
    regions = {v["region"] for v in m.values()}
    assert {"ssm_proj", "ssm_scan", "moe_router", "moe_experts", "moe_shared",
            "embed", "lm_head", "attention", "loss", "optimizer"} <= regions
    named = [v for v in m.values() if v["op_name"]]
    lost = [v["op_name"] for v in named if v["region"] == scopes.UNOWNED]
    assert len(lost) < 0.02 * len(named), sorted(set(lost))[:10]


@pytest.mark.parametrize("op_name, region, which", [
    ("jit(train_step)/jvp(model)/NemotronH/block_0/mixer/ssm_proj/dot_general",
     "ssm_proj", "fwd"),
    ("jit(train_step)/transpose(jvp(model))/NemotronH/checkpoint/block_2/mixer/"
     "ssm_scan/while/body/mul", "ssm_scan", "bwd"),
    ("jit(train_step)/jvp(model)/NemotronH/block_1/mixer/moe_router/top_k",
     "moe_router", "fwd"),
    ("jit(train_step)/transpose(jvp(model))/NemotronH/block_1/mixer/moe_experts/"
     "scatter-add", "moe_experts", "bwd"),
    ("ragged-dot-none", "moe_experts", ""),
    ("jit(train_step)/jvp(model)/NemotronH/block_3/mixer/moe_shared/dot_general",
     "moe_shared", "fwd"),
    ("jit(train_step)/jvp(model)/NemotronH/embed/gather", "embed", "fwd"),
    ("jit(train_step)/jvp(model)/NemotronH/lm_head/dot_general", "lm_head", "fwd"),
    ("jit(train_step)/jvp(model)/NemotronH/block_5/mixer/attn_path/dot_general",
     "attention", "fwd"),
    ("jit(train_step)/jvp(model)/NemotronH/block_5/mul", "model_other", "fwd"),
])
def test_classify_token_regions(op_name, region, which):
    from seist_tpu.obs import scopes

    assert scopes.classify(op_name) == (region, which)


def test_attention_is_causal_and_grouped():
    """The einsum path of ops/causal_attention.py (what non-TPU backends
    take) against a loop over heads with an explicit mask."""
    from seist_tpu.ops.causal_attention import causal_gqa_attention

    k = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k[0], (1, 16, 4, 8))
    kk = jax.random.normal(k[1], (1, 16, 2, 8))
    v = jax.random.normal(k[2], (1, 16, 2, 8))
    out = highest(causal_gqa_attention)(q, kk, v, scale=8 ** -0.5)
    for h in range(4):
        s = (q[0, :, h] @ kk[0, :, h // 2].T) * 8 ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((16, 16), bool)), s, -jnp.inf)
        want = jax.nn.softmax(s, axis=-1) @ v[0, :, h // 2]
        assert float(jnp.abs(out[0, :, h] - want).max()) < 1e-5
    # position 0 sees only itself
    assert float(jnp.abs(out[0, 0, 0] - v[0, 0, 0]).max()) < 1e-6
