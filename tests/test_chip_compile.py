"""Ask the chip's compiler before the chip: the attention kernels of the
main path, at the real widths of ``seist_l_dpk`` (3 channels x 8192 samples,
batch 32), compiled in this process for a DESCRIBED TPU v5e — no chip
attached, nothing runs. What Mosaic/XLA:TPU refuse here (a slice off the
tiling, too much VMEM, a program over 16 GB) they refuse on the chip too, so
these guard every later PR at no chip time. A pass is not a chip run.

Beside the kernels: the augmentation and label synthesis of the cached
train path (``data/device_aug.make_cache_processor``) at the benchmark's row
shape, for what the compiler makes of its per-row indexing — a gather or a
scatter that ``vmap`` made out of a per-row ``dynamic_slice`` /
``dynamic_update_slice`` becomes a ``while`` over the batch, one row an
iteration (PERF.md, PR 30).

The topology is described inside a module-scoped fixture (never at import,
never in conftest.py, never autouse, never in a child process): only one
process may load the TPU library, and under xdist every worker imports this
file. The persistent compile cache is turned off around the compiles — an
executable compiled for a described chip cannot be read back without one.
"""

import collections
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from seist_tpu.ops import pallas_attention as pa

HBM_BYTES = 16 * 1024**3  # one v5e chip

# Folded attention shapes of seist_l_dpk at 8192 samples, batch 32, heads 3
# (one per stage): (L, M, H*E) with q (32, L, H*E) and k/v (32, M, H*E).
BATCH = 32
HEADS = 3
SHAPES = [(1024, 128, 24), (512, 128, 24), (256, 128, 48), (128, 128, 96)]
SHAPE_IDS = [f"L{l}-M{m}-HE{he}" for l, m, he in SHAPES]
DTYPES = [jnp.float32, jnp.bfloat16]
DTYPE_IDS = ["fp32", "bf16"]
RATES = [0.0, 0.3]  # eval, and the attention dropout seist_l_dpk trains with
RATE_IDS = ["nodrop", "drop0.3"]


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _structs(l, m, he, dtype, sharding):
    q = jax.ShapeDtypeStruct((BATCH, l, he), dtype, sharding=sharding)
    kv = jax.ShapeDtypeStruct((BATCH, m, he), dtype, sharding=sharding)
    # (dropout seed, batch offset of the dropout counter)
    seed = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=sharding)
    return q, kv, seed


def _compile(fn, *structs):
    compiled = jax.jit(fn).lower(*structs).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel is not in the program"
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )
    assert total < HBM_BYTES
    return compiled


@pytest.mark.parametrize("rate", RATES, ids=RATE_IDS)
@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("shape", SHAPES, ids=SHAPE_IDS)
def test_forward_kernel_compiles_for_v5e(
    one_chip, no_compile_cache, shape, dtype, rate
):
    l, m, he = shape
    q, kv, seed = _structs(l, m, he, dtype, one_chip)
    scale = (he // HEADS) ** -0.5

    def fwd(q, k, v, seed):
        return pa._fused(q, k, v, seed, scale, rate, HEADS, False)

    _compile(fwd, q, kv, kv, seed)


@pytest.mark.parametrize("rate", RATES, ids=RATE_IDS)
@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("shape", SHAPES, ids=SHAPE_IDS)
def test_backward_kernel_compiles_for_v5e(
    one_chip, no_compile_cache, shape, dtype, rate
):
    l, m, he = shape
    q, kv, seed = _structs(l, m, he, dtype, one_chip)
    scale = (he // HEADS) ** -0.5

    def bwd(q, k, v, seed, g):
        dq, dk, dv, _ = pa._fused_bwd(
            scale, rate, HEADS, False, (q, k, v, seed), g
        )
        return dq, dk, dv

    _compile(bwd, q, kv, kv, seed, q)


def test_public_api_takes_the_kernel_when_the_backend_is_tpu(
    one_chip, no_compile_cache, monkeypatch
):
    # The dispatch asks jax.default_backend(), which sees the CPU during
    # such a compile: steer it from the test. 4-D (N, L, H, E) in, the
    # model's own call, gradient included (forward + backward kernels).
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    l, m, he = SHAPES[0]
    e = he // HEADS
    q = jax.ShapeDtypeStruct((BATCH, l, HEADS, e), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((BATCH, m, HEADS, e), jnp.bfloat16, sharding=one_chip)
    seed = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)

    def loss(q, k, v, seed):
        o = pa.fused_pooled_attention(
            q, k, v, dropout_rate=0.3, dropout_seed=seed
        )
        return (o.astype(jnp.float32) ** 2).sum()  # the output is needed

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv, seed)
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_data_parallel_kernel_compiles_for_four_v5e_chips(
    topo, no_compile_cache, monkeypatch
):
    # Mosaic kernels cannot be partitioned automatically: under a
    # data-parallel mesh the public API must shard_map the kernel over the
    # batch rows, or XLA refuses the whole train step on four chips.
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from seist_tpu.parallel import mesh as mesh_lib

    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    mesh = mesh_lib.make_mesh(data=4, devices=topo.devices)
    rows = NamedSharding(mesh, P("data"))
    l, m, he = SHAPES[0]
    e = he // HEADS
    q = jax.ShapeDtypeStruct((BATCH, l, HEADS, e), jnp.bfloat16, sharding=rows)
    kv = jax.ShapeDtypeStruct((BATCH, m, HEADS, e), jnp.bfloat16, sharding=rows)
    seed = jax.ShapeDtypeStruct(
        (1,), jnp.int32, sharding=NamedSharding(mesh, P())
    )

    def loss(q, k, v, seed):
        o = pa.fused_pooled_attention(
            q, k, v, dropout_rate=0.3, dropout_seed=seed
        )
        return (o.astype(jnp.float32) ** 2).sum()

    with mesh_lib.use_mesh(mesh):
        compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv, seed)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    # each chip's kernel sees its quarter of the batch, not all of it
    assert f"bf16[{BATCH // 4},{l},{he}]" in text
    assert f"bf16[{BATCH},{l},{he}]" not in text


# ------------------------------------------------- augmentation's row loops
AUG_LABELS = [("det", "ppk", "spk"), ("non", "ppk", "spk")]
# (labels, add_event_rate, the region's kernels): the benchmark cells' label
# sets at the trainer's default rates, and the event-duplication augment on
AUG_CASES = [(AUG_LABELS[0], 0.0, 2), (AUG_LABELS[1], 0.0, 2), (AUG_LABELS[0], 0.3, 3)]
AUG_IDS = ["det-ppk-spk", "non-ppk-spk", "det-ppk-spk-add_event"]


def _aug_process(labels, add_event_rate, n_raw):
    from seist_tpu.data import device_aug as da

    cfg = da.AugConfig(
        seed=0, window=8192, raw_len=12000, channels=3, phase_slots=1,
        data_channels=("z", "n", "e"), sampling_rate=100, coda_ratio=2.0,
        min_event_gap=50, shift_event_rate=0.2, generate_noise_rate=0.05,
        drop_channel_rate=0.4, scale_amplitude_rate=0.4,
        pre_emphasis_rate=0.4, add_noise_rate=0.4, add_gap_rate=0.4,
        add_event_rate=add_event_rate,
    )
    return da.make_cache_processor(
        cfg, (("z", "n", "e"),), (labels,), n_raw=n_raw, augmentation=True
    )


def _aug_structs(n_raw, batch, rows, repl):
    def struct(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    cache = {
        "data": struct((n_raw, 3, 12000), jnp.float32, rows),
        "ppks": struct((n_raw, 1), jnp.int32, rows),
        "np_p": struct((n_raw,), jnp.int32, rows),
        "spks": struct((n_raw, 1), jnp.int32, rows),
        "np_s": struct((n_raw,), jnp.int32, rows),
    }
    return cache, struct((batch,), jnp.int32, rows), struct((), jnp.int32, repl)


def _region_ops(text, opcode):
    """op_name of every ``opcode`` the region owns ('.' stops at the line's
    end)."""
    return re.findall(
        rf' {opcode}\(.*op_name="([^"]*device_aug[^"]*)"', text
    )


@pytest.mark.parametrize("labels,add_event_rate,kernels", AUG_CASES, ids=AUG_IDS)
def test_device_aug_walks_the_batch_in_no_loop_and_reads_its_windows_in_kernels(
    one_chip, no_compile_cache, monkeypatch, labels, add_event_rate, kernels
):
    """The benchmark cells' row shape (3 x 12000 raw, 8192 window, one phase
    slot), batch 32: the program for the chip holds NO serial loop over the
    rows under ``device_aug``. It held fourteen while ``soft_label_place``
    sliced and updated a padded buffer per row (PERF.md, PR 30) and two —
    ``shift_event``'s roll and ``cut_window``'s crop — until those became
    ``ops/row_window.circular_window``, one Mosaic kernel for the whole
    batch each (``add_event_once``'s roll is the third, compiled only at
    ``add_event_rate > 0``). The kernels carry the region's scope, so the
    trace's join charges them to ``device_aug`` (obs/scopes.py). A new
    per-row ``dynamic_slice`` under ``vmap`` shows up here as a loop."""
    from seist_tpu.obs import scopes
    from seist_tpu.ops import row_window

    # the dispatch asks jax.default_backend(), which sees the CPU here
    monkeypatch.setattr(row_window, "_on_tpu", lambda: True)
    n_raw = 256
    process = _aug_process(labels, add_event_rate, n_raw)
    text = (
        jax.jit(process)
        .lower(*_aug_structs(n_raw, BATCH, one_chip, one_chip))
        .compile()
        .as_text()
    )
    loops = _region_ops(text, "while")
    assert len(loops) == 0, loops
    calls = [
        line.split(" = ")[0].replace("ROOT ", "").strip()
        for line in text.splitlines()
        if "tpu_custom_call" in line and " custom-call(" in line
    ]
    assert len(calls) == kernels, calls
    smap = scopes.parse_hlo(text)
    assert [smap[name]["region"] for name in calls] == ["device_aug"] * kernels


def test_device_aug_dense_passes_keep_their_layout_around_the_kernels(
    one_chip, no_compile_cache, monkeypatch
):
    """The compiler hands a custom call's operand layout ((B, C, L)
    row-major, tiled T(4,128)) on to the elementwise passes around it unless
    the rows are pinned on both sides of the call: at the cells' batch that
    made the region slower than the loops it replaced and moved the loss's
    last digits (PERF.md, PR 36). The program WITHOUT the kernels keeps a
    batch of 256 rows of 12000 samples batch along the lanes."""
    from seist_tpu.ops import row_window

    monkeypatch.setattr(row_window, "_on_tpu", lambda: True)
    n_raw = batch = 256
    process = _aug_process(AUG_LABELS[0], 0.0, n_raw)
    text = (
        jax.jit(process)
        .lower(*_aug_structs(n_raw, batch, one_chip, one_chip))
        .compile()
        .as_text()
    )
    rows = re.findall(rf"f32\[{batch},3,12000\](\{{[\d,]*:?)", text)
    assert sum(r == "{0,2,1:" for r in rows) > 0.85 * len(rows), (
        collections.Counter(rows)
    )


def test_device_aug_kernels_compile_for_four_v5e_chips(
    topo, no_compile_cache, monkeypatch
):
    # Mosaic kernels cannot be partitioned automatically: under a
    # data-parallel mesh each chip's kernels read ITS rows inside a
    # shard_map, or XLA refuses the cached step on four chips.
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from seist_tpu.ops import row_window
    from seist_tpu.parallel import mesh as mesh_lib

    monkeypatch.setattr(row_window, "_on_tpu", lambda: True)
    mesh = mesh_lib.make_mesh(data=4, devices=topo.devices)
    rows = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())
    n_raw = 256
    process = _aug_process(AUG_LABELS[0], 0.0, n_raw)
    with mesh_lib.use_mesh(mesh):
        text = (
            jax.jit(process)
            .lower(*_aug_structs(n_raw, BATCH, rows, repl))
            .compile()
            .as_text()
        )
    assert len(_region_ops(text, "while")) == 0
    assert text.count("tpu_custom_call") >= 2
    # each chip's kernel sees its quarter of the batch
    assert f"f32[{BATCH // 4},3,12000]" in text
