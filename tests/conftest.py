"""Test configuration: force an 8-device virtual CPU mesh.

Must run before the first `import jax` anywhere in the test process, so this
lives at the top of conftest.py. Multi-device sharding tests use these 8
virtual CPU devices; real-TPU behavior is exercised by bench.py and the
driver's dryrun_multichip hook.
"""

import os

# The suite runs on the CPU backend with 8 virtual devices (multi-device
# sharding tests build their meshes from them); JAX honours JAX_PLATFORMS
# from the environment by itself, so setting it before the import is all
# it takes.
#
# Escape hatch SEIST_TEST_TPU=1: leave the real TPU backend in place so the
# hardware lane (golden parity through the composed/fused TPU-default
# lowerings) runs on the chip. Virtual-mesh multi-device tests will then
# see the real device count and skip.
_USE_TPU = os.environ.get("SEIST_TEST_TPU") == "1"
if not _USE_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax  # noqa: E402, F401

# Persistent XLA compile cache for the suite: tier-1 wall time is
# dominated by jit compiles of the same model/step programs run after
# run. Same function and same placement rule as every entry point
# (utils/misc.enable_compile_cache: JAX_COMPILATION_CACHE_DIR when set,
# else <repo>/.jax_cache). Threshold 2 s: catches every model compile,
# skips trivial jits. First (cold) run pays full price.
from seist_tpu.utils.misc import enable_compile_cache  # noqa: E402

enable_compile_cache(min_compile_seconds=2)

import sys

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Repo root on sys.path once, for every test/fixture importing tools.*
# (tools.fixtures, tools.jaxlint, ...).
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# -- jaxlint runtime audit lane -----------------------------------------------
# `pytest -m smoke --tracer-leaks` re-runs the pure-unit lane with
# jax.check_tracer_leaks active around every test: any tracer escaping its
# trace (closure capture, storing tracers on self, ...) becomes a hard
# error instead of a latent use-after-trace bug. Opt-in flag because leak
# checking disables some jit caching and roughly doubles lane wall time.
def pytest_addoption(parser):
    parser.addoption(
        "--tracer-leaks",
        action="store_true",
        default=False,
        help="run every test under jax.check_tracer_leaks "
        "(jaxlint runtime audit lane; see docs/STATIC_ANALYSIS.md)",
    )
    parser.addoption(
        "--lock-graph",
        action="store_true",
        default=False,
        help="run every test under threadlint's LockGraph: locks created "
        "during the test are instrumented, and the test fails on a "
        "lock-acquisition-order cycle (potential deadlock) or a lock "
        "held across a known blocking call (threadlint runtime audit "
        "lane; see docs/STATIC_ANALYSIS.md)",
    )


@pytest.fixture
def compile_budget():
    """Scoped compile counter (tools/jaxlint/runtime.py): everything jitted
    inside the test is attributed by function name + abstract shape
    signature. Assert with ``compile_budget.assert_compiles_once(name)``
    after driving the jitted path — see tests/test_compile_budget.py."""
    from tools.jaxlint.runtime import CompileBudget

    with CompileBudget() as budget:
        yield budget


@pytest.fixture(autouse=True)
def _tracer_leak_lane(request):
    if request.config.getoption("--tracer-leaks", default=False):
        from tools.jaxlint.runtime import tracer_leak_check

        with tracer_leak_check():
            yield
    else:
        yield


@pytest.fixture(autouse=True)
def _lock_graph_lane(request):
    """`pytest --lock-graph` (threadlint runtime lane, `make lockgraph`):
    every lock CREATED during the test is instrumented; teardown fails
    the test on an acquisition-order cycle or a lock held across a
    blocking call. Graphs nest, so tests that drive their own LockGraph
    still work inside the lane."""
    if request.config.getoption("--lock-graph", default=False):
        from tools.threadlint.runtime import LockGraph

        with LockGraph() as graph:
            yield
        graph.assert_clean()
    else:
        yield


# Smoke lane (`pytest -m smoke`): the pure-unit subset that verifies the
# round's core claims in <5 min on a 1-core host (measured ~90 s). Files are
# marked here centrally so the lane can't silently drift as tests are added;
# model-forward/e2e/golden tests stay out (jit compiles dominate them).
_SMOKE_FILES = {
    "test_losses.py",
    "test_metrics.py",
    "test_postprocess.py",
    "test_misc.py",
    "test_taskspec.py",
    "test_preprocess.py",
    "test_results.py",
    "test_common_ops.py",
    "test_collectives.py",
    "test_visualization.py",
    "test_stream.py",
    "test_stream_session.py",
    "test_stream_mux.py",
    "test_supervise.py",
    "test_native.py",
    "test_bench_unit.py",
    "test_packed.py",
    "test_collective_report.py",
    "test_jaxlint.py",
    "test_io_guard.py",
    "test_obs.py",
    "test_trace.py",
    "test_meters.py",
    "test_router.py",
    "test_threadlint.py",
    "test_dist_broadcast.py",
    "test_batch_fleet.py",  # lease plane: fake work, ms clocks (slow e2e opts out)
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if os.path.basename(str(item.fspath)) in _SMOKE_FILES:
            if item.get_closest_marker("slow") is None:
                item.add_marker(pytest.mark.smoke)


def make_packed_dir(tmp_path_factory, n_events=24, trace_samples=1024,
                    n_parts=2, shard_mb=512):
    """Shared recipe: write a DiTing-light fixture, repack it with
    pack_dataset. Returns (source_dataset, packed_dir). Used by
    tests/test_packed.py and the packed worker-e2e lane."""
    from tools.fixtures import write_diting_light_fixture

    from seist_tpu.data.packed import pack_dataset
    from seist_tpu.registry import DATASETS

    src_dir = str(tmp_path_factory.mktemp("packed_src"))
    write_diting_light_fixture(
        src_dir, n_events=n_events, trace_samples=trace_samples,
        n_parts=n_parts,
    )
    src = DATASETS.create(
        "diting_light",
        seed=0,
        mode="train",
        data_dir=src_dir,
        shuffle=False,
        data_split=False,
    )
    out = str(tmp_path_factory.mktemp("packed_out"))
    pack_dataset(src, out, shard_mb=shard_mb)
    return src, out
