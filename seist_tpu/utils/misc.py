"""Seeds, paths, SNR and small helpers.

Replaces the reference's ``utils/misc.py`` grab-bag. The NCCL helpers
(misc.py:103-172) have **no equivalent here by design**: collectives are
emitted by XLA from sharded jit programs (see seist_tpu/parallel/).
"""

from __future__ import annotations

import os
import random
import time
from typing import Any, Dict

import jax
import numpy as np


def setup_seed(seed: int) -> jax.Array:
    """Seed host-side RNGs and return the root JAX PRNG key.

    The reference seeds torch/cuda/numpy/random and forces cuDNN determinism
    (utils/misc.py:14-21). In JAX, device-side randomness is explicit: all
    on-device sampling flows from the returned key; numpy/random cover the
    host-side input pipeline.
    """
    random.seed(seed)
    np.random.seed(seed)
    return jax.random.PRNGKey(seed)


def get_time_str() -> str:
    return time.strftime("%Y-%m-%d-%H-%M-%S", time.localtime())


def get_safe_path(path: str) -> str:
    """Dedupe a path by appending ``_new`` recursively (ref: misc.py:41-52)."""
    if not os.path.exists(path):
        return path
    base, ext = os.path.splitext(path)
    return get_safe_path(f"{base}_new{ext}")


def strftimedelta(seconds: float) -> str:
    seconds = int(seconds)
    h, rem = divmod(seconds, 3600)
    m, s = divmod(rem, 60)
    return f"{h:d}:{m:02d}:{s:02d}"


def count_params(params) -> int:
    """Total number of elements in a parameter pytree."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))


def cal_snr(data: np.ndarray, pat: int, window: int = 500) -> np.ndarray:
    """Per-channel SNR (dB) around the P arrival (ref: utils/misc.py:228-248).

    Args:
        data: ``(C, L)`` waveform.
        pat: P-arrival sample index.
        window: half-window length in samples.
    """
    data = np.asarray(data)
    snr = np.zeros(data.shape[0], dtype=np.float32)
    if pat - window < 0 or pat + window > data.shape[-1]:
        return snr
    for c in range(data.shape[0]):
        signal = data[c, pat : pat + window]
        noise = data[c, pat - window : pat]
        ps = np.sum(signal.astype(np.float64) ** 2) / max(len(signal), 1)
        pn = np.sum(noise.astype(np.float64) ** 2) / max(len(noise), 1)
        if pn > 0 and ps > 0:
            snr[c] = 10.0 * np.log10(ps / pn)
    return snr


def dump_namespace(args: Any) -> str:
    """Render args (argparse.Namespace or dict) for startup logging
    (ref: misc.py:206-221)."""
    if hasattr(args, "__dict__"):
        d: Dict[str, Any] = vars(args)
    else:
        d = dict(args)
    lines = [f"  {k} = {v!r}" for k, v in sorted(d.items())]
    return "Arguments:\n" + "\n".join(lines)


def device_summary() -> Dict[str, Any]:
    """The devices this process computes on, as JAX reports them — every
    run and every result says where it ran (train log, serve /healthz,
    chip_smoke.py's last line)."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


# The one default place of the persistent XLA compilation cache: a fixed
# path inside the checkout (the path is part of the cache key's context on
# some backends, and a machine that keeps nothing but the checkout keeps
# this). Listed in .gitignore.
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache(
    verbose: bool = False, min_compile_seconds: int = 10
) -> None:
    """Persistent XLA compilation cache (large models cost minutes per
    compile; identical programs across processes hit the disk cache).

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own reading of it is
    the whole story (an empty value is JAX's "no cache") and this function
    sets no directory. Otherwise the cache goes to
    ``DEFAULT_COMPILE_CACHE_DIR``. The one place every entry point (CLI,
    server, bench.py, tools, tests) turns the cache on.
    """
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update(
            "jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR
        )
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", int(min_compile_seconds)
    )
    # Compile accounting rides along: trace / lower / compile seconds and
    # cache hits as bus counters (imported here: obs imports this package).
    from seist_tpu.obs import jit_events

    jit_events.install()
    if verbose:
        import sys

        print(
            f"compilation cache: {jax.config.jax_compilation_cache_dir}",
            file=sys.stderr,
        )
