"""Device mesh + sharding helpers.

TPU-native replacement for the reference's NCCL/DDP layer
(/root/reference/utils/misc.py:103-172, training/train.py:367-374). Instead of
wrapping the model in DDP and hand-placing collectives, we declare a
`jax.sharding.Mesh` and annotate data/parameter shardings; XLA inserts the
gradient all-reduce (over ICI intra-slice, DCN across slices) when the train
step is jit-compiled.

Axis convention (fixed, in this order):

* ``data``  — batch (data parallel). The only axis the SeisT-scale models
  *need* (the reference implements exactly one strategy, DDP — SURVEY §2.4).
* ``model`` — tensor-parallel axis, size 1 by default. Kept first-class so
  channel-sharded variants can be added without re-plumbing.
* ``seq``   — sequence/context-parallel axis, size 1 by default. Ring
  attention / sequence sharding for very long waveforms rides this axis
  (see seist_tpu/ops/ring_attention.py).
"""

from __future__ import annotations

import contextvars
from contextlib import contextmanager
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_DATA = "data"
AXIS_MODEL = "model"
AXIS_SEQ = "seq"
MESH_AXES = (AXIS_DATA, AXIS_MODEL, AXIS_SEQ)

# Trace-time active mesh: the mesh of the step function being traced. Models
# consult it to route through mesh-axis-aware paths (SeisT attention -> ring
# attention when ``seq`` > 1; the Pallas attention kernel per batch shard
# when ``data`` > 1). train.step's jit wrappers scope it around the trace of
# the function they jit (tests scope it by hand), so a jitted step always
# traces under the mesh its shardings name and nothing else — parameter
# init, the server's programs — ever sees one.
_ACTIVE_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "seist_active_mesh", default=None
)


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH.get()


@contextmanager
def use_mesh(mesh: Optional[Mesh]):
    token = _ACTIVE_MESH.set(mesh)
    try:
        yield
    finally:
        _ACTIVE_MESH.reset(token)


def make_mesh(
    data: Optional[int] = None,
    model: int = 1,
    seq: int = 1,
    devices: Optional[Sequence[Any]] = None,
) -> Mesh:
    """Build a ``(data, model, seq)`` mesh over ``devices``.

    ``data=None`` consumes all remaining devices. On real TPU slices
    ``mesh_utils.create_device_mesh`` lays the axes onto the physical torus so
    the heaviest-traffic axis rides ICI neighbors.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if data is None:
        if n % (model * seq):
            raise ValueError(f"{n} devices not divisible by model*seq={model * seq}")
        data = n // (model * seq)
    if data * model * seq != n:
        raise ValueError(
            f"mesh shape {(data, model, seq)} != device count {n}"
        )
    dev_mesh = mesh_utils.create_device_mesh(
        (data, model, seq), devices=np.asarray(devices)
    )
    return Mesh(dev_mesh, MESH_AXES)


def batch_spec(extra_axes: int = 0) -> P:
    """PartitionSpec sharding the leading (batch) axis over ``data``."""
    return P(AXIS_DATA, *([None] * extra_axes))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(AXIS_DATA))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, batch: Any, spec: Optional[P] = None) -> Any:
    """Place a host batch pytree with the leading axis sharded on ``data``
    (the `DistributedSampler`-equivalent placement). Single-process: a plain
    sharded device_put of the full batch. Multi-host: each host passes its
    *local* shard and the global array is assembled without gathering.
    ``spec`` overrides the partitioning (default ``P('data', ...)``)."""
    sharding = (
        NamedSharding(mesh, spec) if spec is not None else batch_sharding(mesh)
    )
    if jax.process_count() > 1:
        return jax.tree_util.tree_map(
            lambda x: jax.make_array_from_process_local_data(sharding, np.asarray(x)),
            batch,
        )
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), batch)


def shard_stacked_batch(mesh: Mesh, batch: Any) -> Any:
    """:func:`shard_batch` for k-stacked micro-batches ``(k, B, ...)``:
    axis 0 is the micro-step axis (replicated), axis 1 is the batch axis
    (sharded on ``data``). Used by the --steps-per-call train path."""
    return shard_batch(mesh, batch, spec=P(None, AXIS_DATA))


def to_local(x: Any) -> np.ndarray:
    """Materialize this host's rows of a batch-sharded array as numpy.

    Single-process: the whole array. Multi-host: the addressable shards in
    global-index order — the same rows (same order) this host fed in via
    :func:`shard_batch` / the input pipeline. Replication over other mesh
    axes (model/seq) makes several local devices hold the same row range —
    deduped by range start so each row appears once.
    """
    if isinstance(x, np.ndarray):
        return x
    if jax.process_count() <= 1 or not hasattr(x, "addressable_shards"):
        return np.asarray(x)
    by_start = {}
    for s in x.addressable_shards:
        start = s.index[0].start or 0
        by_start.setdefault(start, s)
    shards = [by_start[k] for k in sorted(by_start)]
    return np.concatenate([np.asarray(s.data) for s in shards], axis=0)


def replicate(mesh: Mesh, tree: Any) -> Any:
    """Fully replicate a pytree (params/optimizer state) over the mesh."""
    sharding = replicated(mesh)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), tree)
