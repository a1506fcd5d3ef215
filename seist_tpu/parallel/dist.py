"""Multi-host process-group helpers.

Replaces the reference's torchrun/NCCL rendezvous
(/root/reference/utils/misc.py:143-172): `jax.distributed.initialize` reads
the coordinator address + process count from the environment (or TPU metadata)
and wires the hosts into one JAX runtime; collectives then ride ICI/DCN via
the compiled programs — there is no user-visible process group object.

Rank-0-only conventions (printing, checkpoint writes, result CSVs) mirror the
reference's `is_main_process` guards (misc.py:73-100, train.py:192,288,407).
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax


def init_distributed_mode(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Initialize the multi-host runtime if a multi-host env is detected.

    Env contract mirrors the reference's env-var rendezvous
    (misc.py:143-152): set ``COORDINATOR_ADDRESS``, ``NUM_PROCESSES``,
    ``PROCESS_ID`` (or pass explicitly). On Cloud TPU pods all three resolve
    automatically from metadata, so a bare call works too.

    Returns True when distributed mode was initialized.
    """
    coordinator_address = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    if num_processes is None and "NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["NUM_PROCESSES"])
    if process_id is None and "PROCESS_ID" in os.environ:
        process_id = int(os.environ["PROCESS_ID"])

    explicit = coordinator_address is not None
    tpu_hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    auto_tpu = len([h for h in tpu_hosts.split(",") if h]) > 1
    if not (explicit or auto_tpu):
        return False

    # No silent fallback: both trigger conditions (explicit coordinator, or
    # >1 worker in TPU metadata) mean a genuinely multi-host launch, and a
    # host that degrades to single-process would strand the others inside
    # initialize() and corrupt shared checkpoint dirs.
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_dist_avail_and_initialized() -> bool:
    return jax.process_count() > 1


def is_main_process() -> bool:
    return jax.process_index() == 0


def barrier(name: str = "barrier") -> None:
    """Block until all hosts reach this point (ref: dist.barrier())."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


#: broadcast_object call ordinal — every process calls broadcast_object
#: in the same program order (it is a collective), so a per-process
#: counter yields matching KV keys without any extra coordination.
_broadcast_seq = 0
_BROADCAST_TIMEOUT_MS = 300_000


def _coordination_client():
    """The jax distributed coordination-service client: the KV store
    ``jax.distributed.initialize`` rendezvouses through. Present whenever
    ``process_count() > 1`` (tests/test_dist_broadcast.py pins the methods
    used here against the installed jax)."""
    from jax._src import distributed

    client = distributed.global_state.client
    if client is None:
        raise RuntimeError(
            "broadcast_object needs the coordination service: call "
            "jax.distributed.initialize() (parallel/dist.init_distributed)"
        )
    return client


def broadcast_object(obj: Any) -> Any:
    """Broadcast any picklable host-side python object from process 0 to all
    (ref: misc.py:134-140 broadcast_object_list).

    Transport is the coordination-service KV store, NOT an XLA collective:
    process 0 publishes the pickle under a sequenced key, everyone else
    blocks on that key. Host-side control data (checkpoint paths, eval
    verdicts) has no business riding device allreduces, and the KV store
    works on every backend, the CPU's included.
    """
    if jax.process_count() <= 1:
        return obj
    import pickle

    client = _coordination_client()
    global _broadcast_seq
    key = f"seist_tpu/broadcast_object/{_broadcast_seq}"
    _broadcast_seq += 1
    if jax.process_index() == 0:
        client.key_value_set_bytes(key, pickle.dumps(obj))
        result = obj
    else:
        result = pickle.loads(
            client.blocking_key_value_get_bytes(key, _BROADCAST_TIMEOUT_MS)
        )
    # Barrier-then-delete: once every process has read the value, process 0
    # removes the key. Keys must not outlive the call — they would
    # accumulate over a long run, and a relaunched incarnation restarting
    # its sequence at 0 against a still-live coordinator would read the
    # PREVIOUS run's value for the wrong program point.
    client.wait_at_barrier(key + "/read", _BROADCAST_TIMEOUT_MS)
    if jax.process_index() == 0:
        client.key_value_delete(key)
    return result
