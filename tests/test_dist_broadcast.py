"""Direct tests for parallel/dist.broadcast_object.

The transport is the coordination-service KV store (host-side control data
does not ride device collectives). test_multihost's real worker processes
exercise it end to end; these units pin its semantics process-locally with
a fake client, and pin the private client API it relies on against the
installed jax, so a regression shows up in the smoke lane instead of only
on a multi-host launch."""

import pickle

import numpy as np
import pytest

import jax

from seist_tpu.parallel import dist


@pytest.fixture(autouse=True)
def _reset_seq():
    prev = dist._broadcast_seq
    dist._broadcast_seq = 0
    yield
    dist._broadcast_seq = prev


class _FakeKVClient:
    """In-memory stand-in for the jax coordination-service client."""

    def __init__(self, store=None):
        self.store = store if store is not None else {}
        self.barriers = []
        self.deleted = []

    def key_value_set_bytes(self, key, value):
        self.store[key] = value

    def blocking_key_value_get_bytes(self, key, timeout_ms):
        try:
            return self.store[key]
        except KeyError:
            raise TimeoutError(f"key {key} never published") from None

    def wait_at_barrier(self, name, timeout_ms):
        self.barriers.append(name)

    def key_value_delete(self, key):
        self.deleted.append(key)
        self.store.pop(key, None)


def _fake_multiprocess(monkeypatch, index, count=2):
    monkeypatch.setattr(jax, "process_count", lambda: count)
    monkeypatch.setattr(jax, "process_index", lambda: index)


def test_single_process_passthrough():
    obj = {"a": 1}
    assert dist.broadcast_object(obj) is obj


def test_kv_path_rank0_publishes_and_cleans_up(monkeypatch):
    _fake_multiprocess(monkeypatch, index=0)
    client = _FakeKVClient()
    monkeypatch.setattr(dist, "_coordination_client", lambda: client)
    obj = {"ckpt": "/path/step_120", "step": 120}
    assert dist.broadcast_object(obj) == obj
    # sequenced key, read barrier, then the key is deleted (a relaunched
    # incarnation restarting its sequence must not read stale values)
    assert client.barriers == ["seist_tpu/broadcast_object/0/read"]
    assert client.deleted == ["seist_tpu/broadcast_object/0"]
    assert client.store == {}


def test_kv_path_rank1_reads_rank0_payload(monkeypatch):
    _fake_multiprocess(monkeypatch, index=1)
    obj = ["eval", 0.25, np.float64(3.5)]
    store = {"seist_tpu/broadcast_object/0": pickle.dumps(obj)}
    client = _FakeKVClient(store)
    monkeypatch.setattr(dist, "_coordination_client", lambda: client)
    assert dist.broadcast_object(None) == obj
    # non-zero ranks wait at the barrier but never delete (rank 0 owns it)
    assert client.barriers == ["seist_tpu/broadcast_object/0/read"]
    assert client.deleted == []


def test_kv_path_sequences_successive_broadcasts(monkeypatch):
    _fake_multiprocess(monkeypatch, index=0)
    client = _FakeKVClient()
    monkeypatch.setattr(dist, "_coordination_client", lambda: client)
    dist.broadcast_object("first")
    dist.broadcast_object("second")
    assert client.deleted == [
        "seist_tpu/broadcast_object/0",
        "seist_tpu/broadcast_object/1",
    ]


@pytest.mark.parametrize(
    "method",
    [
        "key_value_set_bytes",
        "blocking_key_value_get_bytes",
        "wait_at_barrier",
        "key_value_delete",
    ],
)
def test_installed_jax_client_has_the_methods_used(method):
    """broadcast_object leans on a private client; the installed jax must
    still provide each method it calls."""
    from jax._src import distributed
    from jax._src.lib import _jax

    assert hasattr(distributed.global_state, "client")
    assert callable(getattr(_jax.DistributedRuntimeClient, method))


def test_multiprocess_without_client_raises(monkeypatch):
    """No second transport: a multi-process run whose coordination service
    was never initialized is an error, not a silent collective fallback."""
    from jax._src import distributed

    _fake_multiprocess(monkeypatch, index=0)
    monkeypatch.setattr(distributed.global_state, "client", None)
    with pytest.raises(RuntimeError, match="coordination service"):
        dist.broadcast_object({"x": 1})
