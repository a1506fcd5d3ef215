"""Compile accounting inside the program: the events JAX's own monitoring
emits for every trace, lowering, backend compile and persistent-cache
lookup, as counters on the bus. An operator sees a recompile on
``/metrics`` (``jit_compiles``, ``jit_backend_compile_s`` move); a reader
gets the split of a first call's minutes into tracing, lowering and the
compile or cache load.

| counter | jax.monitoring event |
| --- | --- |
| ``jit_trace_s`` | ``/jax/core/compile/jaxpr_trace_duration`` |
| ``jit_lower_s`` | ``/jax/core/compile/jaxpr_to_mlir_module_duration`` |
| ``jit_backend_compile_s``, ``jit_compiles`` | ``/jax/core/compile/backend_compile_duration`` (a cache load counts: it stands for a compile) |
| ``compile_cache_hits`` | ``/jax/compilation_cache/cache_hits`` |
| ``compile_cache_misses`` | ``/jax/compilation_cache/compile_requests_use_cache`` not followed by a hit |
| ``compile_cache_retrieval_s`` | ``/jax/compilation_cache/cache_retrieval_time_sec`` |
"""

from __future__ import annotations

import threading

from seist_tpu.obs.bus import BUS

_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit_trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit_lower_s",
    "/jax/core/compile/backend_compile_duration": "jit_backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "compile_cache_retrieval_s",
}
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_installed = False
# A compile asks the persistent cache, then either reports a hit or goes on
# to compile, all on one thread: a request still open when the backend
# compile's duration arrives was a miss.
_asked = threading.local()


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_REQUEST:
        _asked.open = True
    elif event == _CACHE_HIT:
        _asked.open = False
        BUS.counter("compile_cache_hits").inc()


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    name = _DURATIONS.get(event)
    if name is None:
        return
    BUS.counter(name).inc(float(duration_secs))
    if event == _BACKEND_COMPILE:
        BUS.counter("jit_compiles").inc()
        if getattr(_asked, "open", False):
            _asked.open = False
            BUS.counter("compile_cache_misses").inc()


def install() -> None:
    """Register the listeners, once per process (``enable_compile_cache``
    calls this from every entry point). The counters are looked up on the
    bus at each event, so a bus reset between tests loses nothing."""
    global _installed
    with _lock:
        if _installed:
            return
        from jax import monitoring

        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
