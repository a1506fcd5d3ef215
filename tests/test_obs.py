"""Unit tests for the telemetry plane (seist_tpu/obs/): metrics bus +
span API, Prometheus exposition, JSONL event log, flight recorder, the
metrics HTTP endpoint, and jaxpr per-op attribution."""

import json
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from seist_tpu import obs
from seist_tpu.obs import bus as bus_mod
from seist_tpu.obs import flight as flight_mod
from seist_tpu.obs.bus import Counter, Gauge, Histogram, MetricsBus


@pytest.fixture
def bus():
    return MetricsBus()


@pytest.fixture
def fresh_flight(monkeypatch):
    """Isolate the module-level installed recorder + dedup clock."""
    monkeypatch.setattr(flight_mod, "_INSTALLED", None)
    monkeypatch.setattr(flight_mod, "_LAST_DUMP_MONO", None)
    monkeypatch.setattr(flight_mod, "DUMPED", [])
    yield


# ------------------------------------------------------------------- bus
def test_counter_gauge_identity_and_values(bus):
    c = bus.counter("reads")
    c.inc()
    c.inc(4)
    assert bus.counter("reads") is c  # same name+labels -> same object
    assert c.value == 5
    g = bus.gauge("loss", model="m1")
    g.set(1.5)
    assert bus.gauge("loss", model="m1") is g
    assert bus.gauge("loss", model="m2") is not g
    assert g.value == 1.5


def test_metric_type_conflict_raises(bus):
    bus.counter("x")
    with pytest.raises(TypeError):
        bus.gauge("x")


def test_span_records_histogram_and_duration(bus):
    with bus.span("phase") as sp:
        time.sleep(0.01)
    assert sp.duration_s is not None and sp.duration_s >= 0.01
    h = bus.histogram("phase_ms")
    assert h.count == 1
    assert h.mean >= 10.0


def test_span_begin_end_idempotent(bus):
    sp = bus.begin("p")
    d1 = sp.end()
    time.sleep(0.005)
    assert sp.end() == d1  # second end() is a no-op
    assert bus.histogram("p_ms").count == 1


def test_span_sink_receives_spans(bus):
    seen = []
    bus.add_span_sink(seen.append)
    with bus.span("s", k="v"):
        pass
    assert len(seen) == 1
    assert seen[0].name == "s" and seen[0].labels == {"k": "v"}
    bus.remove_span_sink(seen.append)
    with bus.span("s"):
        pass
    assert len(seen) == 1


def test_sick_span_sink_never_breaks_timed_path(bus):
    def boom(span):
        raise RuntimeError("sink died")

    bus.add_span_sink(boom)
    with bus.span("s"):
        pass  # must not raise
    assert bus.histogram("s_ms").count == 1


def test_timed_iter_spans_every_next(bus):
    out = list(bus_mod.timed_iter([1, 2, 3], "wait", bus=bus))
    assert out == [1, 2, 3]
    assert bus.histogram("wait_ms").count == 3


def test_collectors_flatten_replace_unregister(bus):
    bus.register_collector("src", lambda: {"a": 1, "nested": {"b": 2.5},
                                           "flag": True, "skip": "str"})
    samples = {name: v for name, _, v in bus._collect()}
    assert samples == {"src_a": 1.0, "src_nested_b": 2.5, "src_flag": 1.0}
    bus.register_collector("src", lambda: {"a": 9})  # same key replaces
    samples = {name: v for name, _, v in bus._collect()}
    assert samples == {"src_a": 9.0}
    bus.unregister_collector("src")
    assert bus._collect() == []


def test_sick_collector_skipped(bus):
    bus.register_collector("bad", lambda: 1 / 0)
    bus.register_collector("good", lambda: {"v": 1})
    assert {n for n, _, _ in bus._collect()} == {"good_v"}


def test_collector_name_override_and_labels(bus):
    bus.register_collector(
        "serve_batcher:m1", lambda: {"n": 3}, name="serve_batcher", model="m1"
    )
    [(name, labels, v)] = bus._collect()
    assert name == "serve_batcher_n" and labels == {"model": "m1"} and v == 3


def test_snapshot_shape(bus):
    bus.counter("c").inc()
    bus.gauge("g").set(2)
    with bus.span("sp"):
        pass
    bus.register_collector("col", lambda: {"k": 7})
    snap = bus.snapshot()
    assert snap["counters"] == {"c": 1.0}
    assert snap["gauges"] == {"g": 2.0}
    assert snap["histograms"]["sp_ms"]["count"] == 1.0
    assert snap["collectors"] == {"col_k": 7.0}
    json.dumps(snap)  # JSON-able end to end


# ------------------------------------------------------------ prometheus
def test_render_prometheus_format(bus):
    bus.counter("reads", source="h5").inc(3)
    bus.gauge("depth").set(4)
    h = bus.histogram("lat_ms", bounds=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(100.0)  # overflow bucket
    bus.register_collector("io", lambda: {"retries": 2})
    text = bus_mod.render_prometheus(bus)
    assert '# TYPE seist_reads_total counter' in text
    assert 'seist_reads_total{source="h5"} 3' in text
    assert "seist_depth 4" in text
    # Cumulative buckets + +Inf == count.
    assert 'seist_lat_ms_bucket{le="1"} 1' in text
    assert 'seist_lat_ms_bucket{le="10"} 2' in text
    assert 'seist_lat_ms_bucket{le="+Inf"} 3' in text
    assert "seist_lat_ms_count 3" in text
    assert "seist_io_retries 2" in text
    assert text.endswith("\n")


def test_prometheus_label_escaping(bus):
    bus.gauge("g", path='a"b\\c').set(1)
    text = bus_mod.render_prometheus(bus)
    assert 'path="a\\"b\\\\c"' in text


# -------------------------------------------------------------- event log
def test_event_log_jsonl(tmp_path):
    log = obs.EventLog(str(tmp_path / "events.jsonl"))
    log.emit("epoch_summary", epoch=1, loss=0.5)
    log.emit("weird", obj=object())  # unserializable -> fallback via str
    log.close()
    log.emit("after_close")  # no-op, no raise
    lines = (tmp_path / "events.jsonl").read_text().strip().splitlines()
    assert len(lines) == 2
    first = json.loads(lines[0])
    assert first["event"] == "epoch_summary" and first["epoch"] == 1
    assert "t" in first
    json.loads(lines[1])


# ---------------------------------------------------------- flight recorder
def test_flight_ring_capacity_and_order():
    rec = obs.FlightRecorder(capacity=8)
    for i in range(20):
        rec.record_step(i, loss=float(i))
    p = rec.payload("test")
    assert len(p["steps"]) == 8
    assert [s["step"] for s in p["steps"]] == list(range(12, 20))
    assert p["last_step"] == 19


def test_flight_spans_tagged_with_current_step(bus):
    rec = obs.FlightRecorder(capacity=8)
    bus.add_span_sink(rec.on_span)
    rec.record_step(5)
    with bus.span("host_wait"):
        pass
    p = rec.payload("test")
    assert p["spans"][0]["name"] == "host_wait"
    assert p["spans"][0]["step"] == 5


def test_flight_dump_writes_json(tmp_path):
    rec = obs.FlightRecorder(capacity=4)
    rec.record_step(1)
    rec.record_event("rollback", rollback_to_step=0)
    path = rec.dump("unit_test", path=str(tmp_path / "f.json"), extra=7)
    data = json.loads(open(path).read())
    assert data["reason"] == "unit_test" and data["extra"] == 7
    assert data["steps"][0]["step"] == 1
    assert data["events"][0]["kind"] == "rollback"
    assert "metrics" in data


def test_dump_on_death_no_recorder_is_noop(fresh_flight):
    assert flight_mod.dump_on_death("x") is None


def test_dump_on_death_and_dedup(fresh_flight, tmp_path, monkeypatch):
    from seist_tpu.utils.logger import logger

    monkeypatch.setattr(logger, "_logdir", str(tmp_path), raising=False)
    rec = obs.FlightRecorder(capacity=4)
    flight_mod.install(rec)
    rec.record_step(3)
    p1 = flight_mod.dump_on_death("stall_watchdog")
    assert p1 and "stall_watchdog" in p1
    # The hard_exit funnel dedups against the richer dump just written...
    assert flight_mod.dump_on_death("hard_exit", dedup_s=5.0) is None
    # ...but an explicit dump (no dedup) still lands.
    assert flight_mod.dump_on_death("hard_exit") is not None
    assert flight_mod.DUMPED[0] == p1
    flight_mod.install(None)


def test_install_swaps_bus_sink(fresh_flight):
    from seist_tpu.obs.bus import BUS

    r1 = obs.FlightRecorder(capacity=4)
    r2 = obs.FlightRecorder(capacity=4)
    flight_mod.install(r1)
    flight_mod.install(r2)  # replaces r1's sink
    r1.record_step(0)
    r2.record_step(0)
    with BUS.span("swap_probe"):
        pass
    assert len(r1.payload("t")["spans"]) == 0
    assert len(r2.payload("t")["spans"]) == 1
    flight_mod.install(None)
    with BUS.span("swap_probe"):
        pass
    assert len(r2.payload("t")["spans"]) == 1


# ------------------------------------------------------------- http server
def _get(url):
    with urllib.request.urlopen(url, timeout=5) as r:
        return r.status, r.read().decode(), r.headers.get("Content-Type", "")


def test_metrics_http_endpoints(bus, fresh_flight):
    bus.counter("reads").inc(2)
    rec = obs.FlightRecorder(capacity=4)
    rec.record_step(1)
    flight_mod.install(rec)
    trigger = obs.ProfileTrigger()
    server = obs.start_metrics_server(0, bus=bus, profile_trigger=trigger)
    try:
        port = server.server_address[1]
        base = f"http://127.0.0.1:{port}"
        status, text, ctype = _get(base + "/metrics")
        assert status == 200 and "seist_reads_total 2" in text
        assert ctype.startswith("text/plain")
        status, text, _ = _get(base + "/metrics.json")
        assert status == 200
        assert json.loads(text)["counters"]["reads"] == 2.0
        status, text, _ = _get(base + "/flight")
        assert status == 200
        assert json.loads(text)["steps"][0]["step"] == 1
        status, text, _ = _get(base + "/healthz")
        assert status == 200
        # POST /profile arms the trigger the train loop polls.
        req = urllib.request.Request(
            base + "/profile?steps=3", method="POST", data=b""
        )
        with urllib.request.urlopen(req, timeout=5) as r:
            assert r.status == 200
            assert json.loads(r.read())["requested_steps"] == 3
        assert trigger.consume() == 3
        assert trigger.consume() == 0  # one-shot
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/nope")
        assert ei.value.code == 404
    finally:
        server.shutdown()
        flight_mod.install(None)


def test_profile_trigger_last_write_wins():
    t = obs.ProfileTrigger()
    assert t.consume() == 0
    t.request(2)
    t.request(7)
    assert t.consume() == 7
    t.request(0)  # clamped to >= 1
    assert t.consume() == 1


def test_profile_trigger_request_during_consume_not_dropped():
    """The PR 6 consumed-and-dropped hazard, re-pinned after the
    lock-free rework: a request landing while consume() is mid-drain
    (HTTP handler thread vs the train loop's step poll) must be captured
    by that poll or the next one, never silently discarded."""
    from collections import deque

    t = obs.ProfileTrigger()

    class MidDrainRequest(deque):
        injected = False

        def popleft(self):
            v = deque.popleft(self)
            if not self.injected:
                # a second requester fires exactly between the drain's
                # atomic popleft operations
                MidDrainRequest.injected = True
                t.request(20)
            return v

    t._requests = MidDrainRequest([5], maxlen=64)
    assert t.consume() == 20  # the mid-drain request survives
    assert t.consume() == 0


# -------------------------------------------------------------- attribution
def test_attribution_dot_flops_exact():
    import jax.numpy as jnp

    def f(a, b):
        return jnp.dot(a, b)

    out = obs.attribute_step(
        f, (np.ones((4, 8), np.float32), np.ones((8, 16), np.float32))
    )
    dot = next(o for o in out["top_ops"] if o["op"] == "dot_general")
    assert dot["flops"] == 2 * 4 * 16 * 8
    assert dot["class"] == "matmul"
    # bytes: lhs + rhs + out, fp32
    assert dot["bytes_accessed"] == 4 * (4 * 8 + 8 * 16 + 4 * 16)


def test_attribution_scan_multiplies_by_length():
    import jax
    import jax.numpy as jnp

    def f(x):
        def body(c, _):
            return jnp.tanh(c), None

        c, _ = jax.lax.scan(body, x, None, length=5)
        return c

    out = obs.attribute_step(f, (np.ones((8,), np.float32),))
    tanh = next(o for o in out["top_ops"] if o["op"] == "tanh")
    assert tanh["count"] == 5
    assert tanh["flops"] == 5 * 8


def test_attribution_conv_flops_exact():
    import jax

    def f(x, k):
        return jax.lax.conv_general_dilated(
            x, k, window_strides=(1,), padding="VALID",
            dimension_numbers=("NCH", "OIH", "NCH"),
        )

    x = np.ones((2, 3, 32), np.float32)  # N=2 C=3 L=32
    k = np.ones((4, 3, 5), np.float32)  # O=4 I=3 K=5
    out = obs.attribute_step(f, (x, k))
    conv = next(o for o in out["top_ops"] if o["op"] == "conv_general_dilated")
    # MACs = N * L_out * O * I * K = 2*28*4*3*5; flops = 2*MACs
    assert conv["flops"] == 2 * (2 * 28 * 4 * 3 * 5)


def test_attribution_through_jit_and_measured_shares():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(a, b):
        return jnp.tanh(a @ b).sum()

    out = obs.attribute_step(
        f,
        (np.ones((16, 16), np.float32), np.ones((16, 16), np.float32)),
        measured_step_ms=10.0,
        peak_flops=1e12,
    )
    fracs = [o["time_frac"] for o in out["top_ops"]]
    assert out["n_op_kinds"] >= 3
    assert abs(sum(d["time_frac"] for d in out["mfu_decomposition"].values())
               - 1.0) < 1e-3
    assert all(o["est_ms"] is not None for o in out["top_ops"])
    assert fracs == sorted(fracs, reverse=True)  # top-k ordered by time
    assert "mfu_model" in out


def test_attribution_top_k_limit():
    import jax.numpy as jnp

    def f(a):
        return jnp.tanh(jnp.exp(a) + jnp.log(a) * a - a / 3).sum()

    out = obs.attribute_step(f, (np.ones((8,), np.float32) + 1,), top_k=2)
    assert len(out["top_ops"]) == 2
    assert out["n_op_kinds"] > 2


# ----------------------------------------------- dedup onto the span API
def test_profiling_stopwatch_delegates_to_obs(monkeypatch):
    from seist_tpu.utils import profiling

    with profiling.stopwatch() as elapsed:
        time.sleep(0.005)
        mid = elapsed()
    assert 0.005 <= mid
    assert elapsed() >= mid  # frozen after exit


def test_step_time_split_span_helpers():
    from seist_tpu.utils.profiling import StepTimeSplit

    split = StepTimeSplit(skip_first=0)
    for _ in range(2):
        # Long enough that a loaded machine's overshoot of a sleep (a few
        # ms under the suite's six workers) cannot turn the ratio around.
        with split.host():
            time.sleep(0.04)
        with split.device():
            time.sleep(0.02)
    s = split.summary()
    assert s["steps"] == 2
    assert s["host_wait_ms_per_step"] >= 40.0
    assert s["device_time_ms_per_step"] >= 20.0
    assert 0.5 < s["input_bound_fraction"] < 1.0


def test_jit_first_call_span_recorded():
    import jax.numpy as jnp

    from seist_tpu.obs.bus import BUS
    from seist_tpu.train.step import _first_call_span

    h = BUS.histogram("jit_first_call_ms", fn="unit_probe")
    before = h.count
    fn = _first_call_span(lambda x: jnp.sum(x), "unit_probe")
    fn(np.ones(4, np.float32))
    fn(np.ones(4, np.float32))
    assert h.count == before + 1  # only the first call is recorded


def test_jit_first_call_runs_to_completion():
    """The first call returns a finished result (its span owns the wait for
    its inputs and one execution; the benchmark's conductor starts its
    trace a fixed delay after that return, PERF.md PR 35)."""
    import jax
    import jax.numpy as jnp

    from seist_tpu.train.step import _first_call_span

    def slow(x):
        return jax.lax.fori_loop(0, 300, lambda _, a: jnp.tanh(a @ a), x)

    x = jnp.eye(256, dtype=jnp.float32) * 0.5
    jax.block_until_ready(jax.jit(slow)(x))  # compiled: the wait below is the run's
    fn = _first_call_span(jax.jit(slow), "unit_probe_ready")
    # Without the wait this reads False: the run takes a tenth of a second
    # and dispatch is asynchronous on the CPU backend too.
    assert fn(x).is_ready()


# ----------------------------------------- scrape-under-load consistency
class TestScrapeUnderLoad:
    """ISSUE 11 satellite: /metrics scrapes racing a flushing batcher
    must return consistent snapshots — no exceptions, parseable
    Prometheus text, and conserved batcher accounting at quiesce."""

    def test_concurrent_scrapes_while_batcher_flushes(self):
        import threading as th

        import numpy as np

        from seist_tpu.obs import trace as obs_trace
        from seist_tpu.obs.bus import BUS, render_prometheus
        from seist_tpu.serve.batcher import BatcherConfig, MicroBatcher

        def forward(batch):
            obs_trace.annotate_flush(program="scr/full/fp32", aot=True)
            time.sleep(0.001)
            return batch

        b = MicroBatcher(
            forward,
            BatcherConfig(max_batch=4, max_delay_ms=1.0, max_queue=64),
            name="scrape_load",
        )
        stop = th.Event()
        scrape_errors = []
        scrapes = {"n": 0}

        def scraper():
            # The scrape path a Prometheus server + the fleet aggregator
            # hit concurrently with traffic.
            while not stop.is_set():
                try:
                    text = render_prometheus(BUS)
                    assert "seist_serve_batcher_submitted" in text
                    for line in text.splitlines():
                        if line.startswith("#"):
                            continue
                        float(line.rsplit(" ", 1)[1])  # every sample parses
                    snap = BUS.snapshot()
                    stats = snap["collectors"]
                    sub = stats.get(
                        "serve_batcher_submitted{model=scrape_load}", 0
                    )
                    done = (
                        stats.get(
                            "serve_batcher_completed{model=scrape_load}", 0)
                        + stats.get(
                            "serve_batcher_expired{model=scrape_load}", 0)
                        + stats.get(
                            "serve_batcher_rejected{model=scrape_load}", 0)
                        + stats.get(
                            "serve_batcher_failed{model=scrape_load}", 0)
                    )
                    # Monotone sanity on a live snapshot: never more
                    # settled than submitted.
                    assert done <= sub
                    scrapes["n"] += 1
                except Exception as e:  # noqa: BLE001 - the assertion
                    scrape_errors.append(repr(e))
                    return

        threads = [th.Thread(target=scraper) for _ in range(2)]
        for t in threads:
            t.start()
        try:
            def client(i):
                rt = obs_trace.RequestTrace(
                    None, buffer=obs_trace.TraceBuffer(capacity=8)
                )
                b.submit(np.zeros((2,), np.float32), timeout_ms=10_000,
                         trace=rt)
                rt.finish(200)

            # ThreadPoolExecutor is imported at module top: concurrent.
            # futures lazy-loads its thread module, which must not first
            # happen inside an instrumented --lock-graph window.
            with ThreadPoolExecutor(8) as ex:
                list(ex.map(client, range(120)))
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
            b.shutdown()
        assert not scrape_errors, scrape_errors
        assert scrapes["n"] > 0, "scrapers never completed a pass"
        stats = b.stats()
        assert stats["submitted"] == 120
        assert (
            stats["completed"] + stats["expired"] + stats["rejected"]
            + stats["failed"]
        ) == 120
