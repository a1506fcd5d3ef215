# seist_tpu build targets.

NATIVE_DIR := seist_tpu/native

.PHONY: native test t1 lint lint-baseline irlint-report lockgraph \
	replay-smoke serve-smoke serve-chaos obs-smoke trace-smoke \
	rollout-smoke chaos pack-smoke bench-loader repick-smoke \
	bench-repick quant-smoke stream-smoke twin-smoke stream-chaos \
	batch-chaos bench-batch-fleet clean

# seist_tpu.native builds its library from wavekit.cpp on first import
# (hash-named, see seist_tpu/native/__init__.py); this target just does
# that import and prints where the library landed.
native:
	python -c "import seist_tpu.native as n; print(n.lib_path())"

test:
	python -m pytest tests/ -x -q

# Static-analysis gate, ALL FOUR analyzers through one shared frontend
# invocation (docs/STATIC_ANALYSIS.md; single interpreter startup, one
# file walk feeding the three AST passes, one manifest walk, combined
# exit code): jaxlint — JAX hot-path hazards (host syncs, PRNG key
# reuse, missing donate_argnums, retraces, wall-clock intervals, broad
# excepts); threadlint — concurrency/lifecycle hazards (unguarded
# shared attrs, unsafe signal handlers, silent thread death, untimed
# waits, SYN-drop backlogs, exit-code contract); detlint — determinism
# hazards (unsorted dir enumeration, unseeded/global RNG, wall-clock or
# unregistered env reads in det-critical modules, set/dict iteration
# order, float reduction order); irlint — IR-level properties of the
# LOWERED programs the repo ships (fp32 matmuls under the bf16 policy,
# donation aliasing, in-program host transfers, bucket padding waste,
# replicated data args on meshes). Each fails only on findings not
# grandfathered in its tools/<tool>_baseline.json.
lint:
	python -m tools.lint

# Re-accept the current jaxlint findings (review the diff before
# committing!). Deliberately does NOT touch tools/threadlint_baseline.json,
# tools/detlint_baseline.json, or tools/irlint_baseline.json: all three
# are empty by construction — fix the code or add a rationale'd
# `# threadlint: disable` / `# detlint: disable` / `# irlint: disable`
# instead of grandfathering (detlint and irlint --update-baseline
# additionally REFUSE to write while their baselines are empty).
lint-baseline:
	python -m tools.jaxlint seist_tpu --update-baseline

# Machine-readable IR audit (docs/STATIC_ANALYSIS.md "IR-level
# analysis"): per-program bf16 matmul-FLOPs coverage, donation-aliasing
# table, bucket padding waste, host-transfer counts — the numbers bench
# and CI trend across commits.
irlint-report:
	python -m tools.irlint --report irlint_report.json

# threadlint runtime audit lane (docs/STATIC_ANALYSIS.md): the smoke
# lane with every in-test lock instrumented — fails on lock-order
# cycles (potential deadlocks) and locks held across blocking calls.
lockgraph:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m smoke --lock-graph \
	  -p no:cacheprovider -p no:xdist -p no:randomly

# detlint runtime audit lane (docs/STATIC_ANALYSIS.md "Determinism
# analysis"): the whole det-critical pipeline — pack -> resume ->
# repick -> journal-restore + alert WAL — run twice under perturbation
# (PYTHONHASHSEED 0 vs 1, 1 vs 2 workers, reversed directory inode
# order via the relink shim) with every digest pinned byte-identical.
# One JSON verdict line (digests + perturbations tried); non-zero on
# any divergence.
replay-smoke:
	JAX_PLATFORMS=cpu python -m tools.replay_smoke

# Tier-1 verify: the exact line from ROADMAP.md (fast lane, CPU backend,
# slow-marked kill/resume e2e excluded). Prints DOTS_PASSED for the driver.
t1: SHELL := /bin/bash
t1:
	set -o pipefail; rm -f /tmp/_t1.log; \
	timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
	  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
	  -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; \
	rc=$${PIPESTATUS[0]}; \
	echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); \
	exit $$rc

# Fault-injection suite (docs/FAULT_TOLERANCE.md): the faults unit lane
# plus the chaos e2e lane — real training runs under injected NaN/kill/
# SIGTERM/flaky-read/corrupt-sample/loader-stall faults.
chaos:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'chaos or faults' \
	  -p no:cacheprovider -p no:xdist -p no:randomly

# Packed data-plane smoke (docs/DATA.md): 2-worker shard-parallel pack
# of the synthetic dataset (cross-checked bit-identical against a serial
# pack), then 2 training epochs on packed vs unpacked at the same seed
# with the loss curves pinned equal. One JSON verdict line; non-zero on
# any parity failure.
pack-smoke:
	JAX_PLATFORMS=cpu python -m tools.pack_smoke

# Packed-ingest throughput ladder (docs/DATA.md "Benchmarks"): hdf5
# per-sample reads vs packed per-sample reads vs packed+direct-ingest
# batch fills on one shared fixture, with the per-stage ms/wf budget,
# plus the fp32/bf16/int8 storage-dtype ladder (measured bytes/wf;
# int8 includes the stage_raw device-dequant lane). Gates: direct >=
# 2x hdf5, int8 bytes <= 0.55x fp32. Committed headline:
# BENCH_loader_r02.json.
bench-loader:
	JAX_PLATFORMS=cpu python -m tools.bench_loader --compare

# Batch re-picking smoke (docs/DATA.md "Batch re-picking"): 2-worker
# CPU map-reduce over a synthetic packed archive — one worker SIGKILL'd
# mid-shard, relaunched at its exact segment offset — asserting the
# merged catalog is BYTE-identical to a serial run and that every
# worker's CompileBudget window recorded ZERO compiles after warm-up.
# One JSON verdict line; non-zero on any violation.
repick-smoke:
	JAX_PLATFORMS=cpu python -m tools.repick_smoke

# int8 end-to-end smoke (docs/DATA.md "Storage dtype"): tiny fp32 +
# int8 packs of the same synthetic source -> direct ingest -> inline
# repick of both -> gates on-disk bytes <= 0.55x fp32, decision parity
# vs the fp32 catalog (pick positions within the repo's 0.1 s residual
# tolerance), host-feed (fill + device_put) speedup >= 1.7x (bytes-
# bound CPU mechanism proof; the end-to-end chip run is flagged
# tpu_run: pending), and zero post-warm-up compiles. One JSON verdict
# line. Committed headline: BENCH_repick_r02.json.
quant-smoke:
	JAX_PLATFORMS=cpu python -m tools.quant_smoke

# Batch-fleet chaos lane (docs/FAULT_TOLERANCE.md "Batch fleet
# faults"): a 3-worker LEASE fleet (tools/supervise_repick.py over
# batch/fleet.py) re-picks a synthetic archive with every batch-plane
# failure class injected at once — worker 0 rides out a lease-store
# partition (commits while locally valid, parks, heals into a counted
# fence-reject), worker 1 is SIGKILL'd at its first lease (expiry ->
# peer reclaim at the next fencing token -> crash-budget relaunch),
# worker 2 is preempted into the exit-75 contract (drain, release,
# rejoin). Gates: fleet finishes unattended, merged catalog sha256 ==
# the serial no-fault run, ZERO double-committed segments, and the
# fence-reject counter accounts the zombie attempt. repick_smoke
# geometry, so the XLA compile cache stays warm across lanes. One JSON
# verdict line.
batch-chaos:
	JAX_PLATFORMS=cpu python -m tools.batch_chaos

# Batch-fleet scaling headline (docs/FAULT_TOLERANCE.md): 3 lease
# workers vs 1 over the same archive via supervise_repick, byte-identity
# HARD-gated; the >= 1.8x wall-clock gate is enforced on >= 3-core
# hosts and recorded as pending on the 1-core CI box (the quant_smoke
# "tpu_run: pending" idiom). Committed headline: BENCH_batch_fleet_r01.json.
bench-batch-fleet:
	JAX_PLATFORMS=cpu python -m tools.bench_batch_fleet

# Batch-vs-serve throughput headline (docs/DATA.md "Batch re-picking"):
# the repick engine and tools/bench_serve on the SAME model/window/host,
# gated at batch >= 5x serve waveforms/sec/chip. Committed headline:
# BENCH_repick_r01.json.
bench-repick:
	JAX_PLATFORMS=cpu python -m tools.bench_repick

# Telemetry-plane smoke (docs/OBSERVABILITY.md): 2-step CPU train run
# with --metrics-port, live Prometheus/JSON/flight scrape, then an
# injected SEIST_FAULT_IO_STALL crash that must exit 75 and leave a
# flight-recorder dump with the final steps' spans. Also runs in the
# chaos lane (tests/test_obs_e2e.py).
obs-smoke:
	JAX_PLATFORMS=cpu python tools/obs_smoke.py

# Checkpoint-free serving smoke: warm-compile (AOT), micro-batch 24
# single-task requests, then a multi-task fan-out pass — 12 requests
# against a shared-trunk seist_s group (dpk+emg+dis on ONE trunk run per
# trace); bench_serve exits non-zero unless EVERY response answered ALL
# requested heads (fanout_complete). Each prints a BENCH-style JSON line.
serve-smoke:
	JAX_PLATFORMS=cpu python tools/bench_serve.py --model-name phasenet \
		--window 256 --requests 24 --concurrency 6 --max-batch 4
	JAX_PLATFORMS=cpu python tools/bench_serve.py --model-name seist_s \
		--tasks dpk,emg,dis --window 256 --requests 12 --concurrency 4 \
		--max-batch 4

# Distributed-tracing smoke (docs/OBSERVABILITY.md "Distributed
# tracing"): 2-replica fleet + router under bench_serve with hedging
# forced on every request; a hedged request's stitched cross-process
# trace (tools/trace_report.py) must total within 10% of the
# client-observed latency, carry queue-wait + device-program spans, and
# GET /fleet/metrics.json must aggregate router + both replicas.
trace-smoke:
	JAX_PLATFORMS=cpu python tools/trace_smoke.py

# Streaming smoke (docs/SERVING.md "Streaming inference"): a real
# phasenet replica driven over HTTP by a 50-station network, 30 s of
# waveform per station through POST /stream — gates zero dropped
# alert-tier windows (no 429/503, no degraded sessions) and
# streaming<->offline pick parity vs POST /annotate on 3 sampled
# stations. One JSON verdict line.
stream-smoke:
	JAX_PLATFORMS=cpu python tools/stream_smoke.py

# Network digital twin (docs/SERVING.md "Streaming inference"): a
# deterministic mainshock + Omori-aftershock scenario over 50 simulated
# stations (noise stations, dropouts, late bursts, duplicate packets)
# driven through the full serve+stream+association plane — gates zero
# missed mainshock alerts, zero alert-tier sheds/dropped windows, and a
# pinned p99 sample->alert latency; writes the BENCH_stream_r01.json
# lane with the per-stage latency breakdown.
twin-smoke:
	JAX_PLATFORMS=cpu python tools/twin.py --smoke \
		--output BENCH_stream_r01.json

# Live-rollout smoke (docs/SERVING.md "Live rollout"): a real 2-replica
# phasenet fleet rolled to a new model version (SIGHUP + --rollout-file)
# under sustained open-loop load — asserts zero failed requests, fleet
# convergence on the target version, and zero stale-version responses
# after convergence (bench_serve --expect-version gate). One JSON
# verdict line; the 3-replica variant is the serve-chaos flywheel test.
rollout-smoke:
	JAX_PLATFORMS=cpu python tools/rollout_smoke.py

# Serving chaos lane (docs/FAULT_TOLERANCE.md "Serving faults"): real
# replica subprocesses under SEIST_FAULT_SERVE_* — SIGKILL-mid-load with
# zero client-visible failures, black-hole circuit open/close, overload
# shedding that protects the alert tier's SLO, the live-rollout
# flywheel (3-replica roll under sustained load: zero failures, zero
# stale versions after convergence), and canary auto-rollback of an
# injected bad candidate. The fleet supervisor + router + rollout units
# (model-free) ride along. Subset of `make chaos`, runnable alone when
# iterating on serve/.
serve-chaos:
	JAX_PLATFORMS=cpu python -m pytest tests/test_serve_chaos.py \
	  tests/test_serve_fleet.py tests/test_router.py -q \
	  -p no:cacheprovider -p no:xdist -p no:randomly

# Streaming chaos lane (docs/FAULT_TOLERANCE.md "Streaming faults"): the
# twin's exported mainshock schedule replayed against a REAL 3-replica
# twin_replica fleet — SIGKILL on the station-heavy replica mid-
# mainshock (journal restore + router re-home, exactly-once alerts at
# the consumer) and a drop/dup/reorder packet-fault run. Each test
# prints a `[stream-chaos] VERDICT {json}` line. Subset of `make chaos`
# (the tests carry the chaos marker), runnable alone when iterating on
# stream/.
stream-chaos:
	JAX_PLATFORMS=cpu python -m pytest tests/test_stream_chaos.py -q \
	  -p no:cacheprovider -p no:xdist -p no:randomly

clean:
	rm -f $(NATIVE_DIR)/libwavekit*.so
