"""Online inference service: micro-batching, bucketed warm compiles,
stdlib HTTP front-end, tiered load shedding, and a replica-fleet front
tier. See docs/SERVING.md.

    seist_tpu.serve.protocol   wire format + error classes (HTTP statuses)
    seist_tpu.serve.batcher    request coalescing, backpressure, deadlines
    seist_tpu.serve.pool       model loading, shared-trunk task groups,
                               AOT warm-up, output decode
    seist_tpu.serve.aot        AOT-compiled executables + bf16/int8
                               quantized variants (parity-gated)
    seist_tpu.serve.shed       priority tiers + queue-delay load shedding
    seist_tpu.serve.server     ServeService core + HTTP shim + `serve` CLI
    seist_tpu.serve.router     front-tier router: health-checked replica
                               registry, circuit breaking, retries, hedging
    seist_tpu.serve.canary     live-rollout traffic shifting: canary with
                               auto-rollback + shadow-mode decision diffs
"""

from seist_tpu.serve.batcher import BatcherConfig, MicroBatcher  # noqa: F401
from seist_tpu.serve.canary import (  # noqa: F401
    CanaryBudget,
    CanaryController,
    ShadowMirror,
    decision_diff,
)
from seist_tpu.serve.pool import ModelPool, load_model_entry  # noqa: F401
from seist_tpu.serve.protocol import PredictOptions, ServeError  # noqa: F401
from seist_tpu.serve.router import (  # noqa: F401
    CircuitBreaker,
    ReplicaRegistry,
    Router,
    RouterConfig,
)
from seist_tpu.serve.server import (  # noqa: F401
    ServeHTTPServer,
    ServeService,
    start_http_server,
)
from seist_tpu.serve.shed import AdmissionController, ShedConfig  # noqa: F401
