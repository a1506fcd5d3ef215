"""Record the small trace the reader tests run on (``tests/data/``): a few
steps of a two-layer model through the program's own ``make_train_step`` /
``jit_step`` — so its ops carry the program's region scopes — under the
benchmark's capture options, each call inside a ``step_dispatch`` bus span,
then one ``val_step`` span; and beside it the step's scope map.

    python benchmarks/tools/record_probe.py [--out DIR] [--steps 4]

Writes ``scoped.xplane.pb`` and ``scoped.scope_map.json``. Runs on whatever
device JAX has; the files in ``tests/data/`` were recorded on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
from types import SimpleNamespace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(BENCH, "tests", "data"))
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args(argv)

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax

    import observe
    from seist_tpu.obs import scopes
    from seist_tpu.obs.bus import BUS
    from seist_tpu.train.state import TrainState
    from seist_tpu.train.step import jit_step, make_train_step

    class Probe(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool):
            x = nn.relu(nn.Dense(512, name="stem0")(x))
            x = nn.Dropout(0.1, deterministic=not train)(x)
            return nn.Dense(128, name="out_head")(x)

    model = Probe()
    x = jnp.ones((512, 512), jnp.float32)
    y = jnp.zeros((512, 128), jnp.float32)
    key = jax.random.PRNGKey(0)
    variables = model.init(key, x, train=False)
    state = TrainState.create(
        apply_fn=model.apply, params=variables["params"], tx=optax.adam(1e-3),
        batch_stats=None,
    )
    spec = SimpleNamespace(targets_transform_for_loss=None,
                           outputs_transform_for_loss=None)
    step = jit_step(
        make_train_step(spec, lambda o, t: jnp.mean((o - t) ** 2),
                        compute_dtype="bf16", guard=True),
        donate_state=False,
    )
    state, loss, _, _ = step(state, x, y, key)  # compiles
    jax.block_until_ready(loss)

    logdir = tempfile.mkdtemp(prefix="probe_trace_")
    observe.start_trace(logdir)
    for _ in range(args.steps):
        with BUS.span("step_dispatch"):
            state, loss, _, _ = step(state, x, y, key)
        jax.block_until_ready(loss)
    with BUS.span("val_step", probe="yes"):
        jax.block_until_ready(loss + 1)
    trace = observe.stop_trace(logdir)
    os.makedirs(args.out, exist_ok=True)
    shutil.copy(trace, os.path.join(args.out, "scoped.xplane.pb"))
    scope_map = scopes.scope_map(step)
    with open(os.path.join(args.out, "scoped.scope_map.json"), "w") as f:
        json.dump(scope_map, f, indent=0, sort_keys=True)
    shutil.rmtree(logdir, ignore_errors=True)
    print(json.dumps({
        "device": jax.devices()[0].device_kind,
        "trace_bytes": os.path.getsize(os.path.join(args.out, "scoped.xplane.pb")),
        "map_instructions": len(scope_map),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
