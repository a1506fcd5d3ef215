"""Launcher for test_lm_cell.py: breaks the token model's timed path
underneath the harness, then drives ``run.py`` as usual (``--rehearse`` skips
only the look for a chip). ``scan_carry_dropped``: every chunk of the scan
starts from a zero state, the carry between chunks is lost.
``expert_mask_off_by_one``: the expert layer computes experts ``first + 1 ..``
with the weights of ``first ..`` — the mask of absent experts is off by one.
``half_positions_loss``: the second half of every sequence is left out of
the loss, which still divides by all positions. ``lr_half_again``: the
optimizer is built with 1.5 times the learning rate, inside what
``train_invariants`` allows a step to move."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)


def main() -> int:
    kind, argv = sys.argv[1], sys.argv[2:]
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax.numpy as jnp

    from seist_tpu.models import losses, nemotron_h

    if kind == "scan_carry_dropped":
        whole = nemotron_h.ssd_chunked

        def broken(x, dt, a, b, c, *, chunk, initial_state=None):
            parts = [
                whole(x[:, i:i + chunk], dt[:, i:i + chunk], a,
                      b[:, i:i + chunk], c[:, i:i + chunk], chunk=chunk)
                for i in range(0, x.shape[1], chunk)
            ]
            return jnp.concatenate([p[0] for p in parts], axis=1), parts[-1][1]

        nemotron_h.ssd_chunked = broken
    elif kind == "expert_mask_off_by_one":
        local = nemotron_h.moe.local_experts

        def broken(x, ids, weights, w_up, w_down, *, first, capacity):
            return local(x, ids, weights, w_up, w_down, first=first + 1,
                         capacity=capacity)

        nemotron_h.moe.local_experts = broken
    elif kind == "half_positions_loss":
        call = losses.TokenCELoss.__call__

        def broken(self, logits, targets):
            half = targets.shape[1] // 2
            kept = jnp.arange(targets.shape[1]) < half
            # the kept half's sum over ALL positions' count
            return call(self, logits, jnp.where(kept, targets, -1)) * (
                jnp.sum((targets >= 0) & kept) / jnp.sum(targets >= 0))

        losses.TokenCELoss.__call__ = broken
    elif kind == "lr_half_again":
        from seist_tpu.train import worker

        build = worker.build_optimizer

        def broken_build(name, learning_rate, *a, **k):
            lr = learning_rate
            scaled = (lambda n: 1.5 * lr(n)) if callable(lr) else 1.5 * lr
            return build(name, scaled, *a, **k)

        worker.build_optimizer = broken_build
    else:
        raise SystemExit(f"unknown fault '{kind}'")
    import run

    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
