"""Launcher for test_runs.py: breaks the timed path underneath the harness,
then drives ``run.py`` as usual (``--rehearse`` skips only the look for a
chip). ``state_unchanged``: the train step computes everything and returns
the state it was given. ``lr_x10``: the optimizer is built with ten times the
learning rate. ``module_grad_zeroed``: the first module's gradient reaches
the optimizer as zeros. ``half_batch_loss``: the second half of every batch
is left out of the loss, which still divides by all the rows.
``answer_altered``: the eval step shifts its output where it
is produced."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)


def main() -> int:
    kind, argv = sys.argv[1], sys.argv[2:]
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from seist_tpu.train import step, worker

    if kind == "state_unchanged":
        make = step.make_train_step

        def broken(*a, **k):
            f = make(*a, **k)
            return lambda state, *rest: (state,) + tuple(f(state, *rest)[1:])

        step.make_train_step = worker.make_train_step = broken
    elif kind == "lr_x10":
        build = worker.build_optimizer

        def broken_build(name, learning_rate, *a, **k):
            lr = learning_rate
            scaled = (lambda n: 10.0 * lr(n)) if callable(lr) else 10.0 * lr
            return build(name, scaled, *a, **k)

        worker.build_optimizer = broken_build
    elif kind == "module_grad_zeroed":
        import jax

        from seist_tpu.train.state import TrainState

        apply = TrainState.apply_gradients

        def broken_apply(self, *, grads, **k):
            first = sorted(grads)[0]
            zeroed = jax.tree.map(lambda g: g * 0.0, grads[first])
            return apply(self, grads={**grads, first: zeroed}, **k)

        TrainState.apply_gradients = broken_apply
    elif kind == "half_batch_loss":
        import jax

        make = step.make_train_step

        def half(tree):
            return jax.tree.map(lambda x: x[: x.shape[0] // 2], tree)

        def broken_loss(spec, loss_fn, *a, **k):
            return make(
                spec, lambda o, t: 0.5 * loss_fn(half(o), half(t)), *a, **k)

        step.make_train_step = worker.make_train_step = broken_loss
    elif kind == "answer_altered":
        make_eval = step.make_eval_step

        def broken_eval(*a, **k):
            f = make_eval(*a, **k)

            def g(*args):
                loss, out = f(*args)
                return loss, 1.0 - out
            return g

        step.make_eval_step = worker.make_eval_step = broken_eval
    else:
        raise SystemExit(f"unknown break {kind!r}")
    import run

    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
