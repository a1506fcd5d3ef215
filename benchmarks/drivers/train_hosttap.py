"""Driver ``train_hosttap``: driver ``train`` — its conductor, its window,
its record, unchanged and not copied — for a configuration whose train state
fills the chip.

``drivers/train.py``'s ``StepTap`` keeps DEVICE copies of the parameters
before and after the first call and of the optimizer's state: 16 bytes a
parameter beside a train state of 12. At 667 M parameters that is 10.7 GB
beside a 10.7 GB step. This driver loads that module, puts a tap in its
place whose copies of the first call are fetched to the host
(leaf by leaf, Adam's unread first moment left out), and runs it: ``train_wf_per_s`` and ``setup_s`` are
defined by the accepted code. The tap also keeps the TYPES of the train
step's first call (as the accepted tap does for the eval step), so that a
check can drive that same compiled program again.
"""

from __future__ import annotations

from typing import Any, Dict


def to_host(tree: Any, skip: str = "") -> Any:
    """``tree`` as numpy on the host, one leaf after the other: a
    ``jax.device_get`` of the whole tree starts every transfer at once, and
    the runtime's staging for 10 GB in flight stays with the process (31 GB
    of host met on the first chip call of PR 29, of 40). Leaves under an
    attribute named ``skip`` are left out (None)."""
    import jax
    import numpy as np

    def one(path, x):
        if skip and any(getattr(k, "name", None) == skip for k in path):
            return None
        return np.asarray(x)

    return jax.tree_util.tree_map_with_path(one, tree)


def host_tap(base: Any) -> type:
    class HostStepTap(base.StepTap):
        def __call__(self, *args, **kwargs):
            import jax

            from seist_tpu.obs.bus import BUS

            first = self.calls == 0
            before = None
            if first and not self.is_eval:
                # the state is donated: fetched before the call
                before = to_host(args[0].params)
            if first:
                self.first_args = jax.tree.map(base._describe, args)
            out = self.fn(*args, **kwargs)
            with self.lock:
                self.calls += 1
                self.gsteps.append(BUS.gauge("global_step").value)
            if first and not self.is_eval:
                self.first = {
                    "before": before,
                    "after": to_host(out[0].params),
                    # Adam's first moment is a parameter tree nobody reads
                    # (train_invariants takes the count and the second)
                    "opt_state": to_host(out[0].opt_state, skip="mu"),
                    "loss": to_host(out[1]),
                }
            return out

    return HostStepTap


def run(ctx: Any) -> Dict[str, Any]:
    base = ctx.load_module("drivers", "train")
    base.StepTap = host_tap(base)
    return base.run(ctx)
