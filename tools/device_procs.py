"""One accelerator chip belongs to one process at a time.

Lanes that start several processes which each initialise a JAX device
(serving replicas, batch-fleet workers) would fail or hang on a chip host:
the first process takes the chip and the rest wait for it. Nothing here
pins processes to chips, so those lanes run on the CPU backend only and
say so instead of hanging. Stdlib-only: supervisors must stay off jax.
"""

from __future__ import annotations

import os


def refuse_shared_chip(n_device_procs: int, what: str) -> None:
    """Exit with an explanation unless ``n_device_procs`` processes can each
    have their own device, i.e. unless the CPU backend is pinned."""
    if n_device_procs <= 1 or os.environ.get("JAX_PLATFORMS") == "cpu":
        return
    raise SystemExit(
        f"{what}: this lane runs {n_device_procs} processes that each "
        f"initialise a JAX device. An accelerator chip belongs to one "
        f"process at a time, so on a chip host they would fail or hang; "
        f"run it with JAX_PLATFORMS=cpu (the Makefile lanes do)."
    )
