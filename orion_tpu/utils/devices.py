"""Virtual-device provisioning for CPU hosts (one copy, five callers).

A tp footprint needs ``tp`` devices in the process. On a CPU host those
are XLA's virtual host devices, requested through ``jax_num_cpu_devices``
— a setting the backend reads ONCE at initialization, so every caller must
run before anything touches a device. The serving/fleet/bench/aot CLIs and
the fleet child all share this helper; ``analysis/spmd_audit
.ensure_cpu_devices`` layers platform forcing and audit-error reporting on
top for the analysis CLI. On an accelerator host the setting is inert: the
mesh is built from the real devices.
"""

from __future__ import annotations

import os
import warnings


def ensure_virtual_devices(n: int) -> None:
    """Ask for ``n`` virtual CPU devices unless some count is already
    pinned (an operator's ``--xla_force_host_platform_device_count`` in
    XLA_FLAGS wins, and so does an earlier, larger request — also how
    nested callers compose). ``n <= 1`` never touches anything. If the
    process's jax backend is ALREADY initialized jax refuses the update —
    warn when that leaves too few devices."""
    if n is None or int(n) <= 1:
        return
    if "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""):
        return
    import jax

    if jax.config.jax_num_cpu_devices >= int(n):
        return
    try:
        jax.config.update("jax_num_cpu_devices", int(n))
    except RuntimeError:  # backend already initialized
        if jax.device_count() < int(n):
            warnings.warn(
                f"ensure_virtual_devices({n}) called after the jax "
                f"backend initialized with {jax.device_count()} "
                "device(s) — provision before the first device op",
                stacklevel=2,
            )


__all__ = ["ensure_virtual_devices"]
