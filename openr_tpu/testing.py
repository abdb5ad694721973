"""Host-side test platform pinning.

No test needs an accelerator; sharding tests use virtual CPU devices
(the standard JAX trick for exercising pjit/shard_map topologies
host-side). The pin sets the env knobs and the config value before any
backend is initialized, so a process started without ``JAX_PLATFORMS``
still lands on the CPU.
"""

from __future__ import annotations

import os
import re


def pin_host_cpu(n_devices: int | None = None) -> None:
    """Force JAX onto the host CPU platform, optionally with ``n_devices``
    virtual devices.

    Idempotent and safe to call after ``import jax`` as long as no backend
    has been initialized yet. If one has, backends are cleared and
    re-initialized on the CPU platform — but XLA latches the host device
    count at first backend init, so a too-late call that cannot deliver
    ``n_devices`` raises instead of letting the caller fail confusingly
    downstream. Overwrites (not merely appends) any existing
    ``xla_force_host_platform_device_count`` flag so callers get the count
    they asked for.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        opt = f"--xla_force_host_platform_device_count={n_devices}"
        if "xla_force_host_platform_device_count" in flags:
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", opt, flags
            )
        else:
            flags = (flags + " " + opt).strip()
        os.environ["XLA_FLAGS"] = flags

    import jax

    jax.config.update("jax_platforms", "cpu")

    def _ok() -> bool:
        try:
            devs = jax.devices()
            return devs[0].platform == "cpu" and (
                n_devices is None or len(devs) >= n_devices
            )
        except Exception:
            return False

    if not _ok():
        # A backend was already initialized with the wrong platform; drop
        # it so the next jax.devices() re-initializes under the pinned
        # settings. jax.extend is not auto-imported by `import jax` — the
        # explicit submodule import is required.
        try:
            import jax.extend.backend

            jax.extend.backend.clear_backends()
        except Exception:
            pass
        if not _ok():
            # XLA latches xla_force_host_platform_device_count at first
            # backend init; clearing recovers the platform but not the
            # device count, so fail loudly with the actionable cause.
            raise RuntimeError(
                "pin_host_cpu could not deliver a "
                f"{n_devices or 1}-device CPU backend: a JAX backend was "
                "already initialized in this process. Call pin_host_cpu "
                "before the first jax.devices()/device operation."
            )
