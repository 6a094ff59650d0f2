"""Backend/platform helpers.

``ensure_cpu_only`` pins this process to the CPU backend (tests, the
multi-chip dry-run on a virtual device mesh, CPU drives of the CLIs). Call it
BEFORE anything touches ``jax.devices()`` / creates arrays: the platform and
the virtual device count are read when the backend initializes.

On a TPU host a chip belongs to ONE process: a parent that has initialized
jax holds it, and a child that needs it then fails or hangs. Entry points
that start children either stay off jax themselves or pin each child to its
own chip through its environment (``serving/supervisor.py``).
"""

from __future__ import annotations

import dataclasses
import os
import re


def ensure_cpu_only(device_count: int | None = None) -> None:
    """Force this process to use only the CPU backend.

    Optionally requests ``device_count`` virtual CPU devices (must run before
    the backend initializes; the XLA flag is ignored afterwards).
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    if device_count is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        flag = f"--xla_force_host_platform_device_count={device_count}"
        if "xla_force_host_platform_device_count" in flags:
            # replace an inherited count (e.g. a test harness spawning
            # subprocesses with a different virtual-device topology)
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", flag, flags
            )
            os.environ["XLA_FLAGS"] = flags
        else:
            os.environ["XLA_FLAGS"] = f"{flags} {flag}"

    # jax reads JAX_PLATFORMS when it is imported; a process that imported
    # jax before this call needs the live config moved too
    import jax

    jax.config.update("jax_platforms", "cpu")


@dataclasses.dataclass(frozen=True)
class BackendInfo:
    """The selected jax backend, as the tools report it."""

    backend: str        # jax.default_backend(): "cpu" / "tpu" / ...
    device_kind: str    # e.g. "TPU v5 lite"
    device_count: int


def probe_backend() -> BackendInfo:
    """Initialize (on first use) and describe the selected jax backend."""
    import jax

    devices = jax.devices()
    return BackendInfo(
        backend=jax.default_backend(),
        device_kind=devices[0].device_kind,
        device_count=len(devices),
    )
