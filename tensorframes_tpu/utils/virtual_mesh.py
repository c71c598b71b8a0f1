"""Retarget JAX onto an n-device virtual CPU mesh.

Multi-chip behavior is validated on virtual CPU devices (the reference
simulates its cluster the same way: a `local[1]` SparkContext with 4
shuffle partitions, `TensorFlossTestSparkContext.scala:14-22`). Getting
n virtual devices is environment-sensitive:

- ``jax.config.update("jax_platforms", "cpu")`` pins the CPU platform
  whatever ``JAX_PLATFORMS`` says, as long as it runs before a backend
  is chosen.
- XLA parses ``XLA_FLAGS`` once per process. If any backend already
  initialized, later edits to ``--xla_force_host_platform_device_count``
  are invisible; the only working recovery is ``clear_backends()`` plus
  the ``jax_num_cpu_devices`` config, which passes the count as a client
  option instead of a flag.

This helper handles both orders (called before or after first backend
init) without ever initializing a hardware backend just to probe it.
"""

from __future__ import annotations

import os
import re

_FLAG = "--xla_force_host_platform_device_count"


def force_virtual_cpu_devices(n: int = 8) -> None:
    """Make ``jax.devices()`` return >= n virtual CPU devices.

    Safe to call whether or not a JAX backend has initialized in this
    process, and whether or not ``XLA_FLAGS`` already carries a (possibly
    smaller) forced device count. Does not probe hardware platforms.
    """
    import jax
    from jax._src import xla_bridge

    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(re.escape(_FLAG) + r"=(\d+)", flags)
    initialized = xla_bridge.backends_are_initialized()

    jax.config.update("jax_platforms", "cpu")

    if not initialized:
        # Flags not parsed yet: the env var route still works.
        if m is None:
            os.environ["XLA_FLAGS"] = f"{flags} {_FLAG}={n}".strip()
        elif int(m.group(1)) < n:
            os.environ["XLA_FLAGS"] = flags.replace(m.group(0), f"{_FLAG}={n}")
        return

    # A backend already initialized. Probing the live backend is cheap
    # (no re-init); keep it when it is already a sufficient CPU mesh.
    devices = jax.devices()
    if len(devices) >= n and all(d.platform == "cpu" for d in devices):
        return

    # Flags are frozen for this process, and the current env value proves
    # nothing about what was parsed at startup — rebuild the CPU client
    # with an option-level device count.
    from jax.extend import backend as _xb

    if m is not None:
        # Drop the flag from the env so the option-level count below
        # doesn't trip jax's flag-conflict check.
        os.environ["XLA_FLAGS"] = flags.replace(m.group(0), "").strip()
    _xb.clear_backends()
    jax.config.update("jax_num_cpu_devices", n)
