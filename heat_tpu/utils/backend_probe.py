"""Pointing JAX at a virtual CPU mesh before the backend initialises.

The distribution paths run without TPU hardware on an ``n``-device virtual
CPU mesh (SURVEY §4); :func:`force_virtual_cpu_mesh` is the one canonical
copy of the environment dance the launchers share. The reference has no
analog (its ranks are real MPI processes).
"""

from __future__ import annotations

import os
import re

__all__ = ["force_virtual_cpu_mesh"]


def force_virtual_cpu_mesh(n: int) -> None:
    """Point jax at an ``n``-device virtual CPU mesh. Must run before the
    first *backend use* (``jax.devices()`` / first dispatch) — importing
    jax earlier is fine, backend init is lazy. One canonical copy of the
    dance (the benchmark harness ``--mesh`` flag, the replica ``--mesh``
    flag and the ``python -m heat_tpu.telemetry.audit --mesh`` CLI all go
    through here):

    * splice ``--xla_force_host_platform_device_count=n`` into
      ``XLA_FLAGS``, replacing an inherited count (a test env's value
      must not win over an explicit request);
    * pin ``JAX_PLATFORMS=cpu`` in the environment (child processes
      inherit it) AND in the live jax config (jax read the variable when
      it was imported).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    want = f"--xla_force_host_platform_device_count={int(n)}"
    m = re.search(r"--xla_force_host_platform_device_count=\d+", flags)
    flags = flags.replace(m.group(0), want) if m else (flags + " " + want).strip()
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
