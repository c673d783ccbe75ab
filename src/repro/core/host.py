"""The host reference computes on the CPU backend on every machine.

Eager ``jnp`` and un-jitted ``lax`` calls run on JAX's default device,
which on a TPU host is the chip. Eq. 1 in ``core.power``, the microgrid
scan and the co-simulation bridge are the reference that the device
program (``repro.sweep.device``) is checked against, so they run on the
CPU wherever they run: bit for bit the same with or without a chip.

This module imports no JAX at load, and neither does the event loop's
import chain: a host pool child that runs only the loop never pays for
importing JAX (seconds on a chip host).
"""
from __future__ import annotations

import os
import sys


def on_host():
    """Context manager placing un-placed JAX work on the host CPU."""
    import jax
    return jax.default_device(jax.devices("cpu")[0])


def use_cpu_only() -> None:
    """Start this process's JAX with the CPU platform alone, for a
    process that computes the host path only (pool children, vectorized
    remote workers). Creating a backend opens every platform JAX may
    use, so pinning a device later would still take the chip from the
    process that holds it. JAX reads ``JAX_PLATFORMS`` when it is first
    imported, so this imports none; where JAX is loaded already, its
    config is set too."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_platforms", "cpu")
