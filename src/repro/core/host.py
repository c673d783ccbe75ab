"""The host reference computes on the CPU backend on every machine.

Eager ``jnp`` and un-jitted ``lax`` calls run on JAX's default device,
which on a TPU host is the chip. Eq. 1 in ``core.power``, the microgrid
scan and the co-simulation bridge are the reference that the device
program (``repro.sweep.device``) is checked against, so they run on the
CPU wherever they run: bit for bit the same with or without a chip.
"""
from __future__ import annotations

import jax


def on_host():
    """Context manager placing un-placed JAX work on the host CPU."""
    return jax.default_device(jax.devices("cpu")[0])
