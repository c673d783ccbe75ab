"""GPU/TPU power model (paper Eq. 1).

    P(mfu) = P_idle + (P_max_inst - P_idle) * (min(mfu, mfu_sat)/mfu_sat)^gamma

Sublinear power-law in MFU with saturation — captures early power
saturation of memory-bound inference (gamma < 1) and clamps at the
empirical saturation threshold. Calibrations follow the paper:
A100 100/400 W, H100 60/700 W, A40 30/300 W, mfu_sat = 0.45, gamma = 0.7.

TPU profiles are our hardware adaptation (documented estimates from
public TDP / efficiency figures; same functional form).

All functions are vectorized jnp so whole MFU traces (and vmapped
scenario sweeps) evaluate in one call, on the host CPU (``core.host``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax.numpy as jnp

from repro.core.host import on_host


@dataclasses.dataclass(frozen=True)
class DeviceProfile:
    name: str
    p_idle: float               # W
    p_max_inst: float           # W, observed maximum under saturation
    mfu_sat: float              # empirical MFU saturation threshold
    gamma: float                # sublinear exponent (< 1)
    peak_flops: float           # FLOP/s (dense, fp16/bf16)
    hbm_bw: float               # bytes/s
    hbm_bytes: float            # capacity
    link_bw: float              # bytes/s per interconnect link
    embodied_kg_per_hour: float  # phi_manuf: embodied carbon rate kgCO2/h


# --- paper-faithful GPU calibrations (Section 3.1 / 4.1) ---
A100_SXM = DeviceProfile(
    name="a100-sxm4-80gb", p_idle=100.0, p_max_inst=400.0, mfu_sat=0.45,
    gamma=0.7, peak_flops=312e12, hbm_bw=2.039e12, hbm_bytes=80e9,
    link_bw=300e9,
    # LLMCarbon-style amortization: ~150 kgCO2 embodied over 5y of use
    embodied_kg_per_hour=150.0 / (5 * 365 * 24))
H100_SXM = DeviceProfile(
    name="h100-sxm5", p_idle=60.0, p_max_inst=700.0, mfu_sat=0.45,
    gamma=0.7, peak_flops=989e12, hbm_bw=3.35e12, hbm_bytes=80e9,
    link_bw=450e9, embodied_kg_per_hour=180.0 / (5 * 365 * 24))
A40_PCIE = DeviceProfile(
    name="a40-pcie", p_idle=30.0, p_max_inst=300.0, mfu_sat=0.45,
    gamma=0.7, peak_flops=149.7e12, hbm_bw=696e9, hbm_bytes=48e9,
    link_bw=32e9, embodied_kg_per_hour=120.0 / (5 * 365 * 24))

# --- TPU adaptation (estimates; same Eq. 1 form) ---
TPU_V5E = DeviceProfile(
    name="tpu-v5e", p_idle=60.0, p_max_inst=200.0, mfu_sat=0.45,
    gamma=0.7, peak_flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
    link_bw=50e9, embodied_kg_per_hour=80.0 / (5 * 365 * 24))
TPU_V5P = DeviceProfile(
    name="tpu-v5p", p_idle=90.0, p_max_inst=350.0, mfu_sat=0.45,
    gamma=0.7, peak_flops=459e12, hbm_bw=2.765e12, hbm_bytes=95e9,
    link_bw=100e9, embodied_kg_per_hour=120.0 / (5 * 365 * 24))

DEVICES: Dict[str, DeviceProfile] = {
    d.name: d for d in (A100_SXM, H100_SXM, A40_PCIE, TPU_V5E, TPU_V5P)
}
DEVICES["a100"] = A100_SXM
DEVICES["h100"] = H100_SXM
DEVICES["a40"] = A40_PCIE
DEVICES["v5e"] = TPU_V5E
DEVICES["v5p"] = TPU_V5P


def power(mfu, dev: DeviceProfile):
    """Eq. 1, vectorized. mfu in [0, 1] (fraction, not percent)."""
    with on_host():
        mfu = jnp.clip(jnp.asarray(mfu, jnp.float32), 0.0, None)
        x = jnp.minimum(mfu, dev.mfu_sat) / dev.mfu_sat
        return dev.p_idle + (dev.p_max_inst - dev.p_idle) * jnp.power(
            x, dev.gamma)


class PowerModel:
    """Object facade used by the simulator and co-simulation bridge."""

    def __init__(self, device: str | DeviceProfile = "a100"):
        self.dev = DEVICES[device] if isinstance(device, str) else device

    def power(self, mfu):
        return power(mfu, self.dev)

    def energy_wh(self, mfu, duration_s, n_devices: int = 1, pue: float = 1.0):
        """Energy in Wh for stages with given MFU and duration (Eq. 3)."""
        p = self.power(mfu)
        with on_host():
            return (jnp.sum(p * jnp.asarray(duration_s) / 3600.0)
                    * n_devices * pue)
