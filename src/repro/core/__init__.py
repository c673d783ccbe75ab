from repro.core.power import DEVICES, DeviceProfile, PowerModel, power
from repro.core.energy import (EnergyReport, operational_energy,
                               operational_energy_trace, stacked_energy_reports,
                               stage_mfu)
from repro.core.carbon import (CarbonReport, emissions, emissions_batch,
                               stage_attributed_carbon)
from repro.core.signals import Signal, aggregate_power

# the microgrid scan and the co-simulation bridge import JAX when they
# load, so they load on first use: the event loop's import chain stays
# free of JAX (``core.host``)
_LAZY = {name: "microgrid" for name in (
    "BatteryConfig", "MicrogridConfig", "simulate", "summarize")}
_LAZY.update({name: "cosim" for name in (
    "CosimResult", "run_cosim", "stages_to_load_signal",
    "trace_to_load_signal")})

__all__ = [
    "DEVICES", "DeviceProfile", "PowerModel", "power",
    "EnergyReport", "operational_energy", "operational_energy_trace",
    "stacked_energy_reports", "stage_mfu",
    "CarbonReport", "emissions", "emissions_batch", "stage_attributed_carbon",
    "Signal", "aggregate_power",
    "BatteryConfig", "MicrogridConfig", "simulate", "summarize",
    "CosimResult", "run_cosim", "stages_to_load_signal",
    "trace_to_load_signal",
]


def __getattr__(name):
    if name not in _LAZY:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib
    return getattr(importlib.import_module(f"{__name__}.{_LAZY[name]}"), name)
