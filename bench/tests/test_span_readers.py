"""The readers of the program's phase spans, on a synthetic context:
each gives its value from the spans it names, and nothing when one of
them is missing, as in a program that does not record it."""
import argparse

import pytest

from conftest import load_run

#: two sweeps of 100 and 300 scenarios, span totals in seconds
SWEEPS = [
    {"wall_s": 0.5, "scenarios": 100, "spans": {
        "sweep.key_digest": 0.010, "device.group": 0.002,
        "device.pad_stack": 0.004, "device.assemble_records": 0.020,
        "device.h2d": 0.003, "device.d2h": 0.001}},
    {"wall_s": 0.7, "scenarios": 300, "spans": {
        "sweep.key_digest": 0.030, "device.group": 0.006,
        "device.pad_stack": 0.008, "device.assemble_records": 0.060,
        "device.h2d": 0.005, "device.d2h": 0.003}},
]

CASES = [
    # metric, value, a span whose absence silences it
    ("digest_us_per_scenario", (0.048 / 400) * 1e6, "device.group"),
    ("digest_us_per_scenario", (0.048 / 400) * 1e6, "sweep.key_digest"),
    ("pad_stack_ms", 0.006 * 1e3, "device.pad_stack"),
    ("assembly_us_per_scenario", (0.080 / 400) * 1e6,
     "device.assemble_records"),
    ("transfer_ms", 0.006 * 1e3, "device.h2d"),
    ("transfer_ms", 0.006 * 1e3, "device.d2h"),
]


def ctx(sweeps):
    return argparse.Namespace(sweeps=sweeps, trace=None, compiles=0,
                              peak={})


@pytest.mark.parametrize("metric,value,span", CASES)
def test_reader_value_and_missing_span(metric, value, span):
    read = load_run().metric_reader(metric)
    assert read(ctx(SWEEPS)) == pytest.approx(value)
    gone = [dict(s, spans={k: v for k, v in s["spans"].items()
                           if k != span}) for s in SWEEPS]
    assert read(ctx(gone)) is None
