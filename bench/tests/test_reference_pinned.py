"""Check digits of the plain reference's records for each deployment
and mix, in the reference's precision and in each control's, computed
before the family hooks existed. A dense family has no hooks, so every
record has to stay the same to the bit."""
import hashlib
import json

import pytest
import reference
import traffic

from conftest import BENCH

DIGITS = {
    ("phi2-a100", "qps_sweep"): {
        "exact": "e8c47ed5d384d2a7", "trace_rel": "cd5e82d7a0a5fdba",
        "duration_rel": "165d7a7a7f9c03b1", "power_rel": "51110ec72583fae7"},
    ("phi2-a100", "hw_plane"): {
        "exact": "c0cb0cf9eb250c44", "trace_rel": "5eab8e0b13871731",
        "duration_rel": "cc7099a55c6577e6", "power_rel": "a0de248bde34ec8c"},
    ("qwen72b-a100-tp2pp2", "qps_sweep"): {
        "exact": "f2a595f14aef8255", "trace_rel": "f4257fb7ade03520",
        "duration_rel": "375cdb1a8889092e", "power_rel": "25ff4c3a980d3765"},
    ("qwen72b-a100-tp2pp2", "hw_plane"): {
        "exact": "57c51e076d401c2b", "trace_rel": "5f90d246150a3792",
        "duration_rel": "8ceebf0c491d15e1", "power_rel": "bfc3429c116c74c6"},
}


def groups(config, mix_name):
    """Sweep 0 of seed 4294967311 with 24 requests a stream; the plane's
    report cut to 2 x 2 scenarios."""
    dep = reference.load_json(BENCH / "configs" / f"{config}.json")
    mix = reference.load_json(BENCH / "traffic" / f"{mix_name}.json")
    mix["workload"] = dict(mix["workload"], n_requests=24)
    if mix_name == "hw_plane":
        mix["report"] = {"pue": [1.0, 1.75], "grid_ci": [25.0, 700.0]}
    return traffic.plan_sweep(dep, mix, 4294967311, "window", 0)


@pytest.mark.parametrize("config,mix", sorted(DIGITS))
@pytest.mark.parametrize("precision", ["exact", *reference.controls()])
def test_records_are_unchanged(config, mix, precision):
    h = hashlib.sha256()
    for g in groups(config, mix):
        recs = (reference.group_records(g) if precision == "exact"
                else reference.control_records(g, precision))
        h.update(json.dumps(recs, sort_keys=True).encode())
    assert h.hexdigest()[:16] == DIGITS[(config, mix)][precision]
