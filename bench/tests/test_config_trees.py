"""Every deployment file becomes the program's config unchanged, and
the program's keys equal the reference's. A field that the program
adds to its config tree breaks this at once: the file's ``model`` block
no longer round-trips, and the program's key digest no longer matches
the reference's hash of the file as written."""
import dataclasses
from typing import Optional

import pytest
import reference
import traffic

from conftest import BENCH

CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))


@pytest.mark.parametrize("config", CONFIGS)
def test_model_block_round_trips(config):
    tree = reference.load_json(BENCH / "configs" / f"{config}.json")
    assert dataclasses.asdict(traffic._model(tree["model"])) == tree["model"]


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("mix", ["qps_sweep", "hw_plane"])
def test_program_keys_equal_reference_keys(config, mix):
    dep = reference.load_json(BENCH / "configs" / f"{config}.json")
    spec = reference.load_json(BENCH / "traffic" / f"{mix}.json")
    spec["report"] = {"pue": spec["report"]["pue"][:2],
                      "grid_ci": spec["report"]["grid_ci"][:2]}
    groups = traffic.plan_sweep(dep, spec, 4294967311, "window", 0)
    scs = traffic.to_program(groups)
    want = [reference.scenario_key(g.tree, s["pue"], s["grid_ci"])
            for g in groups for s in g.scenarios]
    assert [s.key for s in scs] == want


@dataclasses.dataclass(frozen=True)
class _Inner:
    width: int


@dataclasses.dataclass(frozen=True)
class _Outer:
    name: str
    inner: Optional[_Inner] = None
    other: "_Inner | None" = None


def test_nested_configs_are_built_from_field_types():
    out = traffic._build(_Outer, {"name": "x", "inner": {"width": 3},
                                  "other": None})
    assert out == _Outer("x", _Inner(3), None)
    out = traffic._build(_Outer, {"name": "x", "other": {"width": 4}})
    assert out.other == _Inner(4) and out.inner is None
    with pytest.raises(TypeError):
        traffic._build(_Outer, {"name": "x", "unknown": 1})
