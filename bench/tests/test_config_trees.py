"""Every deployment file becomes the program's config unchanged, and
the program's keys equal the reference's.

A file's ``model`` block is the program's config in digest form
(``traffic.model_block``): every field of the schema-6 classes
(``reference.SCHEMA6_FIELDS``) written out, a field added later only
where it differs from its default. The contract cases hold the rule to
a config class derived with one such field; the key cases hold the
program's key digest to it, since the reference hashes each file as
written."""
import copy
import dataclasses
import typing
from typing import Optional

import pytest
import reference
import traffic
from repro.configs import base

from conftest import BENCH

CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))
#: the files written at record schema 6: they write every field of the
#: classes they use, and nothing more
SCHEMA6_FILES = ("phi2-a100", "qwen72b-a100-tp2pp2")
#: a block that writes a nested group of each kind that the files use
QWEN = reference.load_json(BENCH / "configs"
                           / "qwen72b-a100-tp2pp2.json")["model"]


def _block(config: str) -> dict:
    return reference.load_json(BENCH / "configs" / f"{config}.json")["model"]


@pytest.mark.parametrize("config", CONFIGS)
def test_model_block_round_trips(config):
    block = _block(config)
    assert traffic.model_block(traffic._model(block)) == block


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


# ------------------------------------------- the model-block contract ---

@dataclasses.dataclass(frozen=True)
class _LatentAttention(base.AttentionConfig):
    """The program's attention config with a field added after schema 6."""
    kv_lora_rank: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class _LatentModel(base.ModelConfig):
    attention: Optional[_LatentAttention] = None


def _round_trips(block: dict) -> bool:
    """Whether ``block`` is the digest form of the derived config it
    builds; a block that cannot be built does not round-trip."""
    try:
        cfg = traffic._build(_LatentModel, block)
    except TypeError:
        return False
    return traffic.model_block(cfg) == block


@pytest.mark.parametrize("config", CONFIGS)
def test_block_round_trips_past_a_later_field(config):
    assert _round_trips(_block(config))


def test_later_field_at_its_default_is_left_out():
    block = copy.deepcopy(QWEN)
    block["attention"]["kv_lora_rank"] = None
    assert not _round_trips(block)
    cfg = traffic._build(_LatentModel, block)
    assert "kv_lora_rank" not in traffic.model_block(cfg)["attention"]


def test_later_field_off_its_default_is_written():
    block = copy.deepcopy(QWEN)
    block["attention"]["kv_lora_rank"] = 512
    assert _round_trips(block)
    assert traffic.model_block(traffic._build(_LatentModel, block)) \
        ["attention"]["kv_lora_rank"] == 512


@pytest.mark.parametrize("path", [
    (k,) for k in QWEN] + [
    (group, k) for group in ("attention", "mlp") for k in QWEN[group]])
def test_block_without_a_schema6_field_fails(path):
    block = copy.deepcopy(QWEN)
    parent = block
    for key in path[:-1]:
        parent = parent[key]
    del parent[path[-1]]
    assert not _round_trips(block)


def _class_blocks(cls, block: dict):
    """(class, block) for ``block`` and every nested config it writes."""
    yield cls, block
    hints = typing.get_type_hints(cls)
    for f in dataclasses.fields(cls):
        for t in (hints[f.name], *typing.get_args(hints[f.name])):
            if dataclasses.is_dataclass(t) and isinstance(
                    block.get(f.name), dict):
                yield from _class_blocks(t, block[f.name])


@pytest.mark.parametrize("config", CONFIGS)
def test_file_keys_are_schema6_fields(config):
    """A key outside the literal is a field added later, written off its
    default; a file written at schema 6 writes the literal exactly."""
    for cls, block in _class_blocks(base.ModelConfig, _block(config)):
        old = traffic._schema6_fields(cls)
        fields = {f.name: f for f in dataclasses.fields(cls)}
        for key in set(block) - old:
            assert key in fields, (cls.__name__, key)
            assert not traffic._holds_default(fields[key], block[key]), \
                (cls.__name__, key)
        if config in SCHEMA6_FILES:
            assert set(block) == old, cls.__name__


@pytest.mark.parametrize("cls", sorted(reference.SCHEMA6_FIELDS))
def test_schema6_fields_are_program_fields(cls):
    program = {f.name for f in dataclasses.fields(getattr(base, cls))}
    assert reference.SCHEMA6_FIELDS[cls] <= program
