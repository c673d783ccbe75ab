"""The general generator: a deployment file and a traffic mix, with a
seed, become the sweeps that a run drives.

A mix is data (``bench/traffic/<mix>.json``):

- ``workload``: every field of the simulator's workload config except
  ``seed``, which comes from the run's seed;
- ``points``: axes whose product gives the trace groups of one sweep.
  ``device`` names a hardware profile; ``tp_factor`` and ``pp_factor``
  multiply the deployment's TP and PP; ``workload.<field>`` and
  ``scheduler.<field>`` override one field;
- ``report``: the ``pue`` and ``grid_ci`` values every point is
  reported under (one scenario per pair, all sharing the point's
  trace);
- ``seeding``: ``per_point`` draws each point's request stream from its
  own seed, ``shared`` gives every point of a sweep the same stream;
- ``check_groups``: how many trace groups of a run the plain reference
  recomputes after the window.

Every sweep of a run draws fresh seeds from ``(run seed, role, sweep
index)``, so no two sweeps repeat work. A group is described here in
plain data (the config tree as nested dicts); ``to_program`` turns the
description into the program's ``Scenario`` objects, and the reference
reads the description alone.
"""
from __future__ import annotations

import copy
import dataclasses
import hashlib
import itertools
import math
import types
import typing
from typing import Dict, List

import reference

#: prompt and generation lengths of the synthetic bucket warm-up: a
#: fixed 64-token request split 32:32 takes exactly 33 iterations when
#: it is served alone
_PAD_LEN, _PAD_PD, _PAD_ITERS = 64, 1.0, 33


def derive_seed(*parts) -> int:
    """A workload seed from the run's seed and a role: any whole number
    in, 31 bits out, the same on every machine."""
    blob = ":".join(str(p) for p in parts).encode()
    return int.from_bytes(hashlib.sha256(blob).digest()[:8], "big") % 2 ** 31


@dataclasses.dataclass
class Group:
    """One trace group: a config tree and the scenarios reported on it."""
    tree: dict                       # the config as nested plain data
    scenarios: List[dict]            # {"tag", "params", "pue", "grid_ci"}


def base_tree(dep: dict, workload: dict) -> dict:
    return {"model": copy.deepcopy(dep["model"]), "device": dep["device"],
            "n_replicas": dep["n_replicas"], "tp": dep["tp"], "pp": dep["pp"],
            "workload": dict(workload), "scheduler": dict(dep["scheduler"]),
            "execmodel": dict(dep["execmodel"]),
            "auto_kv_budget": dep["auto_kv_budget"]}


def _apply(tree: dict, dep: dict, axis: str, value) -> tuple:
    """Set one point axis on ``tree``; return the (param name, value)
    it is reported under."""
    if axis == "device":
        tree["device"] = value
        return "device", value
    if axis in ("tp_factor", "pp_factor"):
        key = axis[:2]
        tree[key] = dep[key] * int(value)
        return key, tree[key]
    head, _, leaf = axis.partition(".")
    if head in ("workload", "scheduler") and leaf in tree[head]:
        tree[head][leaf] = value
        return leaf, value
    raise ValueError(f"unknown point axis {axis!r}")


def plan_sweep(dep: dict, mix: dict, run_seed: int, role: str,
               index: int) -> List[Group]:
    """The trace groups of sweep ``index`` of a run, in scenario order."""
    axes = list(mix["points"])
    pues, cis = mix["report"]["pue"], mix["report"]["grid_ci"]
    shared = derive_seed(run_seed, mix["name"], role, index)
    groups = []
    for j, combo in enumerate(itertools.product(
            *(mix["points"][a] for a in axes))):
        tree = base_tree(dep, mix["workload"])
        params = dict(_apply(tree, dep, a, v) for a, v in zip(axes, combo))
        tree["workload"]["seed"] = (
            shared if mix["seeding"] == "shared"
            else derive_seed(run_seed, mix["name"], role, index, j))
        scs = []
        for pue, ci in itertools.product(pues, cis):
            p = {**params, "pue": pue, "grid_ci": ci}
            label = ",".join(f"{k}={v}" for k, v in p.items())
            scs.append({"tag": f"{mix['name']}/{role}{index}/{label}",
                        "params": p, "pue": pue, "grid_ci": ci})
        groups.append(Group(tree, scs))
    return groups


def plan_pad_sweep(dep: dict, n_groups: int, k: int, rows: int,
                   index: int) -> List[Group]:
    """A sweep with the padded shape of a real one — ``n_groups``
    groups, ``k`` scenarios in each, and ``rows`` stage rows in each
    trace — for warming a bucket that the mix reaches on some seeds
    only. Its groups differ in TP alone over one isolated stream of
    fixed-length requests, so divergence replay serves them and the
    stage count is exact: 33 iterations per request, ``pp`` rows each."""
    pp = dep["pp"]
    n_req = max(1, math.ceil(rows / (_PAD_ITERS * pp)))
    wl = {"n_requests": n_req, "qps": 0.1, "arrival": "uniform",
          "length_dist": "fixed", "zipf_theta": 0.6, "min_len": _PAD_LEN,
          "max_len": _PAD_LEN, "pd_ratio": _PAD_PD, "seed": index,
          "deferrable_frac": 0.0, "deferrable_deadline_s": 3600.0,
          "interactive_slo_s": 30.0, "envelope": "none",
          "envelope_amplitude": 0.35, "envelope_period_h": 24.0,
          "envelope_phase_h": 0.0, "burst_gain": 1.0, "burst_mean_s": 0.0,
          "burst_idle_mean_s": 3600.0}
    groups = []
    for g in range(n_groups):
        tree = base_tree(dep, wl)
        tree["tp"] = dep["tp"] * (g + 1)
        scs = [{"tag": f"pad{index}/g{g}/k{j}", "params": {"g": g, "k": j},
                "pue": round(1.0 + 0.01 * j, 2), "grid_ci": 250.0}
               for j in range(k)]
        groups.append(Group(tree, scs))
    return groups


def pad_rows(dep: dict, rows: int) -> int:
    """Stage rows that ``plan_pad_sweep`` produces for ``rows``."""
    pp = dep["pp"]
    return max(1, math.ceil(rows / (_PAD_ITERS * pp))) * _PAD_ITERS * pp


def _build(cls, value: dict):
    """``value`` as an instance of dataclass ``cls``. Every field whose
    type is a dataclass, or an optional one, is built from its dict in
    turn, so a nested config that the program adds later is built as
    its class and not passed on as a dict."""
    hints = typing.get_type_hints(cls)
    kw = dict(value)
    for f in dataclasses.fields(cls):
        t = hints[f.name]
        if typing.get_origin(t) in (typing.Union, types.UnionType):
            t = next(a for a in typing.get_args(t) if a is not type(None))
        if kw.get(f.name) is not None and dataclasses.is_dataclass(t):
            kw[f.name] = _build(t, kw[f.name])
    return cls(**kw)


def _model(m: dict):
    from repro.configs import base
    return _build(base.ModelConfig, m)


def _schema6_fields(cls) -> frozenset:
    """The fields ``cls`` had at record schema 6: those of the nearest
    class in its MRO that ``reference.SCHEMA6_FIELDS`` names, so a class
    derived from a schema-6 class keeps its base's; none for a class
    added later."""
    for c in cls.__mro__:
        if c.__name__ in reference.SCHEMA6_FIELDS:
            return reference.SCHEMA6_FIELDS[c.__name__]
    return frozenset()


def _holds_default(f: dataclasses.Field, value) -> bool:
    if f.default is not dataclasses.MISSING:
        return value == f.default
    if f.default_factory is not dataclasses.MISSING:
        return value == f.default_factory()
    return False


def model_block(cfg) -> dict:
    """A program config in the digest form of a deployment file's
    ``model`` block: ``dataclasses.asdict``, nested configs in turn,
    less every field outside ``reference.SCHEMA6_FIELDS`` that holds
    its default."""
    old = _schema6_fields(type(cfg))
    out = {}
    for f in dataclasses.fields(cfg):
        value = getattr(cfg, f.name)
        if f.name not in old and _holds_default(f, value):
            continue
        out[f.name] = (model_block(value) if dataclasses.is_dataclass(value)
                       else copy.deepcopy(value))
    return out


def to_program(groups: List[Group]) -> list:
    """The program's scenarios for ``groups``, in the same order."""
    from repro.sim.execmodel import ExecModelConfig
    from repro.sim.requests import WorkloadConfig
    from repro.sim.scheduler import SchedulerConfig
    from repro.sim.simulator import SimConfig
    from repro.sweep.grid import Scenario

    models: Dict[str, object] = {}
    out = []
    for g in groups:
        t = g.tree
        name = t["model"]["name"]
        if name not in models:
            models[name] = _model(t["model"])
        cfg = SimConfig(model=models[name], device=t["device"],
                        n_replicas=t["n_replicas"], tp=t["tp"], pp=t["pp"],
                        workload=WorkloadConfig(**t["workload"]),
                        scheduler=SchedulerConfig(**t["scheduler"]),
                        execmodel=ExecModelConfig(**t["execmodel"]),
                        auto_kv_budget=t["auto_kv_budget"])
        for s in g.scenarios:
            out.append(Scenario(cfg=cfg, params=dict(s["params"]),
                                tag=s["tag"], pue=s["pue"],
                                grid_ci=s["grid_ci"]))
    return out
