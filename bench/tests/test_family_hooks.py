"""The family hooks of the plain reference, ``routed_experts`` and
``kv_copies``, on a toy sparse family (``fixtures/families``) against
hand-computed values; and a dense family, which has neither."""
import json

import pytest
import reference

from conftest import BENCH

FIX = BENCH / "tests" / "fixtures" / "families"
HOOKS = ("routed_experts", "kv_copies")
FAMILY = reference.family          # kept before any test patches it


def toy(drop=()):
    """The toy family, without the hooks named in ``drop``."""
    fam = FAMILY("sparse_toy", FIX)
    for name in drop:
        delattr(fam, name)
    return fam


def toy_tree(tp=1, pp=1, n_requests=16):
    """phi-2's deployment with the toy sparse model: 256 experts, 8 a
    token, a 576-value latent cache."""
    tree = json.loads((BENCH / "configs" / "phi2-a100.json").read_text())
    tree["model"] = dict(tree["model"], family="sparse_toy", kv_latent=576,
                         mlp=None, moe={"n_experts": 256, "top_k": 8,
                                        "d_expert": 512})
    wl = json.loads((BENCH / "traffic" / "qps_sweep.json").read_text())
    tree.update(tp=tp, pp=pp, n_replicas=1,
                workload=dict(wl["workload"], seed=7, n_requests=n_requests,
                              max_len=512))
    return tree


def params(tree, fam):
    return reference.roofline_params(tree, reference.hardware()["a100"],
                                     fam)


def test_expert_parameters_as_stated():
    tree = toy_tree()
    m = tree["model"]
    P = params(tree, toy())
    per_expert = 3 * m["d_model"] * 512
    assert P["expert_bytes"] == m["n_layers"] * per_expert * 2
    assert (P["n_experts"], P["top_k"]) == (256.0, 8.0)
    # without the hook the other parameters are the same
    plain = params(tree, toy(drop=HOOKS))
    assert {k: P[k] for k in plain} == plain


def test_one_token_reads_the_active_weights():
    P = params(toy_tree(), toy())
    w = reference.weight_traffic(P, 1.0)
    assert abs(w - P["weight_bytes"]) <= 1e-15 * P["weight_bytes"]


def test_many_tokens_read_every_expert():
    P = params(toy_tree(), toy())
    full = P["weight_bytes"] + P["expert_bytes"] * (256 - 8)
    assert reference.weight_traffic(P, 10_000.0) == pytest.approx(
        full, rel=1e-12)


@pytest.mark.parametrize("T,distinct", [(8, 57), (32, 163), (64, 222)])
def test_distinct_experts_per_stage(T, distinct):
    E, k = 256, 8
    expect = E * (1 - (1 - k / E) ** T)
    assert round(expect) == distinct
    P = params(toy_tree(), toy())
    w = P["weight_bytes"] + P["expert_bytes"] * (expect - k)
    assert reference.weight_traffic(P, float(T)) == pytest.approx(
        w, rel=1e-15)
    # a memory-bound decode stage of T tokens, on one GPU, reads that
    t, _ = reference.roofline(P, 0.0, float(T), 0.0, 0.0)
    assert t == pytest.approx((w + T * P["act"]) / P["hbm"] + P["overhead"],
                              rel=1e-12)


def test_latent_cache_halves_the_budget_at_tp2():
    tree = toy_tree(tp=2)
    dev = reference.hardware()["a100"]
    hooked = reference.kv_budget(tree, dev, toy())
    plain = reference.kv_budget(tree, dev, toy(drop=["kv_copies"]))
    room = dev["hbm_bytes"] * 0.9 - toy().param_count(tree["model"]) * 2 / 2
    per_token = 576 * tree["model"]["n_layers"] * 2
    assert plain == int(room / (per_token / 2))
    assert hooked == int(room / per_token) == plain // 2
    assert reference.kv_copies(toy(), tree["model"], 2) == 2


def test_latent_cache_doubles_stage_kv_traffic_at_tp2(monkeypatch):
    tree = toy_tree(tp=2, n_requests=1)
    tree["auto_kv_budget"] = False
    comps = {}
    for name, drop in (("hooked", ()), ("plain", ("kv_copies",))):
        monkeypatch.setattr(reference, "family",
                            lambda n, root=None, d=drop: toy(d))
        s = reference.serve(tree, reference.hardware()["a100"])
        comps[name] = s.comp
    hooked, plain = comps["hooked"], comps["plain"]
    assert hooked.shape == plain.shape and len(plain) > 1
    assert (hooked[:, :3] == plain[:, :3]).all()
    assert (plain[:, 3] > 0).all()
    assert (hooked[:, 3] == 2 * plain[:, 3]).all()


@pytest.mark.parametrize("number", [None, *reference.controls()])
def test_sparse_group_runs_in_every_precision(monkeypatch, number):
    monkeypatch.setattr(reference, "family", lambda n, root=None: toy())
    import traffic
    tree = toy_tree(tp=2, n_requests=12)
    g = traffic.Group(tree, [{"tag": "t", "params": {}, "pue": 1.2,
                              "grid_ci": 250.0}])
    recs = (reference.group_records(g) if number is None
            else reference.control_records(g, number))
    assert recs[0]["metrics"]["n_stages"] > 0
    assert recs[0]["metrics"]["energy_wh"] > 0


def test_dense_family_has_no_hooks():
    tree = json.loads((BENCH / "configs" / "phi2-a100.json").read_text())
    dense = reference.family("dense")
    P = params(tree, dense)
    assert not {"expert_bytes", "n_experts", "top_k"} & set(P)
    assert not any(hasattr(dense, h) for h in HOOKS)
    assert reference.kv_copies(dense, tree["model"], 4) == 1


def test_family_from_another_root():
    assert reference.family("sparse_toy", FIX).kv_copies({}, 3) == 3
    with pytest.raises(KeyError):
        reference.family("sparse_toy")     # not a real config's family
