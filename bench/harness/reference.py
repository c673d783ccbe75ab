"""The plain reference: the simulator's semantics written out once more,
in straightforward numpy and Python, from a group's plain description
alone. It imports nothing of the program.

For one trace group it draws the request stream, serves it with a
first-come-first-served continuous-batching loop over round-robin
replicas (whole-prompt prefill iterations first, then one decode token
for every running sequence), times each iteration with the three-term
roofline, and reports every record column: latency percentiles, stage
count, MFU and batch averages (the trace), then, as the grid kernel
does, the roofline of every stage row again, Eq. 1 power, Eq. 2-3
energy and Eq. 4 carbon, and the record's key, tag and parameters.

A deployment's size arithmetic comes from its family module,
``bench/families/<family>.py``; two optional hooks there state weight
and cache traffic that a dense model does not have (see
``bench/families/dense.py``): ``routed_experts``, whose distinct
experts per stage grow with the stage's tokens, and ``kv_copies``, the
copies of one token's cache that a replica's TP ranks hold. A family
without them is costed exactly as a dense one.

``Precision`` says in which float types it computes, in three places:
the event loop's clocks, the grid kernel's roofline and sums, and Eq. 1.
``EXACT`` is the reference: IEEE double in all three. ``controls()``
gives one step below what the program states in each place alone,
which a sound program must not come near: float32 for the event loop's
float64 clocks, float32 for the kernel's float64 roofline and sums,
bfloat16 for its float32 Eq. 1 power.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
from pathlib import Path
from types import MappingProxyType
from typing import Callable, Dict, List

import numpy as np

BENCH = Path(__file__).resolve().parents[1]

#: the record schema version that keys are digested under
RECORD_SCHEMA = 6

#: the fields of each of the program's model config classes at record
#: schema 6. A deployment file's ``model`` block is the program's config
#: in digest form: it writes every one of these, and a field that the
#: program adds later only where it differs from its default
#: (``traffic.model_block``). The program's key digest has to follow the
#: same rule, or its keys part from the reference's hash of the file.
SCHEMA6_FIELDS = MappingProxyType({
    "ModelConfig": frozenset((
        "name", "family", "n_layers", "d_model", "vocab_size", "attention",
        "mlp", "moe", "ssm", "rwkv", "zamba", "norm", "norm_eps",
        "tie_embeddings", "max_seq_len", "is_encoder_only", "embed_stub",
        "dtype")),
    "AttentionConfig": frozenset((
        "n_heads", "n_kv_heads", "head_dim", "sliding_window", "rope",
        "rope_theta", "rope_pct", "causal", "qkv_bias", "kv_repeat")),
    "MLPConfig": frozenset(("d_ff", "activation", "gated")),
    "MoEConfig": frozenset((
        "n_experts", "top_k", "d_expert", "router_jitter", "aux_loss_coef",
        "n_shared_experts")),
    "SSMConfig": frozenset((
        "d_state", "d_conv", "expand", "head_dim", "n_groups")),
    "RWKVConfig": frozenset(("head_dim", "decay_lora", "mix_lora",
                             "gate_lora")),
    "ZambaConfig": frozenset(("shared_attn_every", "shared_attn_copies")),
})

#: record columns the device program computes from the summed stage
#: durations alone, all in float64
DURATION_COLS = ("duration_s", "gpu_hours", "carbon_embodied_g")
#: record columns the device program computes through Eq. 1 power,
#: which it evaluates in float32
POWER_COLS = ("energy_wh", "energy_kwh", "avg_power_w", "peak_power_w",
              "carbon_operational_g", "carbon_total_g")

#: every metric column of a single-site record
RECORD_COLS = ("energy_wh", "energy_kwh", "avg_power_w", "peak_power_w",
               "avg_mfu", "duration_s", "gpu_hours", "throughput_qps",
               "n_stages", "avg_batch", "carbon_operational_g",
               "carbon_embodied_g", "carbon_total_g", "grid_ci_g_per_kwh",
               "ttft_p50_s", "ttft_p99_s", "e2e_p50_s", "e2e_p99_s")

_MAX_SIM_S = 10_000_000.0


@dataclasses.dataclass(frozen=True)
class Precision:
    name: str
    loop: Callable            # cast of one event-loop time / FLOP value
    kernel: object            # dtype of the kernel's roofline and sums
    power: object             # dtype Eq. 1 is evaluated in


EXACT = Precision("exact", float, np.float64, np.float64)


def controls() -> Dict[str, Precision]:
    """One step below each precision the program states, one at a time,
    keyed by the number of ``compare`` whose upper reading it gives."""
    import ml_dtypes
    return {
        "trace_rel": Precision("loop_f32", np.float32, np.float64,
                               np.float64),
        "duration_rel": Precision("kernel_f32", float, np.float32,
                                  np.float64),
        "power_rel": Precision("power_bf16", float, np.float64,
                               ml_dtypes.bfloat16),
    }


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def hardware() -> Dict[str, dict]:
    return load_json(BENCH / "hardware.json")["devices"]


def family(name: str, root: Path = None):
    """The size arithmetic of a model family, ``<root>/<name>.py``;
    ``root`` is ``bench/families`` unless a test names another."""
    path = Path(root or BENCH / "families") / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no reference for model family {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_family_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------- keys ---

def _canonical(obj) -> str:
    return json.dumps(obj, sort_keys=True, default=str, separators=(",", ":"))


def scenario_key(tree: dict, pue: float, grid_ci: float) -> str:
    """sha256 of the canonical JSON of the config tree and the report
    knobs under the record schema."""
    extra = _canonical({"pue": pue, "grid_ci": grid_ci, "post": None,
                        "post_params": {}})
    blob = (f'{{"cfg":{_canonical(tree)},"extra":{extra},'
            f'"schema":{RECORD_SCHEMA}}}')
    return hashlib.sha256(blob.encode()).hexdigest()


# ------------------------------------------------------------ requests ---

def requests(wl: dict):
    """(arrival_s, prompt tokens, generated tokens) per request id:
    exponential or even gaps at ``qps``, Zipf(theta) lengths over
    [min_len, max_len], split by the prefill:decode ratio."""
    if wl["envelope"] != "none" or wl["burst_gain"] > 1.0 \
            or wl["deferrable_frac"] > 0.0:
        raise ValueError("the reference draws constant-rate, all-"
                         "interactive streams only")
    n = wl["n_requests"]
    rng = np.random.default_rng(wl["seed"])
    if wl["arrival"] == "poisson":
        gaps = rng.exponential(1.0 / max(wl["qps"], 1e-9), n)
    else:
        gaps = np.full(n, 1.0 / max(wl["qps"], 1e-9))
    arrival = np.cumsum(gaps)
    if wl["length_dist"] == "zipf":
        support = np.arange(wl["min_len"], wl["max_len"] + 1,
                            dtype=np.float64)
        p = support ** (-wl["zipf_theta"])
        p /= p.sum()
        lengths = rng.choice(support, size=n, p=p).astype(int)
    else:
        lengths = np.full(n, wl["max_len"], int)
    pf = wl["pd_ratio"] / (wl["pd_ratio"] + 1.0)
    prompt = np.maximum(1, np.round(lengths * pf)).astype(int)
    gen = np.maximum(1, lengths - prompt).astype(int)
    return arrival.astype(np.float64), prompt, gen


# ------------------------------------------------------------ roofline ---

def kv_copies(fam, m: dict, tp: int) -> int:
    """Copies of one token's cache that a replica's ``tp`` ranks hold:
    the family's ``kv_copies`` hook, or 1 (head-sharded caches)."""
    return int(getattr(fam, "kv_copies", lambda m, tp: 1)(m, tp))


def kv_budget(tree: dict, dev: dict, fam=None) -> int:
    fam = fam or family(tree["model"]["family"])
    tp, pp = tree["tp"], tree["pp"]
    w_per_gpu = fam.param_count(tree["model"]) * 2 / (tp * pp)
    room = dev["hbm_bytes"] * 0.9 - w_per_gpu
    kv_per_gpu = (fam.kv_bytes_per_token(tree["model"], 2)
                  * kv_copies(fam, tree["model"], tp) / (tp * pp))
    if room <= 0 or kv_per_gpu <= 0:
        return 0
    return int(room / kv_per_gpu)


def roofline_params(tree: dict, dev: dict, fam=None) -> dict:
    """The roofline's parameters. A family with ``routed_experts`` adds
    ``expert_bytes`` (one expert's weights over every routed layer),
    ``n_experts`` and ``top_k``; ``weight_bytes`` counts k experts."""
    m, e = tree["model"], tree["execmodel"]
    fam = fam or family(m["family"])
    tp, pp = tree["tp"], tree["pp"]
    coll = 0.0
    if tp > 1:   # two ring all-reduces of the activations per layer
        coll += (2.0 * m["d_model"] * 2 * (m["n_layers"] / pp)
                 * 2.0 * (tp - 1) / tp) / dev["link_bw"]
    if pp > 1:   # one activation hand-off between pipeline stages
        coll += m["d_model"] * 2 / dev["link_bw"]
    routed = getattr(fam, "routed_experts", lambda m: None)(m)
    experts = {} if routed is None else {
        "expert_bytes": float(routed["layers"] * routed["params_per_expert"]
                              * e["weight_dtype_bytes"]),
        "n_experts": float(routed["experts"]),
        "top_k": float(routed["top_k"]),
    }
    return {
        "fpt_mlp": float(fam.flops_mlp_per_token(m)),
        "fpt_proj": float(fam.flops_proj_per_token(m)),
        "weight_bytes": float(fam.active_param_count(m)
                              * e["weight_dtype_bytes"]),
        "act": float(m["n_layers"] * m["d_model"]
                     * e["activation_bytes_factor"]),
        "coll": float(coll),
        "coll_scale": float(1.0 - e["collective_overlap"]),
        "overhead": float(e["stage_overhead_s"]),
        "eff_max": float(e["eff_max"]),
        "eff_half": float(e["eff_half_tokens"]),
        "peak": float(dev["peak_flops"] * tp),
        "hbm": float(dev["hbm_bw"] * tp),
        "pp": float(pp),
        **experts,
    }


def weight_traffic(P: dict, tokens):
    """Weight bytes a stage of ``tokens`` tokens reads. With routed
    experts, each of E experts of a layer is taken by a token with
    probability k/E, so the stage reads E(1 - (1 - k/E)^T) distinct
    experts a layer where ``weight_bytes`` counts k: exact in
    expectation for balanced routing and independent tokens, and 0
    extra at T = 1. Without routed experts, ``weight_bytes`` alone."""
    if "expert_bytes" not in P:
        return P["weight_bytes"]
    E, k = P["n_experts"], P["top_k"]
    return P["weight_bytes"] + P["expert_bytes"] * (
        E * (1 - (1 - k / E) ** tokens) - k)


def roofline(P: dict, npt, ndec, score, kv):
    """Time and MFU of stage rows from their composition (prompt and
    decode tokens, attention score FLOPs, KV bytes), in the type of the
    arguments: the event loop calls it per iteration on scalars, the
    kernel pass on whole columns."""
    tokens = npt + ndec
    f_mlp = tokens * P["fpt_mlp"]
    f_attn = tokens * P["fpt_proj"] + score
    flops = (f_mlp + f_attn) / P["pp"]
    mem = (weight_traffic(P, tokens) + kv + tokens * P["act"]) / P["pp"]
    eff = P["eff_max"] * tokens / (tokens + P["eff_half"])
    t_comp = flops / (eff * P["peak"])
    t_mem = mem / P["hbm"]
    t = (np.maximum(t_comp, t_mem) + P["coll_scale"] * (tokens * P["coll"])
         + P["overhead"])
    return t, flops / (P["peak"] * t)


# ---------------------------------------------------------- event loop ---

@dataclasses.dataclass
class _Req:
    rid: int
    arrival: object
    prompt: int
    gen: int
    decoded: int = 0
    prefilled: bool = False
    t_first: object = -1.0
    t_done: object = -1.0


class _Replica:
    def __init__(self, cap: int, budget: int):
        self.cap, self.budget = cap, budget
        self.waiting: List[_Req] = []
        self.running: List[_Req] = []
        self.kv = 0

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    def next_batch(self):
        while (self.waiting and len(self.running) < self.cap
               and self.kv + self.waiting[0].prompt <= self.budget):
            r = self.waiting.pop(0)
            self.running.append(r)
            self.kv += r.prompt
        pre = [r for r in self.running if not r.prefilled]
        if pre:
            return pre, []
        return [], [r for r in self.running if r.decoded < r.gen]


@dataclasses.dataclass
class Served:
    start: np.ndarray         # per stage row, from the event loop
    dur: np.ndarray
    mfu: np.ndarray
    batch: np.ndarray
    comp: np.ndarray          # (rows, 4): prompt, decode, score, KV
    params: dict              # the roofline's parameters
    reqs: List[_Req]


def serve(tree: dict, dev: dict, prec: Precision = EXACT) -> Served:
    """Serve the group's stream; one row per iteration and pipeline
    stage, as the paper's Eq. 2-3 accounting consumes it."""
    if tree["scheduler"]["chunk_prefill"] is not None:
        raise ValueError("the reference serves whole-prompt prefill only")
    F = prec.loop
    m = tree["model"]
    fam = family(m["family"])
    arrival, prompt, gen = requests(tree["workload"])
    reqs = [_Req(i, F(float(arrival[i])), int(prompt[i]), int(gen[i]))
            for i in range(len(arrival))]
    budget = tree["scheduler"]["kv_budget_tokens"]
    if tree["auto_kv_budget"]:
        budget = kv_budget(tree, dev, fam)
        if budget <= 0:
            raise ValueError(f"{m['name']} does not fit {tree['device']}")
    params = roofline_params(tree, dev, fam)
    P = {k: F(v) for k, v in params.items()}
    coef = int(fam.score_flops_per_token(m, 1))
    win = fam.window(m)
    kvpt = (fam.kv_bytes_per_token(m, tree["execmodel"]["kv_dtype_bytes"])
            * kv_copies(fam, m, tree["tp"]))
    pp = tree["pp"]

    reps = [_Replica(tree["scheduler"]["batch_cap"], budget)
            for _ in range(tree["n_replicas"])]
    clock = [F(0.0)] * len(reps)
    rr = 0
    pending = sorted(reqs, key=lambda r: r.arrival)
    pi = 0
    stuck = set()
    rows, comp = [], []
    while True:
        cand = [i for i in range(len(reps))
                if i not in stuck and reps[i].has_work()]
        if cand:
            i = min(cand, key=lambda k: clock[k])
            t_event = clock[i]
        elif pi < len(pending):
            i, t_event = None, pending[pi].arrival
        else:
            break
        if pi < len(pending) and pending[pi].arrival <= t_event:
            while pi < len(pending) and pending[pi].arrival <= t_event:
                r = pending[pi]
                idle = not reps[rr].has_work()
                reps[rr].waiting.append(r)
                if idle:
                    clock[rr] = max(clock[rr], r.arrival)
                rr = (rr + 1) % len(reps)
                pi += 1
            continue
        if i is None:
            continue
        rep = reps[i]
        now = clock[i]
        pre, dec = rep.next_batch()
        if not pre and not dec:
            if pi < len(pending):
                clock[i] = max(now, pending[pi].arrival)
            else:
                stuck.add(i)
            continue

        # batch composition, in whole numbers (exact in any order)
        npt = sum(r.prompt for r in pre)
        ctxs = [r.prompt + r.decoded for r in dec]
        score = sum(r.prompt * (coef * min(max(r.prompt // 2, 1), win))
                    for r in pre) + sum(coef * min(c, win) for c in ctxs)
        kv = sum(r.prompt * kvpt for r in pre) \
            + sum(min(c, win) * kvpt + kvpt for c in ctxs)
        t, mfu = roofline(P, F(float(npt)), F(float(len(dec))),
                          F(float(score)), F(float(kv)))
        bs = len(pre) + len(dec)
        for ps in range(pp):
            rows.append((now + ps * t / max(pp, 1), t, mfu, bs))
            comp.append((npt, len(dec), score, kv))

        now = now + t
        clock[i] = now
        for r in pre:
            r.prefilled = True
            if r.t_first < 0:
                r.t_first = now
        done = []
        for r in dec:
            r.decoded += 1
            rep.kv += 1
            if r.decoded >= r.gen:
                r.t_done = now
                done.append(r)
        for r in done:
            rep.running.remove(r)
            rep.kv -= r.prompt + r.decoded
        if now > _MAX_SIM_S:
            break

    cols = list(zip(*rows)) if rows else [(), (), (), ()]
    return Served(start=np.asarray(cols[0], np.float64),
                  dur=np.asarray(cols[1], np.float64),
                  mfu=np.asarray(cols[2], np.float64),
                  batch=np.asarray(cols[3], np.int64),
                  comp=np.asarray(comp, np.float64).reshape(-1, 4),
                  params=params, reqs=reqs)


# ------------------------------------------------------------- records ---

def trace_columns(s: Served) -> Dict[str, float]:
    """The columns that depend on the served trace alone."""
    dur, mfu = s.dur, s.mfu
    done = [r for r in s.reqs if r.t_done >= 0]
    total = float((s.start + dur).max()) if len(dur) else 0.0
    ttft = [r.t_first - r.arrival for r in s.reqs if r.t_first >= 0]
    e2e = [r.t_done - r.arrival for r in s.reqs if r.t_done >= 0]
    return {
        "avg_mfu": (float(np.sum(mfu * dur) / max(dur.sum(), 1e-12))
                    if len(dur) else 0.0),
        "throughput_qps": (len(done) / max(total, 1e-9)) if done else 0.0,
        "n_stages": len(dur),
        "avg_batch": float(np.mean(s.batch)) if len(s.batch) else 0.0,
        "ttft_p50_s": float(np.median(ttft)) if ttft else -1.0,
        "ttft_p99_s": float(np.percentile(ttft, 99)) if ttft else -1.0,
        "e2e_p50_s": float(np.median(e2e)) if e2e else -1.0,
        "e2e_p99_s": float(np.percentile(e2e, 99)) if e2e else -1.0,
    }


def energy_sums(s: Served, dev: dict, prec: Precision = EXACT):
    """The grid kernel's pass: each stage row's roofline again from its
    composition, Eq. 1 per row, then sum(P dt), sum(dt) and max(P)."""
    K, P = prec.kernel, prec.power
    dur, mfu = roofline({k: K(v) for k, v in s.params.items()},
                        *(c.astype(K) for c in s.comp.T))
    x = np.minimum(np.maximum(mfu, 0).astype(P), P(dev["mfu_sat"])) \
        / P(dev["mfu_sat"])
    p = P(dev["p_idle"]) + P(dev["p_max_inst"] - dev["p_idle"]) \
        * x ** P(dev["gamma"])
    p = p.astype(K)
    return (K(np.sum(p * dur)), K(dur.sum()),
            K(p.max()) if len(p) else K(0.0))


def group_records(group, prec: Precision = EXACT) -> List[dict]:
    """Every record of one trace group, as the sweep reports it."""
    tree = group.tree
    dev = hardware()[tree["device"]]
    s = serve(tree, dev, prec)
    shared = trace_columns(s)
    e_sum, dur, peak = energy_sums(s, dev, prec)
    A = prec.kernel
    ndev = A(tree["n_replicas"] * tree["tp"] * tree["pp"])
    phi = A(dev["embodied_kg"] / (dev["embodied_years"] * 365 * 24))
    gpu_h = dur / A(3600.0) * ndev
    emb = gpu_h * phi * A(1000.0)
    out = []
    for sc in group.scenarios:
        energy = e_sum / A(3600.0) * ndev * A(sc["pue"])
        op = energy / A(1000.0) * A(sc["grid_ci"])
        metrics = {
            "energy_wh": float(energy),
            "energy_kwh": float(energy / A(1000.0)),
            "avg_power_w": float(e_sum / max(dur, A(1e-12))),
            "peak_power_w": float(peak),
            "duration_s": float(dur),
            "gpu_hours": float(gpu_h),
            "carbon_operational_g": float(op),
            "carbon_embodied_g": float(emb),
            "carbon_total_g": float(op + emb),
            "grid_ci_g_per_kwh": float(sc["grid_ci"]),
            **shared,
        }
        out.append({"scenario": sc["tag"],
                    "key": scenario_key(tree, sc["pue"], sc["grid_ci"]),
                    "params": dict(sc["params"]),
                    "metrics": {c: metrics[c] for c in RECORD_COLS}})
    return out


def control_records(group, number: str) -> List[dict]:
    """``group``'s records from the control that gives ``number``'s
    upper reading."""
    return group_records(group, controls()[number])
