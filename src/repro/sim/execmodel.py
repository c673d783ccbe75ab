"""Analytical batch-stage execution model (the Vidur random-forest
replacement — see DESIGN.md §3.2).

Stage latency is a three-term roofline over the batch composition:

  t_compute = FLOPs / (eff(tokens) * peak * TP)        per pipeline stage
  t_memory  = bytes(weights/TP + KV + activations) / (HBM_bw * TP)
  t_coll    = TP all-reduce traffic / link_bw (+ PP activation handoff)
  t_stage   = max(t_compute, t_memory) + (1 - overlap) * t_coll + t_0

The matmul efficiency curve eff(tokens) saturates with batched tokens
(arithmetic intensity): calibrated so Meta-Llama-3-8B on A100 plateaus
near MFU 0.45 at 5-8 QPS, reproducing the paper's Fig. 1. On TPU the
same form is calibrated against the dry-run's compiled cost analysis
(`calibrate_from_dryrun`).

One stage-cost model: a stage's composition reduces to four
aggregates — summed prefill tokens, decode count, score FLOPs, KV
read/write bytes (``StageBatch``). Each sequence's share of the last two
has one definition (``prefill_terms`` for a chunk at an offset,
``decode_terms`` for a decode at a context), elementwise over floats or
arrays. The roofline over the aggregates has one body
(``_live_roofline``); ``_roofline`` is its masked form over stages that
may be empty, which ``stage_cost_batch`` runs with numpy and the device
kernel with ``jax.numpy``. The event loop's ``stage_cost_scalar`` and
``decode_run``, trace replay and the device program all go through
these, so their costs are bit-identical by construction.

All per-model constants (active parameter count, KV bytes/token,
per-token FLOP totals, score coefficients) are computed once at
``ExecutionModel`` construction, not per stage-cost call.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Sequence

import numpy as np

from repro.configs.base import ModelConfig
from repro.core.power import DEVICES, DeviceProfile


@dataclasses.dataclass(frozen=True)
class ExecModelConfig:
    eff_max: float = 0.52          # peak matmul efficiency (fraction of peak)
    eff_half_tokens: float = 192.0  # tokens at which eff reaches half of max
    stage_overhead_s: float = 200e-6
    activation_bytes_factor: float = 8.0  # bytes/token/layer ~ f*d_model
    collective_overlap: float = 0.0       # 0 = no overlap (baseline)
    kv_dtype_bytes: int = 2
    weight_dtype_bytes: int = 2


@dataclasses.dataclass
class StageCost:
    t_total: float
    t_compute: float
    t_memory: float
    t_collective: float
    flops_mlp: float
    flops_attn: float
    mfu: float


@dataclasses.dataclass
class StageBatch:
    """Per-stage batch-composition aggregates, over N stages.

    These four arrays — plus the per-model invariants cached on the
    ``ExecutionModel`` — fully determine the roofline, so a logged
    trace of them can be re-costed in one array pass.
    """
    prefill_tokens: np.ndarray   # summed prefill (chunk) tokens per stage
    decode_count: np.ndarray     # sequences decoding one token per stage
    score_flops: np.ndarray      # context-dependent attention score FLOPs
    kv_rw_bytes: np.ndarray      # KV cache read+write traffic per stage

    def __len__(self) -> int:
        return len(self.prefill_tokens)

    @classmethod
    def concat(cls, batches: Sequence["StageBatch"]) -> "StageBatch":
        return cls(*(np.concatenate([getattr(b, f.name) for b in batches])
                     for f in dataclasses.fields(cls)))

    @classmethod
    def from_trace(cls, trace) -> "StageBatch":
        """Rebuild the aggregates from a logged ``StageTrace``."""
        return cls(
            prefill_tokens=np.asarray(trace.n_prefill_tokens, np.float64),
            decode_count=np.asarray(trace.n_decode_tokens, np.float64),
            score_flops=np.asarray(trace.score_flops, np.float64),
            kv_rw_bytes=np.asarray(trace.kv_rw_bytes, np.float64))


@dataclasses.dataclass
class StageCostBatch:
    """Roofline outputs over N stages (arrays aligned with StageBatch)."""
    t_total: np.ndarray
    t_compute: np.ndarray
    t_memory: np.ndarray
    t_collective: np.ndarray
    flops_mlp: np.ndarray
    flops_attn: np.ndarray
    mfu: np.ndarray

    def __len__(self) -> int:
        return len(self.t_total)


@dataclasses.dataclass(frozen=True)
class _Params:
    """Scalar roofline parameters, resolved once per ExecutionModel.
    The kernel below reads only this (plus the StageBatch arrays), so
    the numpy and jax paths share one implementation."""
    fpt_mlp: float
    fpt_proj: float
    weight_bytes: float
    act_bytes_per_token: float
    coll_s_per_token: float
    coll_scale: float
    overhead_s: float
    eff_max: float
    eff_half_tokens: float
    peak_chips: float
    hbm_chips: float
    pp: float


#: flat field order of the roofline parameter vector
#: (``ExecutionModel.params_vector`` / the device-mode batched program,
#: which reconstructs ``_Params(*row)`` per trace group inside vmap)
PARAMS_FIELDS = tuple(f.name for f in dataclasses.fields(_Params))

def _live_roofline(tokens, f_score, kv_rw, p, maximum):
    """The roofline of stages with ``tokens > 0``: ``tokens``,
    ``f_score`` and ``kv_rw`` are floats (with ``maximum=max``) or
    arrays over stages (with ``np.maximum`` / ``jnp.maximum``). Returns
    ``StageCost``'s fields in order."""
    f_mlp = tokens * p.fpt_mlp
    f_attn = tokens * p.fpt_proj + f_score
    flops_st = (f_mlp + f_attn) / p.pp
    mem_st = (p.weight_bytes + kv_rw
              + tokens * p.act_bytes_per_token) / p.pp
    eff = p.eff_max * tokens / (tokens + p.eff_half_tokens)
    t_comp = flops_st / (eff * p.peak_chips)
    t_mem = mem_st / p.hbm_chips
    t_coll = tokens * p.coll_s_per_token
    t = maximum(t_comp, t_mem) + p.coll_scale * t_coll + p.overhead_s
    return (t, t_comp, t_mem, t_coll, f_mlp / p.pp, f_attn / p.pp,
            flops_st / (p.peak_chips * t))


def _roofline(prefill_tokens, decode_count, score_flops, kv_rw_bytes,
              p, xp=np):
    """``_live_roofline`` over stages that may be empty: an empty stage
    is evaluated at 1 token and every one of its outputs set to 0.
    ``xp`` is ``numpy`` (default) or ``jax.numpy``."""
    tokens = prefill_tokens + decode_count
    live = tokens > 0
    out = _live_roofline(xp.where(live, tokens, 1.0), score_flops,
                         kv_rw_bytes, p, xp.maximum)
    zero = xp.zeros_like(tokens)
    return tuple(xp.where(live, v, zero) for v in out)


class ExecutionModel:
    def __init__(self, model: ModelConfig, device: DeviceProfile,
                 tp: int = 1, pp: int = 1,
                 cfg: ExecModelConfig = ExecModelConfig()):
        self.model = model
        self.dev = device
        self.tp = tp
        self.pp = pp
        self.cfg = cfg

        # ---- per-model invariants, computed ONCE (not per stage) ----
        m, c = model, cfg
        self.active_params = m.active_param_count()
        self.kv_bytes_per_token = float(m.kv_bytes_per_token(c.kv_dtype_bytes))
        self.fpt_mlp = m.flops_per_token_mlp_total()
        self.fpt_proj = m.flops_per_token_attn_proj_total()
        # score(ctx) = score_coef * min(ctx, window) + score_const:
        # the context-linear attention part plus the constant ssm/rwkv
        # per-token mixing terms (flops_attn_score_per_token's shape)
        self.score_const = float(m.flops_attn_score_per_token(0))
        self.score_coef = float(m.flops_attn_score_per_token(1)
                                - self.score_const)
        a = m.attention
        self.sliding_window = (float(a.sliding_window)
                               if (a and a.sliding_window) else math.inf)

        chips = tp
        coll = 0.0
        if tp > 1:
            # 2 all-reduces per layer of the activation block (ring)
            coll += (2.0 * m.d_model * 2 * (m.n_layers / pp)
                     * 2.0 * (tp - 1) / tp) / device.link_bw
        if pp > 1:
            coll += m.d_model * 2 / device.link_bw
        self._params = _Params(
            fpt_mlp=float(self.fpt_mlp),
            fpt_proj=float(self.fpt_proj),
            weight_bytes=float(self.active_params * c.weight_dtype_bytes),
            act_bytes_per_token=float(m.n_layers * m.d_model
                                      * c.activation_bytes_factor),
            coll_s_per_token=float(coll),
            coll_scale=float(1.0 - c.collective_overlap),
            overhead_s=float(c.stage_overhead_s),
            eff_max=float(c.eff_max),
            eff_half_tokens=float(c.eff_half_tokens),
            peak_chips=float(device.peak_flops * chips),
            hbm_chips=float(device.hbm_bw * chips),
            pp=float(pp))

    def params_vector(self) -> np.ndarray:
        """The resolved roofline parameters as a flat float64 vector in
        ``PARAMS_FIELDS`` order — the per-group row the device-mode
        sweep stacks into its (groups, params) tensor."""
        return np.array([getattr(self._params, name)
                         for name in PARAMS_FIELDS], np.float64)

    def replica_tokens_per_s(self, batch_cap: int, kv_budget_tokens: int,
                             mean_prefill: float, mean_decode: float
                             ) -> float:
        """Model-derived steady-state per-replica token throughput at
        full batching: ``B`` requests of the mean shape served per
        ``t_prefill(B*L) + D * t_decode(B @ mid-context)`` seconds,
        with ``B`` capped by the batch cap and the KV budget.

        Used by the day planner's saturation guard as a *capacity
        floor* alongside the autoscaler's configured estimate — a
        config estimate far above what the roofline can actually
        serve would otherwise let a queue-saturated epoch slip
        through the fluid path (whose pilot tiles a growing queue).
        """
        L = max(float(mean_prefill), 1.0)
        D = max(float(mean_decode), 1.0)
        per_req = L + D
        b = min(float(batch_cap), float(kv_budget_tokens) / per_req)
        b = max(1.0, np.floor(b))
        t_pre = self.stage_cost_scalar([L] * int(b), [])[0].t_total
        mid_ctx = L + np.floor(D / 2.0)
        t_dec = self.stage_cost_scalar([], [mid_ctx] * int(b))[0].t_total
        return b * per_req / max(t_pre + D * t_dec, 1e-9)

    def _score_per_token(self, ctx):
        """score FLOPs per token at context length(s) ctx (array op)."""
        return (self.score_coef * np.minimum(ctx, self.sliding_window)
                + self.score_const)

    def prefill_terms(self, plens, offs):
        """Score FLOPs and KV bytes of prefill chunks of ``plens`` tokens
        at offsets ``offs`` (tokens of the prompt already prefilled by
        earlier chunks; 0 for a fresh prefill), elementwise. Causal
        prefill sees an average context of ``o + floor(L/2)``; the chunk
        writes its own K/V and re-reads the prior context's
        (window-bounded)."""
        kvpt = self.kv_bytes_per_token
        avg_ctx = np.maximum(offs + np.floor(plens / 2.0), 1.0)
        return (plens * self._score_per_token(avg_ctx),
                plens * kvpt + np.minimum(offs, self.sliding_window) * kvpt)

    def decode_terms(self, ctxs):
        """Score FLOPs and KV bytes of decodes at contexts ``ctxs``,
        elementwise: each reads its cache (window-bounded) and writes
        one token."""
        kvpt = self.kv_bytes_per_token
        return (self._score_per_token(ctxs),
                np.minimum(ctxs, self.sliding_window) * kvpt + kvpt)

    def stage_cost_batch(self, batch: StageBatch) -> StageCostBatch:
        """Evaluate the roofline over N stages in one numpy pass —
        bit-identical, row for row, to ``stage_cost_scalar``."""
        return StageCostBatch(*_roofline(
            np.asarray(batch.prefill_tokens, np.float64),
            np.asarray(batch.decode_count, np.float64),
            np.asarray(batch.score_flops, np.float64),
            np.asarray(batch.kv_rw_bytes, np.float64), self._params, np))

    def stage_cost_scalar(self, prefill_lens: Sequence[int],
                          decode_ctxs: Sequence[int],
                          prefill_offsets: Optional[Sequence[int]] = None):
        """Cost of ONE batch stage (= one scheduler iteration on one
        pipeline stage's share of layers), on the event loop's hot
        path: the composition terms reduced with numpy's pairwise
        ``.sum()``, the roofline on Python floats (no length-1 arrays).

        prefill_lens: prompt (chunk) token counts prefilled this stage.
        decode_ctxs: context lengths of sequences generating one token.
        prefill_offsets: tokens of each prompt already prefilled by
        earlier chunks (Sarathi chunking); None = fresh prefills.

        Returns ``(StageCost, prefill_tokens, decode_count,
        score_flops, kv_rw_bytes)`` — the cost plus the stage's
        StageBatch aggregates as plain floats (what the trace logs).
        """
        ctxs = np.asarray(decode_ctxs, np.float64)
        nd = float(len(ctxs))
        if len(prefill_lens):
            plens = np.asarray(prefill_lens, np.float64)
            offs = (np.zeros_like(plens) if prefill_offsets is None
                    else np.asarray(prefill_offsets, np.float64))
            npt = float(plens.sum())
            score, kv = self.prefill_terms(plens, offs)
            f_pre = float(score.sum())
            kv_pre = kv.sum()
        else:
            npt = f_pre = kv_pre = 0.0      # the empty sums
        score, kv = self.decode_terms(ctxs)
        f_score = f_pre + float(score.sum())
        kv_rw = float(kv_pre + kv.sum())

        tokens = npt + nd
        if tokens > 0:
            cost = StageCost(*_live_roofline(tokens, f_score, kv_rw,
                                             self._params, max))
        else:
            cost = StageCost(0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0)
        return cost, npt, nd, f_score, kv_rw

    def decode_run(self, decode_ctxs: Sequence[int], k: int):
        """Costs of ``k`` decode-only stages over the same sequences,
        stage ``j`` at contexts ``decode_ctxs + j`` (a run of
        iterations in which only the contexts grow), as arrays over
        the run.

        Bit-identical to ``k`` calls of ``stage_cost_scalar`` by
        construction: each stage's ``decode_terms`` are reduced over one
        contiguous row of a ``(k, n)`` context grid (the same pairwise
        sum as the 1-D one), and the roofline is the scalar path's own,
        with the stage-invariant terms as floats.

        Returns ``(t_total, flops_mlp, flops_attn, mfu, score_flops,
        kv_rw_bytes)``; ``flops_mlp`` is one float, the same for every
        stage.
        """
        grid = (np.asarray(decode_ctxs, np.float64)
                + np.arange(k, dtype=np.float64)[:, None])
        score, kv = self.decode_terms(grid)
        f_score = 0.0 + score.sum(axis=1)
        kv_rw = 0.0 + kv.sum(axis=1)
        t, _, _, _, f_mlp, f_attn, mfu = _live_roofline(
            0.0 + float(len(decode_ctxs)), f_score, kv_rw, self._params,
            np.maximum)
        return t, f_mlp, f_attn, mfu, f_score, kv_rw


@functools.lru_cache(maxsize=512)
def cached_execution_model(model: ModelConfig, device_name: str,
                           tp: int, pp: int,
                           cfg: ExecModelConfig) -> ExecutionModel:
    """Per-process memoized ExecutionModel construction.

    ExecutionModel is stateless after __init__ (pure roofline
    functions over cached invariants), so sweep workers reuse one
    instance across every grid point that shares (model, device,
    TP, PP, exec config) instead of reconstructing it per scenario.
    """
    return ExecutionModel(model, DEVICES[device_name], tp, pp, cfg)


def calibrate_from_dryrun(exec_cfg: ExecModelConfig, hlo_dot_flops: float,
                          analytic_flops: float) -> ExecModelConfig:
    """Scale eff_max by the compiled-vs-analytic FLOP ratio so the
    simulator's time model reflects what XLA actually emits."""
    if analytic_flops <= 0 or hlo_dot_flops <= 0:
        return exec_cfg
    ratio = analytic_flops / hlo_dot_flops
    return dataclasses.replace(exec_cfg,
                               eff_max=exec_cfg.eff_max * min(1.0, ratio))
