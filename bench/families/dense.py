"""Size arithmetic of a dense decoder-only transformer, for the plain
reference: parameter count, forward FLOPs per token and KV-cache bytes,
from the widths in a deployment's ``model`` block alone.

Forward FLOPs are 2 per multiply-accumulate. Attention has
``n_heads`` query heads of ``head_dim`` and ``n_kv_heads`` key/value
heads; the MLP has two matrices, or three when gated. The output head
is untied unless ``tie_embeddings``.

Every family module gives the functions below. Two more are optional
hooks, which a dense model leaves out; ``bench/harness/reference.py``
asks for them by name:

- ``routed_experts(m) -> None | dict``: the routed experts whose
  weights a stage reads in part. Keys ``layers`` (layers with routed
  experts), ``experts`` (E per layer), ``top_k`` (k taken per token)
  and ``params_per_expert`` (parameters of one expert). Default: none.
  The reference then reads ``X = layers * params_per_expert *
  weight_dtype_bytes`` bytes for each distinct expert beyond the k
  that ``active_param_count`` holds: a stage of T tokens reads
  ``active_param_count * weight_dtype_bytes + X * (E * (1 - (1 - k/E)
  ** T) - k)`` bytes of weights.
- ``kv_copies(m, tp) -> int``: the copies of one token's cache that a
  replica's ``tp`` ranks hold, a whole number. Default: 1, a cache
  sharded by head. A latent cache that every rank holds whole gives
  ``tp``. It multiplies ``kv_bytes_per_token`` in the KV budget (bytes
  per GPU) and in each stage's KV traffic (bytes).
"""


def attn_params(m: dict) -> int:
    a = m["attention"]
    q = a["n_heads"] * a["head_dim"]
    kv = a["n_kv_heads"] * a["head_dim"]
    return m["d_model"] * (q + 2 * kv) + q * m["d_model"]


def mlp_params(m: dict) -> int:
    f = m["mlp"]
    return (3 if f["gated"] else 2) * m["d_model"] * f["d_ff"]


def param_count(m: dict) -> int:
    d = m["d_model"]
    emb = m["vocab_size"] * d * (1 if m["tie_embeddings"] else 2)
    return emb + m["n_layers"] * (attn_params(m) + mlp_params(m)) + d


def active_param_count(m: dict) -> int:
    return param_count(m)


def flops_mlp_per_token(m: dict) -> float:
    """MLP plus output-head FLOPs per token, over all layers."""
    return m["n_layers"] * 2.0 * mlp_params(m) + 2.0 * m["d_model"] * m["vocab_size"]


def flops_proj_per_token(m: dict) -> float:
    """Attention projection FLOPs per token, over all layers."""
    return m["n_layers"] * 2.0 * attn_params(m)


def score_flops_per_token(m: dict, ctx):
    """Score and value FLOPs of one token that attends over ``ctx``
    positions (window-bounded), over all layers. ``ctx`` may be an int
    or an array."""
    a = m["attention"]
    w = a["sliding_window"]
    if w is not None:
        ctx = ctx if ctx <= w else w
    return m["n_layers"] * 4.0 * a["n_heads"] * a["head_dim"] * ctx


def kv_bytes_per_token(m: dict, dtype_bytes: int) -> int:
    a = m["attention"]
    return 2 * a["n_kv_heads"] * a["head_dim"] * m["n_layers"] * dtype_bytes


def window(m: dict) -> float:
    w = m["attention"]["sliding_window"]
    return float("inf") if w is None else float(w)
