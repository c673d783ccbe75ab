"""A toy family with both optional hooks, for the reference's tests.

Dense attention as in ``bench/families/dense.py``; each layer's MLP is
``moe.top_k`` of ``moe.n_experts`` gated experts of width
``moe.d_expert``; the cache is a latent of ``kv_latent`` values a token
and layer, which every TP rank holds whole.
"""


def attn_params(m: dict) -> int:
    a = m["attention"]
    q = a["n_heads"] * a["head_dim"]
    kv = a["n_kv_heads"] * a["head_dim"]
    return m["d_model"] * (q + 2 * kv) + q * m["d_model"]


def expert_params(m: dict) -> int:
    return 3 * m["d_model"] * m["moe"]["d_expert"]


def _total(m: dict, experts: int) -> int:
    d = m["d_model"]
    emb = m["vocab_size"] * d * (1 if m["tie_embeddings"] else 2)
    return emb + m["n_layers"] * (attn_params(m)
                                  + experts * expert_params(m)) + d


def param_count(m: dict) -> int:
    return _total(m, m["moe"]["n_experts"])


def active_param_count(m: dict) -> int:
    return _total(m, m["moe"]["top_k"])


def flops_mlp_per_token(m: dict) -> float:
    return (m["n_layers"] * 2.0 * m["moe"]["top_k"] * expert_params(m)
            + 2.0 * m["d_model"] * m["vocab_size"])


def flops_proj_per_token(m: dict) -> float:
    return m["n_layers"] * 2.0 * attn_params(m)


def score_flops_per_token(m: dict, ctx):
    a = m["attention"]
    return m["n_layers"] * 4.0 * a["n_heads"] * a["head_dim"] * ctx


def kv_bytes_per_token(m: dict, dtype_bytes: int) -> int:
    return m["kv_latent"] * m["n_layers"] * dtype_bytes


def window(m: dict) -> float:
    return float("inf")


def routed_experts(m: dict):
    return {"layers": m["n_layers"], "experts": m["moe"]["n_experts"],
            "top_k": m["moe"]["top_k"],
            "params_per_expert": expert_params(m)}


def kv_copies(m: dict, tp: int) -> int:
    return tp
