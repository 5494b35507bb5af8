"""Operations and bytes that the mathematics of a dense RoPE/GQA decoder
needs, from shapes alone: the same work whatever implements it. A matrix
product of [m, k] x [k, n] is 2*m*k*n operations. Recomputation is never
counted. Causal attention counts the half of the score matrix that is used.
"""
from __future__ import annotations

import json
import os


class UnknownDevice(KeyError):
    """A device that is not in peaks.json is an error, not a default."""


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table["devices"][device_kind]


def _dims(cfg: dict):
    h = cfg["hidden_size"]
    hd = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    return h, hd, cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer's seven projections."""
    h, _, q, kv = _dims(cfg)
    return h * q + 2 * h * kv + q * h + 3 * h * cfg["intermediate_size"]


def matmul_params(cfg: dict) -> int:
    """Weights every token is multiplied by: the layers' and the head's (the
    embedding is a lookup)."""
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"]


def n_params(cfg: dict) -> int:
    return matmul_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"] \
        + (2 * cfg["num_hidden_layers"] + 1) * cfg["hidden_size"]


def attn_flops_fwd(cfg: dict, q_tokens: int, ctx_mean: float) -> float:
    """Scores and weighted values of ONE layer: each query token against
    `ctx_mean` keys: 2 products of 2*D operations for each head."""
    _, hd, _, _ = _dims(cfg)
    return 4.0 * cfg["num_attention_heads"] * hd * q_tokens * ctx_mean


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward of one token in a causal sequence of seq_len:
    3 x (2 x weights + attention over (seq_len + 1) / 2 keys a layer)."""
    attn = cfg["num_hidden_layers"] * attn_flops_fwd(cfg, 1, (seq_len + 1) / 2)
    return 3.0 * (2.0 * matmul_params(cfg) + attn)


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """A whole prompt through the layers (no head: the first token comes
    from the first decode step)."""
    body = 2.0 * cfg["num_hidden_layers"] * layer_matmul_params(cfg) * prompt_len
    return body + cfg["num_hidden_layers"] * attn_flops_fwd(cfg, prompt_len, (prompt_len + 1) / 2)


def decode_flops(cfg: dict, context_len: float) -> float:
    """One token against `context_len` cached keys, head included."""
    return 2.0 * matmul_params(cfg) + cfg["num_hidden_layers"] * attn_flops_fwd(cfg, 1, context_len)


def flash_fwd(cfg: dict, rows: int, seq_len: int, itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of causal attention forward of ONE layer over
    rows x seq_len: q, k, v read and o written once."""
    _, hd, q, kv = _dims(cfg)
    flops = rows * attn_flops_fwd(cfg, seq_len, (seq_len + 1) / 2)
    return flops, float(rows * seq_len * (2 * q + 2 * kv) * itemsize)


def flash_bwd(cfg: dict, rows: int, seq_len: int, itemsize: int = 2) -> tuple[float, float]:
    """Backward: scores again from q and k, then dv, dp, dq, dk: five
    products where the forward has two. Reads q, k, v, o, do; writes dq, dk, dv."""
    _, hd, q, kv = _dims(cfg)
    flops = 2.5 * rows * attn_flops_fwd(cfg, seq_len, (seq_len + 1) / 2)
    return flops, float(rows * seq_len * (4 * q + 4 * kv) * itemsize)


def fused_ce(cfg: dict, tokens: int, itemsize: int = 2) -> tuple[float, float]:
    """The head's product with the softmax statistics, forward only (what
    `_ce_stats_kernel` computes): hidden states and the head read once."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return 2.0 * tokens * h * v, float((tokens * h + h * v) * itemsize)


def paged_decode(cfg: dict, context_tokens: float, batch: int, itemsize: int = 2) -> tuple[float, float]:
    """One layer's attention of one decode step over `context_tokens` cached
    tokens in all (summed over the batch): every cached key and value read
    once."""
    _, hd, q, kv = _dims(cfg)
    flops = attn_flops_fwd(cfg, 1, context_tokens)
    return flops, float(context_tokens * 2 * kv * itemsize + batch * 2 * q * itemsize)


def least_seconds(flops: float, bytes_: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak sets it."""
    t_c, t_m = flops / peak["bf16_flops_per_s"], bytes_ / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
