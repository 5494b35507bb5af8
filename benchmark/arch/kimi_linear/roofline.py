"""Operations and bytes that the mathematics of a Kimi-Linear configuration
needs, from shapes alone. A product of [m, k] x [k, n] is 2*m*k*n
operations; recomputation is never counted; causal attention counts the
half of the score matrix that is used; the held experts are counted at the
share of the token-expert pairs a uniform router sends them
(top_k * held / experts a token) or, for their kernel, at the pairs the
program counted. Bytes count every operand and result once."""
from __future__ import annotations

from benchmark.arch.kimi_linear import weights as KW

CHUNK = 64


def expert_weights(d: dict) -> int:
    return 3 * d["h"] * d["expert"]


def matmul_params(cfg: dict, pairs_per_token: float | None = None) -> float:
    """Weights a token is multiplied by, the head included (the embedding
    is a lookup). `pairs_per_token`: how many of a token's `top_k` experts
    are held here, a layer (the program's count); without it the share a
    uniform router sends here, top_k * held / experts."""
    d = KW.dims(cfg)
    if pairs_per_token is None:
        pairs_per_token = d["top_k"] * d["held"] / d["experts"]
    h, inner, rank = d["h"], d["inner"], d["rank"]
    per = {"kda": 4 * h * inner + 2 * (h * rank + rank * inner) + h * d["kda_heads"],
           "mla": h * d["heads"] * (d["nope"] + d["rope"]) + h * (d["latent"] + d["rope"])
           + d["latent"] * d["heads"] * (d["nope"] + d["vd"]) + d["heads"] * d["vd"] * h,
           "dense": 3 * h * d["dense"],
           "moe": h * d["experts"] + 3 * h * d["shared"]
           + pairs_per_token * expert_weights(d)}
    return sum(per[m] + per[f] for m, f in KW.layer_kinds(cfg)) + h * d["vocab"]


def mla_attn_flops_fwd(cfg: dict, q_tokens: float, ctx_mean: float) -> float:
    """Scores over the query/key width, weighted values over the value width."""
    d = KW.dims(cfg)
    return 2.0 * d["heads"] * (d["nope"] + d["rope"] + d["vd"]) * q_tokens * ctx_mean


def kda_flops_fwd(cfg: dict, tokens: float) -> float:
    """ONE layer's chunked gated delta rule over `tokens`, a chunk of C and
    a head at a time: A and P (the causal halves) C*C*K each, Ubar and Wbar
    2*C*C*(V + K), P U C*C*V, T about C^3 / 3, and the three products with
    the state, 2*C*K*V each."""
    d = KW.dims(cfg)
    c, k = CHUNK, d["kda_hd"]
    v = k
    per_chunk = 2 * c * c * k + 2 * c * c * (k + v) + c * c * v + c ** 3 / 3 + 6 * c * k * v
    return d["kda_heads"] * tokens / c * per_chunk


def train_flops_per_token(cfg: dict, seq_len: int, pairs_per_token: float | None = None) -> float:
    """Forward + backward of one token in a causal sequence of seq_len."""
    kinds = [m for m, _ in KW.layer_kinds(cfg)]
    attn = kinds.count("mla") * mla_attn_flops_fwd(cfg, 1, (seq_len + 1) / 2)
    return 3.0 * (2.0 * matmul_params(cfg, pairs_per_token) + attn
                  + kinds.count("kda") * kda_flops_fwd(cfg, 1))


def n_layers(cfg: dict, kind: str) -> int:
    return sum(kind in pair for pair in KW.layer_kinds(cfg))


def kda_scan_fwd(cfg: dict, tokens: float, itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of the sequential part of ONE layer (kernel
    `kda_fwd`): U = Ubar - Wbar S, O = Qd S + P U, S' = gamma S + Kd^T U a
    chunk. Reads Qd, Wbar, Kd [C, K], Ubar [C, V], P [C, C], gamma [K];
    writes O. The states and U it also writes for the backward pass are the
    implementation's, not the mathematics', and are not counted."""
    d = KW.dims(cfg)
    c, k, chunks = CHUNK, d["kda_hd"], d["kda_heads"] * tokens / CHUNK
    flops = chunks * (6 * c * k * k + 2 * c * c * k)
    return flops, chunks * ((5 * c * k + c * c) * itemsize + 4 * k)


def kda_scan_bwd(cfg: dict, tokens: float, itemsize: int = 2) -> tuple[float, float]:
    """Kernel `kda_bwd`: dU (2 products), dQd, dWbar, dKd, dS (2) with the
    state's width, dP and P^T dO with the chunk's. Reads what the forward
    read, the chunk's starting state, U and dO; writes five gradients."""
    d = KW.dims(cfg)
    c, k, chunks = CHUNK, d["kda_hd"], d["kda_heads"] * tokens / CHUNK
    flops = chunks * (14 * c * k * k + 4 * c * c * k)
    bytes_ = chunks * ((5 * c * k + c * c) * itemsize + 4 * k * k + 2 * c * k * itemsize
                       + (4 * c * k + c * c) * itemsize + 4 * k)
    return flops, bytes_


def mla_flash_fwd(cfg: dict, rows: int, seq_len: int, itemsize: int = 2) -> tuple[float, float]:
    d = KW.dims(cfg)
    flops = rows * mla_attn_flops_fwd(cfg, seq_len, (seq_len + 1) / 2)
    qk, vd = d["nope"] + d["rope"], d["vd"]
    return flops, float(rows * seq_len * d["heads"] * (2 * qk + 2 * vd) * itemsize)


def mla_flash_bwd(cfg: dict, rows: int, seq_len: int, itemsize: int = 2) -> tuple[float, float]:
    """Scores again, dQ and dK over the query/key width (3 x 192), dP and dV
    over the value width (2 x 128). Reads q, k, v, o, do; writes dq, dk, dv."""
    d = KW.dims(cfg)
    qk, vd = d["nope"] + d["rope"], d["vd"]
    flops = rows * 2.0 * d["heads"] * (3 * qk + 2 * vd) * seq_len * (seq_len + 1) / 2
    return flops, float(rows * seq_len * d["heads"] * (4 * qk + 4 * vd) * itemsize)


def expert_gmm(cfg: dict, routed_rows: float, itemsize: int = 2) -> tuple[float, float]:
    """The grouped products of ONE expert layer, forward and backward, over
    the token-expert pairs routed to the held experts: three products
    forward, dx and dw of each backward (the gate and up products made again
    in the backward pass are not counted). The held experts' weights read
    once a product forward and once backward, their gradients written once."""
    d = KW.dims(cfg)
    flops = 9 * 2.0 * routed_rows * d["h"] * d["expert"]
    weights = d["held"] * expert_weights(d)
    rows = routed_rows * (d["h"] + d["expert"])
    return flops, float(3 * weights * itemsize + 6 * rows * itemsize)
