"""Operations and bytes that the mathematics of an LFM2-MoE configuration
needs, from shapes alone. A product of [m, k] x [k, n] is 2*m*k*n
operations; recomputation is never counted; causal attention counts the
half of the score matrix that is used; the held experts are counted at the
share of the token-expert pairs a uniform router sends them
(top_k * held / experts a token) or, where the program counted them, at the
pairs it counted. The convolution's three taps and the gates (14 operations
a channel beside 16,384 of the two products) are not counted. Bytes count
every operand and result once."""
from __future__ import annotations

from benchmark import roofline
from benchmark.arch.lfm2_moe import weights as LW


def expert_weights(d: dict) -> int:
    return 3 * d["h"] * d["expert"]


def matmul_params(cfg: dict, pairs_per_token: float | None = None) -> float:
    """Weights a token is multiplied by, the tied head included (the
    embedding is a lookup). `pairs_per_token`: how many of a token's `top_k`
    experts are held here, a layer (the program's count); without it the
    share a uniform router sends here, top_k * held / experts."""
    d = LW.dims(cfg)
    if pairs_per_token is None:
        pairs_per_token = d["top_k"] * d["held"] / d["experts"]
    h, q, kv = d["h"], d["heads"] * d["hd"], d["kv_heads"] * d["hd"]
    per = {"conv": h * 3 * h + h * h,
           "full_attention": 2 * h * q + 2 * h * kv,
           "dense": 3 * h * d["dense"],
           "moe": h * d["experts"] + pairs_per_token * expert_weights(d)}
    return sum(per[m] + per[f] for m, f in LW.layer_kinds(cfg)) + h * d["vocab"]


def n_layers(cfg: dict, kind: str) -> int:
    return sum(kind in pair for pair in LW.layer_kinds(cfg))


def train_flops_per_token(cfg: dict, seq_len: int, pairs_per_token: float | None = None) -> float:
    """Forward + backward of one token in a causal sequence of seq_len:
    3 x (2 x weights + attention over (seq_len + 1) / 2 keys an attention
    layer)."""
    attn = n_layers(cfg, "full_attention") * roofline.attn_flops_fwd(cfg, 1, (seq_len + 1) / 2)
    return 3.0 * (2.0 * matmul_params(cfg, pairs_per_token) + attn)


# (operations, bytes) of ONE attention layer's causal attention at 32 query
# heads on 8 key heads of 64, forward and backward: the dense decoder's count
# reads the same keys of this configuration (hidden / heads is the head)
flash_fwd, flash_bwd = roofline.flash_fwd, roofline.flash_bwd


def expert_gmm(cfg: dict, routed_rows: float, itemsize: int = 2) -> tuple[float, float]:
    """The grouped products of ONE expert layer, forward and backward, over
    the token-expert pairs routed to the held experts: three products
    forward, dx and dw of each backward (the gate and up products made again
    in the backward pass are not counted). The held experts' weights read
    once a product forward and once backward, their gradients written once."""
    d = LW.dims(cfg)
    flops = 9 * 2.0 * routed_rows * d["h"] * d["expert"]
    weights = d["held"] * expert_weights(d)
    rows = routed_rows * (d["h"] + d["expert"])
    return flops, float(3 * weights * itemsize + 6 * rows * itemsize)
