"""Operations and bytes that the mathematics of a DeepSeek-V3-family
configuration needs, from shapes alone: `arch/kimi_linear/roofline.py`'s
counts of latent attention (scores over the query/key width 192, weighted
values over the value width 128, the causal half), of the dense and shared
feed-forwards, of the router and of the held experts, over this
configuration read in that architecture's key names (`weights.kimi_view`: no
linear-attention layer, so those terms are zero). A product of [m, k] x
[k, n] is 2*m*k*n operations; recomputation is never counted; the rotation
(six operations a rotated channel beside 4,096 of its projection) is not
counted; the held experts are counted at the share of the token-expert pairs
a uniform router sends them (top_k * held / experts a token) or, where the
program counted them, at the pairs it counted. Bytes count every operand and
result once."""
from __future__ import annotations

from benchmark.arch.deepseek_v3 import weights as DW
from benchmark.arch.kimi_linear import roofline as KR


def _over_view(fn):
    def counted(cfg: dict, *args, **kw):
        return fn(DW.kimi_view(cfg), *args, **kw)
    counted.__name__, counted.__doc__ = fn.__name__, fn.__doc__
    return counted


matmul_params = _over_view(KR.matmul_params)
train_flops_per_token = _over_view(KR.train_flops_per_token)
n_layers = _over_view(KR.n_layers)
# (operations, bytes) of ONE layer's causal attention at 16 heads of 192/128
flash_fwd = _over_view(KR.mla_flash_fwd)
flash_bwd = _over_view(KR.mla_flash_bwd)
# the nine grouped products of ONE expert layer over the pairs routed here
expert_gmm = _over_view(KR.expert_gmm)


def parameters(cfg: dict) -> dict:
    """Parameters by part, counted from the leaves `weights.leaf_specs`
    lists: one layer's latent attention, a dense layer, an expert layer
    without its experts (attention, router, shared experts, norms), one
    expert, everything held here."""
    size = {}
    for name, shape, *_ in DW.leaf_specs(cfg):
        n = 1
        for s in shape:
            n *= s
        size[name] = n
    kinds = DW.layer_kinds(cfg)

    def layer(i, *parts):
        return sum(v for k, v in size.items()
                   if k.startswith(f"layers.{i}.") and k.split(".")[2] in parts)

    dense = next((i for i, (_, ff) in enumerate(kinds) if ff == "dense"), None)
    moe = next((i for i, (_, ff) in enumerate(kinds) if ff == "moe"), None)
    held = DW.dims(cfg)["held"]
    out = {"attention": layer(0, "mla"), "total": sum(size.values())}
    if dense is not None:
        out["dense_layer"] = layer(dense, "mla", "dense")
    if moe is not None:
        experts = sum(size[f"layers.{moe}.moe.{w}"] for w in ("w_gate", "w_up", "w_down"))
        out["expert"] = experts // held
        out["expert_layer_outside_experts"] = layer(moe, "mla", "moe") - experts
    return out
