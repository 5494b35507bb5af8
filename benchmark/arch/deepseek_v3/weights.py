"""Seeded weights of a DeepSeek-V3-family configuration: the leaves in the
order the program's model lists them, and the program's model with the
seed's values in place of its own. A leaf is `benchmark.weights.make_leaf`'s:
mean + std * normal(fold_in(key(seed), index)), rounded to the
configuration's type; the reference makes the same leaves from the same seed
and takes nothing from the program.

The family's leaves are Kimi-Linear's latent-attention, dense and expert
leaves under the same names and in the same order, so the specs are made by
that architecture's `leaf_specs` over this configuration read in its key
names (`kimi_view`): no second table of shapes.
"""
from __future__ import annotations

from benchmark import weights as W
# the leaves that take no update from the optimizer are the Kimi-Linear
# configuration's, for its reasons: the router's weights (on a share their
# gradient is the held experts' alone and teaches it to route away from them)
# and the correction bias (moved by the balancing rule, never by a gradient)
from benchmark.arch.kimi_linear import weights as KW
from benchmark.arch.kimi_linear.weights import FROZEN, frozen  # noqa: F401


def kimi_view(cfg: dict) -> dict:
    """The configuration under the key names `arch/kimi_linear` reads: every
    layer latent attention (no linear-attention layer), layer 1 of its
    numbering the published layer 0."""
    if cfg.get("q_lora_rank") is not None or cfg.get("n_group", 1) > 1:
        raise NotImplementedError("q_lora_rank / n_group > 1: not built (ROADMAP B-M4, B-M3)")
    n = cfg["num_hidden_layers"]
    return {**cfg,
            "linear_attn_config": {"kda_layers": [], "full_attn_layers": list(range(1, n + 1)),
                                   "num_heads": 0, "head_dim": 0, "short_conv_kernel_size": 0},
            "num_experts": cfg["n_routed_experts"],
            "num_experts_per_token": cfg["num_experts_per_tok"],
            "num_shared_experts": cfg["n_shared_experts"],
            "moe_renormalize": cfg["norm_topk_prob"]}


def dims(cfg: dict) -> dict:
    """`arch/kimi_linear`'s sizes (h, heads, nope, rope, vd, latent, dense,
    expert, held, experts, first, top_k, shared, vocab) and the rotation's base."""
    return {**KW.dims(kimi_view(cfg)), "theta": float(cfg["rope_theta"])}


def layer_kinds(cfg: dict) -> list[tuple[str, str]]:
    """[("mla", "dense" | "moe")] of the layers held here: published layers
    0 .. num_hidden_layers - 1, dense under `first_k_dense_replace`."""
    return KW.layer_kinds(kimi_view(cfg))


def leaf_specs(cfg: dict) -> list[tuple[str, tuple, float, float]]:
    """[(name, shape, mean, std)] as `DeepseekV3ForCausalLM.parameters()`
    lists them: embedding, each layer's latent attention then feed-forward,
    the final norm, the untied head."""
    return KW.leaf_specs(kimi_view(cfg))


def program_config(cfg: dict):
    """The program's config object, filled by key from the configuration's
    file (keys the program does not know stay in the file)."""
    import dataclasses

    from paddle_tpu.models.deepseek_v3 import DeepseekV3Config

    known = {f.name for f in dataclasses.fields(DeepseekV3Config)}
    return DeepseekV3Config(**{k: v for k, v in cfg.items() if k in known})


def seeded_model(cfg: dict, seed: int):
    """The program's model as a user builds it, in the configuration's type,
    with the seed's weights in place of its own (one jitted call that takes
    over the memory of the model's initial values)."""
    from paddle_tpu.models.deepseek_v3 import DeepseekV3ForCausalLM

    model = DeepseekV3ForCausalLM(program_config(cfg))
    model.to(dtype=cfg["dtype"])
    params = model.parameters()
    specs = leaf_specs(cfg)
    if [tuple(p.shape) for p in params] != [s[1] for s in specs]:
        raise ValueError("the program's parameters are not the leaves weights.py makes")
    made = W.make_all(seed, specs, cfg["dtype"], donate=[p._value for p in params])
    for p, v, (name, *_), still in zip(params, made, specs, frozen(specs)):
        bias = name.endswith("router_bias")
        p._set_value(v.astype("float32") if bias else v)
        # frozen the way a user freezes a parameter: the step keeps no
        # moments for it and passes it through (the bias it moves itself)
        p.stop_gradient = still
    return model
