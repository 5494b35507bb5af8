"""Seeded weights of a Kimi-Linear configuration: the leaves in the order the
program's model lists them, and the program's model with the seed's values
in place of its own. A leaf is `benchmark.weights.make_leaf`'s: mean + std *
normal(fold_in(key(seed), index)), rounded to the configuration's type; the
reference makes the same leaves from the same seed and takes nothing from
the program.
"""
from __future__ import annotations

from benchmark import weights as W

STD = 0.02          # every projection (the family's initializer_range)
CONV_STD = 0.3      # the four taps of a short convolution (`assumed`)
DT_BIAS = -2.5      # softplus(-2.5) = 0.079: a channel's log-decay a token
A_LOG = 0.0
ROUTER_STD = 0.02   # the router's weights, as every projection
# Leaves that take no update from the optimizer. The router's weights: with
# a share of the experts its gradient would be the held experts' alone and
# teaches it to route away from them (19,938 -> 26 pairs a step in 16 steps,
# PERF.md section 6, PR 27); in the deployment the other 31 chips' experts
# answer too. The correction bias: moved by the balancing rule, never by a
# gradient; it starts at zero and is float32 whatever the configuration's type.
FROZEN = ("router", "router_bias")

KDA = ("input_norm", "wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "w_fa",
       "w_fb", "a_log", "dt_bias", "w_beta", "w_ga", "w_gb", "o_norm", "wo")
MLA = ("input_norm", "wq", "w_kva", "kv_norm", "w_kvb", "wo")
DENSE = ("post_norm", "w_gate", "w_up", "w_down")
MOE = ("post_norm", "w_gate", "w_up", "w_down", "shared_gate", "shared_up",
       "shared_down", "router", "router_bias")


def dims(cfg: dict) -> dict:
    la = cfg["linear_attn_config"]
    return {"h": cfg["hidden_size"], "kda_heads": la["num_heads"], "kda_hd": la["head_dim"],
            "inner": la["num_heads"] * la["head_dim"], "taps": la["short_conv_kernel_size"],
            "rank": cfg.get("low_rank_gate_dim") or la["head_dim"],
            "heads": cfg["num_attention_heads"], "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "vd": cfg["v_head_dim"],
            "latent": cfg["kv_lora_rank"], "dense": cfg["intermediate_size"],
            "expert": cfg["moe_intermediate_size"], "held": cfg["num_experts"],
            "experts": cfg.get("router_experts") or cfg["num_experts"],
            "first": cfg.get("first_held_expert", 0), "top_k": cfg["num_experts_per_token"],
            "shared": cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
            "vocab": cfg["vocab_size"]}


def layer_kinds(cfg: dict) -> list[tuple[str, str]]:
    """[(mixer, feed-forward)] of layers 1..num_hidden_layers."""
    kda = cfg["linear_attn_config"]["kda_layers"]
    return [("kda" if i in kda else "mla",
             "dense" if i <= cfg["first_k_dense_replace"] else "moe")
            for i in range(1, cfg["num_hidden_layers"] + 1)]


def _shapes(cfg: dict) -> dict:
    d = dims(cfg)
    h, inner, rank = d["h"], d["inner"], d["rank"]
    mat = lambda *s: (s, 0.0, STD)                      # noqa: E731
    one = lambda n, v=1.0: ((n,), v, 0.0)               # noqa: E731
    conv = ((d["taps"], inner), 0.0, CONV_STD)
    return {
        "kda": {"input_norm": one(h), "wq": mat(h, inner), "wk": mat(h, inner),
                "wv": mat(h, inner), "conv_q": conv, "conv_k": conv, "conv_v": conv,
                "w_fa": mat(h, rank), "w_fb": mat(rank, inner),
                "a_log": one(d["kda_heads"], A_LOG), "dt_bias": one(inner, DT_BIAS),
                "w_beta": mat(h, d["kda_heads"]), "w_ga": mat(h, rank),
                "w_gb": mat(rank, inner), "o_norm": one(d["kda_hd"]), "wo": mat(inner, h)},
        "mla": {"input_norm": one(h), "wq": mat(h, d["heads"] * (d["nope"] + d["rope"])),
                "w_kva": mat(h, d["latent"] + d["rope"]), "kv_norm": one(d["latent"]),
                "w_kvb": mat(d["latent"], d["heads"] * (d["nope"] + d["vd"])),
                "wo": mat(d["heads"] * d["vd"], h)},
        "dense": {"post_norm": one(h), "w_gate": mat(h, d["dense"]),
                  "w_up": mat(h, d["dense"]), "w_down": mat(d["dense"], h)},
        "moe": {"post_norm": one(h), "w_gate": mat(d["held"], h, d["expert"]),
                "w_up": mat(d["held"], h, d["expert"]),
                "w_down": mat(d["held"], d["expert"], h),
                "shared_gate": mat(h, d["shared"]), "shared_up": mat(h, d["shared"]),
                "shared_down": mat(d["shared"], h),
                "router": ((h, d["experts"]), 0.0, ROUTER_STD),
                "router_bias": ((d["experts"],), 0.0, 0.0)},
    }


ORDER = {"kda": KDA, "mla": MLA, "dense": DENSE, "moe": MOE}


def leaf_specs(cfg: dict) -> list[tuple[str, tuple, float, float]]:
    """[(name, shape, mean, std)] as `KimiLinearForCausalLM.parameters()`
    lists them: embedding, each layer's mixer then feed-forward, the final
    norm, the untied head."""
    shapes, d = _shapes(cfg), dims(cfg)
    specs = [("embed", (d["vocab"], d["h"]), 0.0, STD)]
    for i, kinds in enumerate(layer_kinds(cfg)):
        for kind in kinds:
            specs += [(f"layers.{i}.{kind}.{leaf}", *shapes[kind][leaf])
                      for leaf in ORDER[kind]]
    return specs + [("final_norm", (d["h"],), 1.0, 0.0), ("head", (d["h"], d["vocab"]), 0.0, STD)]


def frozen(specs) -> list[bool]:
    return [name.rsplit(".", 1)[-1] in FROZEN for name, *_ in specs]


def program_config(cfg: dict):
    """The program's config object, filled by key from the configuration's
    file (keys the program does not know stay in the file)."""
    import dataclasses

    from paddle_tpu.models.kimi_linear import KimiLinearConfig

    known = {f.name for f in dataclasses.fields(KimiLinearConfig)}
    return KimiLinearConfig(**{k: v for k, v in cfg.items() if k in known})


def seeded_model(cfg: dict, seed: int):
    """The program's model as a user builds it, in the configuration's type,
    with the seed's weights in place of its own (one jitted call that takes
    over the memory of the model's initial values)."""
    from paddle_tpu.models.kimi_linear import KimiLinearForCausalLM

    model = KimiLinearForCausalLM(program_config(cfg))
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
