"""Seeded weights of an LFM2-MoE configuration: the leaves in the order the
program's model lists them, and the program's model with the seed's values
in place of its own. A leaf is `benchmark.weights.make_leaf`'s: mean + std *
normal(fold_in(key(seed), index)), rounded to the configuration's type; the
reference makes the same leaves from the same seed and takes nothing from
the program. Embedding and head are ONE leaf, `embed` [vocab, hidden]: the
family ties them.
"""
from __future__ import annotations

from benchmark import weights as W
# the leaves that take no update from the optimizer are the Kimi-Linear
# configuration's, for its reasons: the router's weights (on a share their
# gradient is the held experts' alone and teaches it to route away from them)
# and the router's bias (moved by the balancing rule, never by a gradient)
from benchmark.arch.kimi_linear.weights import FROZEN, frozen  # noqa: F401

STD = 0.02          # every projection, the embedding and the router
CONV_STD = 0.3      # the three taps of a short convolution (`assumed`)

ORDER = {"conv": ("operator_norm", "w_in", "conv", "w_out"),
         "full_attention": ("operator_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo"),
         "dense": ("ffn_norm", "w1", "w3", "w2"),
         "moe": ("ffn_norm", "w_gate", "w_up", "w_down", "router", "router_bias")}


def dims(cfg: dict) -> dict:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"h": h, "heads": heads, "kv_heads": cfg["num_key_value_heads"],
            "hd": h // heads, "taps": cfg["conv_L_cache"],
            "theta": float(cfg["rope_parameters"]["rope_theta"]),
            "dense": cfg["intermediate_size"], "expert": cfg["moe_intermediate_size"],
            "held": cfg["num_experts"],
            "experts": cfg.get("router_experts") or cfg["num_experts"],
            "first": cfg.get("first_held_expert", 0), "top_k": cfg["num_experts_per_tok"],
            "vocab": cfg["vocab_size"]}


def layer_kinds(cfg: dict) -> list[tuple[str, str]]:
    """[(mixer, feed-forward)] of the layers held here: published layers
    `first_layer` .. `first_layer + num_hidden_layers - 1` (0-based), dense
    where the PUBLISHED index is under `num_dense_layers`."""
    first = cfg.get("first_layer", 0)
    return [(cfg["layer_types"][i], "dense" if i < cfg["num_dense_layers"] else "moe")
            for i in range(first, first + cfg["num_hidden_layers"])]


def _shapes(cfg: dict) -> dict:
    d = dims(cfg)
    h, q, kv = d["h"], d["heads"] * d["hd"], d["kv_heads"] * d["hd"]
    mat = lambda *s: (s, 0.0, STD)                      # noqa: E731
    one = lambda n: ((n,), 1.0, 0.0)                    # noqa: E731
    return {
        "conv": {"operator_norm": one(h), "w_in": mat(h, 3 * h),
                 "conv": ((d["taps"], h), 0.0, CONV_STD), "w_out": mat(h, h)},
        "full_attention": {"operator_norm": one(h), "wq": mat(h, q), "wk": mat(h, kv),
                           "wv": mat(h, kv), "q_norm": one(d["hd"]),
                           "k_norm": one(d["hd"]), "wo": mat(q, h)},
        "dense": {"ffn_norm": one(h), "w1": mat(h, d["dense"]), "w3": mat(h, d["dense"]),
                  "w2": mat(d["dense"], h)},
        "moe": {"ffn_norm": one(h), "w_gate": mat(d["held"], h, d["expert"]),
                "w_up": mat(d["held"], h, d["expert"]),
                "w_down": mat(d["held"], d["expert"], h),
                "router": mat(h, d["experts"]),
                "router_bias": ((d["experts"],), 0.0, 0.0)},
    }


def leaf_specs(cfg: dict) -> list[tuple[str, tuple, float, float]]:
    """[(name, shape, mean, std)] as `Lfm2MoeForCausalLM.parameters()` lists
    them: the tied embedding, each layer's mixer then feed-forward, the
    final norm. No head: it is the embedding."""
    shapes, d = _shapes(cfg), dims(cfg)
    specs = [("embed", (d["vocab"], d["h"]), 0.0, STD)]
    for i, kinds in enumerate(layer_kinds(cfg)):
        for kind in kinds:
            specs += [(f"layers.{i}.{kind}.{leaf}", *shapes[kind][leaf])
                      for leaf in ORDER[kind]]
    return specs + [("final_norm", (d["h"],), 1.0, 0.0)]


def program_config(cfg: dict):
    """The program's config object, filled by key from the configuration's
    file (keys the program does not know stay in the file)."""
    import dataclasses

    from paddle_tpu.models.lfm2_moe import Lfm2MoeConfig

    known = {f.name for f in dataclasses.fields(Lfm2MoeConfig)}
    return Lfm2MoeConfig(**{k: v for k, v in cfg.items() if k in known})


def seeded_model(cfg: dict, seed: int):
    """The program's model as a user builds it, in the configuration's type,
    with the seed's weights in place of its own (one jitted call that takes
    over the memory of the model's initial values)."""
    from paddle_tpu.models.lfm2_moe import Lfm2MoeForCausalLM

    model = Lfm2MoeForCausalLM(program_config(cfg))
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
