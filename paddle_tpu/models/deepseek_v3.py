"""DeepSeek-V3 family (`model_type` `deepseek_v3`: DeepSeek-V3, Moonlight):
a decoder in which EVERY layer's token mixer is multi-head latent attention
with a decoupled rotary part.

Every layer is a pre-norm residual block (RMSNorm) of latent attention and a
feed-forward part. Keys and values are expanded from one `kv_lora_rank`-wide
latent; beside the `qk_nope_head_dim` channels a head's query has
`qk_rope_head_dim` more, and ONE key of that width is shared by all heads:
these are turned by position (`rope_theta`, the pair (2i, 2i + 1) together)
before the key is broadcast, so the shared key's gradient is a sum over
heads (`kimi_linear.LatentAttention`, the one latent attention of both
families; there it never rotates). The feed-forward part is a dense SwiGLU
in the first `first_k_dense_replace` layers and sigmoid-routed SwiGLU
experts after them: the `num_experts_per_tok` experts with the largest
`s + b` (`topk_method` `noaux_tc`), weighted `routed_scaling_factor * s_e /
(sum of the chosen s + 1e-20)`, beside `n_shared_experts` shared experts
(one SwiGLU of width `n_shared_experts * moe_intermediate_size`) that every
token passes (`HeldExpertsMoE`; `n_routed_experts` of the `router_experts`
the layer has are held here). docs/moe.md has the router and the share.

A model that holds a cut of the depth holds the family's layers 0 ..
`num_hidden_layers` - 1 (0-based, as the family numbers them): dense under
`first_k_dense_replace`. Matrices are [in, out]. The model takes
`(input_ids, labels)` and returns the mean cross-entropy through the fused
head, or the logits without labels: `CompiledTrainStep` drives it as it
drives `llama.py`, `kimi_linear.py` and `lfm2_moe.py`. Every mixer and every
feed-forward part keeps only its input between the forward and the backward
pass and is computed again there (`recompute`).

Not built, and refused by name: the low-rank query (`q_lora_rank`, ROADMAP
B-M4) and group-limited routing (`n_group` > 1, ROADMAP B-M3).
"""
from __future__ import annotations

from dataclasses import dataclass

import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.core.tensor import apply_op
from paddle_tpu.incubate.distributed.models.moe import HeldExpertsMoE
from paddle_tpu.models.kimi_linear import (DenseMLP, LatentAttention, _Block,
                                           rms_norm)
from paddle_tpu.observability import scopes

__all__ = ["DeepseekV3Config", "DeepseekV3ForCausalLM", "DeepseekV3Model",
           "deepseek_v3_tiny_config"]

RENORM_EPS = 1e-20      # added to the sum the chosen scores are divided by


@dataclass
class DeepseekV3Config:
    """Moonlight-16B-A3B's published values."""
    vocab_size: int = 163840
    hidden_size: int = 2048
    intermediate_size: int = 11264
    moe_intermediate_size: int = 1408
    num_hidden_layers: int = 27         # the layers HELD here, from layer 0
    first_k_dense_replace: int = 1
    num_attention_heads: int = 16
    q_lora_rank: int | None = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 50000.0
    n_routed_experts: int = 64          # the experts HELD here
    router_experts: int = 0             # the layer's experts; 0: n_routed_experts
    first_held_expert: int = 0
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.446
    # the step of the balancing rule that moves the routers' correction
    # bias after every training step (0: the bias stays where it is)
    router_bias_update_rate: float = 0.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    dtype: str = "float32"
    recompute: bool = True

    def layer_kinds(self) -> list[str]:
        """The feed-forward part of each layer held here."""
        return ["dense" if i < self.first_k_dense_replace else "moe"
                for i in range(self.num_hidden_layers)]


def deepseek_v3_tiny_config(**overrides) -> DeepseekV3Config:
    """Published layers 0-2 (latent attention + dense, + experts x 2) at toy
    widths, 6 of 16 experts a token, 4 held, two shared: the tests' model."""
    cfg = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, num_hidden_layers=3,
               num_attention_heads=2, kv_lora_rank=32, qk_nope_head_dim=32,
               qk_rope_head_dim=16, v_head_dim=32, n_routed_experts=4,
               router_experts=16)
    cfg.update(overrides)
    return DeepseekV3Config(**cfg)


class ExpertMLP(_Block):
    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        self.eps = config.rms_norm_eps
        self.post_norm = self._vec(config.hidden_size)
        first = config.first_held_expert
        self.moe = HeldExpertsMoE(
            config.hidden_size, config.router_experts or config.n_routed_experts,
            config.moe_intermediate_size, config.num_experts_per_tok,
            held_experts=(first, first + config.n_routed_experts),
            routed_scale=config.routed_scaling_factor,
            renormalize=config.norm_topk_prob, renorm_eps=RENORM_EPS,
            num_shared=config.n_shared_experts,
            bias_update_rate=config.router_bias_update_rate,
            recompute=config.recompute)

    def forward(self, x):
        y = apply_op(lambda x, w: rms_norm(x, w, self.eps), x, self.post_norm,
                     name="rms_norm")
        return x + self.moe(y)


class DeepseekV3Layer(nn.Layer):
    def __init__(self, config: DeepseekV3Config, ffn: str):
        super().__init__()
        # latent attention in EVERY layer: six layers' q, k and v at 3 x 8192
        # are 2.8 GiB the chip does not have beside 9.4 GB of state
        self.mixer = LatentAttention(config, config.rope_theta, keep_qkv=False)
        self.mlp = DenseMLP(config) if ffn == "dense" else ExpertMLP(config)

    def forward(self, x):
        with scopes.scope("attn"):
            x = self.mixer(x)
        with scopes.scope("mlp"):
            return self.mlp(x)


class DeepseekV3Model(nn.Layer):
    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        if config.q_lora_rank is not None:
            raise NotImplementedError(
                "q_lora_rank: the low-rank query is not built (ROADMAP B-M4)")
        if config.n_group > 1 or config.topk_group > 1:
            raise NotImplementedError(
                "n_group > 1: group-limited routing is not built (ROADMAP B-M3)")
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList([DeepseekV3Layer(config, ffn)
                                    for ffn in config.layer_kinds()])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        with scopes.scope("embed"):
            x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        with scopes.scope("head"):
            return self.norm(x)


class DeepseekV3ForCausalLM(nn.Layer):
    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        if config.tie_word_embeddings:
            raise NotImplementedError("a tied head: the family's models have none")
        self.config = config
        self.model = DeepseekV3Model(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)

    def forward(self, input_ids, labels=None):
        from paddle_tpu.core.flags import flag

        hidden = self.model(input_ids)
        with scopes.scope("head"):
            if labels is None:
                return self.lm_head(hidden)
            if flag("use_fused_head_loss"):
                return F.fused_linear_cross_entropy(
                    hidden, self.lm_head.weight, labels, reduction="mean")
            logits = self.lm_head(hidden)
            return F.cross_entropy(logits.reshape([-1, logits.shape[-1]]),
                                   labels.reshape([-1]))
