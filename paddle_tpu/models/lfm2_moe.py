"""LFM2-MoE: a decoder whose token mixer is a gated short convolution in
three layers of four and grouped-query attention in the fourth.

Every layer is a pre-norm residual block (RMSNorm) of a token mixer and a
feed-forward part, as published (`modeling_lfm2_moe`):

  conv            [B | C | u] = y W_in; z_t = sum_j w[j] (B * u)[t - 2 + j]
                  (depthwise, causal, `conv_L_cache` taps, no bias, no
                  activation); out = (C * z) W_out
  full_attention  GQA at head `hidden / heads`; RMSNorm over each head's
                  channels of q and of k (one weight vector each) BEFORE RoPE
                  (all channels, rotate-half); causal softmax through the
                  flash kernels; W_o

The feed-forward part is a dense SwiGLU in the first `num_dense_layers`
layers and sigmoid-routed SwiGLU experts WITHOUT a shared expert after them:
the `num_experts_per_tok` experts with the largest `s + b`, weighted
`s_e / (sum of the chosen s + 1e-6)` (`HeldExpertsMoE`; `num_experts` of the
`router_experts` the layer has are held here). Embedding and head are ONE
leaf (`tie_word_embeddings`): gathered from at the bottom, multiplied by at
the top, its gradient the sum of both uses.

`layer_types` is the published list, whole. A model that holds a cut of the
depth says where it starts (`first_layer`, 0-based as the list is) and how
many layers it holds (`num_hidden_layers`); a held layer is dense where its
PUBLISHED index is under `num_dense_layers`. Matrices are [in, out]. The
model takes `(input_ids, labels)` and returns the mean cross-entropy through
the fused head, or the logits without labels: `CompiledTrainStep` drives it
as it drives `llama.py` and `kimi_linear.py`. Every mixer and every
feed-forward part keeps only its input between the forward and the backward
pass and is computed again there (`_Block._fn`).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.core.tensor import apply_op
from paddle_tpu.incubate.distributed.models.moe import HeldExpertsMoE
from paddle_tpu.models.kimi_linear import _Block, causal_conv, rms_norm
from paddle_tpu.models.llama import _rope_tables
from paddle_tpu.nn import initializer as I
from paddle_tpu.observability import scopes

__all__ = ["Lfm2MoeConfig", "Lfm2MoeForCausalLM", "Lfm2MoeModel",
           "lfm2_moe_tiny_config"]

RENORM_EPS = 1e-6       # added to the sum the chosen scores are divided by


def _default_layer_types():
    return ["full_attention" if i in (2, 6, 10, 14, 18, 22, 26, 30, 34, 38)
            else "conv" for i in range(40)]


@dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40         # the layers HELD here
    first_layer: int = 0                # published index of the first of them
    layer_types: list = field(default_factory=_default_layer_types)
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    conv_bias: bool = False
    rope_parameters: dict = field(default_factory=lambda: {
        "rope_theta": 1000000, "rope_type": "default"})
    num_experts: int = 64               # the experts HELD here
    router_experts: int = 0             # the layer's experts; 0: num_experts
    first_held_expert: int = 0
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    # the step of the balancing rule that moves the routers' bias after
    # every training step (0: the bias stays where it is)
    router_bias_update_rate: float = 0.0
    norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    dtype: str = "float32"

    def layer_kinds(self) -> list[tuple[str, str]]:
        """[(mixer, feed-forward)] of the layers held here."""
        held = range(self.first_layer, self.first_layer + self.num_hidden_layers)
        return [(self.layer_types[i],
                 "dense" if i < self.num_dense_layers else "moe") for i in held]


def lfm2_moe_tiny_config(**overrides) -> Lfm2MoeConfig:
    """Published layers 1-5 (conv + dense, attention + experts, conv +
    experts x 3) at toy widths, head 64 as published: the tests' model."""
    cfg = dict(vocab_size=128, hidden_size=256, intermediate_size=320,
               moe_intermediate_size=32, num_hidden_layers=5, first_layer=1,
               num_attention_heads=4, num_key_value_heads=1, num_experts=4,
               router_experts=16, num_experts_per_tok=4)
    cfg.update(overrides)
    return Lfm2MoeConfig(**cfg)


class ShortConv(_Block):
    """x + W_out (C * conv(B * u)): the gated short convolution."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        if config.conv_bias:
            raise NotImplementedError("conv_bias: the published models have none")
        h = config.hidden_size
        self.eps = config.norm_eps
        self.operator_norm = self._vec(h)
        self.w_in = self._mat(h, 3 * h)
        self.conv = self._mat(config.conv_L_cache, h, init=I.Normal(0.0, 0.3))
        self.w_out = self._mat(h, h)

    def forward(self, x):
        eps = self.eps

        def mix(x, norm, w_in, conv, w_out):
            f32 = jnp.float32
            b, c, u = jnp.split((rms_norm(x, norm, eps) @ w_in).astype(f32), 3, axis=-1)
            z = causal_conv(b * u, conv.astype(f32))
            return x + (c * z).astype(x.dtype) @ w_out

        return apply_op(self._fn(mix), x, self.operator_norm, self.w_in,
                        self.conv, self.w_out, name="short_conv")


class Attention(_Block):
    """x + W_o softmax(rope(norm(q)) rope(norm(k))^T / sqrt(d)) v, grouped."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        h, self.heads = config.hidden_size, config.num_attention_heads
        self.kv_heads, self.hd = config.num_key_value_heads, h // self.heads
        self.theta = float(config.rope_parameters["rope_theta"])
        self.eps = config.norm_eps
        self.operator_norm = self._vec(h)
        self.wq = self._mat(h, self.heads * self.hd)
        self.wk, self.wv = (self._mat(h, self.kv_heads * self.hd) for _ in range(2))
        self.q_norm, self.k_norm = self._vec(self.hd), self._vec(self.hd)
        self.wo = self._mat(self.heads * self.hd, h)

    def forward(self, x):
        heads, kv_heads, hd, eps, theta = (self.heads, self.kv_heads, self.hd,
                                           self.eps, self.theta)

        def qkv(x, norm, wq, wk, wv, qn, kn):
            b, t, _ = x.shape
            y = rms_norm(x, norm, eps)
            cos, sin = (a[None, :, None, :] for a in _rope_tables(hd, t, theta))

            def norm_rope(z, w):
                # float32 from the product to the rotated head: one rounding
                zf = z.astype(jnp.float32)
                zf = zf * jax.lax.rsqrt(jnp.mean(jnp.square(zf), -1, keepdims=True) + eps)
                z1, z2 = jnp.split(zf * w.astype(jnp.float32), 2, axis=-1)
                return jnp.concatenate([z1 * cos - z2 * sin, z2 * cos + z1 * sin],
                                       axis=-1).astype(z.dtype)

            return (norm_rope((y @ wq).reshape(b, t, heads, hd), qn),
                    norm_rope((y @ wk).reshape(b, t, kv_heads, hd), kn),
                    (y @ wv).reshape(b, t, kv_heads, hd))

        q, k, v = apply_op(self._fn(qkv), x, self.operator_norm, self.wq, self.wk,
                           self.wv, self.q_norm, self.k_norm, name="gqa_qkv",
                           n_outputs=3)
        o = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                           training=self.training)
        return apply_op(lambda x, o, wo: x + o.reshape(*x.shape[:2], -1) @ wo,
                        x, o, self.wo, name="gqa_out")


class DenseFFN(_Block):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        self.eps = config.norm_eps
        self.ffn_norm = self._vec(h)
        self.w1, self.w3, self.w2 = self._mat(h, m), self._mat(h, m), self._mat(m, h)

    def forward(self, x):
        eps = self.eps

        def ffn(x, norm, w1, w3, w2):
            y = rms_norm(x, norm, eps)
            return x + (jax.nn.silu(y @ w1) * (y @ w3)) @ w2

        return apply_op(self._fn(ffn), x, self.ffn_norm, self.w1, self.w3,
                        self.w2, name="dense_ffn")


class ExpertFFN(_Block):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.eps = config.norm_eps
        self.ffn_norm = self._vec(config.hidden_size)
        first = config.first_held_expert
        self.moe = HeldExpertsMoE(
            config.hidden_size, config.router_experts or config.num_experts,
            config.moe_intermediate_size, config.num_experts_per_tok,
            held_experts=(first, first + config.num_experts),
            routed_scale=config.routed_scaling_factor,
            renormalize=config.norm_topk_prob, renorm_eps=RENORM_EPS,
            num_shared=0,
            bias_update_rate=(config.router_bias_update_rate
                              if config.use_expert_bias else 0.0),
            recompute=True)

    def forward(self, x):
        y = apply_op(lambda x, w: rms_norm(x, w, self.eps), x, self.ffn_norm,
                     name="rms_norm")
        return x + self.moe(y)


class Lfm2MoeLayer(nn.Layer):
    def __init__(self, config: Lfm2MoeConfig, mixer: str, ffn: str):
        super().__init__()
        self.scope = "attn" if mixer == "full_attention" else "conv_mixer"
        self.mixer = Attention(config) if mixer == "full_attention" else ShortConv(config)
        self.mlp = DenseFFN(config) if ffn == "dense" else ExpertFFN(config)

    def forward(self, x):
        with scopes.scope(self.scope):
            x = self.mixer(x)
        with scopes.scope("mlp"):
            return self.mlp(x)


class Lfm2MoeModel(nn.Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList([Lfm2MoeLayer(config, *kinds)
                                    for kinds in config.layer_kinds()])
        self.embedding_norm = nn.RMSNorm(config.hidden_size, epsilon=config.norm_eps)

    def forward(self, input_ids):
        with scopes.scope("embed"):
            x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        with scopes.scope("head"):
            return self.embedding_norm(x)


class Lfm2MoeForCausalLM(nn.Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        if not config.tie_word_embeddings:
            raise NotImplementedError("an untied head: the family ties")
        self.config = config
        self.model = Lfm2MoeModel(config)

    def forward(self, input_ids, labels=None):
        from paddle_tpu.core.flags import flag

        hidden = self.model(input_ids)
        with scopes.scope("head"):
            # the ONE leaf a second time, as [hidden, vocab]
            head = apply_op(jnp.transpose, self.model.embed_tokens.weight, name="tied_head")
            if labels is None:
                return apply_op(jnp.matmul, hidden, head, name="head")
            if flag("use_fused_head_loss"):
                return F.fused_linear_cross_entropy(hidden, head, labels,
                                                    reduction="mean")
            logits = apply_op(jnp.matmul, hidden, head, name="head")
            return F.cross_entropy(logits.reshape([-1, logits.shape[-1]]),
                                   labels.reshape([-1]))
