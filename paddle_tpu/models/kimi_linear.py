"""Kimi-Linear: a decoder with three kinds of layer in one stack.

Every layer is a pre-norm residual block (RMSNorm) of a token mixer and a
feed-forward part. The mixer is Kimi Delta Attention (KDA: gated delta-rule
linear attention, `ops/pallas/kda.py`) in three layers of four and
multi-head latent attention WITHOUT positions (NoPE MLA: keys and values
expanded from one 512-wide latent, query/key width 192 beside value width
128, through the flash kernels) in the fourth (`LatentAttention`, which
`deepseek_v3.py` shares and there rotates). The feed-forward part is a
dense SwiGLU MLP in the first `first_k_dense_replace` layers and sigmoid-
routed SwiGLU experts with a shared expert after them
(`incubate.distributed.models.moe.HeldExpertsMoE`; `num_experts` of the
`router_experts` the layer has are held here). docs/linear_attention.md has
the equations, docs/moe.md the router and the held experts.

Layers are numbered from 1 as the published config numbers them
(`linear_attn_config["kda_layers"]`, `["full_attn_layers"]`). Matrices are
[in, out]. The model takes `(input_ids, labels)` and returns the mean
cross-entropy through the fused head (`use_fused_head_loss`), or the logits
without labels: `CompiledTrainStep` drives it as it drives `llama.py`.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.core.tensor import Tensor, apply_op
from paddle_tpu.incubate.distributed.models.moe import HeldExpertsMoE
from paddle_tpu.nn import initializer as I
from paddle_tpu.observability import scopes

__all__ = ["KimiLinearConfig", "KimiLinearForCausalLM", "KimiLinearModel",
           "kimi_linear_tiny_config"]


def _default_linear_attn():
    return {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                           21, 22, 23, 25, 26],
            "num_heads": 32, "head_dim": 128, "short_conv_kernel_size": 4}


@dataclass
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 27
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64          # carried; rotated unless mla_use_nope
    v_head_dim: int = 128
    mla_use_nope: bool = True           # the published models never rotate
    rope_theta: float = 10000.0
    linear_attn_config: dict = field(default_factory=_default_linear_attn)
    low_rank_gate_dim: int = 0          # 0: the linear-attention head_dim
    first_k_dense_replace: int = 1
    num_experts: int = 256              # the experts HELD here
    router_experts: int = 0             # the layer's experts; 0: num_experts
    first_held_expert: int = 0
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    moe_renormalize: bool = True
    # the step of the balancing rule that moves the routers' correction
    # bias after every training step (0: the bias stays where it is)
    router_bias_update_rate: float = 0.0
    rms_norm_eps: float = 1e-5
    dtype: str = "float32"
    # every mixer and every feed-forward part keeps only its input between
    # the forward and the backward pass and is computed again there: a KDA
    # layer's q, k, v, decays and gates at 16k tokens are some 2.5 GB (a KDA
    # row also keeps its scan's output, a fortieth of that: KimiDeltaAttention)
    recompute: bool = True


def kimi_linear_tiny_config(**overrides) -> KimiLinearConfig:
    """Five layers, all three kinds (KDA + dense, KDA + MoE x 2, MLA + MoE,
    KDA + MoE), at toy widths: the tests' model."""
    cfg = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, num_hidden_layers=5,
               num_attention_heads=2, kv_lora_rank=32, qk_nope_head_dim=32,
               qk_rope_head_dim=16, v_head_dim=32,
               linear_attn_config={"full_attn_layers": [4], "kda_layers": [1, 2, 3, 5],
                                   "num_heads": 2, "head_dim": 128,
                                   "short_conv_kernel_size": 4},
               low_rank_gate_dim=16, num_experts=4, router_experts=16,
               num_experts_per_token=4)
    cfg.update(overrides)
    return KimiLinearConfig(**cfg)


def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def causal_conv(x, w):
    """Depthwise causal convolution along time: x [B, T, C], w [taps, C];
    y_t = sum_j w[j] x[t - (taps - 1) + j]."""
    taps, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t] * w[j] for j in range(taps))


def _unit(x, scale=1.0):
    xf = x.astype(jnp.float32)
    return (xf * (jax.lax.rsqrt(jnp.sum(jnp.square(xf), -1, keepdims=True) + 1e-6)
                  * scale)).astype(x.dtype)


class _Block(nn.Layer):
    recompute = True

    def _fn(self, fn, policy=None):
        return jax.checkpoint(fn, policy=policy) if self.recompute else fn

    def _mat(self, *shape, init=None):
        return self.create_parameter(list(shape), None,
                                     default_initializer=init or I.Normal(0.0, 0.02))

    def _vec(self, n, value=1.0):
        return self.create_parameter([n], None,
                                     default_initializer=I.Constant(value))


class KimiDeltaAttention(_Block):
    """x + W_o (RMSNorm(KDA(q, k, v, g, beta)) * gate): docs/linear_attention.md.

    With `recompute` a row keeps its input AND the scan's output `o`
    (`kda_chunked` names it `KDA_OUT`): `2 * T * H * V` bytes a row, 64 MiB at
    8192 x 32 x 128 in bfloat16, against the 2.5 GB the row's other
    intermediates would be. The backward makes those again from the input,
    but not `o`: `rms_norm(o) * gate` and `@ wo` need it, and making it again
    is the whole chunked forward, on top of the once that `kda_chunked`'s own
    backward makes the chunks again."""

    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        la = config.linear_attn_config
        h, self.heads, self.hd = config.hidden_size, la["num_heads"], la["head_dim"]
        inner, taps = self.heads * self.hd, la["short_conv_kernel_size"]
        rank = config.low_rank_gate_dim or self.hd
        self.eps, self.recompute = config.rms_norm_eps, config.recompute
        self.input_norm = self._vec(h)
        self.wq, self.wk, self.wv = (self._mat(h, inner) for _ in range(3))
        conv = I.Normal(0.0, 0.3)
        self.conv_q, self.conv_k, self.conv_v = (
            self._mat(taps, inner, init=conv) for _ in range(3))
        self.w_fa, self.w_fb = self._mat(h, rank), self._mat(rank, inner)
        self.a_log = self._vec(self.heads, 0.0)
        self.dt_bias = self._vec(inner, -2.5)
        self.w_beta = self._mat(h, self.heads)
        self.w_ga, self.w_gb = self._mat(h, rank), self._mat(rank, inner)
        self.o_norm = self._vec(self.hd)
        self.wo = self._mat(inner, h)

    def forward(self, x):
        from paddle_tpu.ops.pallas.kda import KDA_OUT, kda_chunked

        heads, hd, eps = self.heads, self.hd, self.eps

        def mix(x, norm, wq, wk, wv, cq, ck, cv, wfa, wfb, a_log, dt_bias,
                wbeta, wga, wgb, onorm, wo):
            b, t, _ = x.shape
            f32 = jnp.float32
            y = rms_norm(x, norm, eps)
            split = lambda z: z.reshape(b, t, heads, hd)      # noqa: E731
            q, k, v = (split(jax.nn.silu(causal_conv(y @ w, c)))
                       for w, c in ((wq, cq), (wk, ck), (wv, cv)))
            # the decay and beta in float32: they are summed and
            # exponentiated over a chunk (ops/pallas/kda.py)
            g = -jnp.exp(a_log.astype(f32))[:, None] * split(jax.nn.softplus(
                ((y @ wfa) @ wfb).astype(f32) + dt_bias.astype(f32)))
            beta = jax.nn.sigmoid((y @ wbeta).astype(f32))
            o = kda_chunked(_unit(q, hd ** -0.5), _unit(k), v, g, beta)
            gate = jax.nn.sigmoid(((y @ wga) @ wgb).astype(f32))
            o = (rms_norm(o, onorm, eps).astype(f32) * split(gate)).astype(x.dtype)
            return x + o.reshape(b, t, heads * hd) @ wo

        def rows(x, *w):
            # a row at a time, each kept as its input and the scan's output:
            # at 2 x 8192 a layer's float32 decays, gates and their
            # transposes are 256 MB apiece, and both rows' at once do not fit
            # beside the state
            one = self._fn(lambda xr, *w: mix(xr[None], *w)[0],
                           jax.checkpoint_policies.save_only_these_names(KDA_OUT))
            return jax.lax.map(lambda xr: one(xr, *w), x)

        return apply_op(rows, x, self.input_norm, self.wq, self.wk, self.wv,
                        self.conv_q, self.conv_k, self.conv_v, self.w_fa,
                        self.w_fb, self.a_log, self.dt_bias, self.w_beta,
                        self.w_ga, self.w_gb, self.o_norm, self.wo,
                        name="kda_attention")


def _kernel_outputs(prim, *_, **__) -> bool:
    """A recomputation policy: keep what a Pallas kernel wrote (flash's output
    and softmax statistics), make everything else again."""
    return prim.name == "pallas_call"


def rotate_pairs(x, theta):
    """x [B, T, ..., 2n]: the pair (2i, 2i + 1) of position t turned by
    t * theta^(-2i / 2n), in float32, the turned pairs laid out [even | odd].
    The order of the channels is a permutation that a query and its key
    share: their product is the interleaved layout's."""
    n = x.shape[-1] // 2
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], n, 2)
    x0, x1 = xf[..., 0], xf[..., 1]
    t = jnp.arange(x.shape[1], dtype=jnp.float32).reshape(1, -1, *(1,) * (x.ndim - 3), 1)
    angle = t * theta ** (-jnp.arange(n, dtype=jnp.float32) / n)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.concatenate([x0 * cos - x1 * sin, x1 * cos + x0 * sin], -1).astype(x.dtype)


class LatentAttention(_Block):
    """Multi-head latent attention: x + W_o softmax(q k^T / sqrt(192)) v with
    k = [k_nope | k_r], k_r one 64-wide key all heads share. `rope_theta`
    turns the 64 channels of q and k_r beside the latent's by position
    (`rotate_pairs`); None carries them as they are (Kimi-Linear under
    `mla_use_nope`). The model that stacks the layer reads both from its own
    configuration; `config` gives the family's published widths
    (`KimiLinearConfig`, `DeepseekV3Config`).

    `keep_qkv` says what a layer keeps for its backward pass beside its input
    (under `recompute`): flash's q, k and v as well as its output and softmax
    statistics (True), or the output and statistics alone, q, k and v built
    again from the input (False: the concatenations, the broadcast and
    flash's head-major copies a second time, for heads x 512 values a token
    less kept). The model that stacks the layers chooses: only it knows how
    many of them share the chip (PERF.md section 6, PR 37, has both costs)."""

    def __init__(self, config, rope_theta: float | None, keep_qkv: bool = True):
        super().__init__()
        self.keep_qkv = keep_qkv
        h, self.heads = config.hidden_size, config.num_attention_heads
        self.nope, self.rope = config.qk_nope_head_dim, config.qk_rope_head_dim
        self.vd, self.rank = config.v_head_dim, config.kv_lora_rank
        self.eps, self.recompute = config.rms_norm_eps, config.recompute
        self.theta = None if rope_theta is None else float(rope_theta)
        self.input_norm = self._vec(h)
        self.wq = self._mat(h, self.heads * (self.nope + self.rope))
        self.w_kva = self._mat(h, self.rank + self.rope)
        self.kv_norm = self._vec(self.rank)
        self.w_kvb = self._mat(self.rank, self.heads * (self.nope + self.vd))
        self.wo = self._mat(self.heads * self.vd, h)

    def forward(self, x):
        from paddle_tpu.tuning.blocks import Resolution, last_resolution, note_derived

        heads, nope, rope, vd, rank, eps, theta = (
            self.heads, self.nope, self.rope, self.vd, self.rank, self.eps, self.theta)

        def qkv(x, norm, wq, wkva, kvnorm, wkvb):
            b, t, _ = x.shape
            y = rms_norm(x, norm, eps)
            q = (y @ wq).reshape(b, t, heads, nope + rope)
            kva = y @ wkva
            kv = (rms_norm(kva[..., :rank], kvnorm, eps) @ wkvb).reshape(
                b, t, heads, nope + vd)
            k_r = kva[:, :, None, rank:]
            if theta is not None:
                with jax.named_scope("mla_rope"):
                    q = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], theta)], -1)
                    k_r = rotate_pairs(k_r, theta)
            k_r = jnp.broadcast_to(k_r, (b, t, heads, rope))
            return q, jnp.concatenate([kv[..., :nope], k_r], -1), kv[..., nope:]

        def attend(*a):
            q, k, v = (Tensor(z) for z in qkv(*a))
            return F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                                  training=self.training)._value

        before = last_resolution("flash_fwd")
        args = (x, self.input_norm, self.wq, self.w_kva, self.kv_norm, self.w_kvb)
        if self.keep_qkv:
            q, k, v = apply_op(self._fn(qkv), *args, name="mla_qkv", n_outputs=3)
            o = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                               training=self.training)
        else:
            o = apply_op(self._fn(attend, _kernel_outputs), *args, name="mla_attention")
        flash = last_resolution("flash_fwd")
        # static a compiled program, as `last_resolution("kda")` is
        note_derived(Resolution("latent_attention",
                                {"heads": heads, "qk_nope": nope, "qk_rope": rope,
                                 "v": vd, "latent": rank},
                                "config", "rope_theta"),
                     rotated_channels=rope if theta is not None else 0,
                     rope_theta=theta,
                     flash_blocks=dict(flash.values) if flash is not before else None)
        return apply_op(lambda x, o, wo: x + o.reshape(*x.shape[:2], -1) @ wo,
                        x, o, self.wo, name="mla_out")


class DenseMLP(_Block):
    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        self.eps, self.recompute = config.rms_norm_eps, config.recompute
        self.post_norm = self._vec(h)
        self.w_gate, self.w_up, self.w_down = self._mat(h, m), self._mat(h, m), self._mat(m, h)

    def forward(self, x):
        eps = self.eps

        def mlp(x, norm, wg, wu, wd):
            y = rms_norm(x, norm, eps)
            return x + (jax.nn.silu(y @ wg) * (y @ wu)) @ wd

        return apply_op(self._fn(mlp), x, self.post_norm, self.w_gate, self.w_up,
                        self.w_down, name="dense_mlp")


class ExpertMLP(_Block):
    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        self.eps = config.rms_norm_eps
        self.post_norm = self._vec(config.hidden_size)
        first = config.first_held_expert
        self.moe = HeldExpertsMoE(
            config.hidden_size, config.router_experts or config.num_experts,
            config.moe_intermediate_size, config.num_experts_per_token,
            held_experts=(first, first + config.num_experts),
            routed_scale=config.routed_scaling_factor,
            renormalize=config.moe_renormalize,
            num_shared=config.num_shared_experts,
            bias_update_rate=config.router_bias_update_rate,
            recompute=config.recompute)

    def forward(self, x):
        y = apply_op(lambda x, w: rms_norm(x, w, self.eps), x, self.post_norm,
                     name="rms_norm")
        return x + self.moe(y)


class KimiLinearLayer(nn.Layer):
    def __init__(self, config: KimiLinearConfig, number: int):
        super().__init__()
        kda = number in config.linear_attn_config["kda_layers"]
        self.mixer = KimiDeltaAttention(config) if kda else LatentAttention(
            config, None if config.mla_use_nope else config.rope_theta)
        self.scope = "kda" if kda else "attn"
        dense = number <= config.first_k_dense_replace
        self.mlp = DenseMLP(config) if dense else ExpertMLP(config)

    def forward(self, x):
        with scopes.scope(self.scope):
            x = self.mixer(x)
        with scopes.scope("mlp"):
            return self.mlp(x)


class KimiLinearModel(nn.Layer):
    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList([KimiLinearLayer(config, i + 1)
                                    for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        with scopes.scope("embed"):
            x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        with scopes.scope("head"):
            return self.norm(x)


class KimiLinearForCausalLM(nn.Layer):
    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        self.config = config
        self.model = KimiLinearModel(config)
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
