"""LLaMA-2 family (flagship model; BASELINE config[3] LLaMA-2-7B TP+PP).

Reference analog: the PaddleNLP LLaMA built from the reference's Fleet mpu
layers (ColumnParallelLinear mp_layers.py:334, RowParallelLinear :541,
VocabParallelEmbedding :47, ParallelCrossEntropy :742) + flash attention
(nn/functional/flash_attention.py:147) + RMSNorm + rotary embeddings.

TPU-native: attention runs the Pallas flash kernel (XLA fallback elsewhere);
TP shardings ride the "mp" mesh axis via the mpu layers' annotations; the
decoder-layer list is PipelineLayer-compatible for the "pp" axis; everything
trains in bfloat16 on the MXU.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import lru_cache

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.core.tensor import Tensor, apply_op
from paddle_tpu.distributed.fleet.meta_parallel import (
    ColumnParallelLinear, ParallelCrossEntropy, RowParallelLinear,
    VocabParallelEmbedding,
)
from paddle_tpu.observability import scopes

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM", "LlamaDecoderLayer",
           "LlamaPretrainingCriterion", "llama_tiny_config", "llama_7b_config"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_parallel_cross_entropy: bool = True
    dtype: str = "float32"
    # size of the ONE hoisted RoPE cos/sin buffer pair (absolute-position
    # indexed by the serving decode path); 0 = max_position_embeddings.
    # Raise it to serve contexts past the training length — any position at
    # or beyond it is a hard error, never a silent clamped-gather
    rope_max_position: int = 0
    # run the homogeneous decoder stack as ONE lax.scan over layer-stacked
    # params (O(1)-in-depth HLO/compile time); the global `scan_layers` flag
    # or a compiled step's scan packing can also turn this on
    scan_layers: bool = False


def llama_7b_config(**overrides) -> LlamaConfig:
    return LlamaConfig(**overrides)


def llama_tiny_config(**overrides) -> LlamaConfig:
    cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
               num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
               max_position_embeddings=128)
    cfg.update(overrides)
    return LlamaConfig(**cfg)


def _rope_tables(head_dim: int, max_pos: int, theta: float):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_pos, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)  # [max_pos, head_dim/2]
    return jnp.cos(freqs), jnp.sin(freqs)


@lru_cache(maxsize=8)
def _shared_rope_tables(head_dim: int, max_pos: int, theta: float):
    """Process-wide RoPE cos/sin tables (fp32), shared by every attention
    layer of the same geometry. Layers no longer register their own buffer
    copies — LlamaModel holds ONE pair and passes it down; standalone layers
    (pipeline LayerDesc stages, GPT-MoE blocks) fall back to this cache.
    ensure_compile_time_eval: the first call may happen under a jit trace,
    and caching staged tracers would poison the cache for later traces."""
    with jax.ensure_compile_time_eval():
        return _rope_tables(head_dim, max_pos, theta)


def _rope_limit(config: LlamaConfig) -> int:
    return int(config.rope_max_position or config.max_position_embeddings)


def _check_positions(position_ids, limit: int):
    """Clear error when a position indexes past the hoisted RoPE tables.
    XLA gather CLAMPS out-of-range indices, so without this check a too-long
    context would silently reuse the last table row. Only HOST (numpy)
    values are checked — device arrays may be tracers, and syncing eager
    values per layer isn't worth it; traced decode steps are covered by
    the serving engine's constructor check (max_seq_len <= rope limit)
    and full-sequence forwards by the seq-len check below."""
    import numpy as _np

    if position_ids is None or not isinstance(position_ids, _np.ndarray):
        return
    mx = int(position_ids.max()) if position_ids.size else 0
    if mx >= limit:
        raise ValueError(
            f"position {mx} is past the hoisted RoPE table "
            f"(rope_max_position={limit}); raise "
            f"LlamaConfig.rope_max_position (or max_position_embeddings) "
            f"to serve longer contexts")


def _tag_residual(x):
    """`checkpoint_name` tag on the residual stream: the selective-remat
    policies (paddle_tpu.parallel.scan_layers) key on it, e.g.
    `offload_residuals` moves exactly these activations to pinned host
    memory. Numerically the identity."""
    return apply_op(lambda v: checkpoint_name(v, "residual"), x,
                    name="checkpoint_name")


def apply_rotary(q, k, cos, sin):
    """q,k: [B,S,H,D] arrays; cos/sin: [S, D/2] (shared row positions) or
    [B, S, D/2] (per-row positions, e.g. gathered by a packed batch's
    position ids). Interleaved-pair rotation."""
    c = cos[None, :, None, :] if cos.ndim == 2 else cos[:, :, None, :]
    s = sin[None, :, None, :] if sin.ndim == 2 else sin[:, :, None, :]

    def rot(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)

    return rot(q), rot(k)


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.head_dim = config.hidden_size // config.num_attention_heads
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        h = config.hidden_size
        kv = self.num_kv_heads * self.head_dim
        self.q_proj = ColumnParallelLinear(h, h, has_bias=False, gather_output=False)
        self.k_proj = ColumnParallelLinear(h, kv, has_bias=False, gather_output=False)
        self.v_proj = ColumnParallelLinear(h, kv, has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(h, h, has_bias=False, input_is_parallel=True)
        self._rope_geom = (self.head_dim, _rope_limit(config),
                           config.rope_theta)

    def forward(self, x, attn_mask=None, rope=None, segment_ids=None,
                position_ids=None):
        b, s, _ = x.shape
        q = self.q_proj(x).reshape([b, s, -1, self.head_dim])
        k = self.k_proj(x).reshape([b, s, -1, self.head_dim])
        v = self.v_proj(x).reshape([b, s, -1, self.head_dim])

        # packed-sequence metadata: explicit kwargs win; otherwise the
        # pipelined runtimes publish the current microbatch's ids in the
        # segment context (paddle_tpu.parallel.segments)
        if segment_ids is None and position_ids is None:
            from paddle_tpu.parallel.segments import current_segment_ctx

            ctx = current_segment_ctx()
            if ctx is not None:
                segment_ids, position_ids = ctx.segment_ids, ctx.position_ids
        segment_ids = (segment_ids._value if isinstance(segment_ids, Tensor)
                       else segment_ids)
        position_ids = (position_ids._value if isinstance(position_ids, Tensor)
                        else position_ids)

        # rope: (cos, sin) handed down by LlamaModel (one shared buffer pair
        # for the whole stack); standalone use falls back to the process-wide
        # cache — either way no per-layer buffer copies exist in the pytree
        if rope is None:
            rope = _shared_rope_tables(*self._rope_geom)
        cos, sin = (r._value if isinstance(r, Tensor) else r for r in rope)

        limit = self._rope_geom[1]
        _check_positions(position_ids, limit)

        def rope_fn(qv, kv_, c, sn):
            if position_ids is not None:
                # per-row positions (restarting at 0 per packed document):
                # index the shared tables by position id, [B, S, D/2]
                c = c[position_ids].astype(qv.dtype)
                sn = sn[position_ids].astype(qv.dtype)
            else:
                if s > limit:
                    raise ValueError(
                        f"sequence length {s} is past the hoisted RoPE "
                        f"table (rope_max_position={limit}); raise "
                        f"LlamaConfig.rope_max_position to run longer "
                        f"sequences")
                c = c[:s].astype(qv.dtype)
                sn = sn[:s].astype(qv.dtype)
            return apply_rotary(qv, kv_, c, sn)

        q, k = apply_op(rope_fn, q, k, cos, sin, name="rope", n_outputs=2)

        # GQA goes through natively: both the Pallas kernel and the XLA
        # fallback consume [B,S,Hkv,D] K/V without materializing repeats
        out = F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask, is_causal=True,
                                             training=self.training,
                                             segment_ids=segment_ids)
        out = out.reshape([b, s, -1])
        return self.o_proj(out)

    def forward_decode(self, x, *, rope, cache, layer_idx, page_table,
                       context_lens, position_ids, ctx_pad=None,
                       write_mask=None, verify=False, segment_ids=None):
        """Serving forward over the paged KV cache. x: [B, T, H]; T == 1 is
        a decode step (paged ragged attention over the page table), T > 1
        is a page-writing prefill chunk (runs through the standard flash
        path over the gathered context) — unless `verify=True`, which runs
        the T-token SPECULATIVE VERIFY frame through the same paged kernel
        with per-query causal limits (query i at absolute position
        context_lens-1+i). `cache` is the raw
        {"k","v": [L, Hkv, P, page_size, D]} pool pair — plus
        {"k_scale","v_scale": [L, Hkv, P, page_size] float32} when the
        pools are quantized (int8/fp8): writes then quantize through the
        absmax observer and reads dequantize inside the paged kernel;
        this layer reads and functionally updates stack row `layer_idx`. position_ids
        [B, T] are ABSOLUTE positions (index the hoisted RoPE buffer);
        context_lens [B] counts valid cache tokens INCLUDING this chunk
        (for verify: committed context incl. the frame's rewrite token
        only — draft tokens are PROVISIONAL). `write_mask` [B, T] bool
        redirects masked entries' K/V writes to the reserved null page —
        how a verify frame keeps out-of-window draft slots (past a row's
        budget/context cap) from scribbling live cache.

        `segment_ids` [B, T] switches T > 1 into the PACKED MULTI-PROMPT
        prefill frame: several fresh prompts ride one frame, page_table is
        [n_segments + 1, pages] (one page chain per segment; the last row
        is all-null and backs pad/gap tokens), position_ids are
        SEGMENT-LOCAL, and attention runs the PR-5 segment-aware flash
        path over the frame itself. Returns (out, cache)."""
        from paddle_tpu.ops.pallas.paged_attention import paged_attention

        b, t, _ = x.shape
        packed = segment_ids is not None and t > 1 and not verify
        q = self.q_proj(x).reshape([b, t, -1, self.head_dim])
        k = self.k_proj(x).reshape([b, t, -1, self.head_dim])
        v = self.v_proj(x).reshape([b, t, -1, self.head_dim])
        cos, sin = (r._value if isinstance(r, Tensor) else r for r in rope)
        _check_positions(position_ids, self._rope_geom[1])
        qv, kv, vv = q._value, k._value, v._value
        c = cos[position_ids].astype(qv.dtype)
        sn = sin[position_ids].astype(qv.dtype)
        qv, kv = apply_rotary(qv, kv, c, sn)

        # write this chunk's K/V into its cache pages (functional scatter;
        # the engine donates the pools so XLA updates them in place)
        ck, cv = cache["k"], cache["v"]
        ps = ck.shape[3]
        if packed:
            # packed frame: a token's page CHAIN is its segment's row, its
            # column its segment-local position; pad/gap tokens carry the
            # all-null last row, so they spill to page 0 with no mask
            pidx = page_table[segment_ids, position_ids // ps]
        else:
            pidx = jnp.take_along_axis(page_table,
                                       position_ids // ps, axis=1)
        if write_mask is not None:
            # masked entries scatter into the null page (page 0): a
            # harmless spill target the allocator never hands out and the
            # kernel's skip predicate never reads as live context
            pidx = jnp.where(write_mask, pidx, 0)
        slot = position_ids % ps                                   # [B, T]
        # index tuple (int, :, [B,T], [B,T]): the advanced dims land in
        # FRONT position, so the updates keep their natural [B, T, Hkv, D]
        if "k_scale" in cache:
            # quantized pool: quantize-on-write through the SAME observer
            # math training quantization uses (per-slot-per-head absmax);
            # codes land in the int8/fp8 pool, scales in the f32 side pool
            from paddle_tpu.quantization import AbsmaxChannelWiseObserver
            qmax = 127.0 if ck.dtype == jnp.int8 else 448.0
            sck = AbsmaxChannelWiseObserver.kv_page_scales(kv, qmax=qmax)
            scv = AbsmaxChannelWiseObserver.kv_page_scales(vv, qmax=qmax)
            kq = kv.astype(jnp.float32) / sck[..., None]
            vq = vv.astype(jnp.float32) / scv[..., None]
            if ck.dtype == jnp.int8:
                kq = jnp.clip(jnp.round(kq), -127, 127)
                vq = jnp.clip(jnp.round(vq), -127, 127)
            ck = ck.at[layer_idx, :, pidx, slot].set(kq.astype(ck.dtype))
            cv = cv.at[layer_idx, :, pidx, slot].set(vq.astype(cv.dtype))
            cks = cache["k_scale"].at[layer_idx, :, pidx, slot].set(sck)
            cvs = cache["v_scale"].at[layer_idx, :, pidx, slot].set(scv)
            cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
            k_sc, v_sc = cks[layer_idx], cvs[layer_idx]
        else:
            ck = ck.at[layer_idx, :, pidx, slot].set(kv.astype(ck.dtype))
            cv = cv.at[layer_idx, :, pidx, slot].set(vv.astype(cv.dtype))
            cache = {"k": ck, "v": cv}
            k_sc = v_sc = None

        if t == 1:
            out = paged_attention(qv[:, 0], ck[layer_idx], cv[layer_idx],
                                  page_table, context_lens,
                                  k_scales=k_sc, v_scales=v_sc)[:, None]
        elif verify:
            # the [B, T, Hq, D] query frame rides the SAME scalar-prefetch
            # page gather as plain decode; per-query causal limits live in
            # the kernel (query i sees keys < context_lens + i, which
            # includes the draft K/V scattered just above)
            out = paged_attention(qv, ck[layer_idx], cv[layer_idx],
                                  page_table, context_lens,
                                  k_scales=k_sc, v_scales=v_sc)
        elif packed:
            # packed multi-prompt prefill: every segment is a FRESH prompt
            # whose full K/V sits in this very frame, so attention runs the
            # segment-aware flash path over the frame itself — no page
            # gather. The in-frame K/V first round-trips through the cache
            # dtype (identity when the pool stores the model dtype, the
            # chunked gather's dequant when quantized), so packed pages AND
            # outputs stay bit-equal to sequential chunked prefill. Frame
            # causality == per-segment causality because each segment's
            # tokens are contiguous and ordered; pads only see the null
            # segment.
            if k_sc is not None:
                k_in = (kq.astype(ck.dtype).astype(qv.dtype)
                        * sck[..., None].astype(qv.dtype))
                v_in = (vq.astype(cv.dtype).astype(qv.dtype)
                        * scv[..., None].astype(qv.dtype))
            else:
                k_in = kv.astype(ck.dtype).astype(qv.dtype)
                v_in = vv.astype(cv.dtype).astype(qv.dtype)
            out = F.scaled_dot_product_attention(
                qv, k_in, v_in, is_causal=True, training=False,
                segment_ids=segment_ids)
            out = out._value if isinstance(out, Tensor) else out
        else:
            # chunked prefill: gather the full context (pages cover the
            # chunk itself too — just scattered above) and run the SAME
            # flash kernel training uses, with the chunk's queries placed
            # at their absolute rows of a [B, ctx_pad] frame so the causal
            # mask sees true positions; rows past context are padding
            # whose outputs are dropped by the take_along_axis below
            if ctx_pad is None:
                raise ValueError("prefill chunks need ctx_pad (the padded "
                                 "context bucket the engine compiled for)")
            pos_full = jnp.arange(ctx_pad, dtype=jnp.int32)
            pidx_f = page_table[:, pos_full // ps]                 # [B, S]
            slot_f = jnp.broadcast_to(pos_full % ps, (b, ctx_pad))
            k_full = jnp.moveaxis(ck[layer_idx][:, pidx_f, slot_f],
                                  0, 2).astype(qv.dtype)           # [B,S,Hkv,D]
            v_full = jnp.moveaxis(cv[layer_idx][:, pidx_f, slot_f],
                                  0, 2).astype(qv.dtype)
            if k_sc is not None:
                # dequant the gathered context (prefill runs the flash
                # path over bf16 activations; the pool stays quantized)
                ksf = jnp.moveaxis(k_sc[:, pidx_f, slot_f], 0, 2)  # [B,S,Hkv]
                vsf = jnp.moveaxis(v_sc[:, pidx_f, slot_f], 0, 2)
                k_full = k_full * ksf[..., None].astype(qv.dtype)
                v_full = v_full * vsf[..., None].astype(qv.dtype)
            q_full = jnp.zeros((b, ctx_pad) + qv.shape[2:], qv.dtype)
            bidx = jnp.arange(b)[:, None]
            q_full = q_full.at[bidx, position_ids].set(qv)
            out_full = F.scaled_dot_product_attention(
                q_full, k_full, v_full, is_causal=True, training=False)
            out = jnp.take_along_axis(
                out_full._value if isinstance(out_full, Tensor) else out_full,
                position_ids[:, :, None, None], axis=1)
        out = Tensor(out) if not isinstance(out, Tensor) else out
        out = out.reshape([b, t, -1])
        return self.o_proj(out), cache


def _raw(a):
    """Unwrap Tensor -> jnp value (functional_call wraps top-level array
    kwargs; the decode metadata must reach the kernels raw)."""
    if a is None:
        return None
    return a._value if isinstance(a, Tensor) else jnp.asarray(a)


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        self.gate_proj = ColumnParallelLinear(h, m, has_bias=False, gather_output=False)
        self.up_proj = ColumnParallelLinear(h, m, has_bias=False, gather_output=False)
        self.down_proj = RowParallelLinear(m, h, has_bias=False, input_is_parallel=True)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, attn_mask=None, rope=None, segment_ids=None,
                position_ids=None):
        # named scopes change HLO metadata only: a device trace can then
        # say which part of a block an operation belongs to
        with scopes.scope("attn"):
            x = _tag_residual(x + self.self_attn(self.input_layernorm(x),
                                                 attn_mask, rope=rope,
                                                 segment_ids=segment_ids,
                                                 position_ids=position_ids))
        with scopes.scope("mlp"):
            x = _tag_residual(x + self.mlp(self.post_attention_layernorm(x)))
        return x

    def forward_decode(self, x, *, rope, cache, layer_idx, page_table,
                       context_lens, position_ids, ctx_pad=None,
                       write_mask=None, verify=False, segment_ids=None):
        with scopes.scope("attn"):
            attn_out, cache = self.self_attn.forward_decode(
                self.input_layernorm(x), rope=rope, cache=cache,
                layer_idx=layer_idx, page_table=page_table,
                context_lens=context_lens, position_ids=position_ids,
                ctx_pad=ctx_pad, write_mask=write_mask, verify=verify,
                segment_ids=segment_ids)
            x = x + attn_out
        with scopes.scope("mlp"):
            x = x + self.mlp(self.post_attention_layernorm(x))
        return x, cache


class LlamaModel(nn.Layer):
    # cooperation protocol (paddle_tpu.parallel.scan_layers): compiled steps
    # deliver the per-layer remat policy / stacked scan params via
    # layer_execution() instead of wrapping the whole loss in jax.checkpoint
    layer_remat_capable = True

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList([LlamaDecoderLayer(config)
                                    for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        # ONE shared RoPE table pair for the whole stack (previously every
        # attention layer registered its own [max_pos, head_dim/2] copies);
        # sized by rope_max_position so the serving decode path can index it
        # at absolute positions past the training max_position_embeddings
        head_dim = config.hidden_size // config.num_attention_heads
        cos, sin = _rope_tables(head_dim, _rope_limit(config),
                                config.rope_theta)
        self.register_buffer("rope_cos", cos, persistable=False)
        self.register_buffer("rope_sin", sin, persistable=False)

    def scan_group(self):
        """The homogeneous decoder stack, for scan-over-layers packing."""
        return list(self.layers)

    def forward(self, input_ids, attn_mask=None, segment_ids=None,
                position_ids=None):
        with scopes.scope("embed"):
            x = self.embed_tokens(input_ids)
        x = self._run_layers(x, attn_mask, segment_ids, position_ids)
        with scopes.scope("head"):
            return self.norm(x)

    def decode_forward(self, input_ids, cache, page_table, context_lens,
                       position_ids, ctx_pad=None, write_mask=None,
                       verify=False, segment_ids=None):
        """Serving forward over the paged KV cache (decode step when
        input_ids is [B, 1], page-writing prefill chunk when [B, T>1],
        speculative verify frame when [B, T>1] with verify=True, packed
        multi-prompt prefill frame when [B, T>1] with segment_ids).
        `cache` = raw {"k","v": [L, Hkv, P, page_size, D]} pools; returns
        (hidden, updated cache). The layer loop is an unrolled Python loop
        — decode programs are tiny next to training HLO, and every layer
        scatters into its own stack row of the donated pools."""
        page_table = _raw(page_table).astype(jnp.int32)
        context_lens = _raw(context_lens).astype(jnp.int32)
        position_ids = _raw(position_ids).astype(jnp.int32)
        write_mask = _raw(write_mask)
        segment_ids = (_raw(segment_ids).astype(jnp.int32)
                       if segment_ids is not None else None)
        with scopes.scope("embed"):
            x = self.embed_tokens(input_ids)
        rope = (self.rope_cos._value, self.rope_sin._value)
        for i, layer in enumerate(self.layers):
            x, cache = layer.forward_decode(
                x, rope=rope, cache=cache, layer_idx=i,
                page_table=page_table, context_lens=context_lens,
                position_ids=position_ids, ctx_pad=ctx_pad,
                write_mask=write_mask, verify=verify,
                segment_ids=segment_ids)
        with scopes.scope("head"):
            return self.norm(x), cache

    def _run_layers(self, x, attn_mask, segment_ids=None, position_ids=None):
        """Apply the decoder stack: unrolled python loop, or ONE lax.scan
        over layer-stacked params, with the active selective-remat policy
        applied PER LAYER (embed/norm/head never sit in a remat region)."""
        from paddle_tpu.core.flags import flag
        from paddle_tpu.parallel.scan_layers import (
            current_layer_ctx, scan_layer_stack, stack_layer_vals,
            unrolled_layer_call)

        rope = (self.rope_cos._value, self.rope_sin._value)
        layers = list(self.layers)
        ctx = current_layer_ctx()
        policy = ctx.policy if ctx is not None else flag("remat_policy")
        stacked = ctx.stacked if ctx is not None else None
        # packed-batch metadata rides the layer kwargs (layer-invariant, so
        # the scan path broadcasts ONE copy to every scanned layer)
        seg = (segment_ids._value if isinstance(segment_ids, Tensor)
               else segment_ids)
        pos = (position_ids._value if isinstance(position_ids, Tensor)
               else position_ids)
        kwargs = {"attn_mask": attn_mask, "rope": rope,
                  "segment_ids": seg, "position_ids": pos}
        use_scan = stacked is not None or (
            len(layers) > 1 and (self.config.scan_layers
                                 or flag("scan_layers")))
        if not use_scan:
            if policy == "none":
                for layer in layers:
                    x = layer(x, attn_mask, rope=rope, segment_ids=seg,
                              position_ids=pos)
                return x
            for layer in layers:
                x = unrolled_layer_call(layer, x, kwargs=kwargs,
                                        policy=policy)
            return x
        template = layers[0]
        if stacked is not None:
            # stacked [L, ...] arrays arrive from the compiled step's packing
            # (jit inputs — the program never stacks or slices per layer).
            # shard_info: ZeRO-3 — they persist reduce-scattered and the
            # scan gathers layer k+1's weights while layer k computes
            return Tensor(scan_layer_stack(
                template, stacked, x._value, kwargs=kwargs, policy=policy,
                shard_info=getattr(ctx, "shard_info", None)))
        # stack the per-layer parameter values in-program (eager / unpacked
        # traced mode); the tape records ONE scan op with per-param grads
        n_per = len(template.parameters())
        n_layers = len(layers)
        flat = [p for layer in layers for p in layer.parameters()]

        def scan_all(hv, *leafs):
            svals = stack_layer_vals(
                [leafs[l * n_per:(l + 1) * n_per] for l in range(n_layers)])
            return scan_layer_stack(template, svals, hv, kwargs=kwargs,
                                    policy=policy)

        return apply_op(scan_all, x, *flat, name="scan_layers")


class LlamaPretrainingCriterion(nn.Layer):
    """Causal-LM loss; TP-aware CE over the sharded vocab (reference
    ParallelCrossEntropy mp_layers.py:742)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.parallel_ce = ParallelCrossEntropy() if config.use_parallel_cross_entropy else None

    def forward(self, logits, labels):
        if self.parallel_ce is not None:
            loss = self.parallel_ce(logits, labels)
            return loss.mean()
        return F.cross_entropy(
            logits.reshape([-1, logits.shape[-1]]), labels.reshape([-1]))

    def forward_fused(self, hidden, lm_head, labels):
        """Joint head-projection + CE through the chunked fused kernel
        (paddle_tpu.ops.pallas.fused_ce): `CE(hidden @ W_head, labels)`
        without ever materializing the [tokens, vocab] logits, preserving
        this criterion's exact reduction semantics — per-token parallel CE
        then mean over ALL tokens when use_parallel_cross_entropy, else
        F.cross_entropy's mean over non-ignored tokens."""
        if self.parallel_ce is not None:
            return F.fused_linear_cross_entropy(
                hidden, lm_head.weight, labels, bias=lm_head.bias,
                ignore_index=self.parallel_ce.ignore_index,
                reduction="mean_all")
        return F.fused_linear_cross_entropy(
            hidden, lm_head.weight, labels, bias=lm_head.bias,
            reduction="mean")


class LlamaForCausalLM(nn.Layer):
    layer_remat_capable = True

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        self.lm_head = ColumnParallelLinear(config.hidden_size, config.vocab_size,
                                            has_bias=False, gather_output=False)
        self.criterion = LlamaPretrainingCriterion(config)

    def scan_group(self):
        return self.llama.scan_group()

    def forward(self, input_ids, labels=None, attn_mask=None,
                segment_ids=None, position_ids=None):
        from paddle_tpu.amp.fp8 import head_scope

        hidden = self.llama(input_ids, attn_mask, segment_ids=segment_ids,
                            position_ids=position_ids)
        if labels is not None:
            from paddle_tpu.core.flags import flag

            with head_scope(), scopes.scope("head"):
                # head_scope: under fp8_policy='matmuls' the head matmul
                # stays bf16; 'matmuls+head' quantizes it too (the fused-CE
                # kernel keeps its softmax statistics fp32 either way)
                if flag("use_fused_head_loss"):
                    # head projection + CE in one chunked custom-vjp: the
                    # [tokens, vocab] logits never exist (escape hatch:
                    # use_fused_head_loss=False restores the unfused path)
                    return self.criterion.forward_fused(hidden, self.lm_head,
                                                        labels)
                return self.criterion(self.lm_head(hidden), labels)
        with head_scope(), scopes.scope("head"):
            return self.lm_head(hidden)

    def decode_forward(self, input_ids, cache, page_table, context_lens,
                       position_ids, ctx_pad=None, write_mask=None,
                       verify=False, segment_ids=None):
        """Serving decode/prefill/verify entry: (logits [B, T, vocab],
        cache)."""
        hidden, cache = self.llama.decode_forward(
            input_ids, cache, page_table, context_lens, position_ids,
            ctx_pad=ctx_pad, write_mask=write_mask, verify=verify,
            segment_ids=segment_ids)
        with scopes.scope("head"):
            return self.lm_head(hidden), cache

    # ---- pipeline-parallel factory ----------------------------------------
    @staticmethod
    def pipeline_layers(config: LlamaConfig, loss_fn=None):
        """LayerDesc list for PipelineLayer (reference pp_layers.py usage)."""
        from paddle_tpu.distributed.fleet.meta_parallel import LayerDesc

        descs = [LayerDesc(_EmbeddingStage, config)]
        for _ in range(config.num_hidden_layers):
            descs.append(LayerDesc(LlamaDecoderLayer, config))
        descs.append(LayerDesc(_HeadStage, config))
        return descs


class _EmbeddingStage(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size, config.hidden_size)

    def forward(self, input_ids):
        return self.embed_tokens(input_ids)


class _HeadStage(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.lm_head = ColumnParallelLinear(config.hidden_size, config.vocab_size,
                                            has_bias=False, gather_output=False)

    def forward_features(self, x):
        """Pre-projection hidden — the fused head+loss protocol
        (paddle_tpu.parallel.fused_head): forward == lm_head(forward_features)."""
        return self.norm(x)

    def forward(self, x):
        from paddle_tpu.amp.fp8 import head_scope

        with head_scope():
            return self.lm_head(self.forward_features(x))
