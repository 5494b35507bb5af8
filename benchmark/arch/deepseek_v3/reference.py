"""The plain reference of the DeepSeek-V3 family: float32 jax.numpy,
precision highest, nothing of the program imported and nothing it made taken.

The equations are the published ones (`modeling_deepseek`, `model_type`
`deepseek_v3`), a sequence at a time. Latent attention: one query projection
(`q_lora_rank` null), keys and values expanded from the normalised latent,
the 64 channels of each head's query beside the latent's and the ONE 64-wide
key all heads share turned by position, the pair (2i, 2i + 1) by
t * theta^(-2i/64) (`rotate`), then the key broadcast over the heads;
attention is materialised a block of 128 queries at a time against every key
(`attention`). Experts are a plain loop: every held
expert over every token, weighted by what the router gave it (zero where it
was not chosen); the two shared experts are one SwiGLU of twice the width.
The router's correction bias moves by the balancing rule after every step
(`next_biases`), from the loads the step counted. `mm` is the product every
projection goes through (`mm_f32`; `mm_fp8` is the control); the router is
float32 whatever `mm` is, as it is in the program.

Departures from the published description, each also in the configuration's
`assumed`: the published code de-interleaves the rotated channels and then
rotates halves, which permutes q_r and k_r alike and leaves q k^T as it is
here; `seq_aux` adds no loss (no coefficient is published); the router's
weights take no gradient on a share of the experts; the bias is moved by the
balancing rule at `router_bias_update_rate`; what the experts other chips
hold would add is left out.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark import reference as R
from benchmark import weights as W
from benchmark.arch.deepseek_v3 import weights as DW
from benchmark.arch.kimi_linear.reference import (  # noqa: F401
    dense_layer, next_biases, swiglu, tree as _kimi_tree)

HI = R.HI
RENORM_EPS = 1e-20
ATTN_BLOCK = 128    # queries a block: [16, 128, 8192] float32 scores are 67 MB


def attention(q, k, v, block=ATTN_BLOCK):
    """Causal attention of one sequence, q, k [S, H, D], v [S, H, Dv]: a block
    of queries at a time against every key, each block made again in the
    backward pass. `arch/kimi_linear`'s, but for the scale, a Python float
    here: that one divides by `np.sqrt(d)`, a numpy float64, and `import
    paddle_tpu` turns x64 on, so in the benchmark's process its scores,
    softmax and second product are float64, which the chip emulates (PERF.md
    section 6, PR 37: 155 s a step of this reference's gradients)."""
    s, h, d = q.shape
    block = min(block, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def one(q_blk, lo):
        sc = jnp.einsum("qhd,khd->hqk", q_blk, k, precision=HI) * d ** -0.5
        mask = (lo + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(lambda a: one(*a), (q.reshape(s // block, block, h, d),
                                          jnp.arange(0, s, block)))
    return out.reshape(s, h, v.shape[-1])


def rotate(x, theta):
    """R_t x for x [T, ..., 2n] at positions t = 0..T-1: the pair
    (x[2i], x[2i+1]) turned by the angle t * theta^(-2i / 2n), the channels
    left where they were."""
    n = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * theta ** (-2.0 * jnp.arange(n, dtype=jnp.float32) / (2 * n))
    ang = ang.reshape(x.shape[0], *(1,) * (x.ndim - 2), n)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                      odd * jnp.cos(ang) + even * jnp.sin(ang)], axis=-1).reshape(x.shape)


def mla_layer(x, lw, d, eps, mm, rotated=True):
    """`rotated=False` plants the fault 'the rotation left out'."""
    s, heads, nope, latent = x.shape[0], d["heads"], d["nope"], d["latent"]
    y = R.rmsnorm(x, lw["input_norm"], eps)
    q = mm(y, lw["wq"]).reshape(s, heads, nope + d["rope"])
    kva = mm(y, lw["w_kva"])
    kv = mm(R.rmsnorm(kva[:, :latent], lw["kv_norm"], eps), lw["w_kvb"]).reshape(
        s, heads, nope + d["vd"])
    q_r, k_r = q[..., nope:], kva[:, None, latent:]
    if rotated:
        q_r, k_r = rotate(q_r, d["theta"]), rotate(k_r, d["theta"])
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (s, heads, d["rope"]))], axis=-1)
    o = attention(q, k, kv[..., nope:],
                  block=next(b for b in (ATTN_BLOCK, 64, 32, 16, 8, 4, 2, 1) if s % b == 0))
    return x + mm(o.reshape(s, -1), lw["wo"])


def route(scores_in, bias, top_k, scale, renormalize=True):
    """(weights [T, E] with zeros off the chosen, chosen ids [T, k]): the k
    largest of s + b (one group: `n_group` = `topk_group` = 1), weighted
    scale * s / (sum of the chosen s + 1e-20)."""
    s = jax.nn.sigmoid(scores_in)
    _, idx = jax.lax.top_k(s + bias, top_k)
    chosen = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(1.0)
    w = s * chosen
    if renormalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + RENORM_EPS)
    return w * scale, idx


def moe_layer(x, lw, d, cfg, mm, first=None, bias=None, shared=True):
    """(the layer's output, how many tokens chose each of ALL the experts).
    `first`: the first of the held experts (default the configuration's):
    the fault 'other experts computed in place of the held ones' moves it.
    `shared=False` leaves the shared experts out (the shares of a layer count
    them once). The router's weights take no gradient (weights.FROZEN)."""
    y = R.rmsnorm(x, lw["post_norm"], cfg["rms_norm_eps"])
    first = d["first"] if first is None else first
    bias = jnp.zeros((d["experts"],), jnp.float32) if bias is None else bias
    w, idx = route(R.mm_f32(y, jax.lax.stop_gradient(lw["router"])), bias, d["top_k"],
                   cfg["routed_scaling_factor"], cfg["norm_topk_prob"])
    load = jnp.zeros((d["experts"],), jnp.float32).at[idx.reshape(-1)].add(1.0)
    out = swiglu(y, lw["shared_gate"], lw["shared_up"], lw["shared_down"], mm) if shared else 0.0
    held = jax.lax.dynamic_slice_in_dim(w, first, d["held"], axis=1)

    def expert(out, e):                 # a plain loop over the held experts
        return out + held[:, e, None] * swiglu(
            y, lw["w_gate"][e], lw["w_up"][e], lw["w_down"][e], mm), None

    out = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(x) + out,
                       jnp.arange(d["held"]))[0]
    return x + out, load


def tree(leaves: list, cfg: dict) -> dict:
    """{"embed", "layers": [(latent attention's leaves, feed-forward's)],
    "final_norm", "head"}: `arch/kimi_linear`'s over this family's leaves."""
    return _kimi_tree(leaves, DW.kimi_view(cfg))


def row_loss(leaves, ids, labels, cfg, mm, first=None, biases=None, rotated=True):
    """(sum over one row's tokens of the cross-entropy, [expert layers, E]
    tokens that chose each expert). `biases` [expert layers, E]: the
    routers' correction biases."""
    p, d, eps = tree(leaves, cfg), DW.dims(cfg), cfg["rms_norm_eps"]
    x = p["embed"][ids]
    loads = []
    for (_, ff), (mw, fw) in zip(DW.layer_kinds(cfg), p["layers"]):
        x = jax.checkpoint(lambda x, lw: mla_layer(x, lw, d, eps, mm, rotated))(x, mw)
        if ff == "dense":
            x = jax.checkpoint(lambda x, lw: dense_layer(x, lw, d, cfg, mm))(x, fw)
        else:
            bias = None if biases is None else biases[len(loads)]
            x, load = jax.checkpoint(
                lambda x, lw, b: moe_layer(x, lw, d, cfg, mm, first, b))(x, fw, bias)
            loads.append(load)
    x = R.rmsnorm(x, p["final_norm"], eps)

    @jax.checkpoint
    def block_loss(xb, lb):
        logits = mm(xb, p["head"])
        logz = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(logz - jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0])

    blk = next(b for b in (1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1) if x.shape[0] % b == 0)
    parts = jax.lax.map(lambda a: block_loss(*a), (x.reshape(-1, blk, x.shape[-1]),
                                                   labels.reshape(-1, blk)))
    return jnp.sum(parts), jnp.stack(loads)


def n_moe(cfg: dict) -> int:
    return sum(ff == "moe" for _, ff in DW.layer_kinds(cfg))


def batch_loss(leaves, ids, labels, cfg, mm, first=None, biases=None, rotated=True):
    """(the batch's summed loss, the batch's loads): a row at a time, a row
    keeping nothing for the backward pass."""
    def one(total, row):
        loss, loads = jax.checkpoint(
            lambda lv, i, l: row_loss(lv, i, l, cfg, mm, first, biases, rotated))(leaves, *row)
        return (total[0] + loss, total[1] + loads), None

    zero = (jnp.float32(0.0), jnp.zeros((n_moe(cfg), DW.dims(cfg)["experts"]), jnp.float32))
    return jax.lax.scan(one, zero, (ids, labels))[0]


def train_steps(cfg: dict, seed: int, batches, lr: float, mm=R.mm_f32,
                param_dtype="bfloat16", rows=None, first=None, decay=0.01,
                warmup_steps=0, rotated=True) -> dict:
    """`benchmark.reference.train_steps` for this architecture: the loss of
    each step, the norm of every leaf's first gradient, the norm of every
    leaf's change after the last step (0 for a leaf that takes no update:
    `weights.frozen`), the correction biases after the last step and the
    load of every expert at each step. `rows` (a slice), `first` and
    `rotated=False` plant the faults: part of the batch left out, other
    experts held, the rotation left out. With `warmup_steps` step t runs at
    `lr * t / warmup_steps`."""
    specs = DW.leaf_specs(cfg)
    frozen = DW.frozen(specs)
    # the float32 masters stay on the device; the moments wait on the host
    # while the next step's gradients are made and move as ONE list each way
    # (masters, rounded copy, gradients and moments are five times the
    # parameters: 12.5 GiB at the cell's size, before the step's temporaries)
    masters = [x.astype(jnp.float32) for x in W.make_all(seed, specs, param_dtype)]
    moments = [None] * len(masters)
    # `first` is an argument: the fault compiles to the reference's program
    grad_fn = jax.jit(jax.value_and_grad(
        functools.partial(batch_loss, cfg=cfg, mm=mm, rotated=rotated), has_aux=True))
    first = jnp.int32(DW.dims(cfg)["first"] if first is None else first)
    update = jax.jit(functools.partial(R.adamw, decay=decay), donate_argnums=(0, 2, 3))
    sq_diff = jax.jit(lambda p, parts, i, mean, std: jnp.sum(jnp.square(
        p - W.make_leaf(W.key_of(parts), i, p.shape, mean, std, param_dtype
                        ).astype(jnp.float32))))
    parts = W.seed_parts(seed)
    rate = float(cfg.get("router_bias_update_rate", 0.0))
    biases = jnp.zeros((n_moe(cfg), DW.dims(cfg)["experts"]), jnp.float32)
    losses, loads, grad_norms, change = [], [], None, np.zeros(len(masters))
    for t, (ids, labels) in enumerate(batches, start=1):
        t0 = time.perf_counter()
        if rows is not None:
            ids, labels = ids[rows], labels[rows]
        seen = [x.astype(param_dtype).astype(jnp.float32) for x in masters]
        (loss, load), grads = grad_fn(seen, jnp.asarray(ids), jnp.asarray(labels), first=first,
                                      biases=biases)
        del seen
        biases = next_biases(biases, load, rate)
        losses.append(float(loss) / ids.size)
        loads.append(np.asarray(load))
        t1 = time.perf_counter()
        for i in range(len(grads)):
            grads[i] = grads[i] / ids.size
        if grad_norms is None:
            grad_norms = np.where(frozen, 0.0, R._norms(grads))
        moments = jax.device_put(moments)
        for i, (_, _, mean, std) in enumerate(specs):
            if not frozen[i]:
                m, v = moments[i] or (jnp.zeros_like(grads[i]), jnp.zeros_like(grads[i]))
                masters[i], m, v = update(
                    masters[i], grads[i], m, v, jnp.float32(t),
                    jnp.float32(lr * min(t, warmup_steps or t) / (warmup_steps or t)))
                if t == len(batches):
                    change[i] = np.sqrt(float(sq_diff(masters[i], parts, i, mean, std)))
                moments[i] = (m, v) if t < len(batches) else None
            grads[i] = None
        moments = jax.device_get(moments)
        harness.log(f"reference step {t}: gradients {t1 - t0:.1f}s, update {time.perf_counter() - t1:.1f}s")
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change,
            "leaves": [s[0] for s in specs], "biases": np.asarray(biases), "loads": loads}
