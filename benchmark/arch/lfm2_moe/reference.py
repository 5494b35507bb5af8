"""The plain reference of LFM2-MoE: float32 jax.numpy, precision highest,
nothing of the program imported and nothing it made taken.

The equations are the published ones (`modeling_lfm2_moe`), a sequence at a
time. The short convolution is written as the sum of its shifted taps.
Attention is `benchmark.reference.attention`'s: materialised a block of 128
queries at a time against every key, the key heads repeated over their
groups. Experts are a plain loop: every held expert over every token,
weighted by what the router gave it (zero where it was not chosen). The head
is the embedding, transposed: one leaf, used twice. The router's bias moves
by the balancing rule after every step (`next_biases`), from the loads the
step counted. `mm` is the product every projection goes through (`mm_f32`;
`mm_fp8` is the control); the router is float32 whatever `mm` is, as it is in
the program.

Departures from the published description, each also in the configuration's
`assumed`: the router's weights take no gradient on a share of the experts;
the bias is moved by the balancing rule at `router_bias_update_rate` (the
published config has `use_expert_bias` and no rule: training code is not
published); what the experts other chips hold would add is left out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference as R
from benchmark import weights as W
from benchmark.arch.lfm2_moe import weights as LW

RENORM_EPS = 1e-6
ATTN_BLOCK = 128    # queries a block: [32, 128, 8192] float32 scores are 134 MB


def conv_layer(x, lw, d, eps, mm):
    """x + W_out (C * z), z_t = sum_j w[j] (B * u)[t - (taps - 1) + j]."""
    t, taps = x.shape[0], d["taps"]
    b, c, u = jnp.split(mm(R.rmsnorm(x, lw["operator_norm"], eps), lw["w_in"]), 3, axis=-1)
    bu = jnp.pad(b * u, ((taps - 1, 0), (0, 0)))
    z = sum(bu[j:j + t] * lw["conv"][j] for j in range(taps))
    return x + mm(c * z, lw["w_out"])


def conv_recurrence(bu, w):
    """The same convolution token by token, as a decoder carries it: the
    state is the last `taps - 1` values of B * u. bu [T, C], w [taps, C]."""
    def token(state, x):
        window = jnp.concatenate([state, x[None]], axis=0)
        return window[1:], jnp.sum(window * w, axis=0)

    return jax.lax.scan(token, jnp.zeros((w.shape[0] - 1, bu.shape[1]), bu.dtype), bu)[1]


def attention_layer(x, lw, d, eps, mm):
    s, hd = x.shape[0], d["hd"]
    y = R.rmsnorm(x, lw["operator_norm"], eps)
    pos = jnp.arange(s)
    q = R.rmsnorm(mm(y, lw["wq"]).reshape(s, d["heads"], hd), lw["q_norm"], eps)
    k = R.rmsnorm(mm(y, lw["wk"]).reshape(s, d["kv_heads"], hd), lw["k_norm"], eps)
    v = mm(y, lw["wv"]).reshape(s, d["kv_heads"], hd)
    o = R.attention(R.rope(q, pos, d["theta"]), R.rope(k, pos, d["theta"]), v,
                    block=next(b for b in (ATTN_BLOCK, 64, 32, 16, 8, 4, 2, 1) if s % b == 0))
    return x + mm(o.reshape(s, -1), lw["wo"])


def route(scores_in, bias, top_k, scale=1.0, renormalize=True):
    """(weights [T, E] with zeros off the chosen, chosen ids [T, k]): the k
    largest of s + b, weighted scale * s / (sum of the chosen s + 1e-6)."""
    s = jax.nn.sigmoid(scores_in)
    _, idx = jax.lax.top_k(s + bias, top_k)
    chosen = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(1.0)
    w = s * chosen
    if renormalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + RENORM_EPS)
    return w * scale, idx


def swiglu(y, w1, w3, w2, mm):
    return mm(jax.nn.silu(mm(y, w1)) * mm(y, w3), w2)


def dense_layer(x, lw, d, cfg, mm):
    return x + swiglu(R.rmsnorm(x, lw["ffn_norm"], cfg["norm_eps"]), lw["w1"], lw["w3"],
                      lw["w2"], mm)


def moe_layer(x, lw, d, cfg, mm, first=None, bias=None):
    """(the layer's output, how many tokens chose each of ALL the experts).
    `first`: the first of the held experts (default the configuration's):
    the fault 'other experts computed in place of the held ones' moves it.
    No shared expert. The router's weights take no gradient (weights.FROZEN)."""
    y = R.rmsnorm(x, lw["ffn_norm"], cfg["norm_eps"])
    first = d["first"] if first is None else first
    bias = jnp.zeros((d["experts"],), jnp.float32) if bias is None else bias
    w, idx = route(R.mm_f32(y, jax.lax.stop_gradient(lw["router"])), bias, d["top_k"],
                   cfg.get("routed_scaling_factor", 1.0), cfg.get("norm_topk_prob", True))
    load = jnp.zeros((d["experts"],), jnp.float32).at[idx.reshape(-1)].add(1.0)
    held = jax.lax.dynamic_slice_in_dim(w, first, d["held"], axis=1)

    def expert(out, e):                 # a plain loop over the held experts
        return out + held[:, e, None] * swiglu(
            y, lw["w_gate"][e], lw["w_up"][e], lw["w_down"][e], mm), None

    out = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(x), jnp.arange(d["held"]))[0]
    return x + out, load


def tree(leaves: list, cfg: dict) -> dict:
    it = iter(leaves)
    out = {"embed": next(it), "layers": []}
    for kinds in LW.layer_kinds(cfg):
        out["layers"].append(tuple({leaf: next(it) for leaf in LW.ORDER[kind]}
                                   for kind in kinds))
    out["final_norm"] = next(it)
    return out


def row_loss(leaves, ids, labels, cfg, mm, first=None, biases=None, head=None):
    """(sum over one row's tokens of the cross-entropy, [expert layers, E]
    tokens that chose each expert). `biases` [expert layers, E]: the
    routers' biases. `head` [hidden, vocab]: the head's matrix where a test
    wants the two uses of the tied leaf apart; without it the embedding's,
    transposed."""
    p, d, eps = tree(leaves, cfg), LW.dims(cfg), cfg["norm_eps"]
    head = p["embed"].T if head is None else head
    x = p["embed"][ids]
    loads = []
    for (mixer, ff), (mw, fw) in zip(LW.layer_kinds(cfg), p["layers"]):
        mix = conv_layer if mixer == "conv" else attention_layer
        x = jax.checkpoint(lambda x, lw, f=mix: f(x, lw, d, eps, mm))(x, mw)
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
        logits = mm(xb, head)
        logz = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(logz - jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0])

    blk = next(b for b in (1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1) if x.shape[0] % b == 0)
    parts = jax.lax.map(lambda a: block_loss(*a), (x.reshape(-1, blk, x.shape[-1]),
                                                   labels.reshape(-1, blk)))
    return jnp.sum(parts), jnp.stack(loads)


def batch_loss(leaves, ids, labels, cfg, mm, first=None, biases=None):
    """(the batch's summed loss, the batch's loads): a row at a time, a row
    keeping nothing for the backward pass."""
    def one(total, row):
        loss, loads = jax.checkpoint(
            lambda lv, i, l: row_loss(lv, i, l, cfg, mm, first, biases))(leaves, *row)
        return (total[0] + loss, total[1] + loads), None

    n_moe = sum(ff == "moe" for _, ff in LW.layer_kinds(cfg))
    zero = (jnp.float32(0.0), jnp.zeros((n_moe, LW.dims(cfg)["experts"]), jnp.float32))
    return jax.lax.scan(one, zero, (ids, labels))[0]


def next_biases(biases, loads, rate):
    """The balancing rule that moves a router's bias, outside the gradient:
    up by `rate` for an expert under the mean load of its layer, down for
    one over it."""
    return biases + rate * jnp.sign(jnp.mean(loads, axis=-1, keepdims=True) - loads)


def train_steps(cfg: dict, seed: int, batches, lr: float, mm=R.mm_f32,
                param_dtype="bfloat16", rows=None, first=None, decay=0.01,
                warmup_steps=0) -> dict:
    """`benchmark.reference.train_steps` for this architecture: the loss of
    each step, the norm of every leaf's first gradient, the norm of every
    leaf's change after the last step (0 for a leaf that takes no update:
    `weights.frozen`), the routers' biases after the last step and the load
    of every expert at each step. `rows` (a slice) and `first` plant the
    faults: part of the batch left out, other experts held. With
    `warmup_steps` step t runs at `lr * t / warmup_steps`."""
    specs = LW.leaf_specs(cfg)
    frozen = LW.frozen(specs)
    masters = [np.asarray(x.astype(jnp.float32)) for x in W.make_all(seed, specs, param_dtype)]
    moments = [None] * len(masters)
    # `first` is an argument: the fault compiles to the reference's program
    grad_fn = jax.jit(jax.value_and_grad(functools.partial(batch_loss, cfg=cfg, mm=mm),
                                         has_aux=True))
    first = jnp.int32(LW.dims(cfg)["first"] if first is None else first)
    update = jax.jit(functools.partial(R.adamw, decay=decay), donate_argnums=(0, 2, 3))
    sq_diff = jax.jit(lambda p, parts, i, mean, std: jnp.sum(jnp.square(
        p - W.make_leaf(W.key_of(parts), i, p.shape, mean, std, param_dtype
                        ).astype(jnp.float32))))
    parts = W.seed_parts(seed)
    rate = float(cfg.get("router_bias_update_rate", 0.0))
    n_moe = sum(ff == "moe" for _, ff in LW.layer_kinds(cfg))
    biases = jnp.zeros((n_moe, LW.dims(cfg)["experts"]), jnp.float32)
    losses, loads, grad_norms, change = [], [], None, None
    for t, (ids, labels) in enumerate(batches, start=1):
        if rows is not None:
            ids, labels = ids[rows], labels[rows]
        seen = [jnp.asarray(x).astype(param_dtype).astype(jnp.float32) for x in masters]
        (loss, load), grads = grad_fn(seen, jnp.asarray(ids), jnp.asarray(labels), first=first,
                                      biases=biases)
        del seen
        biases = next_biases(biases, load, rate)
        losses.append(float(loss) / ids.size)
        loads.append(np.asarray(load))
        grads = [g / ids.size for g in grads]
        if grad_norms is None:
            grad_norms = np.where(frozen, 0.0, R._norms(grads))
        if t == len(batches):
            change = np.zeros(len(masters))
        for i, (_, _, mean, std) in enumerate(specs):
            if frozen[i]:
                grads[i] = None
                continue
            m, v = moments[i] or (jnp.zeros_like(grads[i]), jnp.zeros_like(grads[i]))
            p, m, v = update(jnp.asarray(masters[i]), grads[i], jnp.asarray(m),
                             jnp.asarray(v), jnp.float32(t),
                             jnp.float32(lr * min(t, warmup_steps or t) / (warmup_steps or t)))
            if t == len(batches):
                change[i] = np.sqrt(float(sq_diff(p, parts, i, mean, std)))
            else:
                masters[i], moments[i] = np.asarray(p), (np.asarray(m), np.asarray(v))
            grads[i] = None
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change,
            "leaves": [s[0] for s in specs], "biases": np.asarray(biases), "loads": loads}
