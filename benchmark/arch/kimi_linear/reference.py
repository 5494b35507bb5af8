"""The plain reference of Kimi-Linear: float32 jax.numpy, precision highest,
nothing of the program imported and nothing it made taken.

The KDA state is carried token by token (`lax.scan`, checkpointed a block of
tokens so the backward pass keeps one state a block): the recurrence of
docs/linear_attention.md as written, never its chunked form. Latent
attention is materialised a block of queries at a time. Experts are a plain loop: every held
expert over every token, weighted by what the router gave it (zero where it
was not chosen). The router's correction bias moves by the balancing rule
after every step (`next_biases`), from the loads the step counted. `mm` is the product every projection goes through
(`mm_f32`; `mm_fp8` is the control); the router is float32 whatever `mm`
is, as it is in the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference as R
from benchmark import weights as W
from benchmark.arch.kimi_linear import weights as KW

HI = R.HI


def kda_recurrence(q, k, v, g, beta, block=64):
    """q, k [T, H, K] (normalised; q scaled), v [T, H, V], g [T, H, K]
    log-decays, beta [T, H]: o [T, H, V], one token after the other."""
    t, h, kd = q.shape
    pad = (-t) % block
    xs = [jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) for x in (q, k, v, g, beta)]

    def token(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[..., None]                               # Diag(a_t) S
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt, precision=HI))
        s = s + kt[..., None] * u[:, None, :]                        # + k u^T
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=HI)      # S^T q

    @jax.checkpoint
    def tokens(s, blk):
        return jax.lax.scan(token, s, blk)

    blocks = [x.reshape(-1, block, *x.shape[1:]) for x in xs]
    _, o = jax.lax.scan(tokens, jnp.zeros((h, kd, v.shape[-1]), jnp.float32), tuple(blocks))
    return o.reshape(-1, h, v.shape[-1])[:t]


def _conv(x, w):
    taps, t = w.shape[0], x.shape[0]
    xp = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(xp[j:j + t] * w[j] for j in range(taps))


def _unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def kda_layer(x, lw, d, eps, mm):
    s, heads, hd = x.shape[0], d["kda_heads"], d["kda_hd"]
    y = R.rmsnorm(x, lw["input_norm"], eps)
    split = lambda z: z.reshape(s, heads, hd)                        # noqa: E731
    q, k, v = (split(jax.nn.silu(_conv(mm(y, lw[w]), lw[c])))
               for w, c in (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v")))
    g = -jnp.exp(lw["a_log"])[:, None] * split(
        jax.nn.softplus(mm(mm(y, lw["w_fa"]), lw["w_fb"]) + lw["dt_bias"]))
    beta = jax.nn.sigmoid(mm(y, lw["w_beta"]))
    o = kda_recurrence(_unit(q) * hd ** -0.5, _unit(k), v, g, beta)
    gate = jax.nn.sigmoid(mm(mm(y, lw["w_ga"]), lw["w_gb"]))
    o = R.rmsnorm(o, lw["o_norm"], eps) * split(gate)
    return x + mm(o.reshape(s, -1), lw["wo"])


def attention(q, k, v, block=512):
    """Causal attention of one sequence, q, k [S, H, D], v [S, H, Dv]
    (`benchmark.reference.attention` assumes Dv = D): a block of queries at
    a time against every key, each block made again in the backward pass."""
    s, h, d = q.shape
    block = min(block, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def one(q_blk, lo):
        sc = jnp.einsum("qhd,khd->hqk", q_blk, k, precision=HI) / np.sqrt(d)
        mask = (lo + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(lambda a: one(*a), (q.reshape(s // block, block, h, d),
                                          jnp.arange(0, s, block)))
    return out.reshape(s, h, v.shape[-1])


def mla_layer(x, lw, d, eps, mm):
    s, heads, nope, latent = x.shape[0], d["heads"], d["nope"], d["latent"]
    y = R.rmsnorm(x, lw["input_norm"], eps)
    q = mm(y, lw["wq"]).reshape(s, heads, nope + d["rope"])
    kva = mm(y, lw["w_kva"])
    kv = mm(R.rmsnorm(kva[:, :latent], lw["kv_norm"], eps), lw["w_kvb"]).reshape(
        s, heads, nope + d["vd"])
    k_r = jnp.broadcast_to(kva[:, None, latent:], (s, heads, d["rope"]))   # no rotation
    k = jnp.concatenate([kv[..., :nope], k_r], axis=-1)
    # 128 queries a block: the chip's compiler keeps eight blocks' scores
    # [32, block, S] at once in the backward pass (4 GB each at 512 x 8192)
    o = attention(q, k, kv[..., nope:], block=next(b for b in (128, 64, 32, 16, 8, 4, 2, 1) if s % b == 0))
    return x + mm(o.reshape(s, -1), lw["wo"])


def route(scores_in, bias, top_k, scale, renormalize=True):
    """(weights [T, E] with zeros off the chosen, chosen ids [T, k]) of the
    sigmoid router: the k largest of s + b, weighted scale * s / sum s."""
    s = jax.nn.sigmoid(scores_in)
    _, idx = jax.lax.top_k(s + bias, top_k)
    chosen = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(1.0)
    w = s * chosen
    if renormalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * scale, idx


def swiglu(y, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(y, wg)) * mm(y, wu), wd)


def moe_layer(x, lw, d, cfg, mm, first=None, bias=None, shared=True):
    """(the layer's output, how many tokens chose each of ALL the experts).
    `first`: the first of the held experts (default the configuration's):
    the fault 'other experts computed in place of the held ones' moves it.
    The router's weights take no gradient: this chip's experts alone would
    teach it to route away from them (weights.FROZEN)."""
    eps = cfg["rms_norm_eps"]
    y = R.rmsnorm(x, lw["post_norm"], eps)
    first = d["first"] if first is None else first
    bias = jnp.zeros((d["experts"],), jnp.float32) if bias is None else bias
    w, idx = route(R.mm_f32(y, jax.lax.stop_gradient(lw["router"])), bias, d["top_k"],
                   cfg["routed_scaling_factor"], cfg.get("moe_renormalize", True))
    load = jnp.zeros((d["experts"],), jnp.float32).at[idx.reshape(-1)].add(1.0)
    out = swiglu(y, lw["shared_gate"], lw["shared_up"], lw["shared_down"], mm) if shared else 0.0
    held = jax.lax.dynamic_slice_in_dim(w, first, d["held"], axis=1)

    def expert(out, e):                 # a plain loop over the held experts
        return out + held[:, e, None] * swiglu(
            y, lw["w_gate"][e], lw["w_up"][e], lw["w_down"][e], mm), None

    out = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(x) + out,
                       jnp.arange(d["held"]))[0]
    return x + out, load


def dense_layer(x, lw, d, cfg, mm):
    y = R.rmsnorm(x, lw["post_norm"], cfg["rms_norm_eps"])
    return x + swiglu(y, lw["w_gate"], lw["w_up"], lw["w_down"], mm)


def tree(leaves: list, cfg: dict) -> dict:
    it = iter(leaves)
    out = {"embed": next(it), "layers": []}
    for kinds in KW.layer_kinds(cfg):
        out["layers"].append(tuple({leaf: next(it) for leaf in KW.ORDER[kind]}
                                   for kind in kinds))
    out["final_norm"], out["head"] = next(it), next(it)
    return out


def row_loss(leaves, ids, labels, cfg, mm, first=None, biases=None):
    """(sum over one row's tokens of the cross-entropy, [expert layers, E]
    tokens that chose each expert). `biases` [expert layers, E]: the
    routers' correction biases."""
    p, d, eps = tree(leaves, cfg), KW.dims(cfg), cfg["rms_norm_eps"]
    x = p["embed"][ids]
    loads = []
    for (mixer, ff), (mw, fw) in zip(KW.layer_kinds(cfg), p["layers"]):
        mix = kda_layer if mixer == "kda" else mla_layer
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
        logits = mm(xb, p["head"])
        logz = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(logz - jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0])

    blk = next(b for b in (1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1) if x.shape[0] % b == 0)
    parts = jax.lax.map(lambda a: block_loss(*a), (x.reshape(-1, blk, x.shape[-1]),
                                                   labels.reshape(-1, blk)))
    return jnp.sum(parts), jnp.stack(loads)


def batch_loss(leaves, ids, labels, cfg, mm, first=None, biases=None):
    """(the batch's summed loss, the batch's loads): a row at a time."""
    def one(total, row):
        # a row keeps nothing: without this checkpoint the program needs
        # 13.25 GiB of temporaries beside 4.5 of leaves and gradients
        # (XLA analysis, PR 27)
        loss, loads = jax.checkpoint(
            lambda lv, i, l: row_loss(lv, i, l, cfg, mm, first, biases))(leaves, *row)
        return (total[0] + loss, total[1] + loads), None

    n_moe = sum(ff == "moe" for _, ff in KW.layer_kinds(cfg))
    zero = (jnp.float32(0.0), jnp.zeros((n_moe, KW.dims(cfg)["experts"]), jnp.float32))
    return jax.lax.scan(one, zero, (ids, labels))[0]


def next_biases(biases, loads, rate):
    """The balancing rule that moves a router's correction bias, outside
    the gradient: up by `rate` for an expert under the mean load of its
    layer, down for one over it."""
    return biases + rate * jnp.sign(jnp.mean(loads, axis=-1, keepdims=True) - loads)


def train_steps(cfg: dict, seed: int, batches, lr: float, mm=R.mm_f32,
                param_dtype="bfloat16", rows=None, first=None, decay=0.01,
                warmup_steps=0) -> dict:
    """`benchmark.reference.train_steps` for this architecture: the loss of
    each step, the norm of every leaf's first gradient, the norm of every
    leaf's change after the last step (0 for a leaf that takes no update:
    `weights.frozen`), the correction biases after the last step and the
    load of every expert at each step. `rows` (a slice) and `first` plant
    the faults: part of the batch left out, other experts held. With
    `warmup_steps` step t runs at `lr * t / warmup_steps`."""
    specs = KW.leaf_specs(cfg)
    frozen = KW.frozen(specs)
    masters = [np.asarray(x.astype(jnp.float32)) for x in W.make_all(seed, specs, param_dtype)]
    moments = [None] * len(masters)
    # `first` is an argument: the fault compiles to the reference's program
    grad_fn = jax.jit(jax.value_and_grad(functools.partial(batch_loss, cfg=cfg, mm=mm),
                                         has_aux=True))
    first = jnp.int32(KW.dims(cfg)["first"] if first is None else first)
    update = jax.jit(functools.partial(R.adamw, decay=decay), donate_argnums=(0, 2, 3))
    sq_diff = jax.jit(lambda p, parts, i, mean, std: jnp.sum(jnp.square(
        p - W.make_leaf(W.key_of(parts), i, p.shape, mean, std, param_dtype
                        ).astype(jnp.float32))))
    parts = W.seed_parts(seed)
    rate = float(cfg.get("router_bias_update_rate", 0.0))
    n_moe = sum(ff == "moe" for _, ff in KW.layer_kinds(cfg))
    biases = jnp.zeros((n_moe, KW.dims(cfg)["experts"]), jnp.float32)
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
