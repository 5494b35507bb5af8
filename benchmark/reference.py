"""The plain reference: a dense decoder in float32 jax.numpy.

RMSNorm, rotary embedding (half-split rotation), grouped-query causal
attention, SwiGLU, untied head, mean cross-entropy and AdamW with decoupled
decay. No kernel, no cache, no batching; nothing of the program is imported
and nothing the program made is taken: weights come from `weights.py` and
the seed. Everything runs in blocks (a row at a time, a layer at a time, a
block of queries at a time) so that it fits beside nothing else on one chip.

`mm` is the matrix product every projection goes through. `mm_f32` is the
reference (float32, precision highest: on a TPU a float32 product is
otherwise rounded to bfloat16). `mm_fp8` is the control of a bfloat16
configuration: both operands rounded to float8_e4m3 with one scale a tensor,
the next precision down and the step a later PR would be tempted by.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights as W

HI = jax.lax.Precision.HIGHEST


def mm_f32(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32), precision=HI)


def _to_fp8(x):
    """Rounded to float8_e4m3 with one scale for the tensor; the gradient
    passes straight through the rounding."""
    x = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(rounded - x)


def mm_fp8(a, b):
    return jnp.matmul(_to_fp8(a), _to_fp8(b), precision=HI)


MATMULS = {"f32": mm_f32, "fp8": mm_fp8}


def rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, positions, theta):
    """x [S, H, D]; rotates the pair (i, i + D/2) by position * theta^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, block=512):
    """Causal grouped-query attention of one sequence. q [S, Hq, D],
    k, v [S, Hkv, D]. A block of queries at a time against every key, the
    later keys masked; each block is computed again in the backward pass, so
    that one block's scores are all that is ever held."""
    s, hq, d = q.shape
    group = hq // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    block = min(block, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def one(q_blk, lo):
        sc = jnp.einsum("qhd,khd->hqk", q_blk, k, precision=HI) / np.sqrt(d)
        mask = (lo + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(lambda a: one(*a), (q.reshape(s // block, block, hq, d),
                                          jnp.arange(0, s, block)))
    return out.reshape(s, hq, d)


def layer(x, lw: dict, cfg: dict, mm):
    """One decoder layer over one sequence x [S, H]."""
    s = x.shape[0]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    pos = jnp.arange(s)
    y = rmsnorm(x, lw["input_norm"], cfg["rms_norm_eps"])
    q = rope(mm(y, lw["wq"]).reshape(s, -1, hd), pos, cfg["rope_theta"])
    k = rope(mm(y, lw["wk"]).reshape(s, -1, hd), pos, cfg["rope_theta"])
    v = mm(y, lw["wv"]).reshape(s, -1, hd)
    x = x + mm(attention(q, k, v).reshape(s, -1), lw["wo"])
    y = rmsnorm(x, lw["post_norm"], cfg["rms_norm_eps"])
    return x + mm(jax.nn.silu(mm(y, lw["w_gate"])) * mm(y, lw["w_up"]), lw["w_down"])


# ---------------------------------------------------------------------------
# training: loss, gradients and AdamW, a row at a time
# ---------------------------------------------------------------------------


def _tree(leaves: list, cfg: dict) -> dict:
    n = len(W.LAYER_LEAVES)
    return {"embed": leaves[0],
            "layers": [dict(zip(W.LAYER_LEAVES, leaves[1 + i * n: 1 + (i + 1) * n]))
                       for i in range(cfg["num_hidden_layers"])],
            "final_norm": leaves[-2], "head": leaves[-1]}


def row_loss(leaves, ids, labels, cfg, mm):
    """Sum over one row's tokens of the cross-entropy."""
    p = _tree(leaves, cfg)
    x = p["embed"][ids]
    for lw in p["layers"]:
        x = jax.checkpoint(lambda x, lw: layer(x, lw, cfg, mm))(x, lw)
    x = rmsnorm(x, p["final_norm"], cfg["rms_norm_eps"])

    @jax.checkpoint
    def block_loss(xb, lb):     # a block of tokens' logits at a time
        logits = mm(xb, p["head"])
        logz = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(logz - jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0])

    blk = min(1024, x.shape[0])
    assert x.shape[0] % blk == 0
    parts = jax.lax.map(lambda a: block_loss(*a), (x.reshape(-1, blk, x.shape[-1]),
                                                   labels.reshape(-1, blk)))
    return jnp.sum(parts)


def adamw(p, g, m, v, step, lr, b1=0.9, b2=0.999, eps=1e-8, decay=0.01):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    mhat = m / (1 - b1 ** step)
    vhat = v / (1 - b2 ** step)
    return p - lr * mhat / (jnp.sqrt(vhat) + eps) - lr * decay * p, m, v


def _norms(leaves):
    return np.array([float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
                     for x in leaves])


def batch_loss(leaves, ids, labels, cfg, mm):
    """Sum of the cross-entropy over every token of ids [R, S], a row at a
    time, each row recomputed in the backward pass."""
    def one(total, row):
        return total + jax.checkpoint(
            lambda lv, i, l: row_loss(lv, i, l, cfg, mm))(leaves, *row), None

    return jax.lax.scan(one, jnp.float32(0.0), (ids, labels))[0]


def train_steps(cfg: dict, seed: int, batches, lr: float, mm=mm_f32,
                param_dtype="bfloat16", rows=None, frozen=False) -> dict:
    """Follow the first len(batches) steps from the seed's weights. Each batch
    is (ids [R, S], labels [R, S]) of numpy int32. Returns the loss of each
    step, the norm of every leaf's first gradient, and the norm of every
    leaf's change after the last step. `rows` (a slice) and `frozen` plant the
    faults a training cell can have: part of the batch left out with the mean
    taken over the rest, and a step that returns its state unchanged.
"""
    specs = W.leaf_specs(cfg)
    # the weights as the configuration trains them: made in its parameter
    # type, mastered in float32. The masters and Adam's moments live on the
    # host between uses: the device holds the rounded copy and the gradients.
    masters = [np.asarray(x.astype(jnp.float32))
               for x in W.make_all(seed, specs, param_dtype)]
    moments = [None] * len(masters)
    grad_fn = jax.jit(jax.value_and_grad(
        functools.partial(batch_loss, cfg=cfg, mm=mm)))
    update = jax.jit(adamw, donate_argnums=(0, 2, 3))
    sq_diff = jax.jit(lambda p, parts, i, mean, std: jnp.sum(jnp.square(
        p - W.make_leaf(W.key_of(parts), i, p.shape, mean, std, param_dtype
                        ).astype(jnp.float32))))
    parts = W.seed_parts(seed)
    losses, grad_norms, change = [], None, None
    last = len(batches)
    for t, (ids, labels) in enumerate(batches, start=1):
        if rows is not None:
            ids, labels = ids[rows], labels[rows]
        # the forward pass sees what the step computes with: the master
        # rounded to the parameter type
        seen = [jnp.asarray(x).astype(param_dtype).astype(jnp.float32) for x in masters]
        loss, grads = grad_fn(seen, jnp.asarray(ids), jnp.asarray(labels))
        del seen
        losses.append(float(loss) / ids.size)
        grads = [g / ids.size for g in grads]
        if grad_norms is None:
            grad_norms = _norms(grads)
        if t == last:
            change = np.zeros(len(masters))
        for i, (_, _, mean, std) in enumerate(specs):
            p = jnp.asarray(masters[i])
            if not frozen:
                m, v = moments[i] or (jnp.zeros_like(p), jnp.zeros_like(p))
                p, m, v = update(p, grads[i], jnp.asarray(m), jnp.asarray(v),
                                 jnp.float32(t), jnp.float32(lr))
            if t == last:       # read what is asked on the device; nothing goes back
                change[i] = np.sqrt(float(sq_diff(p, parts, i, mean, std)))
            elif not frozen:
                masters[i], moments[i] = np.asarray(p), (np.asarray(m), np.asarray(v))
            grads[i] = None
        del grads
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change,
            "leaves": [s[0] for s in specs]}


# ---------------------------------------------------------------------------
# serving: one forward pass over prompt + served tokens, a layer at a time
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg_items", "mm_name", "specs", "dtype"))
def _layer_step(x, key, base, *, cfg_items, mm_name, specs, dtype):
    """Make layer `base`'s weights from the seed and apply the layer to every
    sequence of x [N, S, H]."""
    cfg = dict(cfg_items)
    lw = {name: W.make_leaf(key, base + j, shape, mean, std, dtype).astype(jnp.float32)
          for j, (name, shape, mean, std) in enumerate(specs)}
    return jax.lax.map(lambda row: layer(row, lw, cfg, MATMULS[mm_name]), x)


def served_logit_gaps(cfg: dict, seed: int, sequences, n_prompt, mm_names=("f32",),
                      param_dtype="bfloat16", pad_to: int = 0) -> dict:
    """Run the reference once over each `sequences[i]` (prompt then served
    tokens). For every served token: how far its float32 logit lies below the
    float32 reference's best at that position. For every further name in
    `mm_names` (a control): the same gap for the token THAT precision puts
    first. Causal attention: the padding behind a sequence changes nothing
    before it. Returns {name: [gaps of sequence 0, ...]}; "served" is the
    program's."""
    specs = W.leaf_specs(cfg)
    key = W.seed_key(seed)
    n = len(sequences)
    # one shape a cell (`pad_to`: its longest context), so that the programs
    # below are compiled once and found in the cache by every later run
    smax = -(-max(pad_to, max(len(s) for s in sequences)) // 128) * 128
    ids = np.zeros((n, smax), np.int32)
    for i, s in enumerate(sequences):
        ids[i, :len(s)] = s
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float))))
    per_layer = tuple((nm.split(".")[-1], sh, mu, sd) for nm, sh, mu, sd in specs[1:10])
    embed = W.make_leaf(key, 0, *specs[0][1:], param_dtype)
    x0 = embed[jnp.asarray(ids)].astype(jnp.float32)
    del embed
    # the positions whose logits predict a served token
    rows, cols, served = [], [], []
    for i, s in enumerate(sequences):
        for t in range(n_prompt[i], len(s)):
            rows.append(i), cols.append(t - 1), served.append(int(s[t]))
    rows, cols, served = map(np.asarray, (rows, cols, served))
    fnorm = W.make_leaf(key, len(specs) - 2, *specs[-2][1:], param_dtype).astype(jnp.float32)
    head = W.make_leaf(key, len(specs) - 1, *specs[-1][1:], param_dtype).astype(jnp.float32)
    logits = {}
    for name in mm_names:
        x = x0
        for li in range(cfg["num_hidden_layers"]):
            x = _layer_step(x, key, 1 + li * len(W.LAYER_LEAVES), cfg_items=cfg_items,
                            mm_name=name, specs=per_layer, dtype=jnp.dtype(param_dtype))
        hid = rmsnorm(x[rows, cols], fnorm, cfg["rms_norm_eps"])
        logits[name] = np.asarray(MATMULS[name](hid, head))
        del x
    ref = logits["f32"]
    best = ref.max(axis=-1)
    idx = np.arange(len(served))
    out = {"served": best - ref[idx, served]}
    for name in mm_names:
        if name != "f32":
            out[name] = best - ref[idx, logits[name].argmax(axis=-1)]
    bounds = np.cumsum([0] + [len(s) - p for s, p in zip(sequences, n_prompt)])
    return {k: [g[bounds[i]:bounds[i + 1]] for i in range(n)] for k, g in out.items()}
