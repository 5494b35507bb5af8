"""Ring attention: exact attention over a sequence sharded across chips.

Reference analog: the reference snapshot covers long context with Megatron-SP
+ the 'sep' mesh axis + flash attention (SURVEY §5 'Long-context'); it has NO
ring attention — this is capability headroom over the reference, required by
the north star's long-context mandate.

TPU-native design: inside shard_map over the "sep" axis each rank holds a
sequence shard of Q/K/V. K/V blocks rotate around the ring with
`lax.ppermute` over ICI while each rank accumulates its Q shard's attention
with streaming-softmax merges (m, l, acc). sep_size steps fully overlap
compute with the neighbor exchange (XLA pipelines the permute). Causal
masking uses global positions, so ranks skip no work but mask exactly.
Differentiable end-to-end (grad rides the ppermute transposes = reverse ring).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["ring_attention", "SEP_AXIS"]

SEP_AXIS = "sep"
_NEG_INF = -1e30


def _block_attn(q, k, v, qpos, kpos, scale, causal, q_chunk=512):
    """One Q-shard x K-block attention with stats. q:[B,Sq,H,D] k/v:[B,Sk,H,D].
    Returns (acc [B,Sq,H,D] f32 unnormalized, m [B,Sq,H,1], l [B,Sq,H,1]).
    Q is processed in chunks so peak score memory is O(q_chunk * Sk), not
    O(Sq * Sk) — the flash-style tiling, kept in jnp so the ring stays
    differentiable end-to-end."""
    qh = q.astype(jnp.float32)
    kh = k.astype(jnp.float32)
    vh = v.astype(jnp.float32)
    sq = qh.shape[1]
    chunk = min(q_chunk, sq)

    @jax.checkpoint
    def one_chunk(args):
        qc, qp = args  # [B, C, H, D], [C]
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, kh) * scale
        if causal:
            mask = qp[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None], s, _NEG_INF)
        m = jnp.max(s, axis=-1)  # [B,H,C]
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bhqk,bkhd->bqhd", p, vh)
        m = jnp.transpose(m, (0, 2, 1))[..., None]
        l = jnp.transpose(l, (0, 2, 1))[..., None]
        return acc, m, l

    if sq == chunk:
        return one_chunk((qh, qpos))
    # ceil-division tiling: Q rows are independent, so the remainder tile is
    # zero-padded and sliced off after (no divisor hunting — a prime shard
    # length must not degenerate to chunk=1). one_chunk is rematerialized so
    # the O(chunk * Sk) score bound holds in the BACKWARD pass too (lax.map
    # would otherwise stack every chunk's softmax residuals).
    nc = -(-sq // chunk)
    pad = nc * chunk - sq
    if pad:
        qh = jnp.pad(qh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qpos = jnp.concatenate([qpos, jnp.full((pad,), qpos[-1], qpos.dtype)])
    qs = qh.reshape(qh.shape[0], nc, chunk, *qh.shape[2:]).swapaxes(0, 1)
    qps = qpos.reshape(nc, chunk)
    accs, ms, ls = jax.lax.map(one_chunk, (qs, qps))

    def join(t):
        full = t.swapaxes(0, 1).reshape(t.shape[1], nc * chunk, *t.shape[3:])
        return full[:, :sq]

    return join(accs), join(ms), join(ls)


def ring_attention(q, k, v, axis_name: str = SEP_AXIS, causal: bool = True,
                   scale: float | None = None):
    """Exact attention for seq-sharded q,k,v: [B, S_local, H, D] per rank.
    Must be called inside shard_map with `axis_name` bound."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    # static ring size (a python int: it feeds the static perm list). An
    # unbound axis raises NameError here — silently defaulting to a 1-rank
    # ring would skip every neighbor exchange and corrupt attention.
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_local = q.shape[1]
    qpos = idx * s_local + jnp.arange(s_local)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, r):
        kk, vv, m, l, acc = carry

        def compute(_):
            src = (idx - r) % n  # which rank's block we currently hold
            kpos = src * s_local + jnp.arange(s_local)
            a_j, m_j, l_j = _block_attn(q, kk, vv, qpos, kpos, scale, causal)
            m_new = jnp.maximum(m, m_j)
            c_old = jnp.exp(m - m_new)
            c_new = jnp.exp(m_j - m_new)
            return (l * c_old + l_j * c_new, acc * c_old + a_j * c_new, m_new)

        if causal:
            # a K block strictly in this Q shard's future contributes
            # nothing: skip its matmuls entirely (roughly halves ring FLOPs)
            src = (idx - r) % n
            l, acc, m = jax.lax.cond(
                src > idx, lambda _: (l, acc, m), compute, None)
        else:
            l, acc, m = compute(None)
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return (kk, vv, m, l, acc), None

    b, s_, h, d = q.shape
    m0 = jnp.full((b, s_, h, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, s_, h, 1), jnp.float32)
    acc0 = jnp.zeros((b, s_, h, d), jnp.float32)
    (kk, vv, m, l, acc), _ = jax.lax.scan(
        step, (k, v, m0, l0, acc0), jnp.arange(n))
    out = acc / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)
