"""One chip's share of an expert-parallel layer: gated (SwiGLU) experts, a
shared expert every token passes through (or none: `num_shared=0`), and a
router as wide as the whole layer.

`HeldExpertsMoE` is told which experts it holds (`held_experts`, a range).
It scores and chooses over ALL `num_expert` experts as the whole layer does,
computes the part of the result its own experts give for the token-expert
pairs routed to them, adds the shared expert's output, and adds nothing for
the experts other chips hold: on one chip there is no exchange. The shares
of all chips, with the shared expert counted once, add up to the whole
layer (tests/test_kimi_linear.py::test_shares_add_up).

The layout is a few integer arrays built ONCE a layer and step, outside
what `recompute=True` runs again (`_layout`: `dropless.pair_rows` gives each
pair its row of the dispatcher's block-aligned buckets). Two row movers, each
the other's transpose, fill the buffer and sum the products back
(`ops.pallas.moe_rows`): their device time follows the pairs routed here, not
the rows laid out, as `grouped_matmul(aligned=True)` between them skips the row
blocks no pair fell into. Every shape is static. A chip that holds ALL the
experts lays out every pair and drops none. A chip that holds a share lays out
`CAPACITY_FACTOR` times the share a balanced router sends it (`tokens x top_k
x held / num_expert` rows) and COUNTS the pairs past that (`step_stats[3]`,
`moe.dropped`): the router's correction bias is what keeps the count at zero,
moved after every step by the balancing rule (`SigmoidGate.next_bias`), which
`CompiledTrainStep` applies outside the gradient.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.core.tensor import apply_op
from paddle_tpu.incubate.distributed.models.moe.dropless import (
    _round_up, pair_rows)
from paddle_tpu.incubate.distributed.models.moe.moe_layer import (
    SigmoidGate, _route)
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.layer.layers import Layer
from paddle_tpu.observability import scopes
from paddle_tpu.ops.pallas.grouped_matmul import col_tiles, grouped_matmul, pick_block_rows
from paddle_tpu.ops.pallas.moe_rows import (
    rows_backend, rows_combine, rows_gather, rows_layout)

__all__ = ["HeldExpertsMoE", "held_rows"]

# rows laid out for a share's pairs, in shares of a balanced router. The
# largest share a step saw on the CPU at published widths was 1.2 (PR 27)
CAPACITY_FACTOR = 4.0


def held_rows(pairs: int, held: int, num_expert: int, block_rows: int = 0,
              capacity_factor: float = CAPACITY_FACTOR) -> tuple[int, int]:
    """(rows laid out for the pairs routed here, rows of a block): every
    pair where all experts are held, else `capacity_factor` shares."""
    share = pairs * held // num_expert
    bm = block_rows or pick_block_rows(max(share, 1), held)
    if held == num_expert:
        return pairs, bm
    return min(_round_up(max(int(capacity_factor * share), bm), bm), pairs), bm


def _rows_layout(gids, held, k, block_rows, rows):
    """The layout of pairs with group ids `gids [N * k]` (`held`: not here):
    (the movers' `RowsLayout`; `gbuf [M]`, each buffer row's expert; pairs of
    each held expert). The rows are `ragged_layout(rows=)`'s."""
    pair_row, gbuf, counts, reach = pair_rows(gids, held, block_rows, rows)
    row_pair = jnp.full(gbuf.shape, gids.shape[0], jnp.int32).at[pair_row].set(
        jnp.arange(gids.shape[0], dtype=jnp.int32), mode="drop")
    return (rows_layout(row_pair, pair_row.reshape(-1, k), reach // block_rows,
                        min(rows, gids.shape[0])), gbuf, counts)


def _layout(logits, bias, *, k, first, held, routing, rows, block_rows):
    """Route and lay out, once a layer and step: logits [N, E] float32 ->
    (weights [N, k] float32 of each token's chosen experts; the layout and
    `gbuf` of `_rows_layout`; [pairs routed here, largest and mean load of a
    held expert, pairs left out]; load of ALL experts [E])."""
    with scopes.scope("moe_router"):
        topv, topi, _ = _route(logits.astype(jnp.float32), None, k=k,
                               routing=routing, bias=bias)
        flat = topi.reshape(-1)
    with scopes.scope("moe_layout"):
        local = flat - first
        gids = jnp.where((local >= 0) & (local < held), local, held).astype(jnp.int32)
        layout, gbuf, counts = _rows_layout(gids, held, k, block_rows, rows)
        load = counts.astype(jnp.float32)
        n_here = jnp.sum(load)
        stats = jnp.stack([n_here, jnp.max(load), jnp.mean(load),
                           jnp.maximum(n_here - rows, 0.0)])
    with scopes.scope("moe_router"):
        # pairs of every expert, counted by comparison: a scatter-add of one
        # a pair took a millisecond (measured on one v5e chip)
        load_all = jnp.sum(flat[:, None] == jnp.arange(logits.shape[1], dtype=flat.dtype),
                           axis=0, dtype=jnp.float32)
    return topv, layout, gbuf, stats, load_all


def _experts(xv, topv, layout, gbuf, wg, wu, wd, *shared_w, block_rows, backend):
    """The held experts over a layout, and the shared expert (`shared_w`;
    none for a layer without one): what `recompute=True` runs again."""
    with scopes.scope("moe_layout"):
        buf = rows_gather(xv, layout, block_rows=block_rows, backend=backend)
    with scopes.scope("moe_experts"):
        mm = functools.partial(grouped_matmul, gids=gbuf, block_rows=block_rows,
                               backend=backend, aligned=True)
        act = (jax.nn.silu(mm(buf, wg)) * mm(buf, wu)).astype(xv.dtype)
        out = mm(act, wd)
    with scopes.scope("moe_layout"):
        routed = rows_combine(out, topv, layout, block_rows=block_rows,
                              backend=backend)
    if shared_w:
        sg_w, su_w, sd_w = shared_w
        with scopes.scope("moe_shared"):
            shared = (jax.nn.silu(xv @ sg_w) * (xv @ su_w)) @ sd_w
            routed = routed + shared.astype(jnp.float32)
    return routed.astype(xv.dtype)


def _held_moe(xv, logits, bias, wg, wu, wd, *shared_w, k, first, routing,
              rows, block_rows, backend, recompute=False):
    """The layer on plain arrays: xv [N, d], logits [N, E] float32, the
    held experts' weights [G, ...] (experts first .. first + G - 1) and the
    shared expert's three. The layout is built here, outside what
    `recompute` runs twice. Returns (out [N, d], [pairs routed here, largest
    and mean load of a held expert, pairs left out], load of ALL experts [E])."""
    topv, layout, gbuf, stats, load_all = _layout(
        logits, bias, k=k, first=first, held=wg.shape[0], routing=routing,
        rows=rows, block_rows=block_rows)
    experts = functools.partial(_experts, block_rows=block_rows, backend=backend)
    if recompute:
        experts = jax.checkpoint(experts)
    return experts(xv, topv, layout, gbuf, wg, wu, wd, *shared_w), stats, load_all


class HeldExpertsMoE(Layer):
    """The experts `held_experts = (first, stop)` of a layer of `num_expert`
    gated experts of width `d_hidden`, its sigmoid router (all `num_expert`
    wide, `top_k` a token, its correction bias moved by `bias_update_rate`)
    and its shared expert of width `d_hidden * num_shared` (`num_shared=0`:
    no shared expert, and no leaves for one). `renorm_eps` is added to the sum
    the chosen scores are divided by. After a forward
    `step_stats` holds [pairs routed here, largest load, mean load of a held
    expert, pairs left out] and `gate.next_bias` the bias after this step.

    A share's router should be frozen (`gate.gate_weight.stop_gradient =
    True`) unless an exchange brings the other experts' outputs: its
    gradient is the held experts' alone and teaches it to avoid them."""

    def __init__(self, d_model, num_expert, d_hidden, top_k, *,
                 held_experts=None, routed_scale=1.0, renormalize=True,
                 renorm_eps=0.0, num_shared=1, bias_update_rate=0.0,
                 block_rows=0, backend=None, recompute=False):
        super().__init__()
        first, stop = held_experts or (0, num_expert)
        if not 0 <= first < stop <= num_expert:
            raise ValueError(f"held_experts={held_experts!r} is no range of "
                             f"the layer's {num_expert} experts")
        self.num_expert, self.top_k = num_expert, top_k
        self.held_experts = (first, stop)
        self.block_rows, self.backend = block_rows, backend
        self.recompute = bool(recompute)
        held = stop - first
        self.gate = SigmoidGate(d_model, num_expert, topk=top_k,
                                routed_scale=routed_scale,
                                renormalize=renormalize, renorm_eps=renorm_eps,
                                bias_update_rate=bias_update_rate)
        init = I.XavierNormal()
        mk = lambda *shape: self.create_parameter(      # noqa: E731
            list(shape), None, default_initializer=init)
        self.w_gate, self.w_up = mk(held, d_model, d_hidden), mk(held, d_model, d_hidden)
        self.w_down = mk(held, d_hidden, d_model)
        hs = d_hidden * num_shared
        self.shared = bool(hs)
        if self.shared:
            self.shared_gate, self.shared_up = mk(d_model, hs), mk(d_model, hs)
            self.shared_down = mk(hs, d_model)
        self.l_aux = None           # balanced by the router's bias, no loss
        self.tokens_dropped = None
        self.step_stats = None

    def forward(self, x):
        from paddle_tpu.tuning.blocks import Resolution, note_derived

        shape = x.shape
        x2 = x.reshape([-1, shape[-1]])
        first, stop = self.held_experts
        pairs = x2.shape[0] * self.top_k
        rows, bm = held_rows(pairs, stop - first, self.num_expert,
                             self.block_rows)
        buffer_rows = _round_up(rows, bm) + (stop - first) * bm
        d, h = self.w_gate.shape[1:]
        # static a compiled program, as `last_resolution("kda")` is
        note_derived(Resolution("held_experts", {"rows": rows, "block_rows": bm},
                                "caller" if self.block_rows else "default",
                                "CAPACITY_FACTOR"),
                     pairs=pairs, buffer_rows=buffer_rows,
                     rows_backend=rows_backend(self.backend, *x2.shape, self.top_k,
                                               x2.dtype, buffer_rows, rows),
                     col_tiles={"gate_up": col_tiles(bm, d, h, x2.dtype, self.w_gate.dtype),
                                "down": col_tiles(bm, h, d, x2.dtype, self.w_down.dtype)})
        fn = functools.partial(
            _held_moe, k=self.top_k, first=first,
            routing=self.gate.routing_config(self.training), rows=rows,
            block_rows=bm, backend=self.backend, recompute=self.recompute)
        with scopes.scope("moe_router"):
            logits = self.gate(x2)
        out, stats, load = apply_op(
            fn, x2, logits, self.gate.e_score_correction_bias,
            self.w_gate, self.w_up, self.w_down,
            *((self.shared_gate, self.shared_up, self.shared_down)
              if self.shared else ()), name="held_experts_moe", n_outputs=3)
        self.step_stats = stats
        self.tokens_dropped = stats[3]
        with scopes.scope("moe_router"):
            self.gate.next_bias = self.gate.balanced(load)
        return out.reshape(shape)
