"""Mixture-of-Experts layer (expert parallelism).

Reference parity: `MoELayer` (incubate/distributed/models/moe/moe_layer.py:263)
with `MoEScatter`/`MoEGather` PyLayers (:99/:149) and gates
(gate/{naive,gshard,switch}_gate.py); dispatch collectives
`global_scatter`/`global_gather` (distributed/utils/moe_utils.py:20, CUDA ops
fluid/operators/collective/global_scatter_op.cu).

TPU-native design, two dispatch modes:

* ``dispatch="capacity"`` — SPARSE fixed-capacity dispatch. Tokens are
  scatter-added into per-expert capacity buckets ([E, C, d] — O(E*C*d)
  memory, never the [N, E, C] one-hot dispatch mask), exchanged with the
  expert owners via `lax.all_to_all` over the "ep" mesh axis inside
  shard_map (the reference's global_scatter/global_gather), run through the
  BATCHED expert FFNs (weights [E_local, d, h], one einsum on the MXU), and
  returned by the inverse all_to_all + gather-combine. Capacities stay
  static for XLA; overflow tokens are dropped and COUNTED
  (`tokens_dropped`, the `moe_dropped_tokens_total` registry counter).
* ``dispatch="dropless"`` — sort-based capacity-free dispatch (dropless.py,
  docs/moe.md): argsort tokens by expert into block-aligned ragged buckets,
  run the Pallas grouped matmul over exactly the routed rows, unpermute and
  combine with the gate weights in fp32. No capacity, no drops, zero
  retraces across load shifts; supports token-choice and expert-choice
  routing (``router=``) and a dense shared-expert branch scheduled to
  overlap the ep all_to_alls (``shared_expert_hidden=``).

Aux (load-balance) loss follows GShard. Per-expert token counts, the aux
loss and the dropped-token count are published to the observability
registry after every eager forward (`last_stats`); compiled steps surface
the same numbers through CompiledTrainStep's step telemetry.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.core.tensor import Tensor, apply_op
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.layer.layers import Layer

__all__ = ["MoELayer", "ExpertFFN", "NaiveGate", "GShardGate", "SwitchGate",
           "SigmoidGate"]

EP_AXIS = "ep"


class NaiveGate(Layer):
    """Top-k softmax gate (reference gate/naive_gate.py).

    `forward` produces logits; routing itself (top-k selection, jitter,
    random second-expert drop, gate-level capacity) is a PURE jnp transform
    described by `routing_config()` and executed inside the sharded dispatch
    program (`_route` in `_sparse_moe`) so it traces/shards cleanly."""

    def __init__(self, d_model, num_expert, world_size=1, topk=2):
        super().__init__()
        self.num_expert = num_expert
        self.topk = topk
        self.gate_weight = self.create_parameter(
            [d_model, num_expert], None, default_initializer=I.XavierNormal())

    def forward(self, x):
        logits = F.linear(x, self.gate_weight)
        return logits

    def routing_config(self, training: bool) -> tuple:
        """Hashable static routing spec consumed by _route."""
        return (("kind", "naive"),)

    def cap_rate(self, training: bool):
        """Gate-level per-expert capacity as a fraction of local tokens
        (reference limit_by_capacity), or None for no gate-level cap."""
        return None


class GShardGate(NaiveGate):
    """GShard gate: top-2 + random second-expert routing + gate-level capacity
    (reference gate/gshard_gate.py:30-84: limit_by_capacity with
    cap_rate=capacity[train?0:1], then _random_routing keeping the second
    expert with probability min(1, 2*topk_val[:,1]))."""

    def __init__(self, d_model, num_expert, world_size=1, topk=2, capacity=(1.2, 2.4),
                 random_routing=True, group=None):
        assert topk == 2, "topk should be 2 in gshard"
        super().__init__(d_model, num_expert, world_size, topk)
        self.capacity = tuple(capacity)
        self.random_routing = random_routing

    def routing_config(self, training: bool) -> tuple:
        return (("kind", "gshard"),
                ("random_routing", bool(self.random_routing and training)))

    def cap_rate(self, training: bool):
        return float(self.capacity[0 if training else 1])


class SwitchGate(NaiveGate):
    """Switch transformer top-1 gate (reference gate/switch_gate.py:41-75:
    train-time uniform jitter in [1-eps, 1+eps] added to the logits, then
    top-1 with gate-level capacity)."""

    def __init__(self, d_model, num_expert, world_size=1, topk=1, switch_eps=0.1,
                 capacity=(1.2, 2.4), group=None):
        assert topk == 1, "topk should be 1 in switch"
        super().__init__(d_model, num_expert, world_size, topk=1)
        self.switch_eps = float(switch_eps)
        self.capacity = tuple(capacity)

    def routing_config(self, training: bool) -> tuple:
        return (("kind", "switch"),
                ("switch_eps", self.switch_eps if training else 0.0))

    def cap_rate(self, training: bool):
        return float(self.capacity[0 if training else 1])


class SigmoidGate(NaiveGate):
    """Sigmoid router with a correction bias (DeepSeek-V3 / Kimi family):
    scores `s = sigmoid(x W)` in float32, the k experts with the largest
    `s + b` are chosen, and a chosen expert weighs `scale * s_e / (sum of the
    chosen s + renorm_eps)` (`renormalize`; the epsilon is 0 in the Kimi
    family and 1e-6 in LFM2), else `scale * s_e`. `b` balances load
    without an auxiliary loss. It is a parameter no gradient reaches
    (`stop_gradient`, float32 whatever the model is cast to); the balancing
    rule moves it: after a step, up by `bias_update_rate` for an expert
    that saw fewer tokens than the mean and down for one that saw more
    (`balanced`). The layer that routes leaves the next value in `next_bias`
    and `CompiledTrainStep` stores it with the step's new parameters.
    Groups of experts (`num_expert_group`) are not limited here: with one
    group that is the identity."""

    def __init__(self, d_model, num_expert, world_size=1, topk=8,
                 routed_scale=1.0, renormalize=True, bias_update_rate=0.0,
                 renorm_eps=0.0):
        super().__init__(d_model, num_expert, world_size, topk)
        self.routed_scale = float(routed_scale)
        self.renormalize = bool(renormalize)
        self.renorm_eps = float(renorm_eps)
        self.bias_update_rate = float(bias_update_rate)
        self.e_score_correction_bias = self.create_parameter(
            [num_expert], None, dtype="float32",
            default_initializer=I.Constant(0.0))
        self.e_score_correction_bias.stop_gradient = True
        self.e_score_correction_bias.keep_dtype = True
        self.next_bias = None

    def forward(self, x):
        # float32 scores from the operands as they are: bfloat16 products
        # are exact in float32, so only the order of the sum is left open
        return apply_op(lambda xv, w: jnp.dot(
            xv, w, preferred_element_type=jnp.float32), x, self.gate_weight,
            name="router_scores")

    def balanced(self, load):
        """The correction bias after a step in which expert e was chosen by
        `load[e]` tokens (summed over the data-parallel ranks, where there
        are any)."""
        rate = self.bias_update_rate
        return apply_op(
            lambda b, n: b + rate * jnp.sign(jnp.mean(n) - n),
            self.e_score_correction_bias, load, name="balance_bias")

    def routing_config(self, training: bool) -> tuple:
        return (("kind", "sigmoid"), ("routed_scale", self.routed_scale),
                ("renormalize", self.renormalize),
                ("renorm_eps", self.renorm_eps))


class ExpertFFN(Layer):
    """Batched expert MLPs: weights [E, d, dff] / [E, dff, d], expert dim
    sharded over the ep axis (the per-rank expert list of the reference)."""

    def __init__(self, num_expert, d_model, d_hidden, activation="gelu"):
        super().__init__()
        self.num_expert = num_expert
        self.w1 = self.create_parameter([num_expert, d_model, d_hidden], None,
                                        default_initializer=I.XavierNormal())
        self.w2 = self.create_parameter([num_expert, d_hidden, d_model], None,
                                        default_initializer=I.XavierNormal())
        self.b1 = self.create_parameter([num_expert, 1, d_hidden], None, is_bias=True)
        self.b2 = self.create_parameter([num_expert, 1, d_model], None, is_bias=True)
        # shard the expert dim over ep (falls back to mp if no ep axis)
        for p in (self.w1, self.w2, self.b1, self.b2):
            p._mp_pspec = (EP_AXIS,) + (None,) * (len(p.shape) - 1)
        self.act = activation

    def forward(self, x):
        """x: [E, C, d] -> [E, C, d]."""

        def f(xv, w1, b1, w2, b2):
            h = jnp.einsum("ecd,edh->ech", xv, w1) + b1
            h = jax.nn.gelu(h) if self.act == "gelu" else jax.nn.relu(h)
            return jnp.einsum("ech,ehd->ecd", h, w2) + b2

        return apply_op(f, x, self.w1, self.b1, self.w2, self.b2, name="expert_ffn")


def _route(logits, rng, *, k, routing, bias=None):
    """Pure gate routing: logits [N, float32] -> (topv, topi) [N, k], with
    dropped selections marked topi == -1. Implements the reference gates'
    semantics (gshard_gate.py:77-84 random routing, switch_gate.py:48-52
    jitter) as jnp ops; `sigmoid` is SigmoidGate's (`bias` its correction
    bias, which moves the choice and never the weight)."""
    cfg = dict(routing or ())
    kind = cfg.get("kind", "naive")
    if kind == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, topi = jax.lax.top_k(scores if bias is None else scores + bias, k)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
        if cfg.get("renormalize", True):
            total = jnp.sum(topv, axis=-1, keepdims=True)
            eps = cfg.get("renorm_eps", 0.0)
            # no `+ 0.0` where there is no epsilon: the program stays the same
            topv = topv / (total + eps if eps else total)
        return topv * cfg.get("routed_scale", 1.0), topi, scores
    if kind == "switch" and cfg.get("switch_eps", 0.0) > 0.0:
        eps = cfg["switch_eps"]
        rng, sub = jax.random.split(rng)
        # reference switch_gate.py:49: noise = U(0,1)*2*eps + 1 - eps added
        # to the logits (the constant 1 cancels in softmax)
        logits = logits + (jax.random.uniform(sub, logits.shape)
                           * 2.0 * eps + 1.0 - eps)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    raw_topv = topv  # pre-renormalization softmax probs
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    if kind == "gshard" and cfg.get("random_routing", False):
        # reference gshard_gate.py:77-84 _random_routing: keep the second
        # expert with probability min(1, 2*p2) where p2 is the RAW (pre-
        # renormalization) top-2 softmax prob. Ordering note: the drop is
        # applied here, BEFORE capacity bucketing, so dropped tokens free
        # capacity for survivors (the GShard-paper dispatch order); the
        # reference applies it after limit_by_capacity, so its token-drop
        # statistics differ slightly at saturation.
        rng, sub = jax.random.split(rng)
        pr = jax.random.uniform(sub, (logits.shape[0],))
        drop2 = 2.0 * raw_topv[:, 1] < pr
        topi = topi.at[:, 1].set(jnp.where(drop2, -1, topi[:, 1]))
    return topv, topi, probs


def _sparse_moe(xv, gv, rng, w1, b1, w2, b2, *, E, k, cf, act,
                ep, ep_axis, token_axes, other_axes,
                routing=(), cap_rate=None, rng_axes=None):
    """Sparse capacity-bucketed dispatch/combine on LOCAL arrays.

    xv [N, d] (this rank's tokens), gv [N, E] gate logits, weights are this
    rank's expert shard [E//ep, ...]. When ep > 1 the capacity buffers ride
    lax.all_to_all over `ep_axis` to/from the expert owners (reference
    global_scatter/global_gather). `routing`/`cap_rate` carry the gate's
    semantics (see _route / NaiveGate.cap_rate).
    Returns (out [N, d], l_aux, dropped, counts [E])."""
    N, d = xv.shape
    C = max(1, int(math.ceil(cf * k * N / E)))

    # rng arrives as raw uint32 key bits (differentiable-arg plumbing); wrap
    # back to a typed key, then fold a distinct deterministic routing stream
    # per token shard (rng_axes covers the enclosing-shard_map 'bound' mode,
    # where token_axes is () but dp/ep axes are bound)
    rng = jax.random.wrap_key_data(rng)
    for ax in (token_axes if rng_axes is None else rng_axes):
        rng = jax.random.fold_in(rng, jax.lax.axis_index(ax))
    topv, topi, probs = _route(gv.astype(jnp.float32), rng, k=k,
                               routing=routing)

    flat_e = topi.reshape(-1)                                       # [N*k]
    chosen = flat_e >= 0                                            # routing drop
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)                 # [N*k, E] (-1 -> 0s)
    pos = jnp.sum(jnp.cumsum(oh, axis=0) * oh, axis=-1) - 1         # [N*k]
    limit = C
    if cap_rate is not None:
        # gate-level per-expert capacity (reference limit_by_capacity):
        # ceil(cap_rate * N) tokens per expert, applied before bucketing
        limit = min(C, max(1, int(math.ceil(cap_rate * N))))
    valid = chosen & (pos >= 0) & (pos < limit)
    dropped = jnp.sum((chosen & ~valid).astype(jnp.float32))
    # per-expert PROCESSED token counts (valid selections only) — the
    # load-balance telemetry the registry surfaces
    counts = jnp.zeros((E,), jnp.float32).at[jnp.clip(flat_e, 0, E - 1)].add(
        valid.astype(jnp.float32))
    dest = (jnp.clip(flat_e, 0, E - 1) * C
            + jnp.clip(pos, 0, C - 1))                              # [N*k]

    # scatter tokens into their (expert, slot) buckets: O(E*C*d) memory
    xp = jnp.repeat(xv, k, axis=0)                                  # [N*k, d]
    buf = jnp.zeros((E * C, d), xv.dtype)
    buf = buf.at[dest].add(xp * valid[:, None].astype(xv.dtype))

    if ep > 1:
        El = E // ep
        # [E, C, d] -> [ep(owner), El, C, d] -> a2a -> [ep(source), El, C, d]
        b4 = buf.reshape(ep, El, C, d)
        b4 = jax.lax.all_to_all(b4, ep_axis, 0, 0, tiled=True)
        ein = jnp.moveaxis(b4, 1, 0).reshape(El, ep * C, d)
    else:
        ein = buf.reshape(E, C, d)

    h = jnp.einsum("ecd,edh->ech", ein, w1) + b1
    h = jax.nn.gelu(h) if act == "gelu" else jax.nn.relu(h)
    eo = jnp.einsum("ech,ehd->ecd", h, w2) + b2                     # [El, ep*C, d]

    if ep > 1:
        El = E // ep
        r4 = jnp.moveaxis(eo.reshape(El, ep, C, d), 1, 0)           # [ep, El, C, d]
        r4 = jax.lax.all_to_all(r4, ep_axis, 0, 0, tiled=True)      # back at source
        ybuf = r4.reshape(E * C, d)
    else:
        ybuf = eo.reshape(E * C, d)

    w = (topv.reshape(-1) * valid.astype(jnp.float32)).astype(xv.dtype)
    yp = ybuf[dest] * w[:, None]                                    # [N*k, d]
    out = jnp.sum(yp.reshape(N, k, d), axis=1)

    # GShard load-balance aux loss over this rank's tokens + the shared
    # stat-reduction convention (dropless.py — ONE implementation, the
    # dropless==capacity parity contract depends on it)
    from paddle_tpu.incubate.distributed.models.moe.dropless import (
        _gshard_aux, _reduce_stats)

    l_aux, dropped, counts = _reduce_stats(_gshard_aux(probs, topi, E),
                                           dropped, counts,
                                           token_axes, other_axes)
    return out, l_aux.astype(xv.dtype), dropped, counts


from paddle_tpu.distributed.mesh import shard_map_compat as _shard_map  # noqa: E402

import itertools as _itertools  # noqa: E402

_LAYER_SEQ = _itertools.count()


class MoELayer(Layer):
    """reference: moe_layer.py:263.

    recompute_interval/moe_group kept for API parity; `gate` may be a string
    ('naive'|'gshard'|'switch') or a gate Layer. After forward, `l_aux` holds
    the load-balance loss and `tokens_dropped` the over-capacity token count.
    """

    def __init__(self, d_model, experts=None, gate=None, moe_group=None, mp_group=None,
                 recompute_interval=0, num_expert=None, d_hidden=None, top_k=2,
                 capacity_factor=1.25, dispatch=None, router="token",
                 shared_expert_hidden=0, **kwargs):
        super().__init__()
        from paddle_tpu.core.flags import flag

        self.d_model = d_model
        self.dispatch = dispatch or flag("moe_dispatch")
        if self.dispatch not in ("capacity", "dropless"):
            raise ValueError(
                f"dispatch={self.dispatch!r}: 'capacity' or 'dropless'")
        if router not in ("token", "expert"):
            raise ValueError(f"router={router!r}: 'token' or 'expert'")
        if router == "expert" and self.dispatch != "dropless":
            raise ValueError("expert-choice routing requires the dropless "
                             "dispatch (it has no capacity buckets)")
        self.router = router
        if isinstance(experts, ExpertFFN):
            self.experts = experts
            num_expert = experts.num_expert
        elif experts is not None and not isinstance(experts, (str, type(None))):
            # a LayerList of per-expert MLPs (reference style): batch their weights
            num_expert = len(experts)
            d_hidden = d_hidden or experts[0].parameters()[0].shape[-1]
            self.experts = ExpertFFN(num_expert, d_model, d_hidden)
        else:
            assert num_expert is not None and d_hidden is not None
            self.experts = ExpertFFN(num_expert, d_model, d_hidden)
        self.num_expert = num_expert
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        if gate is None or gate == "gshard":
            self.gate = GShardGate(d_model, num_expert, topk=top_k)
        elif gate == "naive":
            self.gate = NaiveGate(d_model, num_expert, topk=top_k)
        elif gate == "switch":
            self.gate = SwitchGate(d_model, num_expert)
            self.top_k = 1
        else:
            self.gate = gate
        # dense shared-expert branch (applied to EVERY token, scheduled to
        # overlap the ep all_to_all in the dropless body — docs/moe.md)
        self.shared_expert_hidden = int(shared_expert_hidden)
        if self.shared_expert_hidden:
            hs = self.shared_expert_hidden
            self.shared_w1 = self.create_parameter(
                [d_model, hs], None, default_initializer=I.XavierNormal())
            self.shared_b1 = self.create_parameter([hs], None, is_bias=True)
            self.shared_w2 = self.create_parameter(
                [hs, d_model], None, default_initializer=I.XavierNormal())
            self.shared_b2 = self.create_parameter([d_model], None,
                                                   is_bias=True)
        self.l_aux = None
        self.tokens_dropped = None
        self.expert_counts = None
        # stable per-process tag so models with several MoE blocks report
        # distinct registry series instead of overwriting one another
        self._layer_tag = str(next(_LAYER_SEQ))
        import threading

        self._last_stats = None
        self._pending = None        # (l_aux, counts) device arrays
        # per-forward drop scalars queued AS-IS (device arrays from
        # different forwards may live on different shardings — never add
        # them to each other) and folded to host at materialize; the lock
        # serializes forwards against concurrent /metrics scrapes
        self._pending_drops = []
        self._stats_lock = threading.Lock()
        self._collector_registered = False
        self._spmd_cache = {}

    def _dispatch_plan(self, n_tokens):
        """Pick the execution mode: ('bound', ep) inside an enclosing
        shard_map with ep bound; ('spmd', ep) wrap our own shard_map over the
        global mesh; ('local', 1) single-group sparse path (GSPMD still shards
        the expert einsum via the weights' ep annotations)."""
        from paddle_tpu.distributed.collective import _bound_axes
        from paddle_tpu.distributed.mesh import get_mesh

        mesh = get_mesh()
        E = self.num_expert
        if _bound_axes((EP_AXIS,)):
            ep = int(mesh.shape[EP_AXIS]) if mesh is not None else 1
            if ep > 1 and E % ep == 0:
                return "bound", ep, mesh, ()
            return "bound", 1, mesh, ()
        if mesh is not None and EP_AXIS in mesh.shape and mesh.shape[EP_AXIS] > 1 \
                and E % mesh.shape[EP_AXIS] == 0:
            tok_axes = tuple(a for a in ("dp", "sharding", "sep", EP_AXIS)
                             if a in mesh.shape and mesh.shape[a] > 1)
            div = 1
            for a in tok_axes:
                div *= int(mesh.shape[a])
            if tok_axes and n_tokens % div == 0:
                return "spmd", int(mesh.shape[EP_AXIS]), mesh, tok_axes
        return "local", 1, mesh, ()

    def _gate_semantics(self):
        """(routing, cap_rate) from the gate, honoring train/eval mode."""
        training = bool(getattr(self, "training", True))
        routing = ()
        cap_rate = None
        if hasattr(self.gate, "routing_config"):
            routing = tuple(self.gate.routing_config(training))
        if hasattr(self.gate, "cap_rate"):
            cap_rate = self.gate.cap_rate(training)
        return routing, cap_rate

    def _body_fn(self, *, E, k, ep, tok_axes, other_axes, routing, cap_rate,
                 rng_axes=None):
        """The dispatch body for the configured mode, partial-applied with
        every static. All three bodies share one signature and the
        (out, l_aux, dropped, counts) return contract."""
        from paddle_tpu.incubate.distributed.models.moe.dropless import (
            _dropless_moe, _expert_choice_moe)

        common = dict(E=E, k=k, act=self.experts.act, ep=ep,
                      ep_axis=EP_AXIS if ep > 1 else None,
                      token_axes=tok_axes, other_axes=other_axes,
                      routing=routing, rng_axes=rng_axes)
        if self.dispatch == "dropless":
            body = (_expert_choice_moe if self.router == "expert"
                    else _dropless_moe)
            return partial(body, **common)
        return partial(_sparse_moe, cf=self.capacity_factor,
                       cap_rate=cap_rate, **common)

    def _shared_vals(self):
        if not self.shared_expert_hidden:
            return ()
        return (self.shared_w1, self.shared_b1, self.shared_w2,
                self.shared_b2)

    def _spmd_fn(self, mesh, ep, tok_axes, n_tokens, E, k, routing, cap_rate):
        """Build (and cache) the jitted shard_map dispatch program — rebuilt
        per forward it would retrace every step."""
        from paddle_tpu.core.flags import flag

        # the dropless body reads these flags at TRACE time, so they are
        # part of the cached program's identity
        key = (mesh, ep, tok_axes, n_tokens, E, k, self.capacity_factor,
               routing, cap_rate, self.dispatch, self.router,
               self.shared_expert_hidden, int(flag("moe_block_rows")),
               flag("moe_gmm_backend"))
        cached = self._spmd_cache.get(key)
        if cached is not None:
            return cached
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        other = tuple(a for a in mesh.axis_names if a not in tok_axes)
        body = self._body_fn(E=E, k=k, ep=ep, tok_axes=tok_axes,
                             other_axes=other, routing=routing,
                             cap_rate=cap_rate)
        tok_spec = P(tok_axes, None)
        w_spec = P(EP_AXIS, None, None)
        in_specs = (tok_spec, P(tok_axes, None), P(), w_spec, w_spec, w_spec,
                    w_spec)
        if self.shared_expert_hidden and self.dispatch == "dropless":
            # the shared-expert MLP is replicated (every rank runs the
            # dense branch over its own tokens, inside the body so it
            # overlaps the a2a)
            in_specs = in_specs + (P(), P(), P(), P())
        out_specs = (tok_spec, P(), P(), P())
        smapped = jax.jit(_shard_map(body, mesh, in_specs, out_specs))

        def fn(*vals):
            placed = [jax.device_put(v, NamedSharding(mesh, s))
                      for v, s in zip(vals, in_specs)]
            return smapped(*placed)

        self._spmd_cache[key] = fn
        return fn

    def forward(self, x):
        """x: [B, S, d] (or [N, d])."""
        from paddle_tpu.distributed.fleet.rng import current_dropout_key

        orig_shape = x.shape
        d = orig_shape[-1]
        x2 = x.reshape([-1, d])
        n_tokens = x2.shape[0]
        E, k = self.num_expert, self.top_k
        logits = self.gate(x2)  # [N, E]
        routing, cap_rate = self._gate_semantics()
        mode, ep, mesh, tok_axes = self._dispatch_plan(n_tokens)
        # routing RNG only drawn when the gate actually randomizes, so
        # deterministic gates stay bitwise-reproducible run to run
        needs_rng = any(kk in dict(routing) and dict(routing)[kk]
                        for kk in ("random_routing", "switch_eps"))
        rng = current_dropout_key() if needs_rng else jax.random.key(0)
        rng_bits = jax.random.key_data(rng)

        if mode == "spmd":
            fn = self._spmd_fn(mesh, ep, tok_axes, n_tokens, E, k,
                               routing, cap_rate)
        else:
            ep_eff = ep if mode == "bound" else 1
            from paddle_tpu.distributed.collective import _bound_axes
            rng_axes = (_bound_axes(("dp", "sharding", "sep", EP_AXIS))
                        if mode == "bound" else ())
            fn = self._body_fn(E=E, k=k, ep=ep_eff, tok_axes=(),
                               other_axes=(), routing=routing,
                               cap_rate=cap_rate, rng_axes=rng_axes)

        shared = (self._shared_vals()
                  if self.dispatch == "dropless" else ())
        out, l_aux, dropped, counts = apply_op(
            fn, x2, logits, rng_bits,
            self.experts.w1, self.experts.b1, self.experts.w2,
            self.experts.b2, *shared,
            name="moe_dispatch", rng_args=(2,),
        )
        if self.shared_expert_hidden and self.dispatch == "capacity":
            # capacity path: the dense shared branch rides outside the
            # dispatch program (no a2a in eager scope to overlap with)
            h = F.linear(x2, self.shared_w1) + self.shared_b1
            h = F.gelu(h) if self.experts.act == "gelu" else F.relu(h)
            out = out + (F.linear(h, self.shared_w2) + self.shared_b2)
        self.l_aux = l_aux
        self.tokens_dropped = dropped
        self.expert_counts = counts
        self._publish_stats(l_aux, dropped, counts)
        return out.reshape(orig_shape)

    def _publish_stats(self, l_aux, dropped, counts):
        """Queue per-expert load-balance telemetry for the observability
        registry (docs/observability.md) — eager forwards only: under jit
        the values are tracers and the numbers instead ride
        CompiledTrainStep's step-telemetry vector. NO host sync here: the
        device arrays are held (drops accumulate with one async device
        add) and materialize at scrape time via a registry collector (the
        PR-13 hot-path-pays-nothing idiom) or on `last_stats` reads."""
        vals = [getattr(v, "_value", v) for v in (l_aux, dropped, counts)]
        if any(isinstance(v, jax.core.Tracer) for v in vals):
            return
        with self._stats_lock:
            self._pending = (vals[0], vals[2])
            self._pending_drops.append(vals[1])
            if len(self._pending_drops) >= 256:
                # bound the queue on scrape-free runs: fold to one host
                # float (the amortized 1/256 sync)
                import numpy as np

                total = float(sum(float(np.asarray(v))
                                  for v in self._pending_drops))
                self._pending_drops = [total]
        if not self._collector_registered:
            import weakref

            from paddle_tpu.observability import metrics as obs_metrics

            # close over a weakref (a bound method would pin the layer
            # alive in the registry forever); the owner weakref drops the
            # collector when the layer dies
            wself = weakref.ref(self)

            def _collect(reg):
                s = wself()
                if s is not None:
                    s._materialize(reg)

            obs_metrics.registry().add_collector(_collect, owner=self)
            self._collector_registered = True

    def _materialize(self, reg):
        """Fold the pending device stats into the registry (scrape time /
        last_stats reads). The read-and-clear runs under the stats lock so
        a /metrics scrape racing a last_stats read can neither double-count
        drops nor discard a concurrent forward's pending batch."""
        import numpy as np

        with self._stats_lock:
            if self._pending is None and not self._pending_drops:
                return
            aux_dev, counts_dev = self._pending or (None, None)
            dropped_v = float(sum(float(np.asarray(v))
                                  for v in self._pending_drops))
            self._pending = None
            self._pending_drops = []
        tag = self._layer_tag
        reg.counter("moe_dropped_tokens_total",
                    "tokens dropped by capacity-bucketed MoE dispatch "
                    "(identically 0 on the dropless path)").inc(dropped_v)
        if aux_dev is None:
            return
        aux_v = float(np.asarray(aux_dev))
        counts_v = np.asarray(counts_dev, dtype=np.float64)
        mean = float(counts_v.mean()) or 1.0
        imbalance = float(counts_v.max()) / mean
        reg.gauge("moe_aux_loss",
                  "GShard load-balance aux loss of the last eager MoE "
                  "forward", labels=("layer",)).labels(layer=tag).set(aux_v)
        reg.gauge("moe_load_imbalance",
                  "max/mean per-expert processed-token count of the last "
                  "eager MoE forward",
                  labels=("layer",)).labels(layer=tag).set(imbalance)
        g = reg.gauge("moe_expert_tokens",
                      "processed tokens per expert (last eager MoE "
                      "forward)", labels=("layer", "expert"))
        for e, c in enumerate(counts_v):
            g.labels(layer=tag, expert=str(e)).set(float(c))
        self._last_stats = {
            "aux_loss": aux_v, "dropped_tokens": dropped_v,
            "expert_tokens": counts_v.tolist(),
            "imbalance_max_over_mean": imbalance,
        }

    @property
    def last_stats(self):
        """Stats dict of the most recent eager forward (materializes any
        pending device values — the only place the host blocks)."""
        from paddle_tpu.observability import metrics as obs_metrics

        self._materialize(obs_metrics.registry())
        return self._last_stats
