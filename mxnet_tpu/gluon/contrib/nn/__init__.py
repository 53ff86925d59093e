"""gluon.contrib.nn (ref: python/mxnet/gluon/contrib/nn/basic_layers.py)."""
from __future__ import annotations

import weakref

import numpy as np

from ....base import MXNetError
from ...block import HybridBlock
from ...nn import HybridSequential, Sequential, SyncBatchNorm

__all__ = ["Concurrent", "FeedForward", "HybridConcurrent", "Identity",
           "MoEFFN", "RoutedExperts", "SyncBatchNorm"]


class HybridConcurrent(HybridSequential):
    """Parallel children concatenated on ``axis``
    (ref: contrib/nn HybridConcurrent — Inception-style branches)."""

    def __init__(self, axis=-1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.axis = axis

    def hybrid_forward(self, F, x):
        return F.concat(*[block(x) for block in self._children.values()],
                        dim=self.axis)

    def forward(self, x):
        from ... import nn as _nn  # noqa: F401
        from .... import ndarray as F
        return F.concat(*[block(x) for block in self._children.values()],
                        dim=self.axis)


class Concurrent(Sequential):
    """Eager variant (ref: contrib/nn Concurrent)."""

    def __init__(self, axis=-1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.axis = axis

    def forward(self, x):
        from .... import ndarray as F
        return F.concat(*[block(x) for block in self._children.values()],
                        dim=self.axis)


class Identity(HybridBlock):
    """ref: contrib/nn Identity."""

    def hybrid_forward(self, F, x):
        return x


class MoEFFN(HybridBlock):
    """Top-k routed mixture-of-experts FFN as a drop-in Gluon layer
    (net-new TPU capability — the reference has no MoE layer; routing
    follows GShard/Switch, SURVEY §2.4 #32 expert-parallel row).

    Drop it where a ``PositionwiseFFN`` would go::

        ffn = gluon.contrib.nn.MoEFFN(units=512, hidden_size=2048,
                                      num_experts=8, k=2)
        net = ... ffn(x) ...                      # x: (B, T, units)
        mesh = parallel.make_mesh({"data": 1, "expert": 8})
        trainer = parallel.ShardedTrainer(net, loss, "adam", ...,
            mesh=mesh,
            param_rules=[(r".*expert_.*", PartitionSpec("expert"))])

    Under a mesh whose ``expert`` axis matches ``num_experts`` the forward
    dispatches tokens with two ``all_to_all``s and runs ONLY the local
    expert per device at ``capacity_factor`` buffer size
    (parallel.moe_apply_topk — per-device compute O(k·tokens/E); every
    (token, expert) pair past an expert's capacity is DROPPED, its token
    gets nothing from that expert); on any
    other mesh (or eagerly on one device) it falls back to the dense
    formulation: every expert over every token, gate-weighted — same
    math except no capacity dropping, so tiny-scale runs are exact.

    For experts that outnumber the chips (each chip holds several, routes
    over all of them and drops nothing) use :class:`RoutedExperts`.

    Inside a ShardedTrainer step the Switch load-balancing loss is added
    to the training objective automatically (``aux_loss_weight`` times
    it; perfect balance ⇒ aux = k). Eager forwards additionally expose
    the concrete value as ``_last_aux_loss`` for logging — traced steps
    do NOT update it (a traced value would be a leaked tracer).
    """

    def __init__(self, units, hidden_size, num_experts, k=2,
                 capacity_factor=1.5, activation="gelu",
                 aux_loss_weight=0.01, expert_axis="expert", **kwargs):
        super().__init__(**kwargs)
        self._units, self._hidden = int(units), int(hidden_size)
        self._ne, self._k = int(num_experts), int(k)
        self._cf = float(capacity_factor)
        self._act = activation
        self.aux_loss_weight = float(aux_loss_weight)
        self._expert_axis = expert_axis
        self._last_aux_loss = None
        e, u, h = self._ne, self._units, self._hidden
        with self.name_scope():
            self.gate_weight = self.params.get("gate_weight", shape=(e, u))
            self.expert_w1 = self.params.get("expert_w1", shape=(e, u, h))
            self.expert_b1 = self.params.get("expert_b1", shape=(e, h),
                                             init="zeros")
            self.expert_w2 = self.params.get("expert_w2", shape=(e, h, u))
            self.expert_b2 = self.params.get("expert_b2", shape=(e, u),
                                             init="zeros")

    def _activate(self, h):
        import jax
        fns = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
               "tanh": jax.numpy.tanh}
        try:
            return fns[self._act](h)
        except KeyError:
            raise MXNetError(f"MoEFFN: unknown activation {self._act!r}; "
                             f"one of {sorted(fns)}")

    def hybrid_forward(self, F, x, gate_weight, expert_w1, expert_b1,
                       expert_w2, expert_b2):
        import jax
        import jax.numpy as jnp
        from .... import ndarray as nd_mod
        from ....parallel.mesh import current_mesh
        from ....parallel.moe import moe_apply_topk

        xd = x._data if isinstance(x, nd_mod.NDArray) else jnp.asarray(x)
        gw, w1, b1, w2, b2 = (a._data if isinstance(a, nd_mod.NDArray)
                              else jnp.asarray(a)
                              for a in (gate_weight, expert_w1, expert_b1,
                                        expert_w2, expert_b2))
        shape = xd.shape
        tok = xd.reshape(-1, shape[-1])
        gates = tok.astype(jnp.float32) @ gw.astype(jnp.float32).T  # (N, E)

        mesh = current_mesh()
        n_tok = tok.shape[0]
        axis_configured = self._expert_axis in mesh.axis_names
        size_ok = (axis_configured
                   and int(mesh.shape[self._expert_axis]) == self._ne)
        tokens_ok = n_tok % self._ne == 0
        use_a2a = size_ok and tokens_ok
        if axis_configured and not use_a2a:
            # the mesh asked for expert parallelism but the a2a path is
            # rejected: going dense silently would lose expert
            # parallelism AND change training numerics (no capacity
            # dropping) with no signal — the misconfiguration class
            # ADVICE r5 flags and elastic training (ROADMAP items 4/5)
            # cannot tolerate. Warn loudly; the forward still runs.
            import warnings
            if not size_ok:
                why = (f"mesh axis {self._expert_axis!r} has size "
                       f"{int(mesh.shape[self._expert_axis])} but "
                       f"num_experts={self._ne}")
            else:
                why = (f"token count {n_tok} is not divisible by "
                       f"num_experts={self._ne}")
            warnings.warn(
                f"MoEFFN: expert-parallel all-to-all path rejected "
                f"({why}); falling back to the DENSE formulation — "
                f"O(E·tokens) compute and different numerics (no "
                f"capacity dropping). Fix the mesh/batch shape, or use "
                f"a mesh without the {self._expert_axis!r} axis to "
                f"silence this.", RuntimeWarning, stacklevel=2)
        if use_a2a:
            def expert_fn(params_e, t):
                ew1, eb1, ew2, eb2 = params_e
                h = self._activate(t.astype(jnp.float32) @ ew1 + eb1)
                return h @ ew2 + eb2
            if not isinstance(xd, jax.core.Tracer):
                # eager call: stage operands onto the mesh (replicated) so
                # the shard_map sees mesh-addressable arrays; inside a
                # ShardedTrainer trace GSPMD handles placement instead
                from jax.sharding import NamedSharding, PartitionSpec
                rep = NamedSharding(mesh, PartitionSpec())
                tok, gates, w1, b1, w2, b2 = (
                    jax.device_put(a, rep)
                    for a in (tok, gates, w1, b1, w2, b2))
            y, aux, _ = moe_apply_topk(
                expert_fn, (w1, b1, w2, b2), gates, tok, k=self._k,
                capacity_factor=self._cf, mesh=mesh,
                axis_name=self._expert_axis)
            if not isinstance(xd, jax.core.Tracer):
                # bring the eager result home so downstream single-device
                # eager math doesn't mix committed device sets
                y = jax.device_put(np.asarray(y))
                aux = jax.device_put(np.asarray(aux))
        else:
            # dense fallback: every expert over every token, gate-weighted
            probs = jax.nn.softmax(gates, axis=-1)
            top_p, top_e = jax.lax.top_k(probs, self._k)
            if self._k > 1:
                top_p = top_p / jnp.maximum(
                    top_p.sum(-1, keepdims=True), 1e-9)
            onehot = jax.nn.one_hot(top_e, self._ne, dtype=jnp.float32)
            wgt = (onehot * top_p[..., None]).sum(1)        # (N, E)
            h = self._activate(jnp.einsum(
                "nd,edh->neh", tok.astype(jnp.float32), w1) + b1)
            ye = jnp.einsum("neh,ehd->ned", h, w2) + b2
            y = ((ye * wgt[..., None]).sum(1)).astype(xd.dtype)
            load = onehot.sum(1).mean(0)                     # (E,)
            importance = probs.mean(0)
            aux = self._ne * jnp.sum(load * importance)
        # trace channel for ShardedTrainer's objective (read-and-cleared by
        # _collect_aux_losses so no tracer outlives its trace); the public
        # _last_aux_loss only ever holds concrete values (eager forwards)
        self._trace_aux_loss = aux
        if not isinstance(aux, jax.core.Tracer):
            self._last_aux_loss = aux
        y = y.astype(xd.dtype).reshape(shape[:-1] + (self._units,))
        return nd_mod.NDArray(y, _skip_device_put=True)


class FeedForward(HybridBlock):
    """``W2 relu(W1 x)^2`` without biases, or with ``gated`` ``W2 (silu(g) *
    u)``, ``[g | u] = W1 x`` (``W1`` of ``2 * hidden_size`` rows): the forms
    of :class:`RoutedExperts`' experts, and its shared expert."""

    def __init__(self, units, hidden_size, gated=False, **kwargs):
        super().__init__(**kwargs)
        from ...nn import Dense
        self._gated = bool(gated)
        with self.name_scope():
            self.w_in = Dense(hidden_size * (2 if gated else 1),
                              flatten=False, use_bias=False, in_units=units,
                              prefix="in_")
            self.w_out = Dense(units, flatten=False, use_bias=False,
                               in_units=hidden_size, prefix="out_")

    def hybrid_forward(self, F, x):
        if self._gated:
            return self.w_out(F.contrib.swiglu(self.w_in(x)))
        return self.w_out(F.square(F.relu(self.w_in(x))))


class RoutedExperts(HybridBlock):
    """A routed-expert feed-forward layer that holds a share of the experts:
    one chip's layer under expert parallelism, where the experts outnumber
    the chips (net-new TPU capability; ops in ``ops/moe.py``).

    The router scores every token over **all** ``num_experts`` in float32
    (``scoring`` ``"sigmoid"`` of ``x W_r^T``, or its ``"softmax"`` over the
    experts), takes the ``k`` largest of ``score + bias`` (``router_bias``:
    a buffer no gradient reaches, the correction of auxiliary-loss-free
    balancing; it chooses and does not weigh), with ``n_group`` > 1 only
    among the experts of the ``topk_group`` groups whose largest ``score +
    bias`` is largest (group-limited choice over ``n_group`` runs of
    consecutive experts), and weighs the chosen by their scores (divided by
    their sum if ``norm_topk_prob``, times ``scaling_factor``). The layer
    holds the ``experts_held`` experts from ``first_expert`` on (all of them
    by default) and computes, for the pairs whose expert it holds, ``W2_e
    relu(W1_e x)^2`` (``gated``: ``W2_e (silu(g) * u)``, ``[g | u] = W1_e
    x``) as two grouped products over rows ordered by expert; pairs whose
    expert lives on another chip add nothing here, and the partial result
    is what the block returns (the exchange that would add the other chips'
    parts is not this block's). No pair is dropped, unless
    ``capacity_factor`` > 0: then this chip is one device of an expert-
    parallel group and computes at most that factor times its share of the
    pairs (``ops.moe.device_budget``), those of largest score, as
    DeepSeek-V2 trains; the routes mark the others dropped (id less
    ``num_experts``). A shared expert of width ``shared_hidden_size`` (0:
    none), the same form, runs on every token and is added.

    ``x``: (B, S, units) -> (B, S, units); with ``return_routes`` the
    block returns ``(y, routes, rows, scores)``: the chosen experts (B, S,
    k), int32, ids among all ``num_experts``; the rows of each held expert
    that it computed (experts_held,), int32; and the router's float32
    scores (B, S, num_experts), against which a comparison can hold the
    choice.

    In training mode the block adds the rows each held expert received to
    ``expert_rows`` and one to ``steps`` (int32 auxiliary state, carried
    through a compiled step as BatchNorm's running statistics are: no host
    callback); ``mxnet_tpu.observability`` exports them as
    ``mxnet_tpu_moe_expert_rows{layer,expert}`` and
    ``mxnet_tpu_moe_steps{layer}`` whenever a snapshot is taken.

    With ``bias_update_rate`` > 0 the bias moves, as auxiliary-loss-free
    balancing trains it (DeepSeek-V3, arXiv:2412.19437 sec. 2.1.2): in each
    training step the block counts the pairs the router sent to each of
    the ``num_experts`` experts, ``c``, and rebinds ``router_bias`` to
    ``b + rate * sign(mean(c) - c)`` once, after the step's choice (a
    recomputed forward, ``HybridBlock.recompute()``, carries the state of
    the first); it adds ``c`` to the int32 counter ``router_rows``
    (``expert_load()``'s ``"router_rows"``, exported as
    ``mxnet_tpu_moe_router_rows{layer,expert}``). At 0 the bias is frozen
    and the block has no such counter.
    """

    def __init__(self, units, hidden_size, num_experts, k=2, first_expert=0,
                 experts_held=None, norm_topk_prob=True, scaling_factor=1.0,
                 shared_hidden_size=0, return_routes=False,
                 scoring="sigmoid", n_group=1, topk_group=1, gated=False,
                 capacity_factor=0.0, norm_eps=1e-20, bias_update_rate=0.0,
                 **kwargs):
        super().__init__(**kwargs)
        held = num_experts - first_expert if experts_held is None \
            else experts_held
        if not (0 <= first_expert and 0 < held
                and first_expert + held <= num_experts):
            raise MXNetError(
                f"RoutedExperts: experts {first_expert}..{first_expert + held - 1} "
                f"are not among {num_experts}")
        if not 0 < k <= num_experts:
            raise MXNetError(f"RoutedExperts: k {k} of {num_experts} experts")
        if num_experts % n_group or not 0 < topk_group <= n_group:
            raise MXNetError(
                f"RoutedExperts: {num_experts} experts in n_group {n_group}, "
                f"topk_group {topk_group}")
        self._experts, self._k = int(num_experts), int(k)
        self._first, self._held = int(first_expert), int(held)
        self._gated = bool(gated)
        self._route = dict(top_k=self._k,
                           norm_topk_prob=bool(norm_topk_prob),
                           scaling_factor=float(scaling_factor),
                           scoring=str(scoring), n_group=int(n_group),
                           topk_group=int(topk_group),
                           capacity_factor=float(capacity_factor),
                           first_expert=self._first, experts_held=self._held,
                           norm_eps=float(norm_eps))
        self._bias_rate = float(bias_update_rate)
        self._return_routes = bool(return_routes)
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(num_experts, units))
            self.router_bias = self.params.get(
                "router_bias", shape=(num_experts,), init="zeros",
                differentiable=False)
            self.expert_w1 = self.params.get(
                "expert_w1",
                shape=(held, units, hidden_size * (2 if gated else 1)))
            self.expert_w2 = self.params.get(
                "expert_w2", shape=(held, hidden_size, units))
            self.expert_rows = self.params.get(
                "expert_rows", shape=(held,), init="zeros", dtype="int32",
                differentiable=False)
            self.steps = self.params.get(
                "steps", shape=(1,), init="zeros", dtype="int32",
                differentiable=False)
            self.router_rows = self.params.get(
                "router_rows", shape=(num_experts,), init="zeros",
                dtype="int32", differentiable=False) \
                if self._bias_rate > 0 else None
            self.shared = FeedForward(
                units, shared_hidden_size, gated=gated,
                prefix="shared_") if shared_hidden_size else None
        _live_routed.add(self)

    @property
    def experts_held(self):
        """``(first, count)`` of the experts this block computes."""
        return self._first, self._held

    def hybrid_forward(self, F, x, router_weight, router_bias, expert_w1,
                       expert_w2, expert_rows, steps, router_rows=None):
        from .... import autograd
        from ....observability.instrument import device_scope
        from ....ops.moe import device_budget
        with device_scope("moe.router"):
            weights, routes, scores = F.contrib.moe_route(
                x, router_weight, router_bias, **self._route)
        factor = self._route["capacity_factor"]
        capacity = device_budget(int(np.prod(x.shape[:-1])), self._k,
                                 self._held, self._experts,
                                 factor) if factor > 0 else 0
        # the op opens moe.dispatch, moe.experts and moe.combine itself
        y, rows = F.contrib.moe_experts(
            x, weights, routes, expert_w1, expert_w2,
            first_expert=self._first, num_experts=self._experts,
            gated=self._gated, capacity=capacity)
        if autograd.is_training():
            expert_rows._rebind(expert_rows._data + rows._data)
            steps._rebind(steps._data + 1)
            if router_rows is not None:
                counts = balance_counts(routes._data, self._experts)
                router_rows._rebind(router_rows._data + counts)
                router_bias._rebind(moved_bias(router_bias._data, counts,
                                               self._bias_rate))
        if self.shared is not None:
            with device_scope("moe.shared"):
                y = y + self.shared(x)
        return (y, routes, rows, scores) if self._return_routes else y


def balance_counts(routes, experts):
    """The pairs ``routes`` (..., k) sent to each of the ``experts``, (E,)
    int32; a pair dropped past a device's budget (id less E) was sent."""
    import jax.numpy as jnp
    return jnp.bincount(jnp.mod(routes, experts).reshape(-1),
                        length=experts).astype(jnp.int32)


def moved_bias(bias, counts, rate):
    """``bias + rate * sign(mean(counts) - counts)``: auxiliary-loss-free
    balancing's step, in the bias's dtype."""
    import jax.numpy as jnp
    load = counts.astype(jnp.float32)
    return (bias.astype(jnp.float32) + rate * jnp.sign(
        jnp.mean(load) - load)).astype(bias.dtype)


# -- the expert load, for observability ---------------------------------------
_live_routed = weakref.WeakSet()

EXPERT_ROWS_METRIC = "mxnet_tpu_moe_expert_rows"
EXPERT_STEPS_METRIC = "mxnet_tpu_moe_steps"
ROUTER_ROWS_METRIC = "mxnet_tpu_moe_router_rows"


def expert_load():
    """``{layer: {"first_expert", "rows": [...], "steps"}}`` of the live
    :class:`RoutedExperts` blocks whose counters hold concrete arrays: the
    rows each held expert has received, summed over the training steps, and
    the count of those steps; a block whose bias moves adds
    ``"router_rows"``, the pairs its router sent to each of all the experts
    over the same steps. Reads the device."""
    import jax
    out = {}
    for block in list(_live_routed):
        counters = [block.expert_rows, block.steps] + (
            [block.router_rows] if block.router_rows is not None else [])
        if not all(c._data for c in counters):
            continue
        arrays = [c._data[0]._data for c in counters]
        if any(isinstance(a, jax.core.Tracer) for a in arrays):
            continue
        said = {"first_expert": block._first,
                "rows": [int(n) for n in np.asarray(arrays[0])],
                "steps": int(np.asarray(arrays[1])[0])}
        if len(arrays) == 3:
            said["router_rows"] = [int(n) for n in np.asarray(arrays[2])]
        out[block.prefix.rstrip("_")] = said
    return out


def _export_expert_load(registry):
    load = expert_load()
    if not load:
        return
    rows = registry.gauge(
        EXPERT_ROWS_METRIC, "rows a held expert has received, summed over "
        "the training steps", ("layer", "expert"))
    steps = registry.gauge(
        EXPERT_STEPS_METRIC, "training steps a routed-expert layer has "
        "counted its rows over", ("layer",))
    for layer, said in load.items():
        steps.labels(layer=layer).set(said["steps"])
        for i, n in enumerate(said["rows"]):
            rows.labels(layer=layer,
                        expert=str(said["first_expert"] + i)).set(n)
    routed = {layer: said["router_rows"] for layer, said in load.items()
              if "router_rows" in said}
    if routed:
        gauge = registry.gauge(
            ROUTER_ROWS_METRIC, "pairs a router sent to an expert, summed "
            "over the training steps", ("layer", "expert"))
        for layer, counts in routed.items():
            for i, n in enumerate(counts):
                gauge.labels(layer=layer, expert=str(i)).set(n)


from ....observability import metrics as _metrics     # noqa: E402

_metrics.register_collector(_export_expert_load)
