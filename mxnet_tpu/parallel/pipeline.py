"""Pipeline parallelism over a ``pipe`` mesh axis (net-new capability:
MXNet 1.x has no pipeline schedule — SURVEY §2.4 #32 marks PP absent; the
reference's closest tool is hand `ctx_group` placement).

Design (TPU-idiomatic SPMD):
- L = v*P layers live on P devices; device d owns layers {d, P+d, ...}
  (params stacked on a leading layer axis, sharded over ``pipe``);
- microbatches stream through a static tick loop; activations hop to the
  next stage with ``lax.ppermute`` (one ICI neighbor hop per tick) and
  wrap around the ring v times — the **interleaved/circular schedule**
  (Megatron-LM's interleaved 1F1B shape): with v virtual stages per
  device the bubble shrinks from GPipe's (P-1)·v layer-times to (P-1),
  i.e. fraction (P-1)/(v·m+P-1);
- ``v=1`` degenerates to plain GPipe;
- heterogeneous ends: optional ``embed_fn`` runs on the injection edge
  (stage 0) and ``head_fn`` on the exit edge (last stage), so a real
  model (embedding → N blocks → head) maps without padding tricks. Both
  are evaluated redundantly on every device (their cost is O(1%) of the
  blocks in a transformer) and selected by device index — predication
  instead of per-device branching, the XLA-friendly choice;
- the whole schedule is differentiable end-to-end: jax transposes the
  ppermute chain, so backward is the reverse pipeline automatically —
  activation stashing falls out of the scan's saved residuals instead of
  hand-rolled 1F1B bookkeeping.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..base import MXNetError


__all__ = ["pipeline_apply", "pipeline_schedule_info"]


def pipeline_schedule_info(n_stages, num_microbatches, num_virtual=1):
    """Static schedule accounting: total ticks, busy ticks per device,
    and the bubble fraction (P-1)/(v*m+P-1). One "tick" costs one layer
    application (GPipe packs v layers per tick into each of its m+P-1
    ticks, so its bubble is v*(P-1) layer-times — same formula with the
    tick cost scaled)."""
    p, m, v = int(n_stages), int(num_microbatches), int(num_virtual)
    ticks = v * m + p - 1
    busy = v * m
    return {"ticks": ticks, "busy": busy,
            "bubble_fraction": (p - 1) / ticks}


def pipeline_apply(stage_fn, stage_params, x, mesh: Mesh = None,
                   axis_name="pipe", num_microbatches=None,
                   num_virtual_stages=1, embed_fn=None, embed_params=None,
                   head_fn=None, head_params=None, data_axis=None,
                   params_are_split=False, stage_ctx=False):
    """Run ``x`` through L = num_virtual_stages * P pipeline layers.

    stage_fn(params_l, h) -> h'       same signature for every layer;
        activations must share one shape (they ride one ppermute ring)
    stage_ctx: when True, stage_fn is instead called as
        ``stage_fn(params_l, h, ctx)`` with ``ctx = {"layer": <traced
        int, virtual pass * P + device = the layer index>, "tick":
        <traced int, schedule tick>, "shard": <traced int, data-axis
        shard index; 0 when data_axis is None>}`` INSIDE the scan body.
        Fold all three into any RNG key the stage consumes: (layer,
        tick) uniquely identifies one (layer, microbatch) application
        and ``shard`` separates the dp ranks' slices, so dropout masks
        are independent across stages, microbatches AND data shards
        instead of one mask reused everywhere (ADVICE r5 medium).
        ``shard`` must stay 0 when data_axis is None — the batch is
        replicated there and per-device keys would desync the
        replicated computation
    stage_params: pytree, leaves stacked (L, ...) — layer l lives on
        device l % P (virtual pass l // P)
    x: (B, ...) global batch, split into ``num_microbatches`` chunks
        (default: P; interleaving needs m >= P)
    embed_fn(embed_params, micro) -> h   optional stage-0 prologue (e.g.
        token embedding); applied to each microbatch as it enters
    head_fn(head_params, outs) -> y      optional last-stage epilogue
        (e.g. vocab projection); applied batched to the collected
        pipeline outputs
    data_axis: name of a mesh axis to data-parallel over — each dp rank
        pipelines its own slice of every microbatch (independent pipe
        rings per dp shard); None replicates the batch across non-pipe
        axes (the pre-round-5 behavior)
    params_are_split: stage_params leaves already carry the (v, P, ...)
        leading dims (the layout a trainer keeps so optimizer state can
        shard over ``pipe``); False means flat (L, ...) stacks

    Returns the (B, ...) output of the final stage (after head_fn if
    given), replicated across the pipe axis (sharded over ``data_axis``
    when given).
    """
    from .mesh import current_mesh
    mesh = mesh or current_mesh()
    if axis_name not in mesh.axis_names:
        raise MXNetError(f"mesh has no axis {axis_name!r}")
    p_size = int(mesh.shape[axis_name])
    v = int(num_virtual_stages)
    m = int(num_microbatches or p_size)
    b = x.shape[0]
    if b % m:
        raise MXNetError(f"batch {b} not divisible by {m} microbatches")
    if v > 1 and m < p_size:
        raise MXNetError(f"interleaved schedule needs microbatches >= "
                         f"pipeline depth ({m} < {p_size}): the wrapped "
                         f"activation of pass p must be back before its "
                         f"re-injection tick")
    leaves = jax.tree_util.tree_leaves(stage_params)
    if params_are_split:
        if leaves and leaves[0].shape[:2] != (v, p_size):
            raise MXNetError(f"params_are_split leaves must lead with "
                             f"(v, P) = ({v}, {p_size}); got "
                             f"{leaves[0].shape[:2]}")
    elif leaves and leaves[0].shape[0] != v * p_size:
        raise MXNetError(f"stage_params leading dim "
                         f"{leaves[0].shape[0]} != num_virtual_stages * "
                         f"pipe axis = {v * p_size}")
    if data_axis is not None:
        if data_axis not in mesh.axis_names:
            raise MXNetError(f"mesh has no axis {data_axis!r}")
        d_size = int(mesh.shape[data_axis])
        if (b // m) % d_size:
            raise MXNetError(
                f"per-microbatch size {b // m} (batch {b} / {m} "
                f"microbatches) not divisible by data axis "
                f"{data_axis}={d_size}")
    micro = x.reshape((m, b // m) + x.shape[1:])
    ticks = v * m + p_size - 1

    if not params_are_split:
        # (L, ...) -> (v, P, ...): pass-major split, P axis sharded
        stage_params = jax.tree_util.tree_map(
            lambda a: a.reshape((v, p_size) + a.shape[1:]), stage_params)
    param_spec = jax.tree_util.tree_map(
        lambda _: P(None, axis_name), stage_params)
    rep = jax.tree_util.tree_map(lambda _: P(), (embed_params,
                                                 head_params))
    perm = [(i, (i + 1) % p_size) for i in range(p_size)]

    def body(params_local, e_params, h_params, micro_all):
        # params_local leaves: (v, 1, ...) — this device's layer stack
        d = lax.axis_index(axis_name)
        # dp shard identity for stage_ctx keys; MUST be 0 when the batch
        # is replicated (no data_axis) or per-device masks would desync
        # the replicated computation
        shard = lax.axis_index(data_axis) if data_axis is not None else 0
        is_first = d == 0
        is_last = d == p_size - 1
        micro_bs = micro_all.shape[1]

        # embed once, before the scan: inject() reads the pre-embedded
        # buffer so the (possibly expensive) lookup runs m times, not
        # P*(v*m+P-1) times
        embedded = micro_all if embed_fn is None else \
            jax.vmap(lambda mb: embed_fn(e_params, mb))(micro_all)

        def inject(t, wrap_buf):
            """Input for the unit device 0 starts at tick t: microbatch
            t%m, pass t//m — a fresh (embedded) microbatch on pass 0, a
            wrapped activation afterwards."""
            i0 = jnp.mod(t, m)
            fresh = embedded[i0]
            wrapped = jnp.take(wrap_buf, i0, axis=0)
            return jnp.where(t // m > 0, wrapped,
                             fresh.astype(wrapped.dtype))

        def tick(carry, t):
            wrap_buf, cur = carry
            inp = jnp.where(is_first, inject(t, wrap_buf), cur)
            # unit on this device: u = t - d; its virtual pass picks the
            # layer params (device d, pass p -> layer p*P + d)
            p_u = jnp.clip((t - d) // m, 0, v - 1)
            params_u = jax.tree_util.tree_map(
                lambda a: jnp.take(a, p_u, axis=0)[0], params_local)
            if stage_ctx:
                y = stage_fn(params_u, inp,
                             {"layer": p_u * p_size + d, "tick": t,
                              "shard": shard})
            else:
                y = stage_fn(params_u, inp)
            nxt = lax.ppermute(y, axis_name, perm)
            # what device 0 just received from device P-1 is unit
            # t-(P-1) finishing a pass: stash it for re-injection
            wrapped_i = jnp.mod(t - (p_size - 1), m)
            wrap_buf = lax.dynamic_update_index_in_dim(
                wrap_buf, nxt, wrapped_i, axis=0)
            return (wrap_buf, nxt), y

        probe_params = jax.tree_util.tree_map(lambda a: a[0, 0],
                                              params_local)
        probe = (stage_fn(probe_params, embedded[0],
                          {"layer": 0, "tick": 0, "shard": 0})
                 if stage_ctx else stage_fn(probe_params, embedded[0]))
        act0 = jnp.zeros_like(probe)
        # scan wants the initial carry to have the type the body returns,
        # varying manual axes included: what a tick hands on came through
        # ppermute over the pipe axis (and is a shard of the batch), while
        # the probe of a stage that ignores its params or its input is not
        # varying over that axis yet
        want = {axis_name} | ({data_axis} if data_axis is not None else set())
        missing = tuple(sorted(want - set(jax.typeof(act0).vma)))
        if missing:
            act0 = lax.pcast(act0, missing, to="varying")
        wrap0 = jnp.zeros((m,) + act0.shape, act0.dtype) + act0
        _, ys = lax.scan(tick, (wrap0, act0), jnp.arange(ticks))
        # microbatch i exits its LAST pass on device P-1 at tick
        # (v-1)*m + i + (P-1)
        outs = ys[(v - 1) * m + p_size - 1:]
        if head_fn is not None:
            outs = head_fn(h_params,
                           outs.reshape((m * micro_bs,) + outs.shape[2:]))
            outs = outs.reshape((m, micro_bs) + outs.shape[1:])
        outs = jnp.where(is_last, outs, jnp.zeros_like(outs))
        outs = lax.psum(outs, axis_name)       # broadcast from last stage
        return outs                            # (m, micro_bs_local, ...)

    batch_spec = P(None, data_axis) if data_axis is not None else P()
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(param_spec, rep[0], rep[1], batch_spec),
        out_specs=batch_spec)
    outs = fn(stage_params, embed_params, head_params, micro)
    return outs.reshape((b,) + outs.shape[2:])
