"""Schedule-driven pipelined execution (paper §III, Eq 3–5).

The layer stack is partitioned into ``PP`` stages along the pipeline mesh
axis (the inter-pod "pod" axis in the production meshes — the slowest links,
exactly where the paper confines P2P traffic instead of collectives).
Microbatches flow between stages with ``lax.ppermute``; a ``lax.scan`` over
clock ticks realizes the schedule.

Composition: the outer ``shard_map`` is *manual* only over the pipeline axis
(``auto`` over data/ep/tp), so each stage's interior still runs the full
expert-data-parallel machinery — including the nested explicit-``shard_map``
MoE dispatch with its "ep"-local all-to-all.  This is the paper's central
claim made concrete: collectives (a2a, all-gather) stay inside the fast
domain; only point-to-point microbatch hand-offs cross the slow axis.

Two executors interpret the schedule IR of ``core.schedules``:

* :func:`pipelined_stack_forward` — the differentiable *forward* pipeline:
  a scan over the IR's F-projection ticks; ``jax.grad`` through it yields
  the reverse pipeline in GPipe order (all forwards, then all backwards —
  the natural order under reverse-mode AD).  Used for loss evaluation and
  as the ``schedule="gpipe"`` AD oracle in tests.

* :func:`pipelined_step` — the schedule-*executing* train step: it
  interprets the full per-tick op table (``F``/``B``/``Bi``/``Bw``/idle,
  each op tagged
  with its virtual stage) of any built schedule, so 1F1B actually runs with
  its Eq-4 memory profile instead of relying on AD ordering, and
  interleaved 1F1B runs its PP*V chunk ring (per-vstage parameter chunks
  selected per tick, ring ppermutes for the wrap-around hand-offs, the
  loss head owned by chunk (PP-1, V-1)).  Each stage's forward runs under
  ``jax.vjp``;
  residuals are *stage inputs* parked in a scan-carried buffer with
  ``Schedule.num_slots`` slots (``PP`` for 1F1B, ``M`` for GPipe — the
  paper's Eq 4 vs Eq 3 gap realized in allocation), and the backward op
  recomputes the stage from its saved input (stage-granular activation
  checkpointing) before applying the cotangent handed back by the next
  stage over a reverse ``ppermute``.  The per-microbatch loss head runs
  inside the last stage, which is what lets B(mb) start before the last
  F — the defining property of 1F1B.  The executor emits a per-tick
  occupancy trace so tests can check the *executed* peak in-flight count
  against ``schedule_sim`` on the same IR.

  Zero-bubble schedules split the backward into a TWO-PHASE protocol
  (``zb_h1``): a ``Bi`` tick runs the same recompute-and-pullback as a
  fused B and ppermutes the input cotangent upstream, but DEFERS the
  weight grads — it parks the pullback's inputs (the stage input and the
  stage-output cotangent) in a second scan-carried **W-stash** buffer with
  ``Schedule.num_wslots`` slots and frees its residual slot immediately
  (1F1B-equal Eq-4 residency).  A later ``Bw`` tick drains one stash
  entry: it re-runs the stage pullback from the stashed pair,
  differentiating w.r.t. the parameters only, and accumulates the weight
  grads — numerically the same pullback a fused B would have applied, in
  the same ascending-microbatch order, so grads stay exact vs the AD
  oracle.  The executed W-stash occupancy is emitted next to the residual
  trace (``metrics["pipeline_wstash_occupancy"]``).

SPMD cost note: every stage executes the same program each tick and masks
the op it was not assigned, so a tick costs one fwd + one bwd regardless of
schedule — plus one loss-head forward+vjp (full-vocab logits), which only
the last stage's B/Bi ticks consume, plus (split schedules only) one
weight-grad recompute serving the tick's potential Bw; bubbles materialize
as masked compute, identical in cost to idle bubbles and visible to the
roofline analysis.  Fusing the unassigned op (and restricting the head to
the last stage) via ``lax.cond`` is a ROADMAP follow-up, pending stable
pp-manual branch predicates under GSPMD at scale.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.configs.base import ArchConfig
from repro.core import schedules as sched_lib
from repro.core.schedules import OP_B, OP_BI, OP_BW, OP_F
from repro.models import transformer
from repro.sharding import MeshPlan


def _stage_block_params(
    block_params, arch: ArchConfig, plan: MeshPlan, vstages: int = 1
):
    """Chunk-major parameter layout: (reps, ...) -> (PP, V, rpc, ...) with
    chunk ``c = v * PP + s`` living on stage ``s`` as virtual stage ``v``
    (rpc = reps per chunk), explicitly resharded so dim0 lives on the
    pipeline axis and the remaining dims keep their ZeRO-3 sharding
    (leaving this to GSPMD triggers pathological reshards and an XLA SPMD
    crash at 512-device scale)."""
    from repro.models import model as model_lib  # deferred: avoids cycle

    PP = plan.pp
    V = vstages
    period = len(arch.block_pattern)
    reps = arch.num_layers // period
    assert reps % (PP * V) == 0, (
        f"{arch.name}: {reps} pattern-reps not divisible by "
        f"PP*V={PP}*{V}"
    )
    rpc = reps // (PP * V)
    block_specs = model_lib.param_specs(arch, plan)["blocks"]

    def stage_leaf(p, sp):
        # (reps,) = (V, PP, rpc) v-major -> (PP, V, rpc): chunk c = v*PP+s.
        r = p.reshape((V, PP, rpc) + p.shape[1:]).swapaxes(0, 1)
        return lax.with_sharding_constraint(
            r,
            NamedSharding(
                plan.mesh, P(*((plan.pp_axis, None, None) + tuple(sp)[1:]))
            ),
        )

    return jax.tree.map(stage_leaf, block_params, block_specs), rpc


def _unstage_blocks(tree, reps: int):
    """(PP, V, rpc, ...) chunk-major leaves back to the caller's (reps, ...)
    layout (inverse of ``_stage_block_params``)."""
    return jax.tree.map(
        lambda g: g.swapaxes(0, 1).reshape((reps,) + g.shape[3:]), tree
    )


def _act_dtype(block_params, fallback):
    for p in jax.tree.leaves(block_params):
        if jnp.issubdtype(p.dtype, jnp.floating):
            return p.dtype
    return fallback


def _send_fwd(h, plan: MeshPlan, ring: bool = False):
    """Next-stage activation hand-off; ``ring`` adds the PP-1 -> 0 wrap
    edge interleaved schedules use to enter the next virtual stage."""
    perm = [(i, i + 1) for i in range(plan.pp - 1)]
    if ring:
        perm.append((plan.pp - 1, 0))
    if plan.compress_p2p:
        from repro.core.compression import compressed_ppermute

        return compressed_ppermute(h, plan.pp_axis, perm)
    return lax.ppermute(h, plan.pp_axis, perm)


def _send_bwd(g, plan: MeshPlan, ring: bool = False):
    perm = [(i + 1, i) for i in range(plan.pp - 1)]
    if ring:
        perm.append((0, plan.pp - 1))
    if plan.compress_p2p:
        from repro.core.compression import compressed_ppermute

        return compressed_ppermute(g, plan.pp_axis, perm)
    return lax.ppermute(g, plan.pp_axis, perm)


# ---------------------------------------------------------------------------
# Forward executor (differentiable; IR F-projection)
# ---------------------------------------------------------------------------


def pipelined_stack_forward(
    block_params,
    x: jax.Array,  # (b, s, d) embedded inputs OR (b, s) int32 tokens
    arch: ArchConfig,
    plan: MeshPlan,
    *,
    positions: jax.Array,
    impl: str = "xla",
    num_microbatches: Optional[int] = None,
    vstages: Optional[int] = None,
    embed_fn=None,  # (embed_params, tokens (b_mu, s)) -> (b_mu, s, d)
    embed_params=None,
):
    """Drop-in replacement for ``transformer.stack_forward`` that pipelines
    the stack over ``plan.pp_axis``.

    When ``embed_fn`` is given, ``x`` is the raw token ids and the embedding
    lookup runs INSIDE stage 0 — as in the paper's stage placement.  (It also
    keeps the embedding-backward scatter-add inside the manual-pod region;
    letting it cross the shard_map boundary trips an XLA SPMD crash at
    512-device scale.)

    Tick validity masks come from the schedule IR's forward projection.
    With ``vstages > 1`` (default: the plan's depth when its schedule is
    interleaved) the *vstage* F-projection runs instead of the flat
    staircase: PP·V chunks walk the ring, cutting the fill bubble from
    ``(PP-1)/(M+PP-1)`` to ``(PP-1)/(V·M+PP-1)`` — forward-only loss eval
    inherits the interleaved schedule's smaller fill bubble.
    Differentiating this scan with ``jax.grad`` realizes the GPipe
    backward order (per chunk when interleaved).

    Returns (x, {"moe_aux_loss","moe_z_loss"}, expert_load or None).
    """
    pp_axis = plan.pp_axis
    assert pp_axis is not None
    if vstages is not None:
        V = vstages
    else:
        V = plan.vstages if plan.schedule == "interleaved_1f1b" else 1
    if V > 1:
        return _pipelined_stack_forward_v(
            block_params, x, arch, plan, V,
            positions=positions, impl=impl,
            num_microbatches=num_microbatches,
            embed_fn=embed_fn, embed_params=embed_params,
        )
    PP = plan.pp
    period = len(arch.block_pattern)
    reps = arch.num_layers // period
    rps = reps // PP  # reps per stage

    M = num_microbatches or plan.microbatches or 2 * PP
    b, s = x.shape[:2]
    d = arch.d_model
    assert b % M == 0, (b, M)
    b_mu = b // M

    staged, _ = _stage_block_params(block_params, arch, plan)
    xm = x.reshape((M, b_mu, s) + ((d,) if embed_fn is None else ()))
    pos_mu = positions[:b_mu]

    # IR F-projection: F(stage, mb) is valid at tick stage + mb.
    fvalid, _fmb, T = sched_lib.forward_tick_tables(PP, M)

    has_moe = arch.num_moe_layers > 0
    mesh = plan.mesh

    def stage_program(stage_params, emb_params, xm_local):
        # in_spec P(pp_axis) leaves a leading length-1 stage dim; the next
        # dim is the (length-1 here: V=1) vstage chunk dim: drop both.
        stage_params = jax.tree.map(lambda p: p[0][0], stage_params)
        stage = lax.axis_index(pp_axis)
        valid_t = jnp.asarray(fvalid)  # (PP, T) bool

        def stage_fn(h):
            # unroll=True: the nested while(layer-scan)-inside-while(ticks)
            # with checkpoint triggers an XLA SPMD crash at 512-device scale;
            # unrolling the (short) per-stage layer loop sidesteps it.
            return transformer.stack_forward(
                stage_params,
                h,
                arch,
                plan,
                positions=pos_mu,
                impl=impl,
                token_sharded=True,
                unroll=True,
            )

        # Steer GSPMD to the canonical activation layout inside the stage —
        # without this the partitioner invents mixed shardings for the
        # carried microbatch and hits an XLA involuntary-remat bug at
        # 512-device scale.
        act_spec = P(tuple(plan.dp_axes), tuple(plan.sp_axes), None)

        def constrain(h):
            return lax.with_sharding_constraint(h, act_spec)

        def tick(carry, xs):
            x0, t = xs
            h_prev, aux, z, loads = carry
            if embed_fn is not None:
                x0 = embed_fn(emb_params, x0)
            inp = constrain(jnp.where(stage == 0, x0, h_prev))
            h_out, aux_d, loads_d = stage_fn(inp)
            h_out = constrain(h_out)
            valid = valid_t[stage, t].astype(jnp.float32)
            # (1,)-shaped accumulators: out_specs stack them over the
            # pipeline axis.
            aux = aux + aux_d["moe_aux_loss"][None] * valid
            z = z + aux_d["moe_z_loss"][None] * valid
            if loads is not None and loads_d is not None:
                loads = loads + loads_d * valid
            sent = _send_fwd(h_out, plan)
            return (sent, aux, z, loads), h_out

        act_dtype = (
            _act_dtype(block_params, x.dtype) if embed_fn is not None else x.dtype
        )
        zero_h = jnp.zeros((b_mu, s, d), act_dtype)
        zero_loads = (
            jnp.zeros(
                (rps, sum(1 for _, f in arch.block_pattern if f == "moe"),
                 arch.moe.num_experts),
                jnp.float32,
            )
            if has_moe
            else None
        )
        carry0 = (zero_h, jnp.zeros((1,), jnp.float32),
                  jnp.zeros((1,), jnp.float32), zero_loads)
        # Feed microbatches as scan xs (padded with PP-1 dummy ticks): the
        # scan transpose then stacks cotangents instead of scatter-adding
        # into a captured buffer — both faster and a workaround for an XLA
        # SPMD involuntary-remat crash at 512-way scale.
        xm_pad = jnp.concatenate(
            [xm_local, jnp.zeros((PP - 1,) + xm_local.shape[1:], x.dtype)]
        ) if PP > 1 else xm_local
        (h_last, aux, z, loads), ys = lax.scan(
            tick, carry0, (xm_pad, jnp.arange(T))
        )

        # Valid last-stage outputs are ticks [PP-1, PP-1+M).
        out = lax.dynamic_slice_in_dim(ys, PP - 1, M, axis=0)
        return out, aux, z, loads

    out_specs = (
        P(pp_axis),  # (PP, M, b_mu, s, d): stage-stacked; take the last
        P(pp_axis),  # per-stage aux
        P(pp_axis),
        P(pp_axis) if has_moe else P(),
    )
    in_specs = (
        jax.tree.map(lambda v: P(pp_axis), staged),
        jax.tree.map(lambda v: P(), embed_params)
        if embed_params is not None
        else P(),
        P(None),  # microbatches replicated over the pipe axis
    )

    def wrapped(stage_params, emb_params, xm_in):
        out, aux, z, loads = stage_program(stage_params, emb_params, xm_in)
        out = out[None]
        if loads is None:
            return out, aux, z, jnp.zeros((), jnp.float32)
        return out, aux, z, loads[None]

    out, aux, z, loads = jax.shard_map(
        wrapped,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
        axis_names={plan.pp_axis},
    )(staged, embed_params if embed_params is not None else jnp.zeros(()), xm)

    # out: (PP, M, b_mu, s, d) — only the last stage's block is the real
    # model output; slicing it reads one stage's shard (a single cross-pod
    # hand-off, not an all-reduce).
    y = out[-1].reshape(b, s, d)
    # aux/z are token-means per microbatch, accumulated over M microbatches
    # and summed across stages — normalize back to a per-step mean.
    metrics = {
        "moe_aux_loss": jnp.sum(aux) / M,
        "moe_z_loss": jnp.sum(z) / M,
    }
    if has_moe:
        loads = loads.reshape((reps,) + loads.shape[2:])
    else:
        loads = None
    return y, metrics, loads


def _pipelined_stack_forward_v(
    block_params, x, arch: ArchConfig, plan: MeshPlan, V: int, *,
    positions, impl, num_microbatches, embed_fn, embed_params,
):
    """Vstage F-projection executor (see ``pipelined_stack_forward``):
    interprets ``schedules.forward_tick_tables_v`` — per tick, each stage
    selects the scheduled chunk's parameters dynamically, runs it, and
    ppermutes the result around the PP ring (the wrap edge feeds stage 0's
    next virtual stage).  Arrivals park in ``num_slots`` input slots, as in
    the schedule-executing train step.  The executed occupancy is the IR
    F-projection by construction: the tick tables ARE the trace
    (``forward_tick_tables_v`` asserts them against the full schedule)."""
    pp_axis = plan.pp_axis
    PP = plan.pp
    period = len(arch.block_pattern)
    reps = arch.num_layers // period

    M = num_microbatches or plan.microbatches or 2 * PP
    b, s = x.shape[:2]
    d = arch.d_model
    assert b % M == 0, (b, M)
    b_mu = b // M

    staged, rpc = _stage_block_params(block_params, arch, plan, vstages=V)
    xm = x.reshape((M, b_mu, s) + ((d,) if embed_fn is None else ()))
    pos_mu = positions[:b_mu]

    ft = sched_lib.forward_tick_tables_v(PP, M, V)
    K = ft.num_slots

    has_moe = arch.num_moe_layers > 0
    mesh = plan.mesh
    act_dtype = (
        _act_dtype(block_params, x.dtype) if embed_fn is not None else x.dtype
    )
    n_moe_pos = sum(1 for _, f in arch.block_pattern if f == "moe")

    def stage_program(stage_params, emb_params, xm_local):
        # in_spec P(pp_axis) leaves a leading length-1 stage dim: drop it,
        # keeping the (V, rpc, ...) chunk-major layout.
        stage_params = jax.tree.map(lambda p: p[0], stage_params)
        stage = lax.axis_index(pp_axis)
        valid_t = jnp.asarray(ft.valid)
        mb_t = jnp.asarray(ft.mb)
        vs_t = jnp.asarray(ft.vs)
        slot_t = jnp.asarray(ft.slot)
        arrive_t = jnp.asarray(ft.arrive)

        act_spec = P(tuple(plan.dp_axes), tuple(plan.sp_axes), None)

        def constrain(h):
            return lax.with_sharding_constraint(h, act_spec)

        def tick(carry, t):
            in_buf, recv_h, aux, z, loads = carry
            # 1. park the wire arrival in its input slot
            a_f = arrive_t[stage, t]
            cur = lax.dynamic_index_in_dim(in_buf, a_f, 0, keepdims=False)
            in_buf = lax.dynamic_update_index_in_dim(
                in_buf, jnp.where(a_f >= 0, recv_h, cur), a_f, 0
            )
            # 2. the tick's F op (idle ticks run masked, like the train
            # executor: a bubble costs one masked fwd)
            mb_i = mb_t[stage, t]
            vs_i = vs_t[stage, t]
            chunk = jax.tree.map(
                lambda p: lax.dynamic_index_in_dim(p, vs_i, 0, keepdims=False),
                stage_params,
            )
            x0 = lax.dynamic_index_in_dim(xm_local, mb_i, 0, keepdims=False)
            if embed_fn is not None:
                x0 = embed_fn(emb_params, x0)
            h_in = lax.dynamic_index_in_dim(
                in_buf, slot_t[stage, t], 0, keepdims=False
            )
            first_chunk = jnp.logical_and(stage == 0, vs_i == 0)
            inp = constrain(jnp.where(first_chunk, x0, h_in))
            h_out, aux_d, loads_d = transformer.stack_forward(
                chunk, inp, arch, plan,
                positions=pos_mu, impl=impl, token_sharded=True,
                unroll=True,
            )
            h_out = constrain(h_out)
            vmask = valid_t[stage, t].astype(jnp.float32)
            aux = aux + aux_d["moe_aux_loss"][None] * vmask
            z = z + aux_d["moe_z_loss"][None] * vmask
            if loads is not None and loads_d is not None:
                cur_l = lax.dynamic_index_in_dim(loads, vs_i, 0, keepdims=False)
                loads = lax.dynamic_update_index_in_dim(
                    loads, cur_l + loads_d * vmask, vs_i, 0
                )
            sent = _send_fwd(h_out, plan, ring=True)
            return (in_buf, sent, aux, z, loads), h_out

        zero_h = jnp.zeros((b_mu, s, d), act_dtype)
        zero_loads = (
            jnp.zeros((V, rpc, n_moe_pos, arch.moe.num_experts), jnp.float32)
            if has_moe
            else None
        )
        carry0 = (
            jnp.zeros((K, b_mu, s, d), act_dtype), zero_h,
            jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.float32),
            zero_loads,
        )
        (_, _, aux, z, loads), ys = lax.scan(
            tick, carry0, jnp.arange(ft.Tf)
        )
        # The model outputs are chunk (PP-1, V-1)'s F results — their ticks
        # are static in the projection.
        out = ys[jnp.asarray(ft.out_ticks)]
        return out, aux, z, loads

    out_specs = (
        P(pp_axis),  # (PP, M, b_mu, s, d): stage-stacked; take the last
        P(pp_axis),
        P(pp_axis),
        P(pp_axis) if has_moe else P(),
    )
    in_specs = (
        jax.tree.map(lambda v: P(pp_axis), staged),
        jax.tree.map(lambda v: P(), embed_params)
        if embed_params is not None
        else P(),
        P(None),
    )

    def wrapped(stage_params, emb_params, xm_in):
        out, aux, z, loads = stage_program(stage_params, emb_params, xm_in)
        out = out[None]
        if loads is None:
            return out, aux, z, jnp.zeros((), jnp.float32)
        return out, aux, z, loads[None]

    out, aux, z, loads = jax.shard_map(
        wrapped,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
        axis_names={plan.pp_axis},
    )(staged, embed_params if embed_params is not None else jnp.zeros(()), xm)

    y = out[-1].reshape(b, s, d)
    metrics = {
        "moe_aux_loss": jnp.sum(aux) / M,
        "moe_z_loss": jnp.sum(z) / M,
    }
    if has_moe:
        # (PP, V, rpc, n_moe_pos, E) chunk-major -> caller's (reps, ...).
        loads = _unstage_blocks(loads, reps)
    else:
        loads = None
    return y, metrics, loads


# ---------------------------------------------------------------------------
# Schedule-executing train step (forward + hand-rolled pipelined backward)
# ---------------------------------------------------------------------------


def _partition_floats(tree):
    """Split a pytree into (float leaves, merge_fn); vjp differentiates the
    float leaves only (int tables like the expert-migration assignment ride
    along untouched)."""
    leaves, treedef = jax.tree.flatten(tree)
    is_f = [jnp.issubdtype(l.dtype, jnp.floating) for l in leaves]
    floats = [l for l, f in zip(leaves, is_f) if f]

    def merge(new_floats):
        it = iter(new_floats)
        return jax.tree.unflatten(
            treedef, [next(it) if f else l for l, f in zip(leaves, is_f)]
        )

    def rebuild_grads(float_grads):
        """Grad tree matching ``tree``: zeros for non-float leaves."""
        it = iter(float_grads)
        return jax.tree.unflatten(
            treedef,
            [next(it) if f else jnp.zeros_like(l) for l, f in zip(leaves, is_f)],
        )

    return floats, merge, rebuild_grads


def pipelined_step(
    block_params,
    x: jax.Array,  # (b, s) int32 tokens OR (b, s, d) embedded inputs
    labels: jax.Array,  # (b, s) int32
    arch: ArchConfig,
    plan: MeshPlan,
    *,
    positions: jax.Array,
    head_fn: Callable,  # (head_params, embed_params, y (b_mu,s,d), labels) -> ce sum
    head_params,
    schedule: Optional[str] = None,
    vstages: Optional[int] = None,
    impl: str = "xla",
    num_microbatches: Optional[int] = None,
    embed_fn=None,
    embed_params=None,
) -> Tuple[jax.Array, Any, Dict[str, jax.Array], jax.Array]:
    """Execute one training step's forward AND backward under a schedule IR.

    Interprets ``schedules.build(schedule, PP, M, V)`` tick by tick (see
    module docstring).  With ``V > 1`` (interleaved schedules) the layer
    stack is partitioned into PP*V chunks — chunk ``v*PP + s`` runs on
    stage ``s`` as virtual stage ``v`` — the residual/cotangent buffers
    carry per-(vstage, mb) slots, and the fwd/bwd ppermutes become rings so
    the chunk hand-off can wrap from the last stage back to stage 0.
    Gradients are accumulated in fp32 on the stage that owns each parameter
    and returned in the caller's layout:

    Returns ``(loss, grads, metrics, occupancy)`` where ``grads`` is
    ``{"blocks": <same structure as block_params>, "embed": ...,
    "head": <same structure as head_params>}`` and ``occupancy`` is the
    executed (PP, num_ticks) in-flight residual count — comparable 1:1 with
    ``Schedule.occupancy_trace()``.  For split-backward schedules
    (``zb_h1``) ``metrics["pipeline_wstash_occupancy"]`` carries the
    executed deferred-weight-grad residency, comparable 1:1 with
    ``Schedule.wstash_trace()``; for comm-lane schedules
    (``1f1b_overlap``) ``metrics["pipeline_comm_inflight"]`` carries the
    executed comm-buffer residency, comparable 1:1 with
    ``Schedule.comm_trace()``.
    """
    pp_axis = plan.pp_axis
    assert pp_axis is not None
    PP = plan.pp
    sched_name = schedule or plan.schedule
    # The plan's vstage depth belongs to ITS schedule: a per-call override
    # to a flat schedule runs at V=1 (an explicit ``vstages`` contradiction
    # still fails fast in ``build``).
    if vstages is not None:
        V = vstages
    else:
        V = plan.vstages if sched_name == "interleaved_1f1b" else 1
    period = len(arch.block_pattern)
    reps = arch.num_layers // period

    M = num_microbatches or plan.microbatches or 2 * PP
    b, s = x.shape[:2]
    d = arch.d_model
    assert b % M == 0, (b, M)
    b_mu = b // M

    # Host-side schedule construction happens at jit-trace time only — the
    # span fires once per compile, so its presence in the event stream
    # doubles as a retrace detector.
    with obs.span(
        "pipeline.build_schedule", schedule=sched_name, PP=PP, M=M, V=V
    ):
        sched = sched_lib.build(sched_name, PP, M, V)
        tt = sched_lib.tick_tables(sched)
    obs.instant(
        "pipeline.schedule", schedule=sched_name, PP=PP, M=M, V=V,
        num_ticks=sched.num_ticks, slots=sched.num_slots,
        wslots=sched.num_wslots,
        cslots=sched.num_cslots_fwd + sched.num_cslots_bwd,
    )
    T = sched.num_ticks
    K = sched.num_slots
    # Split-backward (zero-bubble) schedules defer weight grads through a
    # W-stash of num_wslots (stage input, output cotangent) pairs; fused
    # schedules allocate none and skip the whole Bw phase at trace time.
    Kw = sched.num_wslots
    has_split = Kw > 0
    # Comm-lane schedules (1f1b_overlap): hand-offs still ride the every-
    # tick ppermute on their SEND tick edge, but a dwelling payload parks
    # in a scan-carried comm buffer (num_cslots_fwd/_bwd double-buffer
    # slots) until its RECV tick instead of being written straight into
    # its residual slot — the IR's in-flight window, executed.  A2A
    # brackets are pricing/legality ops only: the expert a2a itself runs
    # (and overlaps) inside the MoE layer.  Schedules without a comm lane
    # take none of these branches — their trace is unchanged.
    Kcf = sched.num_cslots_fwd
    Kcb = sched.num_cslots_bwd
    has_comm = sched.has_comm
    ring = V > 1  # chunk hand-offs wrap around the stage ring

    staged, rpc = _stage_block_params(block_params, arch, plan, vstages=V)
    xm = x.reshape((M, b_mu, s) + ((d,) if embed_fn is None else ()))
    lm_ = labels.reshape(M, b_mu, s)
    pos_mu = positions[:b_mu]

    has_moe = arch.num_moe_layers > 0
    mesh = plan.mesh
    # Buffer/wire dtype: parameter dtype when embedding in-pipeline, the
    # input embeds' own dtype otherwise (input-driven promotion keeps stage
    # outputs in x.dtype there) — mirrors pipelined_stack_forward.
    act_dtype = (
        _act_dtype(block_params, x.dtype) if embed_fn is not None else x.dtype
    )
    emb_in = embed_params if embed_params is not None else jnp.zeros(())

    def stage_program(stage_params, emb_p, head_p, xm_local, labels_local):
        # in_spec P(pp_axis) leaves a leading length-1 stage dim: drop it,
        # keeping the (V, rpc, ...) chunk-major layout.
        stage_params = jax.tree.map(lambda p: p[0], stage_params)
        stage = lax.axis_index(pp_axis)
        is_last = stage == PP - 1

        kind_t = jnp.asarray(tt.kind)
        mb_t = jnp.asarray(tt.mb)
        vs_t = jnp.asarray(tt.vs)
        slot_t = jnp.asarray(tt.slot)
        afwd_t = jnp.asarray(tt.arrive_fwd)
        abwd_t = jnp.asarray(tt.arrive_bwd)
        wslot_t = jnp.asarray(tt.wslot)
        if has_comm:
            storef_t = jnp.asarray(tt.store_fwd)
            srcf_t = jnp.asarray(tt.src_fwd)
            storeb_t = jnp.asarray(tt.store_bwd)
            srcb_t = jnp.asarray(tt.src_bwd)

        act_spec = P(tuple(plan.dp_axes), tuple(plan.sp_axes), None)

        def constrain(h):
            return lax.with_sharding_constraint(h, act_spec)

        sp_floats, sp_merge, sp_rebuild = _partition_floats(stage_params)

        def full_stage(sp_f, emb_, x0, h_in, vs):
            """(stage float params (V, rpc, ...), embed, raw microbatch,
            arrived act, vstage) -> ((h_out, aux, z), loads).  Runs the
            ``vs``-th chunk; chunk (0, 0) reads the raw microbatch
            (embedding inside the pipeline), every other chunk the arrived
            activation.  Differentiating through the dynamic chunk index
            scatter-adds the chunk grads into the full (V, rpc, ...)
            layout."""
            sp = sp_merge(sp_f)
            chunk = jax.tree.map(
                lambda p: lax.dynamic_index_in_dim(p, vs, 0, keepdims=False),
                sp,
            )
            if embed_fn is not None:
                x_emb = embed_fn(emb_, x0)
            else:
                x_emb = x0
            first_chunk = jnp.logical_and(stage == 0, vs == 0)
            inp = constrain(jnp.where(first_chunk, x_emb, h_in))
            h_out, aux_d, loads_d = transformer.stack_forward(
                chunk, inp, arch, plan,
                positions=pos_mu, impl=impl, token_sharded=True, unroll=True,
            )
            return (
                constrain(h_out),
                aux_d["moe_aux_loss"],
                aux_d["moe_z_loss"],
            ), loads_d

        zero_h = jnp.zeros((b_mu, s, d), act_dtype)
        zero_loads = (
            jnp.zeros(
                (V, rpc,
                 sum(1 for _, f in arch.block_pattern if f == "moe"),
                 arch.moe.num_experts),
                jnp.float32,
            )
            if has_moe
            else None
        )
        f32z = jnp.float32(0.0)
        gacc0 = [jnp.zeros(l.shape, jnp.float32) for l in sp_floats]
        gemb0 = jax.tree.map(
            lambda l: jnp.zeros(l.shape, jnp.float32), emb_p
        )
        ghead0 = jax.tree.map(
            lambda l: jnp.zeros(l.shape, jnp.float32), head_p
        )

        def tick(carry, t):
            (in_buf, cot_buf, wstash, cstate, recv_h, recv_g, gacc, gemb,
             ghead, ce, aux, z, loads, live, live_w) = carry

            # -- 1. park wire arrivals in their residual slots -------------
            # Comm-lane schedules route a dwelling payload through the comm
            # buffer: store the wire arrival at its Send+1 tick, consume it
            # at its Recv tick.  The consume is read BEFORE the store — a
            # comm slot freed at this tick can be re-filled by this tick's
            # arrival.  Zero-dwell payloads (src/store -1) park directly
            # from the wire, exactly the legacy path.
            pay_h, pay_g = recv_h, recv_g
            if has_comm:
                cbuf_h, cbuf_g, live_c = cstate
                if cbuf_h is not None:
                    src_f = srcf_t[stage, t]
                    st_f = storef_t[stage, t]
                    held = lax.dynamic_index_in_dim(
                        cbuf_h, src_f, 0, keepdims=False
                    )
                    pay_h = jnp.where(src_f >= 0, held, recv_h)
                    curs = lax.dynamic_index_in_dim(
                        cbuf_h, st_f, 0, keepdims=False
                    )
                    cbuf_h = lax.dynamic_update_index_in_dim(
                        cbuf_h, jnp.where(st_f >= 0, recv_h, curs), st_f, 0
                    )
                    live_c = (
                        live_c
                        + (st_f >= 0).astype(jnp.int32)
                        - (src_f >= 0).astype(jnp.int32)
                    )
                if cbuf_g is not None:
                    src_b = srcb_t[stage, t]
                    st_b = storeb_t[stage, t]
                    heldg = lax.dynamic_index_in_dim(
                        cbuf_g, src_b, 0, keepdims=False
                    )
                    pay_g = jnp.where(src_b >= 0, heldg, recv_g)
                    curg = lax.dynamic_index_in_dim(
                        cbuf_g, st_b, 0, keepdims=False
                    )
                    cbuf_g = lax.dynamic_update_index_in_dim(
                        cbuf_g, jnp.where(st_b >= 0, recv_g, curg), st_b, 0
                    )
                    live_c = (
                        live_c
                        + (st_b >= 0).astype(jnp.int32)
                        - (src_b >= 0).astype(jnp.int32)
                    )
                cstate = (cbuf_h, cbuf_g, live_c)
            a_f = afwd_t[stage, t]
            cur = lax.dynamic_index_in_dim(in_buf, a_f, 0, keepdims=False)
            in_buf = lax.dynamic_update_index_in_dim(
                in_buf, jnp.where(a_f >= 0, pay_h, cur), a_f, 0
            )
            a_b = abwd_t[stage, t]
            curc = lax.dynamic_index_in_dim(cot_buf, a_b, 0, keepdims=False)
            cot_buf = lax.dynamic_update_index_in_dim(
                cot_buf, jnp.where(a_b >= 0, pay_g, curc), a_b, 0
            )

            # -- 2. the tick's op (F / B / Bi / Bw / idle, from the IR) ----
            kind = kind_t[stage, t]
            mb = mb_t[stage, t]
            vs = vs_t[stage, t]
            slot = slot_t[stage, t]
            is_f = kind == OP_F
            # Cotangent producers: the fused B or the split Bi — both run
            # the recompute-and-pullback and ppermute the input grad.
            is_cot = jnp.logical_or(kind == OP_B, kind == OP_BI)
            is_fused_b = kind == OP_B
            # The op's chunk: only chunk (PP-1, V-1) owns the loss head.
            last_chunk = jnp.logical_and(is_last, vs == V - 1)
            x0 = lax.dynamic_index_in_dim(xm_local, mb, 0, keepdims=False)
            lbl = lax.dynamic_index_in_dim(labels_local, mb, 0, keepdims=False)
            h_in = lax.dynamic_index_in_dim(in_buf, slot, 0, keepdims=False)

            # One vjp serves F and the cotangent backward: its primal
            # output is the F result; its pullback is the B/Bi
            # recompute-and-backprop.  The vstage index is closed over (not
            # differentiated).
            (y, aux_d, z_d), vjp_fn, loads_d = jax.vjp(
                lambda sp_, e_, x_, h_: full_stage(sp_, e_, x_, h_, vs),
                sp_floats, emb_p, x0, h_in, has_aux=True,
            )

            # -- 3. forward bookkeeping ------------------------------------
            fmask = is_f.astype(jnp.float32)
            aux = aux + aux_d * fmask
            z = z + z_d * fmask
            if loads is not None and loads_d is not None:
                cur_l = lax.dynamic_index_in_dim(loads, vs, 0, keepdims=False)
                loads = lax.dynamic_update_index_in_dim(
                    loads, cur_l + loads_d * fmask, vs, 0
                )

            # -- 4. loss head + cotangent seed (last stage only) -----------
            ce_mb, head_vjp = jax.vjp(
                lambda hp, e, yy: head_fn(hp, e, yy, lbl), head_p, emb_p, y
            )
            g_hp, g_emb_h, g_y = head_vjp(jnp.float32(1.0 / (b * s)))
            y_cot = jnp.where(
                last_chunk,
                g_y.astype(act_dtype),
                lax.dynamic_index_in_dim(cot_buf, slot, 0, keepdims=False),
            )

            # -- 5a. cotangent backward (fused B or split Bi) --------------
            inv_m = jnp.float32(1.0 / M)
            g_sp, g_emb_s, _g_x0, g_h = vjp_fn((y_cot, inv_m, inv_m))
            cmask = is_cot.astype(jnp.float32)
            # Weight grads land NOW only for the fused B; a Bi defers them
            # to its Bw.  Head (+ head-side embedding) grads and the loss
            # belong to the cotangent tick — the head pullback seeds y_cot.
            bmask = is_fused_b.astype(jnp.float32)
            lmask = cmask * last_chunk.astype(jnp.float32)
            gacc = [
                a + g.astype(jnp.float32) * bmask for a, g in zip(gacc, g_sp)
            ]
            gemb = jax.tree.map(
                lambda a, g_s, g_hd: a
                + g_s.astype(jnp.float32) * bmask
                + g_hd.astype(jnp.float32) * lmask,
                gemb, g_emb_s, g_emb_h,
            )
            ghead = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) * lmask, ghead, g_hp
            )
            ce = ce + ce_mb * lmask

            # -- 5b. two-phase backward: W-stash park / drain (split only) -
            if has_split:
                is_bi = kind == OP_BI
                is_bw = kind == OP_BW
                wslot = wslot_t[stage, t]
                wh_buf, wc_buf = wstash
                # Bw reads the PRE-update stash (its entry was parked by an
                # earlier Bi; a tick is one op, so no same-tick store).
                w_h = lax.dynamic_index_in_dim(wh_buf, wslot, 0, keepdims=False)
                w_c = lax.dynamic_index_in_dim(wc_buf, wslot, 0, keepdims=False)
                # The weight pullback: re-run the stage from the stashed
                # input, differentiate w.r.t. the parameters only, and
                # apply the stashed output cotangent — numerically the
                # exact weight-grad half of the fused pullback.
                _, wvjp_fn, _ = jax.vjp(
                    lambda sp_, e_: full_stage(sp_, e_, x0, w_h, vs),
                    sp_floats, emb_p, has_aux=True,
                )
                g_sp_w, g_emb_w = wvjp_fn((w_c, inv_m, inv_m))
                wmask = is_bw.astype(jnp.float32)
                gacc = [
                    a + g.astype(jnp.float32) * wmask
                    for a, g in zip(gacc, g_sp_w)
                ]
                gemb = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) * wmask,
                    gemb, g_emb_w,
                )
                # Bi parks (stage input, output cotangent) for its Bw and
                # frees the residual slot (Eq-4-equal residency).
                wh_buf = lax.dynamic_update_index_in_dim(
                    wh_buf, jnp.where(is_bi, h_in, w_h), wslot, 0
                )
                wc_buf = lax.dynamic_update_index_in_dim(
                    wc_buf, jnp.where(is_bi, y_cot, w_c), wslot, 0
                )
                wstash = (wh_buf, wc_buf)
                live_w = (
                    live_w + is_bi.astype(jnp.int32) - is_bw.astype(jnp.int32)
                )

            # -- 6. occupancy + wire sends ---------------------------------
            live = live + is_f.astype(jnp.int32) - is_cot.astype(jnp.int32)
            sent_h = _send_fwd(y, plan, ring=ring)
            sent_g = _send_bwd(g_h.astype(act_dtype), plan, ring=ring)
            carry = (in_buf, cot_buf, wstash, cstate, sent_h, sent_g, gacc,
                     gemb, ghead, ce, aux, z, loads, live, live_w)
            if has_comm:
                return carry, (live, live_w, cstate[2])
            return carry, (live, live_w)

        wstash0 = (
            (
                jnp.zeros((Kw, b_mu, s, d), act_dtype),
                jnp.zeros((Kw, b_mu, s, d), act_dtype),
            )
            if has_split
            else None
        )
        cstate0 = (
            (
                jnp.zeros((Kcf, b_mu, s, d), act_dtype) if Kcf else None,
                jnp.zeros((Kcb, b_mu, s, d), act_dtype) if Kcb else None,
                jnp.int32(0),
            )
            if has_comm
            else None
        )
        carry0 = (
            jnp.zeros((K, b_mu, s, d), act_dtype),
            jnp.zeros((K, b_mu, s, d), act_dtype),
            wstash0,
            cstate0,
            zero_h, zero_h,
            gacc0, gemb0, ghead0,
            f32z, f32z, f32z, zero_loads, jnp.int32(0), jnp.int32(0),
        )
        if has_comm:
            carry, (occ, wocc, cocc) = lax.scan(tick, carry0, jnp.arange(T))
        else:
            carry, (occ, wocc) = lax.scan(tick, carry0, jnp.arange(T))
            cocc = jnp.zeros((T,), jnp.int32)
        (_, _, _, _, _, _, gacc, gemb, ghead, ce, aux, z, loads, _, _) = carry
        g_blocks = sp_rebuild(gacc)
        return g_blocks, gemb, ghead, ce, aux, z, loads, occ, wocc, cocc

    in_specs = (
        jax.tree.map(lambda v: P(pp_axis), staged),
        jax.tree.map(lambda v: P(), emb_in),
        jax.tree.map(lambda v: P(), head_params),
        P(None),
        P(None),
    )
    out_specs = (
        jax.tree.map(lambda v: P(pp_axis), staged),  # stage-stacked grads
        jax.tree.map(lambda v: P(pp_axis), emb_in),
        jax.tree.map(lambda v: P(pp_axis), head_params),
        P(pp_axis),  # ce
        P(pp_axis),  # aux
        P(pp_axis),  # z
        P(pp_axis) if has_moe else P(),
        P(pp_axis),  # occupancy (PP, T)
        P(pp_axis),  # W-stash occupancy (PP, T); zeros for fused schedules
        P(pp_axis),  # comm in-flight (PP, T); zeros without a comm lane
    )

    def wrapped(stage_params, emb_p, head_p, xm_in, lbl_in):
        (g_blocks, gemb, ghead, ce, aux, z, loads, occ, wocc,
         cocc) = stage_program(
            stage_params, emb_p, head_p, xm_in, lbl_in
        )
        lead = lambda v: v[None]
        g_blocks = jax.tree.map(lead, g_blocks)
        gemb = jax.tree.map(lead, gemb)
        ghead = jax.tree.map(lead, ghead)
        if loads is None:
            loads = jnp.zeros((), jnp.float32)
        else:
            loads = loads[None]
        return (g_blocks, gemb, ghead, ce[None], aux[None],
                z[None], loads, occ[None], wocc[None], cocc[None])

    (g_blocks, gemb, ghead, ce, aux, z, loads, occ, wocc,
     cocc) = jax.shard_map(
        wrapped,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
        axis_names={plan.pp_axis},
    )(staged, emb_in, head_params, xm, lm_)

    # Chunk-major (PP, V, rpc, ...) grads -> the caller's (reps, ...) layout.
    g_blocks = _unstage_blocks(g_blocks, reps)
    # Embedding grads: stage 0 (lookup scatter) + last stage (tied head).
    gemb = jax.tree.map(lambda g: jnp.sum(g, axis=0), gemb)
    ghead = jax.tree.map(lambda g: jnp.sum(g, axis=0), ghead)

    ce_mean = jnp.sum(ce) / (b * s)
    aux_mean = jnp.sum(aux) / M
    z_mean = jnp.sum(z) / M
    loss = ce_mean + aux_mean + z_mean
    if has_moe:
        loads = _unstage_blocks(loads, reps)
    else:
        loads = None
    metrics = {
        "loss": loss,
        "ce": ce_mean,
        "moe_aux_loss": aux_mean,
        "moe_z_loss": z_mean,
        "expert_load": loads,
        # Executed deferred-weight-grad residency, comparable 1:1 with
        # Schedule.wstash_trace() (all zeros for fused-backward schedules).
        "pipeline_wstash_occupancy": wocc,
        # Executed comm-buffer residency, comparable 1:1 with
        # Schedule.comm_trace() (all zeros for schedules without a comm
        # lane).
        "pipeline_comm_inflight": cocc,
    }
    grads = {"blocks": g_blocks, "embed": gemb, "head": ghead}
    return loss, grads, metrics, occ


def bubble_fraction(PP: int, M: int) -> float:
    """GPipe / 1F1B bubble: (PP-1)/(M+PP-1) of ticks are idle."""
    return (PP - 1) / (M + PP - 1)
