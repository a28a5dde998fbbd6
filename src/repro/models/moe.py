"""Mixture-of-Experts layer: three dispatch backends, one routing core.

All router math and token ordering lives in :mod:`repro.models.routing` (the
sort-based dropless engine, DESIGN.md §6); this module owns the three
*execution strategies* layered on top of it:

``einsum``  — GShard-style dense dispatch/combine einsums, with the
  dispatch masks built from the shared sort-based ranks.  Fully
  auto-shardable under pjit (the expert dim rides the ``model`` axis and XLA
  inserts the all-to-alls): this is the *paper-faithful baseline* a static
  fabric serves.

``mixnet``  — the paper's data plane (§5.3) as an explicit ``shard_map``
  program over the ``model`` axis: tokens are gathered into per-destination
  send buffers (``ops.moe_dispatch``), exchanged with the **hierarchical
  delegation all-to-all** — the CommRuntime :class:`AllToAll` op built from
  a :class:`repro.core.commruntime.CommSpec` (DESIGN.md §7), with the
  payload and its gate metadata fused into ONE packed wire transfer —
  packed by local expert and computed with the grouped Pallas GEMM
  (``ops.grouped_matmul`` — capacity buffers or the dropless block layout),
  and returned the same way (``ops.moe_combine``).  EP traffic never leaves
  the ``model`` axis — the regional locality the measurement study (§3)
  found.  With ``overlap_chunks > 1`` the whole dispatch/FFN/combine
  sequence runs as a chunked software pipeline over ``AllToAll.stages()``
  (:mod:`repro.core.overlap`, DESIGN.md §8) — chunk k+1's dispatch a2a
  under chunk k's expert FFN under chunk k-1's combine, bit-identical to
  the serial schedule.  Runtime expert re-placement (the
  OCS-reconfiguration analogue) is
  realized by permuting expert->slot assignments *per layer*: the control
  plane (:mod:`repro.core.controlplane`) plans one permutation per MoE
  layer, the trainer gathers that layer's stacked expert weights into their
  new slots (:func:`repro.train.trainer.permute_expert_weights`), and the
  transformer scan feeds this module the matching row of the ``[repeats,
  E_virtual]`` ``expert_perm`` stack so the router addresses the new slots —
  the wire protocol itself never changes, exactly like pushing a per-region
  cross-map to the OCS.  Plans that move WHOLE device blocks skip the
  weight gather entirely: the trainer installs a per-layer ``wire_perm``
  device map and this module re-addresses the a2a's wire chunks instead
  (``dest_perm``/``src_perm`` — the literal cross-map push).

``dense_decode`` — decode-time weight-stationary path: ALL experts computed
  densely on the handful of live tokens, combined by gathering each choice's
  expert output through the routing core's virtual-slot map and summing in
  GATE order — which both applies ``expert_perm`` and keeps decode tokens
  BIT-identical across runtime reconfigurations (DESIGN.md §9).  MoE configs
  with ``decode_backend="sparse"`` skip this path and keep the mixnet
  backend's sparse EP dispatch at decode — the serving engine's
  a2a-per-tick mode, where wire perms re-address the decode all-to-all.

Dispatch semantics (``cfg.moe.dispatch``): **dropless** (default) routes
every token — the einsum backend sizes its dense buffers at the worst case
(~E/(top_k·capacity_factor)× the capacity-mode FFN rows: fine for parity
validation and small models, use capacity mode or the mixnet backend at
scale), the mixnet backend packs the MegaBlocks block layout (dropless
without the padding) — while **capacity** keeps the classic capacity-factor
buffers and drops overflow (bounded wire traffic for the sharded
all-to-all).  ``dropped_fraction`` telemetry counts losses from *every*
stage of a backend's pipeline.

Virtual experts (DESIGN.md §5): when E < model-axis size P, every expert is
split into r = P/E tensor shards; a token is dispatched to all r shards of
its expert and the combine sums the partial products, restoring the
row-split matmul identity.  This makes the expert dim shard exactly for any
assigned architecture (grok-1: 8 experts -> 16 virtual on a 16-wide axis).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import overlap
from repro.core.commruntime import AllToAll, CommSpec, fuse_pack, fuse_unpack
from repro.kernels import ops
from repro.kernels.grouped_matmul import pick_tiles
from repro.models import routing
from repro.models.routing import MoEStats, router_losses
from repro.obs import metrics
from repro.parallel.sharding import ShardingPlan, constrain, virtual_experts

__all__ = [
    "init_moe", "moe_apply", "MoEStats", "router_losses",
    "resolve_draft_mode", "draft_config",
]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_moe(key, cfg, plan: ShardingPlan):
    e = cfg.moe
    d = cfg.d_model
    dtype = jnp.dtype(cfg.dtype)
    ev, r = virtual_experts(e.num_experts, plan.model_size)
    if e.d_ff % r != 0:
        raise ValueError(f"expert d_ff {e.d_ff} not divisible by replication {r}")
    f_shard = e.d_ff // r
    keys = jax.random.split(key, 5)

    params = {
        "router": jax.random.normal(keys[0], (d, e.num_experts), jnp.float32) * d**-0.5,
        "w_in": jax.random.normal(keys[1], (ev, d, f_shard), dtype) * d**-0.5,
        "w_gate": jax.random.normal(keys[2], (ev, d, f_shard), dtype) * d**-0.5,
        "w_out": jax.random.normal(keys[3], (ev, f_shard, d), dtype) * e.d_ff**-0.5,
    }
    ex_ax = plan.dim_axis(ev)
    specs = {
        "router": P(None, None),
        "w_in": P(ex_ax, plan.fsdp_axis, None),
        "w_gate": P(ex_ax, plan.fsdp_axis, None),
        "w_out": P(ex_ax, None, plan.fsdp_axis),
    }
    if e.num_shared_experts:
        f_sh = e.d_ff * e.num_shared_experts
        k5, k6, k7 = jax.random.split(keys[4], 3)
        params["shared"] = {
            "w_in": jax.random.normal(k5, (d, f_sh), dtype) * d**-0.5,
            "w_gate": jax.random.normal(k6, (d, f_sh), dtype) * d**-0.5,
            "w_out": jax.random.normal(k7, (f_sh, d), dtype) * f_sh**-0.5,
        }
        sh_ax = plan.dim_axis(f_sh)
        specs["shared"] = {
            "w_in": P(plan.fsdp_axis, sh_ax),
            "w_gate": P(plan.fsdp_axis, sh_ax),
            "w_out": P(sh_ax, plan.fsdp_axis),
        }
    return params, specs


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------


def _actfn(act: str):
    return jax.nn.silu if act in ("silu", "swiglu") else jax.nn.gelu


def _expert_ffn(x, w_in, w_gate, w_out, act):
    """x [..., E, C, D] grouped through per-expert SwiGLU (einsum form, for
    the pjit-partitioned dense backends)."""
    h = jnp.einsum("...ecd,edf->...ecf", x, w_in)
    g = jnp.einsum("...ecd,edf->...ecf", x, w_gate)
    h = _actfn(act)(g) * h
    return jnp.einsum("...ecf,efd->...ecd", h, w_out)


# ---------------------------------------------------------------------------
# einsum (GShard) backend
# ---------------------------------------------------------------------------


def _moe_einsum(params, x, cfg, plan: ShardingPlan, mesh=None, expert_perm=None):
    e = cfg.moe
    b, s, d = x.shape
    ev, r = virtual_experts(e.num_experts, plan.model_size)
    sc = e.top_k * r
    # Token groups: one group per sequence shard so the dispatch einsum's
    # quadratic term stays bounded and group boundaries match the sharding.
    g = plan.model_size if (plan.model_size > 1 and s % plan.model_size == 0) else 1
    t = s // g
    batch_ok = b % max(plan.data_size, 1) == 0
    gspec = (plan.batch_axes or None) if batch_ok else None
    xg = x.reshape(b * g, t, d)
    # GShard-baseline sharding: groups ride the DP axes only (tokens gathered
    # over the model axis), the expert dim rides the model axis.  Dispatch is
    # an all-gather, combine a reduce-scatter — the static-fabric baseline
    # the mixnet backend's true hierarchical a2a improves on (§Perf).
    xg = constrain(xg, mesh, P(gspec, None, None))
    with jax.named_scope("moe.route"):
        logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), params["router"])
        info = routing.compute_routing(
            logits.reshape(-1, e.num_experts),
            top_k=e.top_k,
            num_virtual=ev,
            replication=r,
            expert_perm=expert_perm,
        )
    vdest = info.vdest.reshape(b * g, t * sc)
    wfull = info.wfull.reshape(b * g, t, sc)

    # Per-virtual-slot capacity: a slot can receive at most t tokens (top-k
    # indices are distinct), so cap = t is exactly dropless; capacity mode
    # keeps the classic factor-bounded buffers and drops overflow.  Dense
    # dropless is inherently padded-worst-case — buffers and expert-FFN rows
    # grow by ~E/(top_k·capacity_factor) over capacity mode, the waste
    # MegaBlocks measures — so at scale run this baseline in capacity mode
    # (or use the mixnet backend, whose block layout is dropless WITHOUT the
    # padding).
    if e.dispatch == "dropless":
        cap = t
    else:
        cap = routing.capacity(t, e.top_k, e.num_experts, e.capacity_factor)
    ex_ax = plan.dim_axis(ev)
    with jax.named_scope("moe.dispatch"):
        rank, _ = jax.vmap(lambda dv: routing.bucket_ranks(dv, ev))(vdest)
        vdest = vdest.reshape(b * g, t, sc)
        rank = rank.reshape(b * g, t, sc)
        keep = rank < cap
        dispatch, combine = routing.dense_dispatch_masks(
            vdest, rank, keep, wfull, ev, cap
        )
        dropped = 1.0 - keep.sum() / (b * g * t * sc)
        xe = jnp.einsum("gtec,gtd->gecd", dispatch.astype(x.dtype), xg)  # [G,Ev,C,D]
        xe = constrain(xe, mesh, P(gspec, ex_ax, None, None))
    with jax.named_scope("moe.experts"):
        ye = _expert_ffn(xe, params["w_in"], params["w_gate"], params["w_out"], cfg.act)
        ye = constrain(ye, mesh, P(gspec, ex_ax, None, None))
    with jax.named_scope("moe.combine"):
        out = jnp.einsum("gtec,gecd->gtd", combine.astype(x.dtype), ye)
        out = out.reshape(b, s, d)

    with jax.named_scope("moe.route"):
        balance, z = router_losses(logits, info.idx, e.num_experts)
        load = routing.expert_load(info.idx, e.num_experts)
    stats = MoEStats(load, balance, z, dropped.astype(jnp.float32))
    return out, stats


# ---------------------------------------------------------------------------
# mixnet (shard_map hierarchical a2a) backend
# ---------------------------------------------------------------------------


def _record_tiles(rows: int, experts: int, d: int, f: int, padded_rows: int):
    """Record at trace time the dropless GEMM tiles :func:`pick_tiles` gave
    ``rows`` routed rows over ``experts`` local experts, as the gauge
    ``moe.gemm_tile`` (value: the static row tiles), one series per
    compiled shape and GEMM direction (``d -> f`` up, ``f -> d`` down)."""
    for din, dout in ((d, f), (f, d)):
        blk, bf, bd = pick_tiles(rows, experts, din, dout)
        metrics.gauge(
            "moe.gemm_tile", rows=rows, experts=experts, d=din, f=dout,
            B=blk, bf=bf, bd=bd, padded_rows=padded_rows,
        ).set(padded_rows // blk)


def _wire_perms(wire_perm, p_axis):
    """(dest_perm, src_perm) realizing a ControlPlane wire re-address.

    ``wire_perm`` is the layer's device map ``D`` (logical device k's experts
    physically live on device ``D[k]``, installed instead of a weight gather
    when a placement plan moves whole device blocks).  The dispatch trip
    re-addresses chunks with ``D^-1`` (physical device j serves logical
    ``D^-1[j]``); the return trip restores logical order with ``D``.
    """
    if wire_perm is None:
        return None, None
    wire_src = wire_perm.astype(jnp.int32)
    wire_dest = (
        jnp.zeros((p_axis,), jnp.int32)
        .at[wire_src]
        .set(jnp.arange(p_axis, dtype=jnp.int32))
    )
    return wire_dest, wire_src


def _moe_mixnet_local(
    params_local, xl, cfg, plan: ShardingPlan, expert_perm, axis_names,
    wire_perm=None, token_axes=(), gate_weights=None,
):
    """Per-device MoE body (runs inside shard_map, or standalone at P=1).

    ``cfg.moe.overlap_chunks > 1`` runs the chunked software pipeline
    (DESIGN.md §8): the token dim splits into C chunks and chunk k+1's
    dispatch a2a runs under chunk k's expert FFN under chunk k-1's combine,
    each a2a further split into its delegation stages
    (``AllToAll.stages()``).  Chunk rows are independent and capacity-mode
    keep decisions are computed globally, so the chunked output is
    bit-identical to the serial path (the only semantic divergence is the
    stage-2 overflow regime of capacity mode, which the chunked layout does
    not drop — see §8).
    """
    e = cfg.moe
    ev, r = virtual_experts(e.num_experts, plan.model_size)
    p_axis = max(plan.model_size, 1)
    ev_local = ev // p_axis
    dropless = e.dispatch == "dropless"
    router, w_in, w_gate, w_out = params_local
    bl, sl, d = xl.shape
    tl = bl * sl
    sc = e.top_k * r
    n = tl * sc
    xt = xl.reshape(tl, d)
    if gate_weights is not None:
        gate_weights = gate_weights.reshape(tl)
    act = _actfn(cfg.act)

    with jax.named_scope("moe.route"):
        logits = xt.astype(jnp.float32) @ router
        info = routing.compute_routing(
            logits, top_k=e.top_k, num_virtual=ev, replication=r,
            expert_perm=expert_perm,
        )
    wire_dest, wire_src = _wire_perms(wire_perm, p_axis)

    with jax.named_scope("moe.dispatch"):
        flat_dev = (info.vdest // ev_local).reshape(n)
        local_e = (info.vdest % ev_local).reshape(n)
        # Stage-1 keep decisions are GLOBAL (chunk-invariant): dropless
        # keeps everything; capacity mode keeps global rank < the serial
        # capacity, so chunking never changes which tokens survive the send
        # stage.
        rank1, _ = routing.bucket_ranks(flat_dev, p_axis)
        cp = n if dropless else routing.capacity(tl, sc, p_axis, e.capacity_factor)
        keep1 = None if dropless else rank1 < cp

    # One CommRuntime op serves the whole layer: the dispatch trip moves the
    # token payload and its expert-id metadata as ONE packed wire transfer
    # (bit-identical payload to the unfused pair, tested), the return trip
    # reuses the same lowering.  P = 1 degrades to identity inside the op.
    a2a = AllToAll(CommSpec.from_plan(plan, group_size=e.a2a_group))
    chunks = overlap.chunk_count(tl, e.overlap_chunks)

    def expert_ffn_block(rx, re):
        """Received rows -> per-expert pack -> grouped GEMM -> unpacked rows.
        Returns (back rows aligned with the receive layout, kept count)."""
        blocks = dropless or chunks > 1
        with jax.named_scope("moe.dispatch"):
            valid = re >= 0
            rank2, counts2 = routing.bucket_ranks(re, ev_local, valid=valid)
            if blocks:
                # Block layout: every valid received row is placed (the
                # chunked pipeline uses it for capacity mode too — static
                # shapes, and stage-2 never drops below the stage-1
                # capacity bound).  The tile follows the shapes: the
                # receive buffer holds p_axis x the rows this device sends.
                rows, f = re.shape[0] // p_axis, w_in.shape[-1]
                blk = pick_tiles(rows, ev_local, d, f)[0]
                plan2 = routing.dropless_plan(
                    re, rank2, counts2, valid, ev_local, blk
                )
                _record_tiles(rows, ev_local, d, f, plan2.num_rows)
                packed = ops.moe_dispatch(rx, plan2.src).reshape(-1, blk, d)
            else:
                c2 = routing.capacity(re.shape[0], 1, ev_local, e.capacity_factor)
                plan2 = routing.capacity_plan(re, rank2, valid, ev_local, c2)
                packed = ops.moe_dispatch(rx, plan2.src).reshape(ev_local, c2, d)
        with jax.named_scope("moe.experts"):
            gmm = functools.partial(
                ops.grouped_matmul, block_experts=plan2.block_experts,
                used_blocks=plan2.used_blocks,
            )
            h = gmm(packed, w_in)
            gt = gmm(packed, w_gate)
            ye = gmm(act(gt) * h, w_out)
        with jax.named_scope("moe.combine"):
            back = ops.moe_dispatch(ye.reshape(plan2.num_rows, d), plan2.slot)
        return back, plan2.kept

    if chunks == 1:
        # --- serial path: one send buffer, one a2a pair ---------------------
        with jax.named_scope("moe.dispatch"):
            plan1 = routing.capacity_plan(flat_dev, rank1, keep1, p_axis, cp)
            src_tok = jnp.where(plan1.src >= 0, plan1.src // sc, -1)
            send_x = ops.moe_dispatch(xt, src_tok).reshape(p_axis, cp, d)
            send_e = jnp.where(
                plan1.src >= 0, local_e[jnp.clip(plan1.src, 0, n - 1)], -1
            ).reshape(p_axis, cp).astype(jnp.int32)
            if e.a2a_fuse:
                recv_x, recv_e = a2a.fused(send_x, send_e, dest_perm=wire_dest)
            else:
                recv_x = a2a(send_x, dest_perm=wire_dest)
                recv_e = a2a(send_e[..., None], dest_perm=wire_dest)[..., 0]
        back, kept = expert_ffn_block(
            recv_x.reshape(p_axis * cp, d), recv_e.reshape(p_axis * cp)
        )
        with jax.named_scope("moe.combine"):
            ret = a2a(back.reshape(p_axis, cp, d), src_perm=wire_src)
            out = ops.moe_combine(
                ret.reshape(p_axis * cp, d), plan1.slot.reshape(tl, sc), info.wfull
            )
    else:
        # --- chunked software pipeline (repro.core.overlap) -----------------
        out, kept = _mixnet_chunked(
            xt, info, flat_dev, local_e, keep1, a2a, expert_ffn_block,
            wire_dest, wire_src, chunks=chunks, p_axis=p_axis, sc=sc, cp=cp,
            fuse=e.a2a_fuse,
        )
    out = out.reshape(bl, sl, d).astype(xl.dtype)

    with jax.named_scope("moe.route"):
        balance, z = router_losses(logits, info.idx, e.num_experts)
        load = routing.expert_load(info.idx, e.num_experts, weights=gate_weights)
        # Drop telemetry folds BOTH stages: `kept` counts received rows that
        # won an expert slot, i.e. choices that survived the send-buffer
        # stage AND the pack stage (stage-1 drops never arrive).  psum'ing
        # kept and offered over the mesh yields the global realized loss the
        # control plane acts on (exactly 0 in dropless mode).
        kept = kept.astype(jnp.float32)
        offered = jnp.asarray(float(n), jnp.float32)
        # Reduce telemetry over every mesh axis so replicated out_specs hold.
        # Counts SUM over axes that shard the token dim and MEAN over axes
        # the tokens ride replicated (the sparse decode path: every device
        # sees the whole live batch — a psum there would overcount the load
        # P-fold).
        for ax in axis_names:
            red = jax.lax.psum if ax in token_axes else jax.lax.pmean
            load = red(load, ax)
            balance = jax.lax.pmean(balance, ax)
            z = jax.lax.pmean(z, ax)
            kept = red(kept, ax)
            offered = red(offered, ax)
        drop = 1.0 - kept / offered
    return out, load, balance, z, drop


def _mixnet_chunked(
    xt, info, flat_dev, local_e, keep1, a2a, expert_ffn_block,
    wire_dest, wire_src, *, chunks, p_axis, sc, cp, fuse,
):
    """Chunked double-buffered dispatch/FFN/combine pipeline (DESIGN.md §8).

    The token dim splits into ``chunks`` equal chunks; each chunk runs
    send-buffer build -> dispatch a2a (per delegation stage) -> expert FFN ->
    return a2a (per stage) -> weighted combine, and the stage list executes
    through :func:`repro.core.overlap.software_pipeline` so chunk k+1's
    dispatch is issued under chunk k's FFN under chunk k-1's combine.
    Returns (``[T, D]`` f32 combined output, kept-choice count).
    """
    tl, d = xt.shape
    tc = tl // chunks
    nc = tc * sc
    # Per-chunk send capacity: dropless keeps the exact worst case (all nc
    # choices to one device); capacity mode is bounded by the GLOBAL serial
    # capacity (keep decisions are global, so no chunk exceeds it).
    cp_c = nc if keep1 is None else min(nc, cp)
    disp_stages = a2a.stages()
    ret_stages = a2a.stages()
    fused = fuse and jnp.dtype(xt.dtype).itemsize in (2, 4)

    @jax.named_scope("moe.dispatch")
    def s_build(_, k):
        lo = k * nc
        dest_c = jax.lax.slice_in_dim(flat_dev, lo, lo + nc)
        keep_c = None if keep1 is None else jax.lax.slice_in_dim(keep1, lo, lo + nc)
        rank_c, _ = routing.bucket_ranks(dest_c, p_axis, valid=keep_c)
        plan1_c = routing.capacity_plan(dest_c, rank_c, keep_c, p_axis, cp_c)
        src_tok = jnp.where(plan1_c.src >= 0, k * tc + plan1_c.src // sc, -1)
        send_x = ops.moe_dispatch(xt, src_tok).reshape(p_axis, cp_c, d)
        le_c = jax.lax.slice_in_dim(local_e, lo, lo + nc)
        send_e = jnp.where(
            plan1_c.src >= 0, le_c[jnp.clip(plan1_c.src, 0, nc - 1)], -1
        ).reshape(p_axis, cp_c).astype(jnp.int32)
        st = {"plan1": plan1_c}
        if fused:
            st["x"] = disp_stages[0](fuse_pack(send_x, send_e), dest_perm=wire_dest)
        else:
            st["x"] = disp_stages[0](send_x, dest_perm=wire_dest)
            st["e"] = disp_stages[0](send_e[..., None], dest_perm=wire_dest)
        return st

    @jax.named_scope("moe.dispatch")
    def s_disp2(st, _):
        st = dict(st)
        st["x"] = disp_stages[1](st["x"])
        if not fused:
            st["e"] = disp_stages[1](st["e"])
        return st

    def s_ffn(st, _):
        with jax.named_scope("moe.dispatch"):
            if fused:
                recv_x, recv_e = fuse_unpack(st["x"], d)
            else:
                recv_x, recv_e = st["x"], st["e"][..., 0]
        back, kept_c = expert_ffn_block(
            recv_x.reshape(p_axis * cp_c, d), recv_e.reshape(p_axis * cp_c)
        )
        with jax.named_scope("moe.combine"):
            back = back.reshape(p_axis, cp_c, d)
            if len(ret_stages) == 1:
                back = ret_stages[0](back, src_perm=wire_src)
            else:
                back = ret_stages[0](back)
        return {"plan1": st["plan1"], "back": back, "kept": kept_c}

    @jax.named_scope("moe.combine")
    def s_ret2(st, _):
        st = dict(st)
        st["back"] = ret_stages[1](st["back"], src_perm=wire_src)
        return st

    @jax.named_scope("moe.combine")
    def s_combine(st, k):
        wf_c = jax.lax.slice_in_dim(info.wfull, k * tc, (k + 1) * tc)
        out_c = ops.moe_combine(
            st["back"].reshape(p_axis * cp_c, d),
            st["plan1"].slot.reshape(tc, sc),
            wf_c,
        )
        return out_c, st["kept"]

    stage_fns = [s_build]
    if len(disp_stages) == 2:
        stage_fns.append(s_disp2)
    stage_fns.append(s_ffn)
    if len(ret_stages) == 2:
        stage_fns.append(s_ret2)
    stage_fns.append(s_combine)

    results = overlap.software_pipeline(chunks, stage_fns)
    out = jnp.concatenate([r[0] for r in results], axis=0)
    kept = sum(r[1] for r in results)
    return out, kept


def _moe_mixnet(
    params, x, cfg, plan: ShardingPlan, mesh, expert_perm, wire_perm=None,
    gate_weights=None,
):
    """``expert_perm`` is THIS layer's ``[E_virtual]`` expert->slot map (one
    row of the trainer's per-layer perm stack); ``wire_perm`` its optional
    ``[P]`` device map when the plan was installed as a wire re-address
    instead of a weight gather (``op.reconfigure`` semantics);
    ``gate_weights`` an optional ``[B, S]`` per-token telemetry weight (the
    serving engine's live-slot mask, DESIGN.md §9)."""
    e = cfg.moe
    ev, _ = virtual_experts(e.num_experts, plan.model_size)

    def body(
        router, w_in, w_gate, w_out, xl, perm, wire=None, gw=None,
        axis_names=(), token_axes=(),
    ):
        return _moe_mixnet_local(
            (router, w_in, w_gate, w_out), xl, cfg, plan, perm, axis_names,
            wire_perm=wire, token_axes=token_axes, gate_weights=gw,
        )

    if mesh is None or plan.model_size <= 1:
        out, load, balance, z, drop = body(
            params["router"], params["w_in"], params["w_gate"], params["w_out"],
            x, expert_perm, wire_perm, gate_weights,
        )
    else:
        ex_ax = plan.dim_axis(ev)
        axis_names = tuple(a for a in (plan.batch_axes or ()) if a) + (
            (plan.model_axis,) if plan.model_axis else ()
        )
        # Token sharding for the shard_map region: seq over the model axis
        # for train/prefill; decode (S=1) shards batch only — every device
        # dispatches its batch rows to the expert owners over the a2a.
        b_sz, s_sz = x.shape[0], x.shape[1]
        batch_ax = (
            (plan.batch_axes or None)
            if b_sz % max(plan.data_size, 1) == 0
            else None
        )
        seq_ax = plan.model_axis if s_sz % plan.model_size == 0 else None
        # Axes that actually shard the token dim (telemetry psums over these;
        # axes the tokens ride replicated take pmean instead).
        token_axes = (
            tuple(a for a in (batch_ax or ()) if a)
            + ((seq_ax,) if seq_ax else ())
        )
        tok_spec = P(batch_ax, seq_ax, None)
        gw_spec = P(batch_ax, seq_ax)
        weight_specs = (
            P(None, None),
            P(ex_ax, None, None),
            P(ex_ax, None, None),
            P(ex_ax, None, None),
        )
        out_specs = (tok_spec, P(None), P(), P(), P())
        args = [
            params["router"], params["w_in"], params["w_gate"], params["w_out"],
            x, expert_perm,
        ]
        in_specs = [*weight_specs, tok_spec, P(None)]
        has_wire = wire_perm is not None
        has_gw = gate_weights is not None
        if has_wire:
            args.append(wire_perm)
            in_specs.append(P(None))
        if has_gw:
            args.append(gate_weights)
            in_specs.append(gw_spec)

        def wrapped(*a):
            base, rest = a[:6], list(a[6:])
            wire = rest.pop(0) if has_wire else None
            gw = rest.pop(0) if has_gw else None
            return body(
                *base, wire=wire, gw=gw, axis_names=axis_names,
                token_axes=token_axes,
            )

        fn = jax.shard_map(
            wrapped, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs,
            check_vma=False,
        )
        out, load, balance, z, drop = fn(*args)
    return out, MoEStats(load, balance, z, drop)


# ---------------------------------------------------------------------------
# dense decode backend
# ---------------------------------------------------------------------------


def _moe_dense_decode(
    params, x, cfg, plan: ShardingPlan, mesh=None, expert_perm=None,
    gate_weights=None,
):
    """Decode-time MoE: compute ALL experts densely on the handful of live
    tokens and combine with the (sparse) gate weights.

    At decode the token count is tiny, so the extra FLOPs of computing every
    expert (~1 ms on 256 chips for deepseek-v2's 128 tokens) are nothing —
    while the sparse dispatch path must gather 2D-sharded expert weights
    over the FSDP axis every layer (~27 GB/step for deepseek-v2).  Dense
    decode keeps weights stationary: activations ride the contractions
    (psums of a few MB).  §Perf beyond-paper optimization.

    The gate map comes from the routing core's virtual-slot destinations, so
    the layer's ``expert_perm`` re-addressing applies here exactly as it
    does on the sparse paths (decode after a runtime reconfiguration hits
    physically permuted expert weights).  The combine gathers each choice's
    expert output by ``vdest`` and sums in GATE order — the contraction
    never sees the physical slot positions, so decode tokens are
    BIT-identical under any ``expert_perm`` (the DESIGN.md §9
    generation-consistency guarantee; a dense ``[T, Ev]`` scatter would sum
    in slot order, whose float association moves with the permutation).

    ``gate_weights`` (``[B, S]``) weights the exported expert-load telemetry
    per token — the serving engine's live-slot mask (DESIGN.md §9).
    """
    e = cfg.moe
    b, s, d = x.shape
    ev, r = virtual_experts(e.num_experts, plan.model_size)
    xt = x.reshape(b * s, d)
    with jax.named_scope("moe.route"):
        logits = xt.astype(jnp.float32) @ params["router"]
        info = routing.compute_routing(
            logits, top_k=e.top_k, num_virtual=ev, replication=r,
            expert_perm=expert_perm,
        )

    ex_ax = plan.dim_axis(ev)
    with jax.named_scope("moe.experts"):
        h = jnp.einsum("td,edf->tef", xt, params["w_in"])
        g = jnp.einsum("td,edf->tef", xt, params["w_gate"])
        h = _actfn(cfg.act)(g) * h
        h = constrain(h, mesh, P(None, ex_ax, None))
        y = jnp.einsum("tef,efd->ted", h, params["w_out"])
    with jax.named_scope("moe.combine"):
        ysel = jnp.take_along_axis(y, info.vdest[:, :, None], axis=1)  # [T, S, D]
        out = jnp.einsum("ts,tsd->td", info.wfull.astype(y.dtype), ysel)
        out = out.reshape(b, s, d)

    with jax.named_scope("moe.route"):
        balance, z = router_losses(logits, info.idx, e.num_experts)
        load = routing.expert_load(info.idx, e.num_experts, weights=gate_weights)
    return out, MoEStats(load, balance, z, jnp.zeros((), jnp.float32))


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def moe_apply(
    params,
    x: jax.Array,
    cfg,
    plan: ShardingPlan,
    *,
    mesh=None,
    expert_perm=None,
    wire_perm=None,
    backend: str | None = None,
    mode: str | None = None,
    gate_weights: jax.Array | None = None,
):
    """``wire_perm``: optional ``[P]`` device map from a wire-level
    re-address (this layer's experts logically on device k physically live
    on device ``wire_perm[k]``; weights were NOT gathered).  The mixnet
    backend realizes it on the a2a wire; the dense backends compose it into
    the slot addressing so every path hits the physically-resident weights.
    ``gate_weights``: optional ``[B, S]`` per-token telemetry weight — the
    serving engine's live-slot mask, so the exported expert load counts only
    occupied decode slots (DESIGN.md §9)."""
    e = cfg.moe
    if e.draft_mode == "topk1" and e.top_k > 1:
        # Speculative draft (DESIGN.md §11): narrow the routed fan-out to the
        # gate's single best expert.  Rewriting the frozen config keeps every
        # backend below unchanged; the draft step jit-compiles separately
        # because the config is its static key.
        cfg = dataclasses.replace(
            cfg,
            moe=dataclasses.replace(
                e, top_k=routing.effective_top_k(e.top_k, e.draft_mode),
                draft_mode="off",
            ),
        )
        e = cfg.moe
    backend = backend or e.backend
    if backend != "einsum" and (x.shape[1] == 1 or mode == "decode"):
        # Single-token decode: weight-stationary dense path (see docstring)
        # unless the config pins the sparse EP dispatch at decode — the
        # serving engine's a2a-per-tick path (``MoEConfig.decode_backend``).
        if not (backend == "mixnet" and e.decode_backend == "sparse"):
            backend = "dense_decode"
    ev, _ = virtual_experts(e.num_experts, plan.model_size)
    perm = routing.resolve_perm(expert_perm, ev)
    if wire_perm is not None and backend != "mixnet":
        # Logical slot s lives at physical slot wire[s // epd] * epd + s % epd.
        p_axis = max(plan.model_size, 1)
        epd = ev // p_axis
        wire = jnp.asarray(wire_perm, jnp.int32)
        perm = wire[perm // epd] * epd + perm % epd
        wire_perm = None
    if e.draft_mode == "shared_only":
        # Speculative draft with only the always-on lane: skip the routed
        # experts (and their dispatch a2a) entirely.  Zero routed output plus
        # the shared-expert addition below; telemetry exports a zero load so
        # draft passes never perturb the control plane's gate statistics.
        zero = jnp.zeros((), jnp.float32)
        out = jnp.zeros_like(x)
        stats = MoEStats(
            jnp.zeros((e.num_experts,), jnp.float32), zero, zero, zero
        )
    elif backend == "dense_decode":
        out, stats = _moe_dense_decode(
            params, x, cfg, plan, mesh=mesh, expert_perm=perm,
            gate_weights=gate_weights,
        )
    elif backend == "mixnet":
        out, stats = _moe_mixnet(
            params, x, cfg, plan, mesh, perm, wire_perm=wire_perm,
            gate_weights=gate_weights,
        )
    elif backend == "einsum":
        out, stats = _moe_einsum(params, x, cfg, plan, mesh=mesh, expert_perm=perm)
    else:
        raise ValueError(f"unknown MoE backend {backend!r}")
    if "shared" in params:
        with jax.named_scope("moe.experts"):
            sh = params["shared"]
            h = x @ sh["w_in"]
            g = jax.nn.silu(x @ sh["w_gate"])
            out = out + (g * h) @ sh["w_out"]
    return out, stats


# ---------------------------------------------------------------------------
# speculative drafts (DESIGN.md §11)
# ---------------------------------------------------------------------------


def resolve_draft_mode(cfg, mode: str = "auto") -> str:
    """Pick the cheap draft pass for speculative decoding.

    ``auto`` prefers ``shared_only`` when the model has an always-on shared
    lane (the draft is then a dense sub-network of the full model) and falls
    back to ``topk1`` for pure sparse MoEs; non-MoE models have no cheaper
    self-draft, so the draft IS the target model (``off`` — acceptance 1.0).
    """
    if mode != "auto":
        return mode
    if not cfg.is_moe:
        return "off"
    return "shared_only" if cfg.moe.num_shared_experts > 0 else "topk1"


def draft_config(cfg, mode: str = "auto"):
    """The draft-model config: same weights, ``draft_mode`` set on the MoE
    block.  A distinct frozen config means the draft step is its own jit
    program (Kossmann et al.: bucket the specializations, don't re-jit)."""
    mode = resolve_draft_mode(cfg, mode)
    if mode == "off" or not cfg.is_moe:
        return cfg
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, draft_mode=mode)
    )
