"""Pipeline-parallel train step: the GPipe schedule of
:mod:`repro.train.pipeline` composed with the real MoE data plane.

One flat ``shard_map`` over ``('stage', [batch axes], 'model')`` runs the
whole block stack (DESIGN.md §13):

* **Blocks stack on the stage axis.**  The canonical ``[repeats, ...]``
  block params are reshaped to ``[S, repeats/S, ...]`` inside the jitted
  step; each stage owns a contiguous slice of layers.  Trainer-side state
  (checkpoints, :func:`permute_expert_weights`, the PlacementApplier) keeps
  the canonical layout — PP is invisible to everything outside the step.

* **The carrier stays sequence-sharded.**  The residual microbatch rides
  the pipe as the local ``[mb, T/P, D]`` shard (the same layout the non-PP
  step's activation spec pins), so the MoE body below sees exactly the
  token shard the non-PP ``shard_map`` region sees.  Attention gathers the
  full sequence (``all_gather`` over ``model``), computes redundantly per
  device, and slices its shard back — per-output-element math identical to
  the single-device program.

* **The MoE data plane runs unchanged inside the stage.**  Each MoE block
  calls :func:`repro.models.moe._moe_mixnet_local` — dropless/capacity
  dispatch, the fused hierarchical a2a, ``overlap_chunks`` software
  pipelining, per-layer expert/wire perms — with the same
  ``axis_names``/``token_axes`` the non-PP region uses, so per-device MoE
  numerics are bit-identical to the non-PP step.

* **Schedule = ``lax.scan`` over M + S - 1 ticks**, activations shifted
  stage-to-stage with ``lax.ppermute``; differentiating the scan yields the
  reverse pipeline.  Warmup/drain ticks feed zeros and their telemetry is
  masked (``valid = sidx <= t < sidx + M``), so bubble ticks never reach
  the ControlPlane's gate-load observations.

* **Embedding, final norm, and the chunked CE run OUTSIDE the stage
  region** under pjit — the identical program the non-PP step runs, which
  is what makes end-to-end gradient parity exact: the only difference
  between PP(S) and PP(1) is the schedule, and the loss is ONE
  ``value_and_grad`` over the full pipeline (full-batch CE + microbatch-
  averaged aux losses), so no gradient-accumulation reassociation sneaks
  in.

Gate-load telemetry accumulates per stage over valid ticks and is emitted
``[S, repeats/S, E]`` -> reshaped to the canonical ``[repeats, E]``, so
``Trainer._reconfigure_step`` (observe -> plan -> apply) works under PP
without modification.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.commruntime import AllGather, CommSpec
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import routing
from repro.models import transformer as tfm
from repro.models.transformer import _FFN_PREFETCH_DIMS
from repro.optim.adamw import AdamWConfig, adamw_update
from repro.parallel.sharding import (
    ShardingPlan,
    constrain,
    virtual_experts,
)
from repro.train.pipeline import num_ticks

__all__ = ["make_pp_train_step"]


def _validate(cfg, plan, mesh, pp_stages, stage_axis):
    if mesh is None or stage_axis not in mesh.axis_names:
        raise ValueError(
            f"pp_stages={pp_stages} needs a mesh with a {stage_axis!r} axis"
        )
    if mesh.shape[stage_axis] != pp_stages:
        raise ValueError(
            f"mesh {stage_axis!r} axis is {mesh.shape[stage_axis]}, "
            f"pp_stages is {pp_stages}"
        )
    if cfg.pattern_repeats % pp_stages:
        raise ValueError(
            f"{cfg.pattern_repeats} block repeats not divisible by "
            f"{pp_stages} stages"
        )
    bad = [k for k in cfg.block_pattern if k not in ("global", "local")]
    if bad or cfg.tail_pattern or cfg.encoder_layers or cfg.vision_patches:
        raise NotImplementedError(
            "pipeline-parallel stages support attention(+MLP/MoE) block "
            f"patterns only (got pattern={cfg.block_pattern}, "
            f"tail={cfg.tail_pattern})"
        )
    if cfg.is_moe:
        if cfg.moe.backend != "mixnet":
            raise NotImplementedError(
                "PP composes with the mixnet MoE data plane only "
                f"(backend={cfg.moe.backend!r})"
            )
        if cfg.moe.num_shared_experts:
            raise NotImplementedError(
                "shared experts are not wired through the PP stage body yet"
            )
        p = max(plan.model_size, 1)
        if p > 1 and cfg.moe.num_experts % p:
            raise NotImplementedError(
                f"PP stage specs shard the expert dim over the model axis; "
                f"{cfg.moe.num_experts} experts do not divide over {p} "
                "devices (virtual-expert replication is not wired through "
                "the stage body)"
            )


def _stage_leaf_spec(plan, stage_axis, sub, leafname, spec, prefetch):
    """in_spec for one stacked block leaf inside the flat stage shard_map.

    ``spec`` is the canonical ``P(None, *rest)`` (leading repeats dim).  The
    repeats dim splits over the stage axis; the expert dim keeps its EP
    sharding (``_moe_mixnet_local`` consumes the local shard); FFN leaves
    keep their FSDP sharding when the in-stage ring prefetch gathers them;
    every other axis is dropped so shard_map feeds the full leaf (TP
    attention inside stages is future work — attention computes replicated
    on the gathered sequence).
    """
    rest = list(spec)[1:]
    out = [None] * len(rest)
    dims = _FFN_PREFETCH_DIMS.get(sub or "", {})
    if sub == "moe" and leafname in dims:
        out[dims[leafname][1]] = rest[dims[leafname][1]]  # expert dim (EP)
    if prefetch and sub in ("moe", "mlp") and leafname in dims:
        fdim = dims[leafname][0]
        if rest[fdim] == plan.fsdp_axis:
            out[fdim] = rest[fdim]
    return P(stage_axis, None, *out)


def make_pp_train_step(
    cfg,
    plan: ShardingPlan,
    opt_cfg: AdamWConfig,
    mesh,
    *,
    pp_stages: int,
    microbatches: int = 1,
    block_specs=None,
    stage_axis: str = "stage",
):
    """jit-able ``(params, opt_state, batch, expert_perm, wire_perm) ->
    (params, opt_state, metrics)`` with blocks pipelined over ``pp_stages``.

    ``params`` stay in the canonical ``[repeats, ...]`` layout; ``plan`` is
    the usual :func:`make_plan` of the mesh (the ``stage`` axis is invisible
    to it — batch/model semantics inside a stage match the non-PP step).
    ``block_specs``: the init-time ``specs["blocks"]`` tree (derived from a
    throwaway init when omitted).
    """
    _validate(cfg, plan, mesh, pp_stages, stage_axis)
    s = pp_stages
    m = microbatches
    reps = cfg.pattern_repeats
    reps_local = reps // s
    names = [f"{i}_{k}" for i, k in enumerate(cfg.block_pattern)]
    pattern = cfg.block_pattern
    p_model = max(plan.model_size, 1)
    ticks = num_ticks(m, s)
    pperm = [(i, i + 1) for i in range(s - 1)]
    ev, _ = virtual_experts(cfg.moe.num_experts, p_model) if cfg.is_moe else (1, 1)

    if block_specs is None:
        key = jax.random.PRNGKey(0)
        block_specs = {}
        for i, kind in enumerate(pattern):
            _, spec1 = tfm._block_init(key, kind, cfg, plan)
            block_specs[names[i]] = jax.tree.map(
                lambda sp: P(None, *sp), spec1,
                is_leaf=lambda sp: isinstance(sp, P),
            )

    prefetch = bool(cfg.fsdp_prefetch and plan.fsdp_axis is not None)
    fsdp_ag = (
        AllGather(
            CommSpec(axis=plan.fsdp_axis, axis_size=max(plan.data_size, 1)),
            impl="ring",
        )
        if prefetch
        else None
    )
    axis_names = tuple(a for a in (plan.batch_axes or ()) if a) + (
        (plan.model_axis,) if plan.model_axis else ()
    )

    def staged_in_specs():
        out = {}
        for name in names:
            sub_specs = block_specs[name]
            staged = {}
            for sub, tree in sub_specs.items():
                if isinstance(tree, P):
                    staged[sub] = _stage_leaf_spec(
                        plan, stage_axis, None, sub, tree, prefetch
                    )
                else:
                    staged[sub] = {
                        leaf: _stage_leaf_spec(
                            plan, stage_axis, sub, leaf, sp, prefetch
                        )
                        for leaf, sp in tree.items()
                    }
            out[name] = staged
        return out

    blocks_in_specs = staged_in_specs()

    def _gather_ffn(sub_name, sub_params, sub_specs):
        """Ring-gather FSDP-sharded FFN leaves inside the stage region."""
        if not prefetch or sub_name not in _FFN_PREFETCH_DIMS:
            return sub_params
        out = dict(sub_params)
        for wname, (fdim, _) in _FFN_PREFETCH_DIMS[sub_name].items():
            if wname not in out:
                continue
            sp = sub_specs[wname]
            if len(sp) > 2 + fdim and sp[2 + fdim] == plan.fsdp_axis:
                out[wname] = fsdp_ag(out[wname], axis=fdim)
        return out

    def _apply_block_local(kind, p, x, perm_row, wire_row, midx, token_axes):
        """One transformer block on the local ``[mb, T/P, D]`` shard."""
        sl = x.shape[1]
        h = L.rms_norm(x, p["norm1"], cfg.norm_eps)
        if p_model > 1:
            hg = lax.all_gather(h, plan.model_axis, axis=1, tiled=True)
        else:
            hg = h
        y, _ = L.attention_apply(p["attn"], hg, cfg, kind=kind, mode="train")
        if p_model > 1:
            y = lax.dynamic_slice_in_dim(y, midx * sl, sl, axis=1)
        x = x + y
        h2 = L.rms_norm(x, p["norm2"], cfg.norm_eps)
        stats = None
        if cfg.is_moe:
            mp = p["moe"]
            perm_row = routing.resolve_perm(perm_row, ev)
            y, load, bal, z, _ = moe_mod._moe_mixnet_local(
                (mp["router"], mp["w_in"], mp["w_gate"], mp["w_out"]),
                h2, cfg, plan, perm_row, axis_names,
                wire_perm=wire_row, token_axes=token_axes,
            )
            stats = (load, bal, z)
        else:
            y = L.mlp_apply(p["mlp"], h2, cfg)
        x = x + y
        return x, stats

    def train_step(
        params, opt_state, batch, expert_perm=None, wire_perm=None,
    ):
        tokens, labels = batch["tokens"], batch["labels"]
        b, t_len = tokens.shape
        if b % m:
            raise ValueError(f"batch {b} not divisible by {m} microbatches")
        mb = b // m
        seq_ok = t_len % p_model == 0 and p_model > 1
        batch_ok = mb % max(plan.data_size, 1) == 0
        if p_model > 1 and not seq_ok:
            raise ValueError(
                f"seq {t_len} must divide the model axis {p_model} for the "
                "sequence-sharded PP carrier"
            )
        batch_ax = (plan.batch_axes or None) if batch_ok else None
        seq_ax = plan.model_axis if seq_ok else None
        token_axes = tuple(a for a in (batch_ax or ()) if a) + (
            (seq_ax,) if seq_ax else ()
        )
        mb_spec = P(None, batch_ax, seq_ax, None)

        if expert_perm is None and cfg.is_moe:
            expert_perm = jnp.broadcast_to(
                jnp.arange(ev, dtype=jnp.int32), (reps, ev)
            )

        def per_device(blocks_local, mbs, perm_local, wire_local):
            blocks_here = jax.tree.map(lambda p: p[0], blocks_local)
            perm_here = perm_local[0] if perm_local is not None else None
            wire_here = wire_local[0] if wire_local is not None else None
            sidx = lax.axis_index(stage_axis)
            midx = lax.axis_index(plan.model_axis) if p_model > 1 else 0
            zero = jnp.zeros_like(mbs[0])

            def rep_body(x, xs):
                gp = xs["blocks"]
                prow = xs.get("perm")
                wrow = xs.get("wire")
                stats_list = []
                for i, kind in enumerate(pattern):
                    bp = dict(gp[names[i]])
                    for fk in ("moe", "mlp"):
                        if fk in bp:
                            bp[fk] = _gather_ffn(
                                fk, bp[fk], blocks_in_specs[names[i]][fk]
                            )
                    x, st = _apply_block_local(
                        kind, bp, x, prow, wrow, midx, token_axes
                    )
                    if st is not None:
                        stats_list.append(st)
                nstat = max(len(stats_list), 1)
                load = (
                    stats_list[0][0]
                    if stats_list
                    else jnp.zeros((1,), jnp.float32)
                )
                bal = sum(st[1] for st in stats_list) / nstat if stats_list \
                    else jnp.zeros((), jnp.float32)
                z = sum(st[2] for st in stats_list) / nstat if stats_list \
                    else jnp.zeros((), jnp.float32)
                return x, (load, bal, z)

            def run_stage(x):
                xs = {"blocks": blocks_here}
                if perm_here is not None:
                    xs["perm"] = perm_here
                if wire_here is not None:
                    xs["wire"] = wire_here
                return lax.scan(rep_body, x, xs)

            if cfg.remat != "none":
                run_stage = jax.checkpoint(run_stage)

            e_dim = cfg.moe.num_experts if cfg.is_moe else 1

            def tick(carry, t):
                buf, lacc, bacc, zacc = carry
                feed = jnp.where(t < m, mbs[jnp.minimum(t, m - 1)], 0)
                xin = jnp.where(sidx == 0, feed, buf)
                y, (loads, bals, zs) = run_stage(xin)
                nxt = lax.ppermute(y, stage_axis, pperm) if s > 1 else y
                # Bubble ticks (warmup on stage i: t < i; drain: t >= i + M)
                # carry zeros and MUST NOT pollute the gate telemetry.
                valid = (t >= sidx) & (t - sidx < m)
                w = jnp.where(valid, 1.0, 0.0).astype(jnp.float32)
                lacc = lacc + w * loads
                bacc = bacc + w * bals / m
                zacc = zacc + w * zs / m
                return (nxt, lacc, bacc, zacc), y

            init = (
                zero,
                jnp.zeros((reps_local, e_dim), jnp.float32),
                jnp.zeros((reps_local,), jnp.float32),
                jnp.zeros((reps_local,), jnp.float32),
            )
            (_, lacc, bacc, zacc), outs = lax.scan(
                tick, init, jnp.arange(ticks)
            )
            return outs[None], lacc[None], bacc[None], zacc[None]

        def loss_fn(params):
            x = jnp.take(params["embed"], tokens, axis=0).astype(
                jnp.dtype(cfg.dtype)
            )
            x = x * (cfg.d_model**0.5)
            mbs = x.reshape(m, mb, t_len, cfg.d_model)
            staged_blocks = jax.tree.map(
                lambda p: p.reshape(s, reps_local, *p.shape[1:]),
                params["blocks"],
            )
            args = [staged_blocks, mbs]
            in_specs = [blocks_in_specs, mb_spec]
            has_perm = expert_perm is not None
            has_wire = wire_perm is not None
            if has_perm:
                args.append(expert_perm.reshape(s, reps_local, -1))
                in_specs.append(P(stage_axis, None, None))
            if has_wire:
                args.append(
                    jnp.asarray(wire_perm, jnp.int32).reshape(s, reps_local, -1)
                )
                in_specs.append(P(stage_axis, None, None))
            out_specs = (
                P(stage_axis, None, batch_ax, seq_ax, None),
                P(stage_axis, None, None),
                P(stage_axis, None),
                P(stage_axis, None),
            )

            def wrapped(*a):
                rest = list(a[2:])
                perm_l = rest.pop(0) if has_perm else None
                wire_l = rest.pop(0) if has_wire else None
                return per_device(a[0], a[1], perm_l, wire_l)

            fn = jax.shard_map(
                wrapped, mesh=mesh, in_specs=tuple(in_specs),
                out_specs=out_specs, check_vma=False,
            )
            outs, loads, bal, zl = fn(*args)
            # outs [S, ticks, mb, T, D]: the last stage's emissions at ticks
            # S-1 .. S-1+M-1 are the model outputs, in microbatch order.
            feats = outs[s - 1, s - 1 : s - 1 + m].reshape(
                b, t_len, cfg.d_model
            )
            feats = L.rms_norm(feats, params["final_norm"], cfg.norm_eps)
            feats = constrain(feats, mesh, plan.activation_spec())
            ce = tfm.chunked_cross_entropy(params, feats, labels, cfg)
            bal_mean = jnp.mean(bal.reshape(reps))
            z_mean = jnp.mean(zl.reshape(reps))
            loss = ce
            if cfg.is_moe:
                loss = loss + cfg.moe.balance_loss * bal_mean
                loss = loss + cfg.moe.router_z_loss * z_mean
            return loss, (ce, bal_mean, z_mean, loads.reshape(reps, -1))

        (loss, (ce, bal, zl, loads)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        params, opt_state, opt_metrics = adamw_update(
            params, grads, opt_state, opt_cfg
        )
        metrics = {
            "loss": loss,
            "ce": ce,
            "balance_loss": bal,
            "z_loss": zl,
            **opt_metrics,
        }
        if cfg.is_moe:
            metrics["expert_load"] = loads
        return params, opt_state, metrics

    return train_step
