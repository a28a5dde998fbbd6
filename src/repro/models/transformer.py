"""Model assembly: embeddings -> scanned block stack -> (chunked) LM head.

One scan step = one repetition of ``cfg.block_pattern`` (e.g. gemma2's
[local, global] pair, recurrentgemma's [rglru, rglru, local] triple), with
per-kind params stacked over repetitions — the HLO stays one pattern body
regardless of depth, which keeps 88-layer dry-runs compilable in seconds.

Modes:
  * ``train``   — full-sequence forward, returns chunked-CE-ready features;
  * ``prefill`` — forward + emits per-layer caches (KV / SSM / LRU / conv);
  * ``decode``  — one token against the caches (flash-decoding KV layout).

Whisper (family "audio") adds an encoder scan over stub frame embeddings and
cross-attention in every decoder block.  VLM (qwen2-vl) splices stub patch
embeddings into the first positions and uses M-RoPE positions.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.parallel.sharding import ShardingPlan, constrain, virtual_experts

__all__ = ["init_model", "model_apply", "init_caches", "chunked_cross_entropy"]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _block_init(key, kind: str, cfg, plan):
    """(params, specs) for one block of the given kind."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    params: dict = {}
    specs: dict = {}
    params["norm1"], specs["norm1"] = L.init_rms_norm(cfg.d_model, jnp.dtype(cfg.dtype))
    if kind in ("global", "local"):
        if cfg.mla is not None:
            params["attn"], specs["attn"] = L.init_mla_attention(k1, cfg, plan)
        else:
            params["attn"], specs["attn"] = L.init_attention(k1, cfg, plan)
        if cfg.family == "audio":  # decoder cross-attention
            params["xnorm"], specs["xnorm"] = L.init_rms_norm(
                cfg.d_model, jnp.dtype(cfg.dtype)
            )
            params["xattn"], specs["xattn"] = L.init_attention(k4, cfg, plan)
        params["norm2"], specs["norm2"] = L.init_rms_norm(
            cfg.d_model, jnp.dtype(cfg.dtype)
        )
        if cfg.is_moe:
            params["moe"], specs["moe"] = moe_mod.init_moe(k2, cfg, plan)
        else:
            params["mlp"], specs["mlp"] = L.init_mlp(k2, cfg, plan)
    elif kind == "rglru":
        params["rglru"], specs["rglru"] = rglru_mod.init_rglru(k1, cfg, plan)
        params["norm2"], specs["norm2"] = L.init_rms_norm(
            cfg.d_model, jnp.dtype(cfg.dtype)
        )
        params["mlp"], specs["mlp"] = L.init_mlp(k2, cfg, plan)
    elif kind == "ssm":
        params["ssm"], specs["ssm"] = ssm_mod.init_ssm(k1, cfg, plan)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    return params, specs


def init_model(key, cfg, plan: ShardingPlan):
    cfg.validate()
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    reps = cfg.pattern_repeats

    params: dict = {
        "embed": jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model), dtype)
        * cfg.d_model**-0.5
    }
    specs: dict = {"embed": P(plan.dim_axis(cfg.vocab_size), plan.fsdp_axis)}

    blocks = {}
    block_specs = {}
    for i, kind in enumerate(cfg.block_pattern):
        kname = f"{i}_{kind}"
        bkeys = jax.random.split(keys[1 + (i % 5)], reps)
        stacked = jax.vmap(lambda k: _block_init(k, kind, cfg, plan)[0])(bkeys)
        _, spec1 = _block_init(bkeys[0], kind, cfg, plan)
        blocks[kname] = stacked
        block_specs[kname] = jax.tree.map(
            lambda s: P(None, *s), spec1, is_leaf=lambda s: isinstance(s, P)
        )
    params["blocks"] = blocks
    specs["blocks"] = block_specs
    if cfg.tail_pattern:
        tail, tail_specs = {}, {}
        tkeys = jax.random.split(keys[5], len(cfg.tail_pattern))
        for i, kind in enumerate(cfg.tail_pattern):
            tail[f"{i}_{kind}"], tail_specs[f"{i}_{kind}"] = _block_init(
                tkeys[i], kind, cfg, plan
            )
        params["tail"] = tail
        specs["tail"] = tail_specs
    params["final_norm"], specs["final_norm"] = L.init_rms_norm(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(keys[6], (cfg.d_model, cfg.vocab_size), dtype)
            * cfg.d_model**-0.5
        )
        specs["lm_head"] = P(plan.fsdp_axis, plan.dim_axis(cfg.vocab_size))

    if cfg.encoder_layers:
        ekeys = jax.random.split(keys[7], cfg.encoder_layers)
        enc = jax.vmap(lambda k: _enc_block_init(k, cfg, plan)[0])(ekeys)
        _, enc_spec1 = _enc_block_init(ekeys[0], cfg, plan)
        params["encoder"] = {
            "blocks": enc,
            "final_norm": L.init_rms_norm(cfg.d_model, dtype)[0],
        }
        specs["encoder"] = {
            "blocks": jax.tree.map(
                lambda s: P(None, *s), enc_spec1, is_leaf=lambda s: isinstance(s, P)
            ),
            "final_norm": P(None),
        }
    return params, specs


def _enc_block_init(key, cfg, plan):
    k1, k2 = jax.random.split(key)
    params, specs = {}, {}
    params["norm1"], specs["norm1"] = L.init_rms_norm(cfg.d_model, jnp.dtype(cfg.dtype))
    params["attn"], specs["attn"] = L.init_attention(k1, cfg, plan)
    params["norm2"], specs["norm2"] = L.init_rms_norm(cfg.d_model, jnp.dtype(cfg.dtype))
    params["mlp"], specs["mlp"] = L.init_mlp(k2, cfg, plan)
    return params, specs


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def init_caches(cfg, batch: int, max_len: int, plan: ShardingPlan | None = None):
    """Stacked per-pattern-position caches: leaves [repeats, ...]."""
    reps = cfg.pattern_repeats

    def one(kind):
        if kind in ("global", "local"):
            if cfg.mla is not None:
                c = L.init_mla_cache(cfg, batch, max_len)
            else:
                c = L.init_attention_cache(cfg, batch, max_len)
            if cfg.family == "audio":
                c["xk"] = jnp.zeros(
                    (batch, cfg.encoder_seq, cfg.num_kv_heads, cfg.resolved_head_dim),
                    jnp.dtype(cfg.dtype),
                )
                # Distinct buffer, not an alias: serving scatters caches
                # through donated jit calls, and XLA rejects a pytree that
                # donates the same buffer twice.
                c["xv"] = jnp.zeros_like(c["xk"])
            return c
        if kind == "rglru":
            return rglru_mod.init_rglru_cache(cfg, batch)
        if kind == "ssm":
            return ssm_mod.init_ssm_cache(cfg, batch)
        raise ValueError(kind)

    caches = {}
    for i, kind in enumerate(cfg.block_pattern):
        c = one(kind)
        caches[f"{i}_{kind}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (reps, *x.shape)), c
        )
    if cfg.tail_pattern:
        caches["__tail__"] = {
            f"{i}_{kind}": one(kind) for i, kind in enumerate(cfg.tail_pattern)
        }
    return caches


def paged_supported(cfg) -> bool:
    """True when every decode-time cache is a plain GQA attention K/V pair —
    the only layout the page pool holds.  MLA (compressed kv), audio
    cross-attention, and rglru/ssm state caches stay on the dense path."""
    return (
        all(k in ("global", "local") for k in cfg.block_pattern)
        and not cfg.tail_pattern
        and cfg.mla is None
        and cfg.family != "audio"
    )


def init_paged_caches(cfg, num_pages: int, page_size: int):
    """Paged decode caches: one ``[repeats, N, page, Hkv, dh]`` K/V page pool
    per pattern position, shared by every serving slot.  Sequences own pages
    through a single ``[slots, P]`` page table (the same logical position
    maps to the same page id in every layer's pool), passed to
    :func:`model_apply` as a traced argument — shapes stay static under jit
    while the table contents change every tick."""
    if not paged_supported(cfg):
        raise ValueError(
            f"paged KV cache requires an attention-only pattern, got "
            f"{cfg.block_pattern} / tail {cfg.tail_pattern} / mla={cfg.mla}"
        )
    reps = cfg.pattern_repeats
    caches = {}
    for i, kind in enumerate(cfg.block_pattern):
        c = L.init_paged_attention_cache(cfg, num_pages, page_size)
        caches[f"{i}_{kind}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (reps, *x.shape)), c
        )
    return caches


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _apply_block(
    kind, p, x, cfg, plan, mesh, mode, cache, t, enc_out, expert_perm, positions,
    act_spec=None, wire_perm=None, gate_weights=None, page_table=None,
):
    new_cache = dict(cache) if cache is not None else ({} if mode != "train" else None)
    stats = None
    # Decode-time live mask: dead continuous-batching slots must not write
    # K/V at their stale positions (a mid-chunked-prefill slot's cache would
    # be stomped).  Derived from the same per-token weights the MoE gate
    # telemetry uses (DESIGN.md §9).
    write_mask = None
    if mode == "decode" and gate_weights is not None:
        write_mask = gate_weights[:, 0] > 0

    def seq_shard(y):
        # Constrain each sublayer output to the sequence-parallel spec BEFORE
        # the residual add: TP partial sums lower to reduce-scatters instead
        # of full-sequence all-reduces.
        return constrain(y, mesh, act_spec) if act_spec is not None else y

    h = L.rms_norm(x, p["norm1"], cfg.norm_eps)
    if kind in ("global", "local"):
        attn_cache = (
            {k: v for k, v in cache.items() if k not in ("xk", "xv")} if cache else None
        )
        with jax.named_scope("attn"):
            if cfg.mla is not None:
                y, ac = L.mla_attention_apply(
                    p["attn"], h, cfg, mode=mode, cache=attn_cache, t=t,
                    positions=positions, plan=plan, mesh=mesh,
                    write_mask=write_mask,
                )
            else:
                y, ac = L.attention_apply(
                    p["attn"], h, cfg, kind=kind, mode=mode, cache=attn_cache,
                    t=t, positions=positions, plan=plan, mesh=mesh,
                    write_mask=write_mask, page_table=page_table,
                )
        x = x + seq_shard(y)
        if ac is not None:
            new_cache.update(ac)
        if cfg.family == "audio":
            hx = L.rms_norm(x, p["xnorm"], cfg.norm_eps)
            y, xc = _cross_attention(p["xattn"], hx, cfg, mode, cache, enc_out)
            x = x + seq_shard(y)
            if xc is not None:
                new_cache.update(xc)
        h2 = L.rms_norm(x, p["norm2"], cfg.norm_eps)
        if cfg.is_moe:
            y, stats = moe_mod.moe_apply(
                p["moe"], h2, cfg, plan, mesh=mesh, expert_perm=expert_perm,
                wire_perm=wire_perm, mode=mode, gate_weights=gate_weights,
            )
        elif cfg.sp_shardmap and L.can_use_sp_mlp(p["mlp"], h2, cfg, plan, mesh, mode):
            y = L.mlp_apply_sp(p["mlp"], h2, cfg, plan, mesh)
        else:
            y = L.mlp_apply(p["mlp"], h2, cfg)
        x = x + seq_shard(y)
    elif kind == "rglru":
        y, rc = rglru_mod.rglru_apply(p["rglru"], h, cfg, mode=mode, cache=cache, t=t)
        x = x + seq_shard(y)
        if rc is not None:
            new_cache = rc
        h2 = L.rms_norm(x, p["norm2"], cfg.norm_eps)
        if cfg.sp_shardmap and L.can_use_sp_mlp(p["mlp"], h2, cfg, plan, mesh, mode):
            x = x + seq_shard(L.mlp_apply_sp(p["mlp"], h2, cfg, plan, mesh))
        else:
            x = x + seq_shard(L.mlp_apply(p["mlp"], h2, cfg))
    elif kind == "ssm":
        y, sc = ssm_mod.ssm_apply(p["ssm"], h, cfg, mode=mode, cache=cache, t=t)
        x = x + seq_shard(y)
        if sc is not None:
            new_cache = sc
    return x, new_cache, stats


def _cross_attention(p, x, cfg, mode, cache, enc_out):
    """Non-causal attention over encoder output (whisper decoder)."""
    b = x.shape[0]
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if mode == "decode":
        k, v = cache["xk"], cache["xv"]
        new_cache = None
    else:
        k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
        new_cache = {"xk": k, "xv": v} if mode == "prefill" else None
    group = h // hkv
    scale = dh**-0.5
    qg = (q * scale).reshape(b, -1, hkv, group, dh)
    logits = jnp.einsum(
        "bskgd,btkd->bkgst", qg, k, preferred_element_type=jnp.float32
    )
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bkgst,btkd->bskgd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    out = out.reshape(b, -1, h, dh).astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


def _encoder_apply(params, frames, cfg, plan):
    """Whisper encoder over stub frame embeddings (bidirectional)."""
    x = frames
    pos = jnp.arange(x.shape[1])[None, :]

    def body(carry, p):
        x = carry
        h = L.rms_norm(x, p["norm1"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wv"])
        from repro.kernels import ops

        o = ops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            causal=False,
        ).transpose(0, 2, 1, 3)
        x = x + jnp.einsum("bshk,hkd->bsd", o, p["attn"]["wo"])
        h2 = L.rms_norm(x, p["norm2"], cfg.norm_eps)
        x = x + L.mlp_apply(p["mlp"], h2, cfg)
        return x, None

    if cfg.remat != "none":
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["blocks"])
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps)


@dataclasses.dataclass
class ForwardAux:
    moe_stats: object | None  # stacked MoEStats or None
    balance_loss: jax.Array
    z_loss: jax.Array


jax.tree_util.register_dataclass(
    ForwardAux, data_fields=["moe_stats", "balance_loss", "z_loss"], meta_fields=[]
)


_FFN_PREFETCH_DIMS = {
    # weight leaf -> (fsdp-sharded dim, model-sharded dim) per FFN kind,
    # matching the init specs in layers.init_mlp / moe.init_moe.
    "mlp": {"w_in": (0, 1), "w_gate": (0, 1), "w_out": (1, 0)},
    "moe": {"w_in": (1, 0), "w_gate": (1, 0), "w_out": (2, 0)},
}


def model_apply(
    params,
    batch: dict,
    cfg,
    plan: ShardingPlan,
    *,
    mesh=None,
    mode: str = "train",
    caches=None,
    t=None,
    expert_perm=None,
    wire_perm=None,
    gate_weights=None,
    page_table=None,
):
    """Run the model.

    ``batch``: tokens [B,S] (+ optional "frames" [B,Se,D] for audio,
    "patches" [B,Np,D] for vlm, "positions" for M-RoPE).
    ``expert_perm``: [repeats, E_virtual] per-layer expert->slot maps;
    ``wire_perm``: optional [repeats, P] per-layer device maps for plans the
    control plane installed as wire re-addresses instead of weight gathers.
    ``gate_weights``: optional [B, S] per-token weight for the exported MoE
    gate-load telemetry (the serving engine's live-slot mask, DESIGN.md §9).
    ``page_table``: optional [B, P] i32 page ids (-1 = unallocated) switching
    decode-mode attention onto the paged KV pool from
    :func:`init_paged_caches` (DESIGN.md §10).
    Returns (features [B,S,D], aux, new_caches).  Use
    :func:`chunked_cross_entropy` / :func:`logits` on the features.
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    x = x * (cfg.d_model**0.5)
    if cfg.vision_patches and "patches" in batch and mode != "decode":
        np_ = batch["patches"].shape[1]
        x = jnp.concatenate([batch["patches"].astype(x.dtype), x[:, np_:]], axis=1)
    positions = batch.get("positions")

    enc_out = None
    if cfg.encoder_layers and "frames" in batch:
        enc_out = _encoder_apply(params["encoder"], batch["frames"], cfg, plan)

    reps = cfg.pattern_repeats
    pattern = cfg.block_pattern
    names = [f"{i}_{k}" for i, k in enumerate(pattern)]
    perm_stack = expert_perm  # [reps, Ev] or None

    # Sequence-parallel residual stream: keep the scan carry sharded
    # (batch over DP axes, seq over the model axis) so TP partial sums lower
    # to reduce-scatters instead of full all-reduces.
    from jax.sharding import PartitionSpec as _P

    seq_shardable = mode != "decode" and s % max(plan.model_size, 1) == 0
    batch_ok = b % max(plan.data_size, 1) == 0
    if mode == "decode":
        # Weight-stationary decode: residual [B, 1, D] keeps D sharded over
        # the FSDP axis so projections contract against *local* weight shards
        # (psum of tiny activations) instead of all-gathering multi-GB
        # weights every token; batch stays replicated (it is tiny), the KV
        # cache carries the batch x seq sharding.
        d_ok = cfg.d_model % max(plan.data_size, 1) == 0
        _act_spec = _P(None, None, plan.fsdp_axis if d_ok else None)
    else:
        _act_spec = _P(
            (plan.batch_axes or None) if batch_ok else None,
            plan.model_axis if seq_shardable else None,
            None,
        )

    scan_caches = (
        {k: v for k, v in caches.items() if k != "__tail__"} if caches else None
    )

    # --- FSDP weight prefetch (DESIGN.md §8): gather block l+1's FFN weights
    # over the fsdp axis with the explicit AllGather ring while block l
    # computes.  The gathered tree rides the scan carry (double buffer); the
    # gather for the NEXT step is issued at the top of the body, before this
    # step's compute, so its ring hops are independent of — and overlap —
    # the current block's FFN.
    from repro.core import overlap as overlap_mod

    ffn_kinds = {}
    if (
        cfg.fsdp_prefetch and mesh is not None and plan.fsdp_axis is not None
        and mode == "train"
    ):
        for name in names:
            bp = params["blocks"][name]
            if "moe" in bp:
                ffn_kinds[name] = "moe"
            elif "mlp" in bp:
                ffn_kinds[name] = "mlp"
    prefetch = bool(ffn_kinds)

    def gather_ffn_group(li):
        out = {}
        for name, fkind in ffn_kinds.items():
            sub = params["blocks"][name][fkind]
            got = {}
            for wname, (fdim, mdim) in _FFN_PREFETCH_DIMS[fkind].items():
                if wname not in sub:
                    continue
                leaf = jax.lax.dynamic_index_in_dim(
                    sub[wname], li, 0, keepdims=False
                )
                got[wname] = overlap_mod.ring_gather_leaf(
                    leaf, mesh, plan.fsdp_axis, fdim, plan.model_axis, mdim
                )
            out[name] = got
        return out

    def group_body(carry, xs):
        if prefetch:
            x, full_caches, li, gathered = carry
        else:
            x, full_caches, li = carry
            gathered = None
        if wire_perm is not None:
            group_params, perm, wire = xs
        else:
            group_params, perm = xs
            wire = None
        if prefetch:
            # Issue the NEXT block group's weight gather first — it depends
            # only on li, so its ring hops overlap this group's compute.
            nxt_gathered = gather_ffn_group(jnp.minimum(li + 1, reps - 1))
        new_caches = {} if mode != "train" else None
        stats_list = []
        for i, kind in enumerate(pattern):
            # Caches live in the carry (not xs/ys): dynamic index in/out lets
            # XLA alias the stacked buffers in place instead of keeping a
            # second multi-GB copy across the while loop.
            cache_i = None
            if full_caches is not None:
                cache_i = jax.tree.map(
                    lambda c: jax.lax.dynamic_index_in_dim(c, li, 0, keepdims=False),
                    full_caches[names[i]],
                )
            gp = group_params[names[i]]
            if gathered is not None and names[i] in ffn_kinds:
                fkind = ffn_kinds[names[i]]
                gp = dict(gp)
                gp[fkind] = {**gp[fkind], **gathered[names[i]]}
            x, nc, st = _apply_block(
                kind, gp, x, cfg, plan, mesh, mode, cache_i, t,
                enc_out, perm, positions, act_spec=_act_spec, wire_perm=wire,
                gate_weights=gate_weights, page_table=page_table,
            )
            x = constrain(x, mesh, _act_spec)
            if new_caches is not None:
                new_caches[names[i]] = nc if nc is not None else cache_i
            if st is not None:
                stats_list.append(st)
        if full_caches is not None and new_caches is not None:
            full_caches = {
                k: jax.tree.map(
                    lambda full, nc: jax.lax.dynamic_update_index_in_dim(
                        full, nc.astype(full.dtype), li, 0
                    ),
                    full_caches[k],
                    new_caches[k],
                )
                for k in full_caches
            }
        elif new_caches is not None:
            # prefill: build stacked caches up from per-group outputs.
            pass
        bal = (
            sum(s.balance_loss for s in stats_list) / max(len(stats_list), 1)
            if stats_list
            else jnp.zeros((), jnp.float32)
        )
        zl = (
            sum(s.z_loss for s in stats_list) / max(len(stats_list), 1)
            if stats_list
            else jnp.zeros((), jnp.float32)
        )
        load = stats_list[0].expert_load if stats_list else jnp.zeros((1,), jnp.float32)
        ys = (new_caches if full_caches is None else None, bal, zl, load)
        if prefetch:
            return (x, full_caches, li + 1, nxt_gathered), ys
        return (x, full_caches, li + 1), ys

    body = group_body
    if cfg.remat == "full" and mode == "train":
        body = jax.checkpoint(group_body)
    elif cfg.remat == "dots" and mode == "train":
        body = jax.checkpoint(
            group_body,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        )

    if perm_stack is None:
        ev, _ = (
            virtual_experts(cfg.moe.num_experts, plan.model_size)
            if cfg.is_moe
            else (1, 1)
        )
        perm_stack = jnp.broadcast_to(jnp.arange(ev, dtype=jnp.int32), (reps, ev))

    xs = (
        (params["blocks"], perm_stack)
        if wire_perm is None
        else (params["blocks"], perm_stack, wire_perm)
    )
    init_carry = (x, scan_caches, jnp.zeros((), jnp.int32))
    if prefetch:
        init_carry = (*init_carry, gather_ffn_group(0))
    carry_out, (stacked_caches, bal, zl, loads) = jax.lax.scan(
        body, init_carry, xs
    )
    x, carried_caches = carry_out[0], carry_out[1]
    new_caches = carried_caches if carried_caches is not None else stacked_caches

    # Non-repeating tail blocks (e.g. recurrentgemma's final 2 RG-LRU layers).
    if cfg.tail_pattern:
        tail_caches = caches.get("__tail__") if caches else None
        new_tail = {} if mode != "train" else None
        for i, kind in enumerate(cfg.tail_pattern):
            name = f"{i}_{kind}"
            cache_i = tail_caches.get(name) if tail_caches else None
            x, nc, _ = _apply_block(
                kind, params["tail"][name], x, cfg, plan, mesh, mode, cache_i, t,
                enc_out, perm_stack[0] if perm_stack is not None else None, positions,
                act_spec=_act_spec,
                wire_perm=wire_perm[0] if wire_perm is not None else None,
                gate_weights=gate_weights,
            )
            if new_tail is not None:
                new_tail[name] = nc if nc is not None else cache_i
        if new_caches is not None and new_tail is not None:
            new_caches = dict(new_caches)
            new_caches["__tail__"] = new_tail

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    aux = ForwardAux(
        moe_stats=loads if cfg.is_moe else None,
        balance_loss=jnp.mean(bal),
        z_loss=jnp.mean(zl),
    )
    return x, aux, new_caches


def cache_specs(cfg, plan):
    """PartitionSpec tree matching :func:`init_caches` (flash-decoding
    layout: attention caches shard their sequence axis over ``model``)."""
    batch = plan.batch_axes or None
    m = plan.model_axis

    def attn():
        c = {
            "k": P(None, batch, m, None, None),
            "v": P(None, batch, m, None, None),
        }
        if cfg.mla is not None:
            c = {
                "ckv": P(None, batch, m, None),
                "k_rope": P(None, batch, m, None),
            }
        if cfg.family == "audio":
            c["xk"] = P(None, batch, None, None, None)
            c["xv"] = P(None, batch, None, None, None)
        return c

    def one(kind):
        if kind in ("global", "local"):
            return attn()
        if kind == "rglru":
            w = cfg.d_model
            return {
                "state": P(None, batch, plan.dim_axis(w)),
                "conv": P(None, batch, None, plan.dim_axis(w)),
            }
        if kind == "ssm":
            inner = cfg.ssm.expand * cfg.d_model
            heads = inner // cfg.ssm.head_dim
            return {
                "state": P(None, batch, plan.heads_axis(heads), None, None),
                "conv": P(None, batch, None, plan.dim_axis(inner)),
            }
        raise ValueError(kind)

    specs = {f"{i}_{k}": one(k) for i, k in enumerate(cfg.block_pattern)}
    if cfg.tail_pattern:
        def drop_lead(spec_tree):
            return jax.tree.map(
                lambda s: P(*s[1:]), spec_tree, is_leaf=lambda s: isinstance(s, P)
            )

        specs["__tail__"] = {
            f"{i}_{k}": drop_lead(one(k))
            for i, k in enumerate(cfg.tail_pattern)
        }
    return specs


_SEQ_CACHE_KEYS = ("k", "v", "ckv", "k_rope")


def pad_caches(caches, target_len: int):
    """Grow the sequence axis of attention caches to ``target_len`` (zeros).

    Decode writes at ``t mod cache_len`` (ring/streaming eviction at
    capacity); padding after prefill gives true append semantics while the
    cache still has headroom.
    """

    def pad(path, x):
        key = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if key in _SEQ_CACHE_KEYS and x.ndim >= 3:
            cur = x.shape[2]
            if cur < target_len:
                pad_width = [(0, 0)] * x.ndim
                pad_width[2] = (0, target_len - cur)
                return jnp.pad(x, pad_width)
        return x

    return jax.tree_util.tree_map_with_path(pad, caches)


@jax.named_scope("lm_head")
def logits_from_features(params, x, cfg):
    if cfg.tie_embeddings:
        out = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        out = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    if cfg.final_softcap is not None:
        out = cfg.final_softcap * jnp.tanh(out / cfg.final_softcap)
    return out


@jax.named_scope("loss")
def chunked_cross_entropy(
    params, features, labels, cfg, *, num_chunks: int = 8
) -> jax.Array:
    """Mean CE computed in sequence chunks so [B,S,V] logits never fully
    materialize (vocab-sharded logsumexp lowers to local + all-reduce)."""
    b, s, d = features.shape
    while s % num_chunks:
        num_chunks -= 1
    fc = features.reshape(b, num_chunks, s // num_chunks, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, num_chunks, s // num_chunks).transpose(1, 0, 2)

    # checkpoint: recompute each chunk's logits in backward instead of saving
    # [B, S, V] f32 across the scan (13+ GB/device for 250k vocabs).
    @jax.checkpoint
    def body(acc, xs):
        f, l = xs
        lg = logits_from_features(params, f, cfg).astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, l[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (fc, lc))
    return total / (b * s)
