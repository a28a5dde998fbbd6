"""Shared neural layers: norms, RoPE (+M-RoPE), GQA attention (full /
sliding-window / softcap), MLA attention (DeepSeek-V2), MLPs.

All layers are pure functions ``apply(params, x, ...)`` with matching
``init(key, cfg, plan)`` that return ``(params, specs)`` — the spec tree
mirrors the param tree with `jax.sharding.PartitionSpec` leaves so the
launcher can feed both straight into pjit.  ``mode`` selects train / prefill
/ decode paths; decode consumes and updates a KV cache laid out for
flash-decoding (sequence dim sharded over the ``model`` axis).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops

Params = dict
Specs = dict

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dtype)


def init_rms_norm(d: int, dtype) -> tuple[jax.Array, P]:
    return jnp.zeros((d,), dtype), P(None)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    theta: float = 10_000.0,
    mrope_sections: tuple | None = None,
) -> jax.Array:
    """Rotate ``x [..., S, H, D]`` by ``positions``.

    ``positions`` is ``[..., S]`` for standard RoPE or ``[3, ..., S]`` for
    M-RoPE (qwen2-vl): the frequency axis is split into (t, h, w) sections,
    each rotated by its own position stream.  For text tokens all three
    streams are equal, reducing to standard RoPE.
    """
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)  # [d/2]
    if mrope_sections is not None:
        if positions.ndim == x.ndim - 2:  # text-only: broadcast to 3 streams
            positions = jnp.stack([positions] * 3)
        # angles[..., S, d/2]: frequency slots are partitioned into (t, h, w)
        # sections, each driven by its own position stream.
        ang = positions[..., None].astype(jnp.float32) * freqs  # [3, ..., S, d/2]
        parts, start = [], 0
        for i, sec_size in enumerate(mrope_sections):
            parts.append(ang[i, ..., start : start + sec_size])
            start += sec_size
        angles = jnp.concatenate(parts, axis=-1)
    else:
        angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, d/2]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads: [..., S, 1, d/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------


def init_attention(key, cfg, plan) -> tuple[Params, Specs]:
    d, h, hkv = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    dh = cfg.resolved_head_dim
    dtype = jnp.dtype(cfg.dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale = d**-0.5
    head_ax = plan.heads_axis(h)
    kv_ax = plan.heads_axis(hkv)
    params = {
        "wq": jax.random.normal(k1, (d, h, dh), dtype) * scale,
        "wk": jax.random.normal(k2, (d, hkv, dh), dtype) * scale,
        "wv": jax.random.normal(k3, (d, hkv, dh), dtype) * scale,
        "wo": jax.random.normal(k4, (h, dh, d), dtype) * (h * dh) ** -0.5,
    }
    specs = {
        "wq": P(plan.fsdp_axis, head_ax, None),
        "wk": P(plan.fsdp_axis, kv_ax, None),
        "wv": P(plan.fsdp_axis, kv_ax, None),
        "wo": P(head_ax, None, plan.fsdp_axis),
    }
    return params, specs


def _decode_attention(
    q: jax.Array,  # [B, C, H, dh]
    k_new: jax.Array,  # [B, C, Hkv, dh]
    v_new: jax.Array,
    cache: dict,
    t: jax.Array,  # first written position (scalar or [B])
    *,
    window: int | None,
    softcap: float | None,
    write_mask: jax.Array | None = None,
) -> tuple[jax.Array, dict]:
    """Chunk attention against a [B, S, Hkv, dh] cache (flash-decoding
    layout: S shardable; reductions over S lower to local + all-reduce).

    ``C = 1`` is the classic decode step; ``C > 1`` is the chunked-prefill
    continuation (DESIGN.md §9): positions ``t .. t+C-1`` are written into
    the cache and attended causally together with the ``<= t`` prefix.
    ``write_mask`` ([B] bool) suppresses the K/V write for dead batch rows —
    a continuous-batching slot that is empty or still mid-chunked-prefill
    must not have its cache stomped at a stale position."""
    ck, cv = cache["k"], cache["v"]
    b, s, hkv, dh = ck.shape
    c, h = q.shape[1], q.shape[2]
    group = h // hkv
    # Write the new K/V at positions t..t+C-1 (ring-buffer semantics beyond
    # S).  t may be a scalar (lockstep batch) or [B] (continuous batching:
    # every slot at its own position).
    t = jnp.broadcast_to(jnp.asarray(t), (b,))
    pos_c = t[:, None] + jnp.arange(c)  # [B, C] absolute positions
    idx = jnp.mod(pos_c, s)
    bi = jnp.arange(b)[:, None]
    if write_mask is not None:
        # Dead rows scatter out of bounds and are dropped.
        idx = jnp.where(write_mask[:, None], idx, s)
    with jax.named_scope("kv.write"):
        ck = ck.at[bi, idx].set(k_new.astype(ck.dtype), mode="drop")
        cv = cv.at[bi, idx].set(v_new.astype(cv.dtype), mode="drop")
    out = _attend_cache_view(q, ck, cv, pos_c, window=window, softcap=softcap)
    return out, {"k": ck, "v": cv}


def _attend_cache_view(
    q: jax.Array,  # [B, C, H, dh]
    ck: jax.Array,  # [B, S, Hkv, dh] — dense cache or gathered paged view
    cv: jax.Array,
    pos_c: jax.Array,  # [B, C] absolute position of each chunk row
    *,
    window: int | None,
    softcap: float | None,
) -> jax.Array:
    """Full-softmax chunk attention against a contiguous K/V view.

    Shared by the dense ring-buffer path and the paged path's gathered view:
    positions past ``pos_c`` (and, for paged, anything reachable through an
    unallocated table entry) are forced to -1e30 before the softmax, so the
    two paths run the identical graph on identical post-mask values — this
    is what makes paged-vs-dense generation bit-identical off-TPU."""
    b, s, hkv, dh = ck.shape
    c, h = q.shape[1], q.shape[2]
    group = h // hkv
    scale = dh**-0.5
    # bf16 operands + f32 accumulation: the cache is read in its own dtype
    # (no f32 copy of a multi-GB buffer), scores accumulate in f32.
    qg = (q * scale).reshape(b, c, hkv, group, dh)
    logits = jnp.einsum(
        "bckgd,bskd->bkgcs", qg, ck, preferred_element_type=jnp.float32
    )  # [B, Hkv, group, C, S]
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    pos = jnp.arange(s)
    valid = pos[None, None, :] <= pos_c[:, :, None]  # [B, C, S]
    if window is not None:
        valid &= (pos_c[:, :, None] - pos[None, None, :]) < window
    logits = jnp.where(valid[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bkgcs,bskd->bckgd", probs.astype(cv.dtype), cv,
        preferred_element_type=jnp.float32,
    ).reshape(b, c, h, dh)
    return out.astype(q.dtype)


def _paged_decode_attention(
    q: jax.Array,  # [B, C, H, dh]
    k_new: jax.Array,  # [B, C, Hkv, dh]
    v_new: jax.Array,
    cache: dict,  # {"k","v": [N, page, Hkv, dh]} page pool shared by slots
    page_table: jax.Array,  # [B, P] i32 page ids, -1 = unallocated
    t: jax.Array,  # first written position (scalar or [B])
    *,
    window: int | None,
    softcap: float | None,
    write_mask: jax.Array | None = None,
) -> tuple[jax.Array, dict]:
    """Paged-cache counterpart of :func:`_decode_attention` (DESIGN.md §10).

    K/V for positions ``t .. t+C-1`` are scattered into the page pool at
    ``(table[b, pos // page], pos % page)``; rows whose table entry is -1
    (or whose ``write_mask`` is off) scatter out of bounds and drop — the
    host-side allocator is responsible for mapping every live position to a
    private (CoW-forked) page before the step runs.  Reads go through the
    pallas kernel on TPU and through a gathered contiguous view into the
    shared full-softmax math elsewhere, which keeps off-TPU generation
    bit-identical to the dense ring buffer."""
    ck, cv = cache["k"], cache["v"]
    n_pages, page, hkv, dh = ck.shape
    b, c = q.shape[0], q.shape[1]
    maxp = page_table.shape[1]
    t = jnp.broadcast_to(jnp.asarray(t), (b,))
    pos_c = t[:, None] + jnp.arange(c)  # [B, C] absolute positions
    logical = jnp.minimum(pos_c // page, maxp - 1)
    pid = jnp.take_along_axis(page_table, logical, axis=1)  # [B, C]
    off = jnp.mod(pos_c, page)
    if write_mask is not None:
        pid = jnp.where(write_mask[:, None], pid, -1)
    pid = jnp.where(pid >= 0, pid, n_pages)  # unallocated/dead -> dropped
    with jax.named_scope("kv.write"):
        ck = ck.at[pid, off].set(k_new.astype(ck.dtype), mode="drop")
        cv = cv.at[pid, off].set(v_new.astype(cv.dtype), mode="drop")
    new_cache = {"k": ck, "v": cv}
    if ops.on_tpu():
        out = ops.paged_flash_decode(
            q, ck, cv, page_table, t, window=window, softcap=softcap
        )
        return out.astype(q.dtype), new_cache
    safe = jnp.maximum(page_table, 0)
    view_k = jnp.take(ck, safe, axis=0).reshape(b, maxp * page, hkv, dh)
    view_v = jnp.take(cv, safe, axis=0).reshape(b, maxp * page, hkv, dh)
    out = _attend_cache_view(
        q, view_k, view_v, pos_c, window=window, softcap=softcap
    )
    return out, new_cache


def _flash_per_head_shard(q, k, v, mesh, hspec, *, backend, window=None, softcap=None):
    """Causal flash attention on ``[B, H, S, D]`` tensors.  On the TPU, with
    a head sharding ``hspec``, it runs per device on the local head shard: XLA
    cannot partition a Mosaic kernel, so the kernel needs the ``shard_map``.
    XLA partitions the jnp paths taken elsewhere itself."""
    def attend(q, k, v):
        return ops.flash_attention(
            q, k, v, causal=True, window=window, softcap=softcap, backend=backend
        )

    if hspec is None or not ops.on_tpu():
        return attend(q, k, v)
    return jax.shard_map(
        attend, mesh=mesh, in_specs=(hspec, hspec, hspec), out_specs=hspec,
        check_vma=False,
    )(q, k, v)


def attention_apply(
    params: Params,
    x: jax.Array,  # [B, S, D]
    cfg,
    *,
    kind: str = "global",  # "global" | "local"
    mode: str = "train",  # "train" | "prefill" | "decode"
    positions: jax.Array | None = None,
    cache: dict | None = None,
    t: jax.Array | None = None,
    attn_backend: str = "auto",
    plan=None,
    mesh=None,
    write_mask: jax.Array | None = None,
    page_table: jax.Array | None = None,
) -> tuple[jax.Array, dict | None]:
    import math

    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import constrain

    b, s, d = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    window = cfg.window_size if kind == "local" else None
    if positions is None:
        positions = jnp.arange(s)[None, :] if mode != "decode" else t
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if mode == "decode":
        # Per-slot positions: t scalar (lockstep) or [B] (continuous
        # batching); s > 1 writes the chunked-prefill positions t..t+s-1.
        pos = jnp.broadcast_to(jnp.asarray(t), (b,))[:, None] + jnp.arange(s)
        if cfg.mrope_sections is not None:
            pos = jnp.stack([pos] * 3)
        q = apply_rope(q, pos, cfg.rope_theta, cfg.mrope_sections)
        k = apply_rope(k, pos, cfg.rope_theta, cfg.mrope_sections)
        if page_table is not None:
            out, cache = _paged_decode_attention(
                q, k, v, cache, page_table, t, window=window,
                softcap=cfg.logit_softcap, write_mask=write_mask,
            )
        else:
            out, cache = _decode_attention(
                q, k, v, cache, t, window=window, softcap=cfg.logit_softcap,
                write_mask=write_mask,
            )
    else:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
        if mode == "prefill":
            cache = {"k": k, "v": v}  # [B, S, Hkv, dh] seq-shardable layout
        # GQA / TP alignment: when q heads shard over the model axis but the
        # kv-head count does not divide it, replicate kv heads up to the axis
        # size.  Each device then holds exactly the kv head its q heads read
        # (a local slice of a replicated tensor — no collective), instead of
        # XLA inserting a resharding gather around the grouped einsum.
        if plan is not None and plan.heads_axis(h) and not plan.heads_axis(hkv):
            rep = plan.model_size // math.gcd(hkv, plan.model_size)
            if rep > 1:
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        # SP -> TP reshard: attention runs head-sharded over the model axis
        # (one all-to-all in, one out) — without this the partitioner keeps
        # seq sharding and re-gathers full K/V inside every q-chunk step.
        hspec = None
        if plan is not None and mesh is not None and plan.heads_axis(h):
            batch_ok = b % max(plan.data_size, 1) == 0
            hspec = P(
                (plan.batch_axes or None) if batch_ok else None,
                plan.model_axis, None, None,
            )
            qt = constrain(qt, mesh, hspec)
            kt = constrain(kt, mesh, hspec)
            vt = constrain(vt, mesh, hspec)
        out = _flash_per_head_shard(
            qt, kt, vt, mesh, hspec,
            window=window, softcap=cfg.logit_softcap, backend=attn_backend,
        ).transpose(0, 2, 1, 3)
        if (
            cfg.sp_shardmap
            and plan is not None
            and mesh is not None
            and plan.heads_axis(h)
            and s % plan.model_size == 0
            and b % max(plan.data_size, 1) == 0
        ):
            # Explicit row-parallel o-proj + seq reduce-scatter (§Perf).
            y = oproj_sp(out, params["wo"], plan, mesh)
            return y, cache
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, cache


def init_attention_cache(cfg, batch: int, max_len: int, dtype=None) -> dict:
    dh = cfg.resolved_head_dim
    dt = dtype or jnp.dtype(cfg.dtype)
    return {
        "k": jnp.zeros((batch, max_len, cfg.num_kv_heads, dh), dt),
        "v": jnp.zeros((batch, max_len, cfg.num_kv_heads, dh), dt),
    }


def init_paged_attention_cache(
    cfg, num_pages: int, page_size: int, dtype=None
) -> dict:
    """Page-pool K/V cache: ``[N, page, Hkv, dh]`` shared by every slot —
    sequences own pages through a ``[slots, P]`` table, not a batch dim."""
    dh = cfg.resolved_head_dim
    dt = dtype or jnp.dtype(cfg.dtype)
    return {
        "k": jnp.zeros((num_pages, page_size, cfg.num_kv_heads, dh), dt),
        "v": jnp.zeros((num_pages, page_size, cfg.num_kv_heads, dh), dt),
    }


# ---------------------------------------------------------------------------
# MLA attention (DeepSeek-V2) — compressed KV cache
# ---------------------------------------------------------------------------


def init_mla_attention(key, cfg, plan) -> tuple[Params, Specs]:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 6)
    qk = m.nope_head_dim + m.rope_head_dim
    head_ax = plan.heads_axis(h)
    params = {
        "wq_a": jax.random.normal(keys[0], (d, m.q_lora_rank), dtype) * d**-0.5,
        "q_norm": jnp.zeros((m.q_lora_rank,), dtype),
        "wq_b": jax.random.normal(keys[1], (m.q_lora_rank, h, qk), dtype)
        * m.q_lora_rank**-0.5,
        "wkv_a": jax.random.normal(
            keys[2], (d, m.kv_lora_rank + m.rope_head_dim), dtype
        )
        * d**-0.5,
        "kv_norm": jnp.zeros((m.kv_lora_rank,), dtype),
        "wk_b": jax.random.normal(keys[3], (m.kv_lora_rank, h, m.nope_head_dim), dtype)
        * m.kv_lora_rank**-0.5,
        "wv_b": jax.random.normal(keys[4], (m.kv_lora_rank, h, m.v_head_dim), dtype)
        * m.kv_lora_rank**-0.5,
        "wo": jax.random.normal(keys[5], (h, m.v_head_dim, d), dtype)
        * (h * m.v_head_dim) ** -0.5,
    }
    specs = {
        "wq_a": P(plan.fsdp_axis, None),
        "q_norm": P(None),
        "wq_b": P(plan.fsdp_axis, head_ax, None),
        "wkv_a": P(plan.fsdp_axis, None),
        "kv_norm": P(None),
        "wk_b": P(plan.fsdp_axis, head_ax, None),
        "wv_b": P(plan.fsdp_axis, head_ax, None),
        "wo": P(head_ax, None, plan.fsdp_axis),
    }
    return params, specs


def mla_attention_apply(
    params: Params,
    x: jax.Array,
    cfg,
    *,
    mode: str = "train",
    positions: jax.Array | None = None,
    cache: dict | None = None,
    t: jax.Array | None = None,
    attn_backend: str = "auto",
    plan=None,
    mesh=None,
    write_mask: jax.Array | None = None,
) -> tuple[jax.Array, dict | None]:
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import constrain
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.num_heads
    if positions is None:
        positions = jnp.arange(s)[None, :]
    cq = rms_norm(x @ params["wq_a"], params["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, params["wq_b"])
    q_nope = q[..., : m.nope_head_dim]
    q_rope = q[..., m.nope_head_dim :]
    ckv_full = x @ params["wkv_a"]  # [B, S, kv_lora + rope]
    ckv = rms_norm(ckv_full[..., : m.kv_lora_rank], params["kv_norm"], cfg.norm_eps)
    k_rope = ckv_full[..., m.kv_lora_rank :][:, :, None, :]  # [B,S,1,rope]

    if mode == "decode":
        # s = 1 is the decode step, s > 1 the chunked-prefill continuation
        # writing positions t..t+s-1 (attended causally within the chunk).
        tb = jnp.broadcast_to(jnp.asarray(t), (b,))
        pos = tb[:, None] + jnp.arange(s)  # [B, s]
        q_rope = apply_rope(q_rope, pos, cfg.rope_theta)
        k_rope = apply_rope(k_rope, pos, cfg.rope_theta)
        c_cache, r_cache = cache["ckv"], cache["k_rope"]
        smax = c_cache.shape[1]
        idx = jnp.mod(pos, smax)
        bi = jnp.arange(b)[:, None]
        if write_mask is not None:
            idx = jnp.where(write_mask[:, None], idx, smax)  # drop dead rows
        c_cache = c_cache.at[bi, idx].set(
            ckv.astype(c_cache.dtype), mode="drop"
        )
        r_cache = r_cache.at[bi, idx].set(
            k_rope[:, :, 0, :].astype(r_cache.dtype), mode="drop"
        )
        # Absorbed attention: score = q_nope·(W_uk c) + q_rope·k_rope.
        # Cache stays in its storage dtype; f32 only in the accumulators.
        q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, params["wk_b"])  # [B,s,H,r]
        logits = jnp.einsum(
            "bshr,btr->bhst", q_abs, c_cache, preferred_element_type=jnp.float32
        )
        logits += jnp.einsum(
            "bshk,btk->bhst", q_rope, r_cache, preferred_element_type=jnp.float32
        )
        scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
        logits = logits * scale
        valid = jnp.arange(smax)[None, None, :] <= pos[:, :, None]  # [B, s, S]
        logits = jnp.where(valid[:, None, :, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        ov = jnp.einsum(
            "bhst,btr->bshr", probs.astype(c_cache.dtype), c_cache,
            preferred_element_type=jnp.float32,
        )
        out = jnp.einsum(
            "bshr,rhk->bshk", ov.astype(params["wv_b"].dtype), params["wv_b"]
        )
        cache = {"ckv": c_cache, "k_rope": r_cache}
    else:
        q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
        k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
        k_nope = jnp.einsum("bsr,rhk->bshk", ckv, params["wk_b"])
        v = jnp.einsum("bsr,rhk->bshk", ckv, params["wv_b"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, s, h, m.rope_head_dim))], axis=-1
        )
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        # v head dim may differ from qk head dim -> pad v for the kernel.
        qk_dim = qq.shape[-1]
        v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk_dim - m.v_head_dim)))
        qt = qq.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v_pad.transpose(0, 2, 1, 3)
        # SP -> TP reshard (same as attention_apply): without this the
        # q-chunk scan re-gathers full-sequence K every step — the single
        # biggest collective in the deepseek-v2 baseline (§Perf).
        hspec = None
        if plan is not None and mesh is not None and plan.heads_axis(h):
            batch_ok = b % max(plan.data_size, 1) == 0
            hspec = P(
                (plan.batch_axes or None) if batch_ok else None,
                plan.model_axis, None, None,
            )
            qt = constrain(qt, mesh, hspec)
            kt = constrain(kt, mesh, hspec)
            vt = constrain(vt, mesh, hspec)
        out = _flash_per_head_shard(
            qt, kt, vt, mesh, hspec, backend=attn_backend,
        ).transpose(0, 2, 1, 3)[..., : m.v_head_dim]
        if mode == "prefill":
            cache = {"ckv": ckv, "k_rope": k_rope[:, :, 0, :]}
        out = out.astype(jnp.float32)
    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), params["wo"])
    return y, cache


def init_mla_cache(cfg, batch: int, max_len: int, dtype=None) -> dict:
    m = cfg.mla
    dt = dtype or jnp.dtype(cfg.dtype)
    return {
        "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dt),
        "k_rope": jnp.zeros((batch, max_len, m.rope_head_dim), dt),
    }


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, cfg, plan, d_ff: int | None = None) -> tuple[Params, Specs]:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    dtype = jnp.dtype(cfg.dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    gated = cfg.act in ("silu", "swiglu", "geglu")
    params = {
        "w_in": jax.random.normal(k1, (d, f), dtype) * d**-0.5,
        "w_out": jax.random.normal(k2, (f, d), dtype) * f**-0.5,
    }
    specs = {
        "w_in": P(plan.fsdp_axis, plan.model_axis),
        "w_out": P(plan.model_axis, plan.fsdp_axis),
    }
    if gated:
        params["w_gate"] = jax.random.normal(k3, (d, f), dtype) * d**-0.5
        specs["w_gate"] = P(plan.fsdp_axis, plan.model_axis)
    return params, specs


def mlp_apply(params: Params, x: jax.Array, cfg) -> jax.Array:
    h = x @ params["w_in"]
    if "w_gate" in params:
        act = jax.nn.silu if cfg.act in ("silu", "swiglu") else jax.nn.gelu
        h = act(x @ params["w_gate"]) * h
    else:
        h = jax.nn.gelu(h) if cfg.act == "gelu" else jax.nn.silu(h)
    return h @ params["w_out"]


def mlp_apply_sp(params: Params, x: jax.Array, cfg, plan, mesh) -> jax.Array:
    """Megatron sequence-parallel MLP as an explicit shard_map program.

    x arrives seq-sharded over the model axis; the program is
    all-gather(seq) -> column-parallel w_in/w_gate -> row-parallel w_out ->
    reduce-scatter(seq).  Guarantees the TP combine is a reduce-scatter (half
    the wire bytes of the all-reduce the auto-partitioner emits) regardless
    of backend heuristics.  §Perf beyond-paper optimization.
    """
    from jax.sharding import PartitionSpec as P

    gated = "w_gate" in params
    actfn = (
        (jax.nn.silu if cfg.act in ("silu", "swiglu") else jax.nn.gelu)
        if gated
        else (jax.nn.gelu if cfg.act == "gelu" else jax.nn.silu)
    )

    def local(xl, w_in, w_gate, w_out):
        xg = jax.lax.all_gather(xl, "model", axis=1, tiled=True)  # [B, S, D]
        h = xg @ w_in  # [B, S, F/m]
        if gated:
            h = actfn(xg @ w_gate) * h
        else:
            h = actfn(h)
        y_part = h @ w_out  # [B, S, D] partial over the model axis
        return jax.lax.psum_scatter(y_part, "model", scatter_dimension=1, tiled=True)

    w_gate = params.get("w_gate", params["w_in"])
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(plan.batch_axes or None, plan.model_axis, None),
            P(None, plan.model_axis),
            P(None, plan.model_axis),
            P(plan.model_axis, None),
        ),
        out_specs=P(plan.batch_axes or None, plan.model_axis, None),
        check_vma=False,
    )
    return fn(x, params["w_in"], w_gate, params["w_out"])


def can_use_sp_mlp(params, x, cfg, plan, mesh, mode) -> bool:
    if mesh is None or plan is None or plan.model_axis is None or mode == "decode":
        return False
    b, s, _ = x.shape
    f = params["w_in"].shape[1]
    return (
        s % plan.model_size == 0
        and f % plan.model_size == 0
        and b % max(plan.data_size, 1) == 0
    )


def oproj_sp(out: jax.Array, wo: jax.Array, plan, mesh) -> jax.Array:
    """Row-parallel attention output projection with an explicit seq
    reduce-scatter.  out [B, S, H, dh] head-sharded -> y [B, S, D]
    seq-sharded."""
    from jax.sharding import PartitionSpec as P

    def local(o, w):
        y_part = jnp.einsum("bshk,hkd->bsd", o, w)
        return jax.lax.psum_scatter(y_part, "model", scatter_dimension=1, tiled=True)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(plan.batch_axes or None, None, plan.model_axis, None),
            P(plan.model_axis, None, None),
        ),
        out_specs=P(plan.batch_axes or None, plan.model_axis, None),
        check_vma=False,
    )
    return fn(out, wo)
