"""Shared MoE routing/dispatch engine (DESIGN.md §6).

One implementation of the router math and the token-ordering machinery that
all three dispatch backends in :mod:`repro.models.moe` consume:

  router logits -> top-k (``ops.topk_gating``) -> renormalize -> virtual-slot
  destinations (replication r + the runtime ``expert_perm`` re-addressing the
  control plane plans) -> **argsort-by-expert token permutation** -> layout.

The ordering core is MegaBlocks-style (Gale et al.): ranks within each
destination bucket come from one stable ``argsort`` over the flat choice
array — O(N log N) — instead of the O(N·E) ``one_hot``+``cumsum`` rank
machinery the backends used to triplicate.  Shapes stay static everywhere
(Kossmann et al.: dynamic shapes force recompilation), so "dropless" is
expressed as a data-independent worst-case layout, not a dynamic one:

* ``dropless`` (default) — every routed token is placed.  The expert-side
  layout packs tokens into ``block``-row tiles (``dropless_plan``), each tile
  owned by one expert via a block→expert map that feeds the grouped GEMM's
  scalar-prefetch index map; padding is bounded by ``E·(block-1)`` rows.
* ``capacity`` — classic GShard buffers ``[E, C]`` with overflow dropped
  (``capacity_plan``); kept as an option because it bounds wire traffic for
  the sharded all-to-all stage.

The heavy data movement (gathering token rows into the packed layout and the
weighted combine back) goes through ``ops.moe_dispatch`` / ``ops.moe_combine``
(:mod:`repro.kernels.moe_dispatch` on TPU, jnp oracles elsewhere).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

__all__ = [
    "MoEStats",
    "RoutingInfo",
    "DispatchPlan",
    "compute_routing",
    "effective_top_k",
    "resolve_perm",
    "router_losses",
    "expert_load",
    "capacity",
    "bucket_ranks",
    "capacity_plan",
    "dropless_plan",
    "dense_dispatch_masks",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MoEStats:
    """Per-layer telemetry consumed by the MixNet control plane (§5.1)."""

    expert_load: jax.Array  # [E] tokens routed to each (real) expert
    balance_loss: jax.Array
    z_loss: jax.Array
    dropped_fraction: jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RoutingInfo:
    """Router decisions for a flat batch of T tokens (S = top_k · r)."""

    weights: jax.Array  # [T, K] f32, renormalized over the kept top-k
    idx: jax.Array  # [T, K] i32 real-expert ids (for load/loss telemetry)
    vdest: jax.Array  # [T, S] i32 physical virtual-slot destinations
    wfull: jax.Array  # [T, S] f32 combine weight per virtual destination


@dataclasses.dataclass
class DispatchPlan:
    """Static-shape token layout for one dispatch stage.

    ``slot[i]`` is the packed-buffer row of flat choice ``i`` (-1 dropped);
    ``src[p]`` is the flat choice occupying packed row ``p`` (-1 empty) —
    the two are inverse views of the same permutation.  ``num_rows`` is the
    static packed-buffer height; ``block_experts`` (dropless layouts only)
    maps each ``block``-row tile to its owning expert for the grouped GEMM,
    and ``used_blocks`` counts the leading tiles that hold rows (the GEMM
    skips the rest).  ``kept`` counts the placed choices (telemetry).
    """

    slot: jax.Array  # [N] i32
    src: jax.Array  # [num_rows] i32
    num_rows: int
    block_experts: jax.Array | None
    kept: jax.Array  # scalar
    used_blocks: jax.Array | None = None  # scalar i32


# ---------------------------------------------------------------------------
# router math
# ---------------------------------------------------------------------------


def effective_top_k(top_k: int, draft_mode: str = "off") -> int:
    """Routed choices per token under a speculative draft mode (DESIGN.md §11).

    ``topk1`` narrows the gate to its single best expert; ``shared_only``
    routes nothing (the draft is attention + shared experts — callers must
    skip routing entirely, so 0 is returned as a sentinel, not a valid
    ``top_k``).  Engines use this for a2a accounting: draft tokens pay
    ``effective_top_k`` choices on the wire, verify tokens the full ``top_k``.
    """
    if draft_mode == "topk1":
        return min(top_k, 1)
    if draft_mode == "shared_only":
        return 0
    return top_k


def compute_routing(
    logits: jax.Array,
    *,
    top_k: int,
    num_virtual: int,
    replication: int,
    expert_perm: jax.Array | None = None,
    renormalize: bool = True,
    draft_mode: str = "off",
) -> RoutingInfo:
    """Top-k gate + virtual-slot destination map for ``[T, E]`` logits.

    Each choice (t, k) targets all ``r = replication`` tensor shards of its
    expert, re-addressed by the layer's ``expert_perm`` (virtual expert ->
    physical slot, the OCS cross-map analogue); ``wfull`` repeats the full
    combine weight per shard (row-split matmul partials sum under one
    weight).  ``draft_mode`` narrows the fan-out for speculative draft
    passes (``shared_only`` callers bypass routing and must not land here).
    """
    top_k = effective_top_k(top_k, draft_mode)
    if top_k <= 0:
        raise ValueError("shared_only drafts skip routing entirely")
    t = logits.shape[0]
    weights, idx = ops.topk_gating(logits, top_k)
    if renormalize:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    r = replication
    vdest = (idx[..., None] * r + jnp.arange(r, dtype=jnp.int32)).reshape(
        t, top_k * r
    )
    if expert_perm is not None:
        vdest = resolve_perm(expert_perm, num_virtual)[vdest]
    wfull = jnp.repeat(weights, r, axis=-1)
    return RoutingInfo(weights=weights, idx=idx, vdest=vdest, wfull=wfull)


def resolve_perm(expert_perm, num_virtual: int) -> jax.Array:
    """Validate one layer's [E_virtual] expert->slot map (identity if None)."""
    if expert_perm is None:
        return jnp.arange(num_virtual, dtype=jnp.int32)
    perm = jnp.asarray(expert_perm, jnp.int32)
    if perm.shape != (num_virtual,):
        raise ValueError(
            f"expert_perm must be this layer's [E_virtual]={num_virtual} row, "
            f"got shape {perm.shape}"
        )
    return perm


def expert_load(
    idx: jax.Array, num_experts: int, weights: jax.Array | None = None
) -> jax.Array:
    """[E] f32 routed-token counts per real expert (scatter-add, no one-hot).

    ``weights`` is an optional per-token weight ``[T]`` (broadcast over the
    top-k choices).  The serving engine passes the live-slot mask here so the
    control plane's monitor only sees traffic from occupied decode slots —
    the decode-path gate-stat export (DESIGN.md §9); ``None`` keeps the
    historical unweighted count.
    """
    if weights is None:
        contrib = jnp.ones(idx.size, jnp.float32)
    else:
        k = idx.shape[-1]
        contrib = jnp.repeat(weights.reshape(-1).astype(jnp.float32), k)
    return jnp.zeros((num_experts,), jnp.float32).at[idx.reshape(-1)].add(contrib)


def router_losses(logits: jax.Array, idx: jax.Array, num_experts: int):
    """Switch-style balance loss + router z-loss (both f32 scalars)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    mean_prob = probs.reshape(-1, num_experts).mean(axis=0)
    counts = expert_load(idx, num_experts)
    frac = counts / jnp.maximum(counts.sum(), 1.0)
    balance = num_experts * jnp.sum(frac * mean_prob)
    z = jnp.mean(jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1) ** 2)
    return balance, z


def capacity(tokens: int, top_k: int, num_buckets: int, factor: float) -> int:
    """Per-bucket capacity for the capacity-factor mode (multiple of 4)."""
    c = int(np.ceil(tokens * top_k * factor / num_buckets))
    return max(4, int(np.ceil(c / 4) * 4))


# ---------------------------------------------------------------------------
# sort-based token ordering
# ---------------------------------------------------------------------------


def bucket_ranks(
    dest: jax.Array, num_buckets: int, *, valid: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """Rank of each flat choice within its destination bucket.

    One stable argsort over ``dest [N]`` orders choices by bucket while
    preserving token order inside each bucket — so capacity-mode keep
    decisions match the historical cumsum ranks exactly, at O(N log N)
    instead of O(N·E).  Entries with ``valid`` False sort into a trash bucket
    and get ranks that no real bucket counts.  Returns ``(rank [N] i32,
    counts [num_buckets] i32)``.
    """
    n = dest.shape[0]
    if valid is not None:
        key = jnp.where(valid, dest, num_buckets)
        total = num_buckets + 1
    else:
        key = dest
        total = num_buckets
    order = jnp.argsort(key, stable=True)
    skey = key[order]
    counts = jnp.zeros((total,), jnp.int32).at[key].add(1)
    starts = jnp.cumsum(counts) - counts  # cumsum over buckets, not tokens
    rank_sorted = jnp.arange(n, dtype=jnp.int32) - starts[skey].astype(jnp.int32)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)
    return rank, counts[:num_buckets]


def _invert_slots(slot: jax.Array, keep: jax.Array, num_rows: int) -> jax.Array:
    """src[p] = flat choice occupying packed row p, -1 where empty."""
    n = slot.shape[0]
    scatter_to = jnp.where(keep, slot, num_rows)
    src = (
        jnp.full((num_rows + 1,), -1, jnp.int32)
        .at[scatter_to]
        .set(jnp.arange(n, dtype=jnp.int32))
    )
    return src[:num_rows]


def capacity_plan(
    dest: jax.Array,
    rank: jax.Array,
    valid: jax.Array | None,
    num_buckets: int,
    cap: int,
) -> DispatchPlan:
    """GShard layout: bucket-major ``[num_buckets · cap]`` rows, overflow
    (rank >= cap) dropped.  With ``cap`` >= the worst-case bucket count this
    layout is dropless (how the all-to-all send stage expresses dropless
    without dynamic buffer sizes)."""
    keep = rank < cap
    if valid is not None:
        keep = keep & valid
    slot = jnp.where(keep, dest * cap + rank, -1)
    num_rows = num_buckets * cap
    src = _invert_slots(slot, keep, num_rows)
    return DispatchPlan(
        slot=slot, src=src, num_rows=num_rows, block_experts=None,
        kept=keep.sum(),
    )


def dropless_plan(
    dest: jax.Array,
    rank: jax.Array,
    counts: jax.Array,
    valid: jax.Array | None,
    num_buckets: int,
    block: int,
) -> DispatchPlan:
    """MegaBlocks layout: every valid choice placed, buckets padded up to a
    multiple of ``block`` rows so each ``block``-row tile is owned by exactly
    one expert (``block_experts`` drives the grouped GEMM's scalar-prefetch
    index map).  Static height: padding never exceeds ``E·(block-1)`` rows
    regardless of the realized load split; the tiles past ``used_blocks``
    hold no rows."""
    n = dest.shape[0]
    nblk = (n + num_buckets * (block - 1)) // block
    num_rows = nblk * block
    pcounts = ((counts + block - 1) // block) * block
    ends = jnp.cumsum(pcounts)
    starts = ends - pcounts
    ok = valid if valid is not None else jnp.ones((n,), bool)
    safe_dest = jnp.clip(dest, 0, num_buckets - 1)
    slot = jnp.where(ok, starts[safe_dest] + rank, -1)
    src = _invert_slots(slot, ok, num_rows)
    block_experts = jnp.clip(
        jnp.searchsorted(ends, jnp.arange(nblk) * block, side="right"),
        0,
        num_buckets - 1,
    ).astype(jnp.int32)
    return DispatchPlan(
        slot=slot, src=src, num_rows=num_rows, block_experts=block_experts,
        kept=ok.sum(), used_blocks=(ends[-1] // block).astype(jnp.int32),
    )


# ---------------------------------------------------------------------------
# dense masks (einsum backend)
# ---------------------------------------------------------------------------


def dense_dispatch_masks(
    vdest: jax.Array,
    rank: jax.Array,
    keep: jax.Array,
    wfull: jax.Array,
    num_slots: int,
    cap: int,
) -> tuple[jax.Array, jax.Array]:
    """(dispatch, combine) masks ``[..., num_slots, cap]`` for the GShard
    einsum backend, built from the sort-based ranks (any leading batch/group
    dims broadcast through).  ``dispatch`` is the 0/1 token->buffer scatter;
    ``combine`` additionally carries the combine weights."""
    de = jax.nn.one_hot(vdest, num_slots, dtype=jnp.float32)
    dc = jax.nn.one_hot(jnp.clip(rank, 0, cap - 1), cap, dtype=jnp.float32)
    keepf = keep.astype(jnp.float32)
    dispatch = jnp.einsum("...se,...sc,...s->...ec", de, dc, keepf)
    combine = jnp.einsum("...se,...sc,...s->...ec", de, dc, keepf * wfull)
    return dispatch, combine
