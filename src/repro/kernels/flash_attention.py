"""Blockwise (flash) attention Pallas kernels — the prefill hot spot plus
the paged decode path.

:func:`flash_attention_pallas` — streaming-softmax attention tiled for the
TPU memory hierarchy: a ``(bq x D)`` query tile stays VMEM-resident while
``(bk x D)`` key/value tiles stream through the innermost grid axis; running
max / sum / output accumulators live in VMEM scratch and persist across the
kv axis (TPU grids iterate the last axis innermost, revisiting the same
output block).

:func:`paged_flash_decode_pallas` — the decode / chunked-continuation
variant over a block-paged KV cache (DESIGN.md §10): K/V live in a page
pool ``[N, page, Hkv, D]`` shared by every slot, and each sequence's pages
are gathered through a scalar-prefetched page table — the same
``PrefetchScalarGridSpec`` index-map idiom :mod:`repro.kernels.moe_dispatch`
uses for token gathers, so the DMA for page ``p+1`` is issued from an SMEM
lookup while page ``p``'s tile computes.

Supports GQA (kv-head picked by index map — no materialized repeat), causal
masking, sliding windows (gemma2 / recurrentgemma local attention) and logit
soft-capping (gemma2).  Validated in interpret mode against
:func:`repro.kernels.ref.flash_attention` /
:func:`repro.kernels.ref.paged_flash_decode` (the paged oracle mirrors the
page-at-a-time streaming schedule, so the check is bit-exact).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.grouped_matmul import pick_block

__all__ = ["flash_attention_pallas", "paged_flash_decode_pallas"]

_NEG_INF = -1e30


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    kv_steps: int,
    bq: int,
    bk: int,
    scale: float,
    causal: bool,
    window: int | None,
    softcap: float | None,
):
    kv_i = pl.program_id(3)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)  # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)  # [bk, D]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [bq, bk]
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = pl.program_id(2) * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kv_i * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(kv_i == kv_steps - 1)
    def _store():
        # Fully-masked rows (can happen for non-causal windows) get zeros.
        denom = jnp.where(l_ref[...] > 0.0, l_ref[...], 1.0)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "bq", "bk", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Attention ``q[B,Hq,S,D], k/v[B,Hkv,S,D] -> [B,Hq,S,D]``."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    if hq % hkv != 0:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    group = hq // hkv
    bq = pick_block(s, bq)
    bk = pick_block(s, bk)
    kv_steps = s // bk
    grid = (b, hq, s // bq, kv_steps)
    scale = 1.0 / (d**0.5)

    kernel = functools.partial(
        _flash_kernel,
        kv_steps=kv_steps,
        bq=bq,
        bk=bk,
        scale=scale,
        causal=causal,
        window=window,
        softcap=softcap,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec(
                (1, 1, bk, d), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)
            ),
            pl.BlockSpec(
                (1, 1, bk, d), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _paged_decode_kernel(
    table_ref,  # SMEM [B, P] page id per (seq, logical page), -1 = unallocated
    len_ref,  # SMEM [B] first chunk position t (row c attends pos <= t + c)
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    pages: int,
    page: int,
    chunk: int,
    scale: float,
    window: int | None,
    softcap: float | None,
):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale  # [C, D]
    k = k_ref[0]  # [page, D]
    v = v_ref[0]
    s = jnp.dot(q, k.astype(jnp.float32).T, preferred_element_type=jnp.float32)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    # Row c of the chunk sits at absolute position t + c; page p covers key
    # positions [p*page, (p+1)*page).  Unallocated pages (table -1, gathered
    # as page 0) only cover positions past the sequence end, so the causal
    # mask alone discards them.
    q_pos = len_ref[b] + jax.lax.broadcasted_iota(jnp.int32, (chunk, page), 0)
    k_pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (chunk, page), 1)
    mask = k_pos <= q_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p_tile = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p_tile, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p_tile, v.astype(jnp.float32), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(p == pages - 1)
    def _store():
        denom = jnp.where(l_ref[...] > 0.0, l_ref[...], 1.0)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "interpret")
)
def paged_flash_decode_pallas(
    q: jax.Array,  # [B, C, Hq, D] — C=1 decode, C>1 chunked continuation
    k_pool: jax.Array,  # [N, page, Hkv, D] shared page pool
    v_pool: jax.Array,
    page_table: jax.Array,  # [B, P] i32 page ids, -1 = unallocated
    lengths: jax.Array,  # [B] i32 — chunk row c attends positions <= t + c
    *,
    window: int | None = None,
    softcap: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention through a paged KV cache: ``-> [B, C, Hq, D]``.

    The pools must already hold the chunk's own K/V (positions
    ``t .. t+C-1``), matching the write-then-attend order of
    :func:`repro.models.layers._decode_attention`.  The page table and
    per-sequence lengths ride the scalar-prefetch channel: the K/V BlockSpec
    index maps read ``table[b, p]`` from SMEM to aim each page's DMA, so an
    arbitrary slot-length mix streams through one static grid
    ``(B, Hq, P)`` with no gather materialized in HBM.

    Heads are addressed through free row-major views that merge them into
    the last dim (``q [B, C, Hq*D]``, pools ``[N, page, Hkv*D]``): a one-head
    block is then ``(C, D)`` / ``(page, D)``, which meets the TPU tiling rule
    (last two block dims divisible by (8, 128) or equal to the array's) for
    ``D % 128 == 0``, where a ``(1, D)`` head slice of the 4-D layout does
    not.
    """
    b, c, hq, d = q.shape
    n_pages, page, hkv, _ = k_pool.shape
    pages = page_table.shape[1]
    if hq % hkv != 0:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    group = hq // hkv
    scale = 1.0 / (d**0.5)

    # Chunk rows are independent (per-row softmax, per-row accumulator), so
    # padding the chunk never changes live rows — but a 2-row tile DOES
    # change which contraction strategy XLA picks for the [C, D] x [D, page]
    # dot, drifting 1 ulp from every other chunk width (and from the ref.py
    # oracle's fori_loop form).  K=1 draft/verify spans are exactly C=2, so
    # pad that one width up to 4 and slice; bit-exact acceptance depends on
    # verify rescoring positions with the same rounding the serial path saw.
    c_in = c
    if c == 2:
        q = jnp.concatenate(
            [q, jnp.zeros((b, 2, hq, d), q.dtype)], axis=1
        )
        c = 4

    kernel = functools.partial(
        _paged_decode_kernel,
        pages=pages,
        page=page,
        chunk=c,
        scale=scale,
        window=window,
        softcap=softcap,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hq, pages),
        in_specs=[
            pl.BlockSpec((1, c, d), lambda bi, hi, pi, tref, lref: (bi, 0, hi)),
            pl.BlockSpec(
                (1, page, d),
                lambda bi, hi, pi, tref, lref: (
                    jnp.maximum(tref[bi, pi], 0), 0, hi // group
                ),
            ),
            pl.BlockSpec(
                (1, page, d),
                lambda bi, hi, pi, tref, lref: (
                    jnp.maximum(tref[bi, pi], 0), 0, hi // group
                ),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, c, d), lambda bi, hi, pi, tref, lref: (bi, 0, hi)
        ),
        scratch_shapes=[
            pltpu.VMEM((c, 1), jnp.float32),
            pltpu.VMEM((c, 1), jnp.float32),
            pltpu.VMEM((c, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c, hq * d), q.dtype),
        interpret=interpret,
    )(
        page_table.astype(jnp.int32),
        lengths.astype(jnp.int32),
        q.reshape(b, c, hq * d),
        k_pool.reshape(n_pages, page, hkv * d),
        v_pool.reshape(n_pages, page, hkv * d),
    )
    out = out.reshape(b, c, hq, d)
    return out[:, :c_in] if c_in != c else out
