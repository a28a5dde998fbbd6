"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics of record: each kernel's test sweeps shapes/dtypes
and asserts allclose against these functions, and on non-TPU backends the
``ops`` wrappers route here (interpret-mode Pallas is for validation, not
speed).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = [
    "grouped_matmul",
    "grouped_matmul_blocks",
    "moe_dispatch",
    "moe_combine",
    "topk_gating",
    "flash_attention",
    "flash_attention_chunked",
    "paged_flash_decode",
    "paged_gather_kv",
]


def grouped_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """Per-expert GEMM: ``[E, C, D] @ [E, D, F] -> [E, C, F]`` (f32 accum)."""
    out = jnp.einsum(
        "ecd,edf->ecf", x.astype(jnp.float32), w.astype(jnp.float32)
    )
    return out.astype(x.dtype)


def grouped_matmul_blocks(
    x: jax.Array,
    w: jax.Array,
    block_experts: jax.Array,
    used_blocks: jax.Array | None = None,
) -> jax.Array:
    """Block-wise grouped GEMM: ``[n, B, D] @ w[block_experts[n], D, F]``,
    tiles ``i >= used_blocks`` zero (``None``: every tile is used).

    Oracle for the dropless (MegaBlocks) layout: a ``lax.scan`` over row
    tiles gathers ONE expert's ``[D, F]`` weights per step, so peak memory
    stays O(D·F) instead of materializing the ``[n, D, F]`` weight gather.
    """

    def step(_, xs):
        xb, be = xs
        yb = xb.astype(jnp.float32) @ w[be].astype(jnp.float32)
        return _, yb.astype(x.dtype)

    _, out = jax.lax.scan(step, None, (x, block_experts))
    if used_blocks is None:
        return out
    live = jnp.arange(x.shape[0]) < used_blocks
    return jnp.where(live[:, None, None], out, jnp.zeros_like(out))


def moe_dispatch(x: jax.Array, src: jax.Array) -> jax.Array:
    """Gather token rows into a packed dispatch layout.

    Args:
      x: ``[T, D]`` token rows.
      src: ``[P]`` i32 source row per packed slot, -1 for empty/padding.
    Returns:
      ``[P, D]``: ``x[src[p]]`` where ``src[p] >= 0``, zeros elsewhere.
    """
    rows = jnp.take(x, jnp.clip(src, 0, x.shape[0] - 1), axis=0)
    return jnp.where(src[:, None] >= 0, rows, 0).astype(x.dtype)


def moe_combine(y: jax.Array, slot: jax.Array, weights: jax.Array) -> jax.Array:
    """Weighted combine back to token order (f32 accumulation).

    Args:
      y: ``[P, D]`` packed expert outputs.
      slot: ``[T, S]`` i32 packed row per (token, choice), -1 if dropped.
      weights: ``[T, S]`` combine weights.
    Returns:
      ``[T, D]`` f32: ``out[t] = Σ_s w[t,s] · y[slot[t,s]]`` over kept terms.
    """
    rows = jnp.take(y, jnp.clip(slot, 0, y.shape[0] - 1), axis=0)  # [T, S, D]
    w = jnp.where(slot >= 0, weights.astype(jnp.float32), 0.0)
    return jnp.sum(rows.astype(jnp.float32) * w[..., None], axis=1)


def topk_gating(logits: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Softmax over experts then top-k.

    Args:
      logits: ``[T, E]`` router logits.
    Returns:
      (weights ``[T, k]`` f32 softmax probabilities of the chosen experts,
       indices ``[T, k]`` i32, descending by probability).
    """
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, idx = jax.lax.top_k(probs, k)
    return weights, idx.astype(jnp.int32)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
) -> jax.Array:
    """Reference attention with GQA, causal/sliding-window mask, logit softcap.

    Shapes: q ``[B, Hq, S, D]``, k/v ``[B, Hkv, S, D]`` with Hq % Hkv == 0.
    """
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    kx = jnp.repeat(k, group, axis=1)
    vx = jnp.repeat(v, group, axis=1)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kx.astype(jnp.float32))
    logits = logits * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= qpos - kpos < window
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vx.astype(jnp.float32))
    return out.astype(q.dtype)


def flash_attention_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    bq: int = 512,
) -> jax.Array:
    """Memory-efficient attention: ``lax.scan`` over query chunks —
    O(S * bq) score memory instead of O(S^2).

    Same semantics as :func:`flash_attention`; this is the pure-jnp path the
    *models* use off-TPU so that 32k+ prefill graphs lower with bounded
    buffers (the Pallas kernel covers the TPU target).  Partitioner-friendly
    by construction: every tensor keeps the ``[B, H, S, D]`` layout (chunks
    via dynamic slices on the seq dim, output accumulated in place), and the
    dots take bf16 operands with f32 accumulation — the heads dim shards
    cleanly with zero collectives.
    """
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    while s % bq:
        bq //= 2
    nq = s // bq
    scale = 1.0 / float(d) ** 0.5
    kpos = jnp.arange(s)

    def body(out, qstart):
        qc = jax.lax.dynamic_slice_in_dim(q, qstart, bq, axis=2)  # [B,H,bq,D]
        logits = jnp.einsum(
            "bhqd,bhkd->bhqk", qc, k, preferred_element_type=jnp.float32
        )
        logits = logits * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        qpos = qstart + jnp.arange(bq)
        mask = jnp.ones((bq, s), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= qpos[:, None] - kpos[None, :] < window
        logits = jnp.where(mask[None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        oc = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        out = jax.lax.dynamic_update_slice_in_dim(out, oc, qstart, axis=2)
        return out, None

    starts = jnp.arange(nq) * bq
    out, _ = jax.lax.scan(body, jnp.zeros_like(q), starts)
    return out


def paged_gather_kv(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """Gather a paged pool into a contiguous per-sequence view.

    Args:
      pool: ``[N, page, ...]`` page pool (K, V, or any per-position leaf).
      page_table: ``[B, P]`` i32 page ids, -1 = unallocated (gathered as page
        0 — callers mask positions past the sequence length).
    Returns:
      ``[B, P * page, ...]``: position ``pos`` of sequence ``b`` at view
      index ``pos`` (page ``pos // page``, offset ``pos % page``).
    """
    gathered = jnp.take(pool, jnp.maximum(page_table, 0), axis=0)  # [B,P,page,...]
    b, p, page = gathered.shape[:3]
    return gathered.reshape(b, p * page, *gathered.shape[3:])


@functools.partial(jax.jit, static_argnames=("window", "softcap"))
def paged_flash_decode(
    q: jax.Array,  # [B, C, Hq, D]
    k_pool: jax.Array,  # [N, page, Hkv, D]
    v_pool: jax.Array,
    page_table: jax.Array,  # [B, P] i32, -1 = unallocated
    lengths: jax.Array,  # [B] i32 — chunk row c attends positions <= t + c
    *,
    window: int | None = None,
    softcap: float | None = None,
) -> jax.Array:
    """Oracle for :func:`..flash_attention.paged_flash_decode_pallas`.

    Mirrors the kernel's exact execution structure: one ``fori_loop`` over
    the flattened ``(B, Hq, pages)`` grid (pages innermost, like the TPU
    grid), the same ``[C, D] x [page, D]`` 2D tile dots, select-based scratch
    init at ``p == 0``, and the identical streaming-softmax recurrence.
    Deliberately NOT a batched einsum formulation and jitted at the
    definition: batching the dots or unrolling the page loop changes XLA's
    contraction/FMA-fusion choices and drifts from the interpret-mode kernel
    by ~1 ulp per page, while this loop form is bit-exact (asserted
    ``== 0.0`` in the tests — including the C in {1, 2, 4} speculative
    verify-span widths; the kernel pads its C=2 tile to 4 because a 2-row
    dot picks a different XLA contraction strategy).  Models read paged
    caches off-TPU through a dense gathered view instead (see
    ``models/layers.py``); this function is the kernel's semantics of
    record.
    """
    b, c, hq, d = q.shape
    _, page, hkv, _ = k_pool.shape
    pages = page_table.shape[1]
    group = hq // hkv
    scale = 1.0 / (d**0.5)
    table = page_table.astype(jnp.int32)
    lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))

    def body(i, carry):
        m, l, acc, out = carry
        bi = i // (hq * pages)
        hi = (i // pages) % hq
        p = i % pages
        m = jnp.where(p == 0, jnp.full_like(m, -1e30), m)
        l = jnp.where(p == 0, jnp.zeros_like(l), l)
        acc = jnp.where(p == 0, jnp.zeros_like(acc), acc)
        qt = jax.lax.dynamic_slice(q, (bi, 0, hi, 0), (1, c, 1, d))[0, :, 0, :]
        pid = jnp.maximum(table[bi, p], 0)
        kh = hi // group
        k = jax.lax.dynamic_slice(
            k_pool, (pid, 0, kh, 0), (1, page, 1, d)
        )[0, :, 0, :]
        v = jax.lax.dynamic_slice(
            v_pool, (pid, 0, kh, 0), (1, page, 1, d)
        )[0, :, 0, :]
        s = jnp.dot(
            qt.astype(jnp.float32) * scale,
            k.astype(jnp.float32).T,
            preferred_element_type=jnp.float32,
        )  # [C, page]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = lens[bi] + jax.lax.broadcasted_iota(jnp.int32, (c, page), 0)
        k_pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (c, page), 1)
        mask = k_pos <= q_pos
        if window is not None:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p_tile = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p_tile, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(
            p_tile, v.astype(jnp.float32), preferred_element_type=jnp.float32
        )
        # The same (bi, hi) output block is revisited for every p; the last
        # visit (p == pages - 1) leaves the final normalized tile in place.
        denom = jnp.where(l > 0.0, l, 1.0)
        o = (acc / denom).astype(q.dtype)
        out = jax.lax.dynamic_update_slice(out, o[None, :, None, :], (bi, 0, hi, 0))
        return (m_new, l, acc, out)

    init = (
        jnp.full((c, 1), -1e30, jnp.float32),
        jnp.zeros((c, 1), jnp.float32),
        jnp.zeros((c, d), jnp.float32),
        jnp.zeros(q.shape, q.dtype),
    )
    _, _, _, out = jax.lax.fori_loop(0, b * hq * pages, body, init)
    return out
