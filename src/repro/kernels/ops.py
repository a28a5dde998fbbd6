"""Public kernel entry points.

Each op dispatches between the Pallas TPU kernel and the pure-jnp reference:

  * ``backend="auto"``  — Pallas on TPU, reference elsewhere (CPU containers
    validate kernels in interpret mode through the tests, but run models on
    the reference path for speed).
  * ``backend="pallas"`` — force the kernel (interpret=True off-TPU).
  * ``backend="ref"``   — force the oracle.

Pallas kernels have no autodiff rule, so every kernel on the training path
(``flash_attention``, ``grouped_matmul`` in both layouts, ``moe_dispatch``,
``moe_combine``, ``topk_gating``) runs as a ``jax.custom_vjp``: the kernel is
the forward and the backward runs in XLA.  The backward is the VJP of the
op's :mod:`repro.kernels.ref` oracle, which shares the kernel's semantics;
the dropless grouped GEMM alone has a hand-written XLA backward of the same
math (see :func:`_gmm_blocks_bwd`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.flash_attention import (
    flash_attention_pallas,
    paged_flash_decode_pallas,
)
from repro.kernels.grouped_matmul import (
    grouped_matmul_blocks_pallas,
    grouped_matmul_pallas,
)
from repro.kernels.moe_dispatch import moe_combine_pallas, moe_dispatch_pallas
from repro.kernels.topk_gating import topk_gating_pallas

__all__ = [
    "grouped_matmul", "topk_gating", "moe_dispatch", "moe_combine",
    "flash_attention", "paged_flash_decode", "rmsnorm", "ssd_chunk", "on_tpu",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(backend: str) -> str:
    if backend == "auto":
        return "pallas" if on_tpu() else "chunked"
    return backend


def _resolve_simple(backend: str) -> str:
    """For ops with no chunked variant: auto -> pallas on TPU, ref off it."""
    mode = _resolve(backend)
    return "ref" if mode == "chunked" else mode


def _with_vjp(kernel, bwd):
    """``kernel(*args)`` as a ``custom_vjp`` whose backward is
    ``bwd(args, cotangent)``; the residuals are the primal args."""
    f = jax.custom_vjp(lambda *args: kernel(*args))
    f.defvjp(lambda *args: (kernel(*args), args), bwd)
    return f


def _with_ref_vjp(kernel, oracle):
    """``kernel`` forward; backward = the VJP of its ``ref.py`` ``oracle``
    (integer index args get the oracle's float0 cotangents)."""
    return _with_vjp(kernel, lambda args, g: jax.vjp(oracle, *args)[1](g))


def _gmm_blocks_bwd(args, g):
    """XLA backward of :func:`ref.grouped_matmul_blocks`.

    The oracle's own VJP differentiates a ``lax.scan`` that gathers one
    expert's weights per row tile, and would stack an f32 ``[D, F]`` weight
    residual per tile (hundreds of GB at published widths).  Here the tile
    ownership becomes a one-hot ``[n, E]`` mask, zero on the tiles past the
    used count, so each gradient is one contraction:
    ``dx[n] = g[n] @ w[e(n)]^T`` and ``dw[e] = sum over used tiles owned by
    e of x[n]^T g[n]``, both in f32."""
    x, w, block_experts, used_blocks = args
    live = jnp.arange(x.shape[0]) < used_blocks
    onehot = (
        (block_experts[:, None] == jnp.arange(w.shape[0], dtype=jnp.int32))
        & live[:, None]
    ).astype(jnp.float32)  # [n, E]
    f32 = jnp.float32
    xe = jnp.einsum("nbd,ne->nbed", x.astype(f32), onehot)
    dw = jnp.einsum("nbed,nbf->edf", xe, g.astype(f32))
    ge = jnp.einsum("nbf,edf->nbed", g.astype(f32), w.astype(f32))
    dx = jnp.einsum("nbed,ne->nbd", ge, onehot)
    return (
        dx.astype(x.dtype),
        dw.astype(w.dtype),
        np.zeros(block_experts.shape, jax.dtypes.float0),
        np.zeros(jnp.shape(used_blocks), jax.dtypes.float0),
    )


def grouped_matmul(
    x, w, *, block_experts=None, used_blocks=None, backend: str = "auto"
):
    """Per-expert GEMM.  ``block_experts=None``: capacity layout ``[E, C, D]``
    against ``w [E, D, F]``; with a ``[n]`` block->expert map: dropless block
    layout ``[n, B, D]`` (rows of tile ``i`` use ``w[block_experts[i]]``;
    tiles ``i >= used_blocks`` come out zero, and the kernel skips them).
    Pallas path: backward in XLA (the capacity layout through the oracle's
    VJP, the block layout through :func:`_gmm_blocks_bwd`)."""
    mode = _resolve_simple(backend)
    interpret = not on_tpu()
    if block_experts is None:
        if mode == "pallas":
            return _with_ref_vjp(
                functools.partial(grouped_matmul_pallas, interpret=interpret),
                ref.grouped_matmul,
            )(x, w)
        return ref.grouped_matmul(x, w)
    if mode == "pallas":
        if used_blocks is None:
            used_blocks = x.shape[0]
        return _with_vjp(
            functools.partial(grouped_matmul_blocks_pallas, interpret=interpret),
            _gmm_blocks_bwd,
        )(x, w, block_experts, jnp.asarray(used_blocks, jnp.int32))
    return ref.grouped_matmul_blocks(x, w, block_experts, used_blocks)


def moe_dispatch(x, src, *, backend: str = "auto"):
    """Gather token rows ``x [T, D]`` into a packed layout by ``src [P]``
    (i32 source row per slot, -1 = empty -> zeros).  Pallas path: backward
    is the VJP of :func:`ref.moe_dispatch` in XLA."""
    mode = _resolve_simple(backend)
    if mode == "pallas":
        return _with_ref_vjp(
            functools.partial(moe_dispatch_pallas, interpret=not on_tpu()),
            ref.moe_dispatch,
        )(x, src)
    return ref.moe_dispatch(x, src)


def moe_combine(y, slot, weights, *, backend: str = "auto"):
    """Weighted combine of packed rows ``y [P, D]`` back to token order via
    ``slot``/``weights [T, S]`` (f32 result; ``slot < 0`` terms skipped).
    Pallas path: backward is the VJP of :func:`ref.moe_combine` in XLA."""
    mode = _resolve_simple(backend)
    if mode == "pallas":
        return _with_ref_vjp(
            functools.partial(moe_combine_pallas, interpret=not on_tpu()),
            ref.moe_combine,
        )(y, slot, weights)
    return ref.moe_combine(y, slot, weights)


def topk_gating(logits, k: int, *, backend: str = "auto"):
    """Softmax + top-k router.  Pallas path: backward is the VJP of
    :func:`ref.topk_gating` in XLA."""
    mode = _resolve_simple(backend)
    if mode == "pallas":
        return _with_ref_vjp(
            functools.partial(topk_gating_pallas, k=k, interpret=not on_tpu()),
            functools.partial(ref.topk_gating, k=k),
        )(logits)
    return ref.topk_gating(logits, k)


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    backend: str = "auto",
):
    """GQA attention ``q [B, Hq, S, D]``, ``k/v [B, Hkv, S, D]``.  Pallas
    path: backward is the VJP of :func:`ref.flash_attention` in XLA."""
    mode = _resolve(backend)
    if mode == "pallas":
        kw = dict(causal=causal, window=window, softcap=softcap)
        return _with_ref_vjp(
            functools.partial(
                flash_attention_pallas, interpret=not on_tpu(), **kw
            ),
            functools.partial(ref.flash_attention, **kw),
        )(q, k, v)
    if mode == "ref":
        return ref.flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap
        )
    # auto off-TPU: chunked memory-efficient path so big-S graphs lower with
    # bounded buffers (semantically identical to ref; tested).
    return ref.flash_attention_chunked(
        q, k, v, causal=causal, window=window, softcap=softcap
    )


def paged_flash_decode(
    q,
    k_pool,
    v_pool,
    page_table,
    lengths,
    *,
    window: int | None = None,
    softcap: float | None = None,
    backend: str = "auto",
):
    """Decode / chunked-continuation attention through a paged KV cache:
    ``q [B, C, Hq, D]`` against pools ``[N, page, Hkv, D]`` gathered via
    ``page_table [B, P]`` (i32 page ids, -1 = unallocated) with per-sequence
    ``lengths [B]``.  Both paths run the same streaming-softmax schedule, so
    pallas-vs-ref is bit-exact (tested in interpret mode).  ``C > 1`` also
    carries speculative verify spans (K drafted tokens + 1): bit-exactness
    across C is what lets verify's rescoring reproduce the serial decode
    rounding token for token (DESIGN.md §11), including the padded C=2
    tile."""
    mode = _resolve_simple(backend)
    if mode == "pallas":
        return paged_flash_decode_pallas(
            q, k_pool, v_pool, page_table, lengths,
            window=window, softcap=softcap, interpret=not on_tpu(),
        )
    return ref.paged_flash_decode(
        q, k_pool, v_pool, page_table, lengths, window=window, softcap=softcap
    )


def rmsnorm(x, w, *, eps: float = 1e-6, backend: str = "auto"):
    """Fused RMSNorm over [T, D] tokens (TPU kernel; jnp path elsewhere)."""
    mode = _resolve_simple(backend)
    if mode == "pallas":
        from repro.kernels.rmsnorm import rmsnorm_pallas

        return rmsnorm_pallas(x, w, eps=eps, interpret=not on_tpu())
    from repro.models.layers import rms_norm

    return rms_norm(x[None], w, eps)[0]


def ssd_chunk(x, da, bmat, cmat, *, backend: str = "auto"):
    """Mamba-2 SSD intra-chunk compute: (y_intra, chunk_state)."""
    mode = _resolve_simple(backend)
    if mode == "pallas":
        from repro.kernels.ssd_chunk import ssd_chunk_pallas

        return ssd_chunk_pallas(x, da, bmat, cmat, interpret=not on_tpu())
    l = x.shape[1]
    cum = jnp.cumsum(da, axis=1)
    cb = jnp.einsum("gln,gsn->gls", cmat, bmat)
    gate = jnp.exp(cum[:, :, None] - cum[:, None, :])
    mask = np.tril(np.ones((l, l), bool))
    y = jnp.einsum("gls,gls,gsp->glp", cb, jnp.where(mask, gate, 0.0), x)
    st = jnp.einsum("gsn,gs,gsp->gnp", bmat, jnp.exp(cum[:, -1:] - cum), x)
    return y, st
