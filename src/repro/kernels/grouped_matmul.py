"""Grouped (per-expert) matmul Pallas kernel — the MoE expert-FFN hot spot.

Two layouts, one accumulation scheme:

* capacity dispatch leaves ``x[E_local, C, D]`` per-expert buffers next to
  stacked weights ``w[E_local, D, F]`` (``grouped_matmul_pallas``);
* dropless sort-based dispatch (:mod:`repro.models.routing`) leaves a
  block-padded ``x[n, B, D]`` row-tile layout plus a block->expert map
  (``grouped_matmul_blocks_pallas``, scalar-prefetched weight indexing).

Either way the kernel tiles output blocks into VMEM with a ``D``-step
accumulation loop so the MXU sees aligned ``(bc x bd) @ (bd x bf)`` tiles and
the working set (``bc*bd + bd*bf + bc*bf`` elements) stays inside the ~16 MB
VMEM budget.  The dropless layout's tiles come from the shapes alone
(:func:`pick_tiles`): rows per tile from the mean rows per expert, weight
tiles from the widths.

TPU is the target; CPU validation runs in ``interpret=True`` mode against
:func:`repro.kernels.ref.grouped_matmul`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "grouped_matmul_pallas", "grouped_matmul_blocks_pallas", "pick_block",
    "pick_tiles",
]


def pick_block(dim: int, target: int) -> int:
    """Largest divisor of ``dim`` that is <= ``target`` (hardware-aligned
    blocks when the caller passes multiples of 128)."""
    if dim <= target:
        return dim
    for b in range(target, 0, -1):
        if dim % b == 0:
            return b
    return dim


# Bounds of the dropless tile.  Rows: 8 is the f32 sublane minimum; past 256
# the static height's E x (B - 1) pad rows cost the XLA backward more than
# the kernel gains (one v5e, Mixtral widths, 8192 rows over 4 experts: the
# layer's forward + backward 178 ms at B = 256, 189 ms at 512).  Widths: a
# 1024 x 512 bf16 weight tile (1 MB) keeps a grid step's MXU time near its
# DMA time; at B = 256 the double-buffered working set is about 4 MB of VMEM.
ROW_TILE_MIN, ROW_TILE_MAX = 8, 256
BF_MAX, BD_MAX = 512, 1024


def pick_tiles(rows: int, experts: int, d: int, f: int) -> tuple[int, int, int]:
    """The dropless grouped GEMM's tile ``(B, bf, bd)`` for ``rows`` routed
    rows over ``experts`` local experts, ``[.., d] @ [d, f]``.

    ``B`` (rows per tile, the height :func:`repro.models.routing.dropless_plan`
    pads each expert's rows to) is the smallest power of two above the mean
    rows per expert, within ``[ROW_TILE_MIN, ROW_TILE_MAX]``: an expert whose
    load runs over the mean still fits one tile, and every further tile
    streams the expert's whole weight matrix again.  ``bf`` and ``bd``
    depend on the widths only, so a row's f32 accumulation order is the same
    whatever rows share the call."""
    b = 1 << (rows // experts).bit_length()
    b = min(max(b, ROW_TILE_MIN), ROW_TILE_MAX)
    return b, pick_block(f, BF_MAX), pick_block(d, BD_MAX)


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int):
    """One (expert, c-block, f-block) output tile; grid axis 3 walks D."""

    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[0], w_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _store():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bc", "bf", "bd", "interpret")
)
def grouped_matmul_pallas(
    x: jax.Array,
    w: jax.Array,
    *,
    bc: int = 128,
    bf: int = 128,
    bd: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """``[E, C, D] @ [E, D, F] -> [E, C, F]`` with per-expert tiling."""
    e, c, d = x.shape
    e2, d2, f = w.shape
    if (e, d) != (e2, d2):
        raise ValueError(f"shape mismatch {x.shape} @ {w.shape}")
    bc = pick_block(c, bc)
    bf = pick_block(f, bf)
    bd = pick_block(d, bd)
    k_steps = d // bd
    grid = (e, c // bc, f // bf, k_steps)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda ei, ci, fi, ki: (ei, ci, ki)),
            pl.BlockSpec((1, bd, bf), lambda ei, ci, fi, ki: (ei, ki, fi)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda ei, ci, fi, ki: (ei, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        interpret=interpret,
    )(x, w)


def _gmm_blocks_kernel(
    be_ref, used_ref, x_ref, w_ref, o_ref, acc_ref, *, k_steps: int
):
    """One (row-block, f-block) output tile; grid axis 2 walks D.  The weight
    block is addressed by the scalar-prefetched block->expert map, so each
    row tile multiplies against *its own* expert's weights — the MegaBlocks
    dropless layout with no per-expert padding to a common capacity.  Tiles
    at or past the used-tile count skip the dot and store zeros."""
    del be_ref  # consumed by the index maps

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(pl.program_id(0) < used_ref[0])
    def _dot():
        acc_ref[...] += jnp.dot(
            x_ref[0], w_ref[0], preferred_element_type=jnp.float32
        )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bf", "bd", "interpret"))
def grouped_matmul_blocks_pallas(
    x: jax.Array,
    w: jax.Array,
    block_experts: jax.Array,
    used_blocks: jax.Array | None = None,
    *,
    bf: int | None = None,
    bd: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """``[n, B, D] @ w[block_experts[n], D, F] -> [n, B, F]``.

    ``x`` is the block-padded dropless token layout from
    :func:`repro.models.routing.dropless_plan`: ``n`` row tiles of ``B``
    tokens, tile ``i`` owned entirely by expert ``block_experts[i]``.  Tiles
    ``i >= used_blocks`` (a traced count; ``None`` = all ``n``) come out
    zero: their grid steps keep the last used tile's blocks, so they issue
    no weight or row DMA, and skip the dot.  ``bf``/``bd`` default to
    :func:`pick_tiles`' widths.
    """
    n, b, d = x.shape
    e, d2, f = w.shape
    if d != d2:
        raise ValueError(f"shape mismatch {x.shape} @ {w.shape}")
    _, tf, td = pick_tiles(n * b, e, d, f)
    bf = pick_block(f, bf or tf)
    bd = pick_block(d, bd or td)
    k_steps, f_steps = d // bd, f // bf
    if used_blocks is None:
        used_blocks = n
    used = jnp.reshape(jnp.asarray(used_blocks, jnp.int32), (1,))

    def held(ni, used):
        """(tile whose blocks step ``ni`` reads, whether tile ``ni`` is used):
        an unused tile holds the last used tile's final blocks."""
        return jnp.minimum(ni, jnp.maximum(used[0] - 1, 0)), ni < used[0]

    def x_map(ni, fi, ki, be, used):
        src, live = held(ni, used)
        return src, 0, jnp.where(live, ki, k_steps - 1)

    def w_map(ni, fi, ki, be, used):
        src, live = held(ni, used)
        return (be[src], jnp.where(live, ki, k_steps - 1),
                jnp.where(live, fi, f_steps - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, f_steps, k_steps),
        in_specs=[
            pl.BlockSpec((1, b, bd), x_map),
            pl.BlockSpec((1, bd, bf), w_map),
        ],
        out_specs=pl.BlockSpec((1, b, bf), lambda ni, fi, ki, be, used: (ni, 0, fi)),
        scratch_shapes=[pltpu.VMEM((b, bf), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gmm_blocks_kernel, k_steps=k_steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, b, f), x.dtype),
        interpret=interpret,
    )(block_experts.astype(jnp.int32), used, x, w)
