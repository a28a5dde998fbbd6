"""Fused MoE dispatch/combine Pallas kernels (the sort-based data movers).

After :mod:`repro.models.routing` has computed the argsort-by-expert token
permutation, the remaining hot-path work is pure data movement:

* ``moe_dispatch`` — gather token rows into the packed expert layout:
  ``out[p] = x[src[p]]`` (zeros where ``src[p] < 0``, i.e. padding/drops).
* ``moe_combine`` — weighted gather-sum back to token order:
  ``out[t] = Σ_s w[t, s] · y[slot[t, s]]`` (terms with ``slot < 0`` skipped).

Both kernels drive the gather with **scalar-prefetched** index arrays
(``pltpu.PrefetchScalarGridSpec``): the index map of the data input reads the
packed-row/source-row id from SMEM before the block DMA is issued, so the
pipeline streams exactly the rows it needs from HBM — no one-hot matrices,
no host-side reordering.  Row blocks are single token rows; the grid walks
packed rows (dispatch) or (token, choice) pairs (combine), and the combine
accumulates its S terms in a VMEM scratch like the grouped GEMM accumulates
its K steps.  Rows are addressed through a ``[rows, 1, D]`` view so that a
one-row block ``(1, 1, D)`` meets the TPU tiling rule (the last two block
dims equal the array's), which a ``(1, D)`` block of ``[rows, D]`` does not.

TPU is the target; CPU validation runs in ``interpret=True`` mode against
:func:`repro.kernels.ref.moe_dispatch` / :func:`repro.kernels.ref.moe_combine`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["moe_dispatch_pallas", "moe_combine_pallas"]


def _dispatch_kernel(src_ref, x_ref, o_ref):
    p = pl.program_id(0)

    @pl.when(src_ref[p] >= 0)
    def _copy():
        o_ref[...] = x_ref[...]

    @pl.when(src_ref[p] < 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_dispatch_pallas(
    x: jax.Array, src: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """``x [T, D]`` gathered by ``src [P]`` (i32, -1 = empty) -> ``[P, D]``."""
    t, d = x.shape
    p = src.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(p,),
        in_specs=[
            pl.BlockSpec(
                (1, 1, d), lambda i, src_ref: (jnp.maximum(src_ref[i], 0), 0, 0)
            )
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda i, src_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _dispatch_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((p, 1, d), x.dtype),
        interpret=interpret,
    )(src.astype(jnp.int32), x.reshape(t, 1, d))
    return out.reshape(p, d)


def _combine_kernel(slot_ref, w_ref, y_ref, o_ref, acc_ref, *, s_steps: int):
    ti = pl.program_id(0)
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = ti * s_steps + si
    w = jnp.where(slot_ref[i] >= 0, w_ref[i], 0.0)
    acc_ref[...] += w * y_ref[0].astype(jnp.float32)

    @pl.when(si == s_steps - 1)
    def _store():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_combine_pallas(
    y: jax.Array,
    slot: jax.Array,
    weights: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """``y [P, D]`` combined by ``slot/weights [T, S]`` -> ``[T, D]`` f32."""
    p, d = y.shape
    t, s = slot.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t, s),
        in_specs=[
            pl.BlockSpec(
                (1, 1, d),
                lambda ti, si, slot_ref, w_ref: (
                    jnp.maximum(slot_ref[ti * s + si], 0),
                    0,
                    0,
                ),
            )
        ],
        out_specs=pl.BlockSpec(
            (1, 1, d), lambda ti, si, slot_ref, w_ref: (ti, 0, 0)
        ),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_combine_kernel, s_steps=s),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, 1, d), jnp.float32),
        interpret=interpret,
    )(
        slot.reshape(-1).astype(jnp.int32),
        weights.reshape(-1).astype(jnp.float32),
        y.reshape(p, 1, d),
    )
    return out.reshape(t, d)
