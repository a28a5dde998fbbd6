"""Pipeline parallelism: GPipe-schedule microbatch pipeline as a jax-native
``shard_map`` program over a ``stage`` mesh axis.

The paper's training configurations all pipeline MoE blocks across stages
(Table 1), and its locality insight — EP all-to-all never crosses PP stages
— is what makes regional reconfigurable domains possible in the first
place.  This module provides that axis for the framework: stages hold
disjoint layer slices (params stacked on a leading stage dim, sharded over
``stage``), activations flow stage-to-stage with ``ppermute``, and the
schedule is a ``lax.scan`` over M + S - 1 ticks.  Differentiating through
the scan yields the reverse pipeline automatically, so one definition
serves forward and backward.

This module is the bare schedule; the composition with the real training
stack lives in :mod:`repro.train.pp_step` (``make_pp_train_step``): there the
stage body runs the actual transformer blocks with the MoE mixnet data plane
(dispatch a2a, ``overlap_chunks`` chunking, per-layer expert/wire perms) on
the ``model`` mesh axis *inside* each stage, and the Trainer drives it via
``TrainerConfig.pp_stages`` / ``num_microbatches`` (DESIGN.md §13).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply", "num_ticks"]


def num_ticks(num_microbatches: int, num_stages: int) -> int:
    return num_microbatches + num_stages - 1


def pipeline_apply(
    stage_fn,
    stage_params,
    microbatches: jax.Array,
    mesh,
    *,
    axis: str = "stage",
    extra_specs: P | None = None,
):
    """Run ``microbatches [M, mb, ...]`` through a GPipe pipeline.

    Args:
      stage_fn: ``(params_for_stage, x) -> y`` applied by every stage to its
        resident activation.  Stages are homogeneous (the usual transformer
        case: each stage = L/S blocks).
      stage_params: pytree with leading stage dim on every leaf
        (``[S, ...]``), sharded ``P(axis, ...)``.
      microbatches: ``[M, mb, ...]`` inputs (replicated across stages).
      mesh: mesh containing ``axis`` of size S.

    Returns:
      ``[M, mb, ...]`` outputs of the last stage, in microbatch order.
    """
    s = mesh.shape[axis]
    m = microbatches.shape[0]
    ticks = num_ticks(m, s)
    perm = [(i, i + 1) for i in range(s - 1)]

    def per_stage(params_local, mbs):
        # params_local: stage slice [1, ...] -> squeeze; mbs replicated [M,...]
        params_here = jax.tree.map(lambda p: p[0], params_local)
        stage_idx = lax.axis_index(axis)
        buf0 = jnp.zeros_like(mbs[0])

        def tick(carry, t):
            buf = carry
            # Stage 0 ingests microbatch t (when one is due); other stages
            # work on whatever arrived from the previous stage last tick.
            # Past the last microbatch (drain ticks — every tick >= M when
            # M < S) stage 0 feeds zeros, so the garbage riding the pipe is
            # a fixed point of well-behaved stage fns instead of a stale
            # re-fed microbatch; those ticks' outputs are discarded by the
            # final slice either way.
            feed = jnp.where(t < m, mbs[jnp.minimum(t, m - 1)], 0)
            x = jnp.where(stage_idx == 0, feed, buf)
            y = stage_fn(params_here, x)
            # Shift the pipe: stage i's output becomes stage i+1's input.
            nxt = lax.ppermute(y, axis, perm)
            # The last stage emits its result this tick (valid for ticks
            # >= S-1); gather on the host side below.
            return nxt, y

        _, outs = lax.scan(tick, buf0, jnp.arange(ticks))
        # outs: [ticks, mb, ...] = every stage's per-tick output; only the
        # last stage's outputs at ticks S-1 .. S-1+M-1 are the model outputs.
        return outs[None]  # [1, ticks, ...] stage-major for the out_spec

    in_specs = (
        jax.tree.map(lambda _: P(axis), stage_params,
                     is_leaf=lambda x: hasattr(x, "shape")),
        P(),  # microbatches replicated
    )
    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(axis),
        check_vma=False,
    )
    all_outs = fn(stage_params, microbatches)  # [S, ticks, mb, ...]
    return all_outs[s - 1, s - 1 : s - 1 + m]
