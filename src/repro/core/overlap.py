"""Chunked comm/compute overlap engine (DESIGN.md §8).

MixNet's cost-efficiency case rests on the EP all-to-all being *hideable*
behind expert compute once the circuits match the demand (Fig 28's flat
region).  This module is the scheduling layer that turns the CommRuntime's
staged ops (:meth:`repro.core.commruntime.AllToAll.stages`, the ``Permute``
ring steps of ``AllGather``/``ReduceScatter``) into an actual schedule, on
both sides of the repo:

* **Execution side** (the trainer / MoE data plane):
  :func:`software_pipeline` runs S stage functions over K chunks in the
  skewed tick order ``stage s of chunk k at tick k+s``, draining late stages
  before issuing early ones.  Within a tick every stage call is data
  independent, which is exactly what lets the compiler overlap chunk k+1's
  dispatch all-to-all under chunk k's expert FFN under chunk k-1's combine
  (MoNTA-style chunked software pipelining; the math is unchanged because
  every chunk's rows are independent — see DESIGN.md §8 for the static-shape
  argument).

* **Pricing side** (netsim): :func:`pipelined_phase` is the flow-level event
  timeline of the same schedule — two resources (network, compute), chunked
  dispatch -> expert -> combine with precedence, greedy non-preemptive list
  scheduling in the identical skewed order.  With ``chunks=1`` it degenerates
  *exactly* to the additive serial sum, so the pre-overlap simulator results
  are reproduced bit-for-bit; with ``chunks>1`` it reports how much of the
  priced communication was hidden under the compute window
  (``IterationResult.hidden_comm``/``exposed_comm``).

Both sides consume the same per-stage ``bytes_on_link`` accounting carried
by the ops themselves — there is no second model of what a stage moves.
"""

from __future__ import annotations

__all__ = [
    "chunk_count",
    "software_pipeline",
    "pipelined_phase",
    "decode_tick_phase",
    "ring_gather_leaf",
]


def chunk_count(total: int, requested: int) -> int:
    """Largest divisor of ``total`` that is <= ``requested``.

    The overlap scheduler needs equal static chunk shapes (dynamic shapes
    would force recompilation, DESIGN.md §6), so a request that does not
    divide the token count degrades to the nearest divisor instead of
    failing mid-train.
    """
    c = max(min(int(requested), int(total)), 1)
    while total % c:
        c -= 1
    return c


def software_pipeline(num_chunks: int, stages):
    """Run ``stages`` (list of ``fn(prev_result, chunk_index)``) over
    ``num_chunks`` chunks in software-pipeline order.

    Stage ``s`` of chunk ``k`` is issued at tick ``k + s``; within a tick,
    later stages are issued first (drain order), mirroring
    :func:`pipelined_phase`'s event model.  Stage 0 receives ``prev=None``;
    stage ``s>0`` receives stage ``s-1``'s result for the same chunk.
    Returns the list of last-stage results, one per chunk.

    This is a *schedule*, not a semantic change: every stage call only
    depends on its own chunk's previous stage, so the interleaving is free
    to overlap on hardware while the composed dataflow — and therefore the
    numerics — is identical to running each chunk start-to-finish.
    """
    s_count = len(stages)
    if s_count == 0:
        return [None] * num_chunks
    results = [[None] * num_chunks for _ in range(s_count)]
    for t in range(num_chunks + s_count - 1):
        for s in reversed(range(s_count)):
            k = t - s
            if 0 <= k < num_chunks:
                prev = results[s - 1][k] if s > 0 else None
                results[s][k] = stages[s](prev, k)
    return results[-1]


def pipelined_phase(
    dispatch: float,
    compute: float,
    combine: float,
    chunks: int,
    *,
    serial_prefix: float = 0.0,
) -> tuple[float, float]:
    """Event-timeline completion of one chunked dispatch->compute->combine
    phase on two resources (network, compute engine).

    ``dispatch``/``combine`` are the phase's total network seconds (e.g. the
    fabric-priced EP all-to-all pair), ``compute`` the total expert-FFN
    seconds, each split into ``chunks`` equal chunks.  ``serial_prefix`` is
    un-overlappable compute preceding the phase (the attention block).

    Precedence per chunk k: dispatch_k -> compute_k -> combine_k; the network
    serializes dispatches and combines (shared NICs), the compute engine
    serializes FFN chunks.  Tasks are issued greedily in the skewed tick
    order with combines drained before later dispatches — the same order
    :func:`software_pipeline` executes.

    Returns ``(total_seconds, exposed_comm_seconds)`` where
    ``exposed = total - serial_prefix - compute`` — the network time not
    hidden under the compute window.  Invariants (tested):
    ``chunks=1`` gives exactly the additive serial sum (all comm exposed);
    ``total`` never exceeds the serial sum and never undercuts
    ``max(compute path, network busy time)``; ``0 <= exposed <= comm``.
    """
    c = max(int(chunks), 1)
    d, e, cb = dispatch / c, compute / c, combine / c
    net_free = 0.0
    comp_free = 0.0
    d_done = [0.0] * c
    e_done = [0.0] * c
    c_done = [0.0] * c
    for t in range(c + 2):
        k = t - 2  # combine of chunk t-2 (drain first)
        if 0 <= k < c:
            start = max(net_free, e_done[k])
            c_done[k] = start + cb
            net_free = c_done[k]
        k = t - 1  # expert FFN of chunk t-1
        if 0 <= k < c:
            start = max(comp_free, d_done[k])
            e_done[k] = start + e
            comp_free = e_done[k]
        k = t  # dispatch of chunk t
        if 0 <= k < c:
            d_done[k] = net_free + d
            net_free = d_done[k]
    total = serial_prefix + c_done[c - 1]
    exposed = max(total - serial_prefix - compute, 0.0)
    return total, exposed


def decode_tick_phase(
    dispatch: float,
    expert: float,
    combine: float,
    chunks: int,
    *,
    attn: float = 0.0,
    prefill_compute: float = 0.0,
) -> tuple[float, float]:
    """Event timeline of ONE serving decode tick per MoE layer (DESIGN.md §9).

    A decode tick is the same dispatch -> expert-FFN -> combine phase the
    trainer pipelines, at live-batch scale, with two serving-specific terms:
    ``attn`` is the tick's un-overlappable decode-attention prefix (the
    router needs its output), and ``prefill_compute`` is the interleaved
    chunked-prefill work scheduled INTO this tick — compute with no ordering
    dependence on the decode a2a, so it joins the hideable window.  That is
    the scheduling argument for chunked prefill: tiny decode batches leave
    the network exposed, and the prefill chunk is what widens the compute
    window the a2a hides under.

    Returns ``(total_seconds, exposed_comm_seconds)`` with the same
    invariants as :func:`pipelined_phase` (``chunks=1`` with no prefill is
    the additive serial tick).
    """
    return pipelined_phase(
        dispatch, expert + prefill_compute, combine, chunks, serial_prefix=attn
    )


def ring_gather_leaf(
    x, mesh, fsdp_axis: str, fsdp_dim: int, model_axis: str | None = None,
    model_dim: int | None = None,
):
    """Gather one FSDP-sharded weight leaf with the explicit AllGather ring.

    This is the FSDP-prefetch building block: the transformer scan issues it
    for block l+1's FFN weights while block l computes, so the gather's
    collective_permute hops overlap the FFN instead of XLA's on-demand
    gather serializing at first use.  ``fsdp_dim`` is the leaf dim sharded
    over ``fsdp_axis`` (gathered away); ``model_dim``'s sharding over
    ``model_axis`` is preserved through the shard_map.  Leaves the leaf
    untouched when the dim does not divide the axis (matching how the init
    specs shard conditionally).
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.core.commruntime import AllGather, CommSpec

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    fsize = sizes.get(fsdp_axis, 1)
    if fsize <= 1 or x.shape[fsdp_dim] % fsize != 0:
        return x
    in_axes: list = [None] * x.ndim
    in_axes[fsdp_dim] = fsdp_axis
    if (
        model_axis is not None
        and model_dim is not None
        and sizes.get(model_axis, 1) > 1
        and x.shape[model_dim] % sizes[model_axis] == 0
    ):
        in_axes[model_dim] = model_axis
    out_axes = list(in_axes)
    out_axes[fsdp_dim] = None
    ag = AllGather(CommSpec(axis=fsdp_axis, axis_size=fsize), impl="ring")
    fn = jax.shard_map(
        lambda v: ag(v, axis=fsdp_dim),
        mesh=mesh,
        in_specs=P(*in_axes),
        out_specs=P(*out_axes),
        check_vma=False,
    )
    return fn(x)
