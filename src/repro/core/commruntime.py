"""CommRuntime: ONE topology-aware collective API shared by the trainer, the
flow-level simulator, and the control plane.

The paper's prototype rests on a "customized collective communication
runtime" that routes EP all-to-all through the regionally reconfigurable OCS
domain.  This module is that runtime's repo-level analogue: a declarative
:class:`CommSpec` (mesh axes + region/group factorization + runtime wire
permutations) and a family of :class:`CollectiveOp` objects, each carrying

  (a) an **executable lowering** — the ``shard_map`` program a TPU mesh runs
      (flat, hierarchical/delegation, or ring, selected per spec),
  (b) an **analytic cost function** — bytes per link class
      (:class:`LinkBytes`) and phase latency priced against a
      :class:`repro.core.fabric.Fabric`'s link rates, which
      :func:`repro.core.netsim.simulate_iteration` consumes instead of
      private formulas, and
  (c) a **reconfiguration hook** — ``op.reconfigure(dest_perm, src_perm)``
      re-addresses wire chunks after a ControlPlane plan without any caller
      changing (the analogue of pushing a new cross-map to the OCS; the same
      permutation also re-routes the op's demand matrix in the cost model).

The delegation structure (paper §5.3): intra-host gather over NVSwitch ->
inter-host transfer on the OCS circuits -> intra-host all-to-all -> scatter.
On a TPU mesh the same structure is a *two-stage factored all-to-all* over
the regional axis: the axis of size P is treated as a (G groups x H
per-group) grid; stage 1 exchanges within a group (the scale-up analogue),
stage 2 across groups (the scale-out analogue).  The composition is
bit-identical to the flat ``lax.all_to_all`` (tested), but each stage's
transfer only crosses one hierarchy level — which is what lets the compiler
schedule them on different link classes and overlap them.

DP gradients use the paper's hierarchical all-reduce (§5.3): reduce-scatter
inside the region, all-reduce across regions on the gateway shard,
all-gather back.

This module is the only home of the collective lowerings (the historical
``repro.core.collectives`` shim has been removed); build :class:`CommSpec`
+ ops (DESIGN.md §7).
"""

from __future__ import annotations

import dataclasses
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.obs import metrics

__all__ = [
    "CommSpec",
    "LinkBytes",
    "record_link_bytes",
    "CollectiveOp",
    "AllToAll",
    "AllReduce",
    "AllGather",
    "ReduceScatter",
    "Permute",
    "AllToAllStage",
    "ep_alltoall_bytes",
    "dp_gradient_bytes",
    "device_perm_from_slots",
    "fuse_pack",
    "fuse_unpack",
    # functional lowerings (the shard_map programs the ops execute)
    "flat_all_to_all",
    "hierarchical_all_to_all",
    "mixnet_all_to_all",
    "hierarchical_psum",
    "ring_all_gather",
    "ring_reduce_scatter",
]


# ---------------------------------------------------------------------------
# functional lowerings (the shard_map programs the ops execute)
# ---------------------------------------------------------------------------


def _grid_groups(p: int, group_size: int) -> tuple[list[list[int]], list[list[int]]]:
    if p % group_size != 0:
        raise ValueError(f"axis size {p} not divisible by group size {group_size}")
    g = p // group_size
    intra = [[gg * group_size + h for h in range(group_size)] for gg in range(g)]
    inter = [[gg * group_size + h for gg in range(g)] for h in range(group_size)]
    return intra, inter


def flat_all_to_all(x: jax.Array, axis_name: str) -> jax.Array:
    """Baseline single-shot all-to-all. ``x``: [P, ...] chunks by destination."""
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0)


def _a2a_scale_up(x: jax.Array, axis_name: str, group_size: int) -> jax.Array:
    """Delegation stage 1 — intra-group exchange (the scale-up/NVSwitch
    analogue): split/concat the h-chunk dim.  ``[P, ...]`` in and out."""
    p = lax.axis_size(axis_name)
    h = group_size
    g = p // h
    intra, _ = _grid_groups(p, h)
    xr = x.reshape(g, h, *x.shape[1:])
    z = lax.all_to_all(xr, axis_name, split_axis=1, concat_axis=1, axis_index_groups=intra)
    return z.reshape(x.shape)


def _a2a_scale_out(x: jax.Array, axis_name: str, group_size: int) -> jax.Array:
    """Delegation stage 2 — inter-group exchange (the scale-out/OCS
    analogue): split/concat the g-chunk dim.  ``[P, ...]`` in and out."""
    p = lax.axis_size(axis_name)
    h = group_size
    g = p // h
    _, inter = _grid_groups(p, h)
    xr = x.reshape(g, h, *x.shape[1:])
    w = lax.all_to_all(xr, axis_name, split_axis=0, concat_axis=0, axis_index_groups=inter)
    return w.reshape(x.shape)


def hierarchical_all_to_all(
    x: jax.Array, axis_name: str, group_size: int
) -> jax.Array:
    """Two-stage (delegation) all-to-all over a factored axis.

    Args:
      x: ``[P, ...]`` local chunks ordered by destination device on
        ``axis_name`` (device index = g * group_size + h).
      axis_name: mesh axis of size P = G * group_size.
      group_size: size of the scale-up (intra-host analogue) stage H.

    Returns:
      ``[P, ...]`` chunks ordered by source device — identical to
      :func:`flat_all_to_all`.  The two halves are exposed separately
      through :meth:`AllToAll.stages` so the overlap scheduler can run
      another chunk's compute between them.
    """
    p = lax.axis_size(axis_name)
    h = group_size
    if p == 1 or h == 1 or h >= p:
        return flat_all_to_all(x, axis_name)
    return _a2a_scale_out(
        _a2a_scale_up(x, axis_name, h), axis_name, h
    )


def mixnet_all_to_all(
    x: jax.Array,
    axis_name: str,
    group_size: int,
    *,
    dest_perm: jax.Array | None = None,
    src_perm: jax.Array | None = None,
) -> jax.Array:
    """Hierarchical all-to-all with an expert-placement permutation.

    ``dest_perm`` re-addresses outgoing chunks (the chunk physically sent to
    device ``k`` is the one logically addressed to ``dest_perm[k]``);
    ``src_perm`` restores the logical ordering of received chunks.  This is
    how a runtime-reconfigured placement is realized on the wire without
    touching the collective itself — the analogue of pushing a new cross-map
    to the OCS.
    """
    if dest_perm is not None:
        x = x[dest_perm]
    y = hierarchical_all_to_all(x, axis_name, group_size)
    if src_perm is not None:
        y = y[src_perm]
    return y


def hierarchical_psum(
    x: jax.Array,
    inner_axis: str,
    outer_axis: str | None = None,
    *,
    scatter_dim: int = 0,
) -> jax.Array:
    """Paper §5.3 hierarchical all-reduce.

    reduce-scatter over ``inner_axis`` (intra-host reduction to the gateway
    shard) -> all-reduce over ``outer_axis`` (the global ring over EPS) ->
    all-gather over ``inner_axis`` (broadcast back).  Cross-region bytes drop
    by a factor of the inner axis size versus a flat all-reduce.  Scalars and
    shapes the inner axis does not divide fall back to the flat psum.
    """
    inner = lax.axis_size(inner_axis)
    if inner == 1 or x.ndim == 0 or x.shape[scatter_dim] % inner != 0:
        y = lax.psum(x, inner_axis)
        return lax.psum(y, outer_axis) if outer_axis else y
    part = lax.psum_scatter(x, inner_axis, scatter_dimension=scatter_dim, tiled=True)
    if outer_axis is not None:
        part = lax.psum(part, outer_axis)
    return lax.all_gather(part, inner_axis, axis=scatter_dim, tiled=True)


def ring_all_gather(x: jax.Array, axis_name: str) -> jax.Array:
    """Explicit ring all-gather via collective_permute (comm/compute overlap
    building block for the perf path; semantically = lax.all_gather(tiled))."""
    p = lax.axis_size(axis_name)
    if p == 1:
        return x
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % p) for i in range(p)]

    def body(carry, _):
        block, rot = carry
        nxt = lax.ppermute(block, axis_name, perm)
        return (nxt, rot - 1), nxt

    (_, _), rest = lax.scan(body, (x, p - 1), None, length=p - 1)
    # rest[k] came from device (idx - 1 - k); roll into ascending device order.
    all_blocks = jnp.concatenate([x[None], rest], axis=0)  # [P, ...] by hop
    src = (idx - jnp.arange(p)) % p
    order = jnp.argsort(src)
    return all_blocks[order].reshape(p * x.shape[0], *x.shape[1:])


def ring_reduce_scatter(
    x: jax.Array, axis_name: str, *, scatter_dim: int = 0
) -> jax.Array:
    """Explicit ring reduce-scatter via collective_permute stepping.

    The partial destined for device ``d`` starts at ``d+1`` and rides the
    ring for P-1 hops, each holder adding its own chunk — the overlap
    building block (one :class:`Permute` hop per step interleaves with
    compute).  Numerically a sum of the same terms as
    ``lax.psum_scatter(tiled=True)`` in ring order (f32 summation order
    differs from XLA's tree, so equality is allclose, exact for ints).
    """
    p = lax.axis_size(axis_name)
    if p == 1:
        return x
    idx = lax.axis_index(axis_name)
    xm = jnp.moveaxis(x, scatter_dim, 0)
    if xm.shape[0] % p != 0:
        raise ValueError(
            f"dim {scatter_dim} ({x.shape[scatter_dim]}) not divisible by "
            f"axis size {p}"
        )
    chunks = xm.reshape(p, xm.shape[0] // p, *xm.shape[1:])
    perm = [(i, (i + 1) % p) for i in range(p)]
    # After t additions the partial this device holds is destined for
    # device (idx - t); take(t) is the local chunk for that destination.
    acc = chunks[(idx - 1) % p]
    for t in range(1, p):
        acc = lax.ppermute(acc, axis_name, perm) + chunks[(idx - 1 - t) % p]
    return jnp.moveaxis(acc, 0, scatter_dim) if scatter_dim else acc


# ---------------------------------------------------------------------------
# CommSpec — the declarative half of the runtime
# ---------------------------------------------------------------------------


def _as_tuple(perm) -> tuple[int, ...] | None:
    if perm is None:
        return None
    return tuple(int(i) for i in np.asarray(perm).tolist())


@dataclasses.dataclass(frozen=True)
class CommSpec:
    """Where a collective runs and how its axis factors into regions/groups.

    ``axis`` is the regional mesh axis the lowering runs over (``None`` for
    single-device or cost-only specs — the simulator prices transfers without
    a mesh).  ``axis_size = num_groups * group_size``: ``group_size`` is the
    scale-up stage width (the intra-host/NVSwitch analogue), groups exchange
    over the scale-out (OCS) stage.  ``outer_axis/outer_size`` name the
    cross-region domain hierarchical reductions ring over (the EPS fabric).

    ``dest_perm``/``src_perm`` are the runtime wire re-addressing state the
    ControlPlane installs: static tuples (hashable — specs can be jit
    constants) produced by :meth:`reconfigure`.
    """

    axis: str | None = None
    axis_size: int = 1
    group_size: int = 1
    outer_axis: str | None = None
    outer_size: int = 1
    dest_perm: tuple[int, ...] | None = None
    src_perm: tuple[int, ...] | None = None

    def __post_init__(self):
        if self.axis_size < 1 or self.group_size < 1 or self.outer_size < 1:
            raise ValueError(f"bad CommSpec sizes: {self}")
        if self.hierarchical and self.axis_size % self.group_size != 0:
            raise ValueError(
                f"axis size {self.axis_size} not divisible by group size "
                f"{self.group_size}"
            )
        for perm in (self.dest_perm, self.src_perm):
            if perm is not None and sorted(perm) != list(range(len(perm))):
                raise ValueError(f"not a permutation: {perm}")

    # -- factorization ------------------------------------------------------
    @property
    def hierarchical(self) -> bool:
        """True when the lowering runs the two-stage delegation grid."""
        return 1 < self.group_size < self.axis_size

    @property
    def num_groups(self) -> int:
        return self.axis_size // self.group_size if self.hierarchical else 1

    # -- constructors -------------------------------------------------------
    @classmethod
    def from_plan(cls, plan, *, group_size: int = 1) -> "CommSpec":
        """Spec for the trainer's regional (``model``) axis from a
        :class:`repro.parallel.sharding.ShardingPlan`.

        A ``group_size`` spanning the whole axis degrades to the flat
        lowering (a one-group hierarchy IS flat); a group that does not
        divide the axis is a misconfiguration and raises (via the spec
        validator), exactly like the pre-runtime ``_grid_groups`` did."""
        if plan.model_axis is None or plan.model_size <= 1:
            return cls(axis=None, axis_size=1)
        g = 1 if group_size >= plan.model_size else group_size
        return cls(
            axis=plan.model_axis,
            axis_size=plan.model_size,
            group_size=g,
        )

    @classmethod
    def for_grad_reduce(cls, plan, mesh) -> "CommSpec":
        """Spec for DP gradient reduction over the plan's batch axes:
        innermost batch axis = the region (reduce-scatter stage), outer batch
        axis = the cross-region ring."""
        axes = plan.batch_axes
        if mesh is None or not axes:
            return cls(axis=None, axis_size=1)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        inner = axes[-1]
        outer = axes[0] if len(axes) > 1 else None
        return cls(
            axis=inner,
            axis_size=sizes[inner],
            group_size=sizes[inner],
            outer_axis=outer,
            outer_size=sizes[outer] if outer else 1,
        )

    @classmethod
    def from_fabric(
        cls, fabric, num_servers_region: int | None = None
    ) -> "CommSpec":
        """Cost-only spec whose region/group factorization comes from the
        fabric topology: groups = servers of the OCS region, group width =
        the intra-server scale-up domain (NVSwitch)."""
        cfg = fabric.cfg
        region = num_servers_region or cfg.num_servers
        gps = max(cfg.gpus_per_server, 1)
        return cls(
            axis=None,
            axis_size=region * gps,
            group_size=gps,
            outer_size=max(cfg.num_servers, 1),
        )

    # -- reconfiguration hook ----------------------------------------------
    def reconfigure(self, dest_perm=None, src_perm=None) -> "CommSpec":
        """New spec with updated wire re-addressing (a ControlPlane plan
        lands here; pass ``None`` to clear a side)."""
        return dataclasses.replace(
            self, dest_perm=_as_tuple(dest_perm), src_perm=_as_tuple(src_perm)
        )


# ---------------------------------------------------------------------------
# bytes-on-link accounting
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LinkBytes:
    """Per-device wire bytes of one collective phase, split by link class.

    ``scale_up``: intra-group traffic (NVSwitch / the delegation's stage 1).
    ``scale_out``: inter-group regional traffic (the OCS circuits / stage 2).
    ``cross_region``: global traffic (the EPS fabric — DP ring, PP hops).
    """

    scale_up: float = 0.0
    scale_out: float = 0.0
    cross_region: float = 0.0

    @property
    def total(self) -> float:
        return self.scale_up + self.scale_out + self.cross_region


_LB_COUNTERS: dict[tuple[str, str], metrics.Counter] = {}
_LB_GENERATION = -1


def record_link_bytes(op: str, lb: LinkBytes) -> None:
    """Fold one priced phase's wire bytes into the process metrics registry
    as ``comm.link_bytes{link=...,op=...}`` (DESIGN.md §14).

    Called from every op's ``cost`` — each priced phase is one wire phase in
    the simulated/accounted timeline.  Children are cached per (op, link)
    tuple so the inner netsim loops pay one dict hit + one float add; the
    cache is invalidated when the registry is reset (its generation bumps)."""
    global _LB_GENERATION
    reg = metrics.default()
    if reg.generation != _LB_GENERATION:
        _LB_GENERATION = reg.generation
        _LB_COUNTERS.clear()
    for link, v in (
        ("scale_up", lb.scale_up),
        ("scale_out", lb.scale_out),
        ("cross_region", lb.cross_region),
    ):
        if v:
            c = _LB_COUNTERS.get((op, link))
            if c is None:
                c = _LB_COUNTERS[(op, link)] = reg.counter(
                    "comm.link_bytes", op=op, link=link
                )
            c.inc(v)


def ep_alltoall_bytes(
    tokens: int, top_k: int, d_model: int, dtype_bytes: int
) -> float:
    """Payload bytes of ONE EP all-to-all phase (whole EP group): every routed
    token copy carries its d_model activation row."""
    return float(tokens) * top_k * d_model * dtype_bytes


def dp_gradient_bytes(
    param_count: float,
    gpus_per_replica: int,
    gpus_per_server: int,
    dtype_bytes: int,
) -> float:
    """Gradient bytes one server contributes to the DP ring: each GPU holds
    params / (gpus per model replica); a server aggregates its GPUs' shards
    through the gateway (hierarchical all-reduce, §5.3)."""
    per_gpu = float(param_count) / max(gpus_per_replica, 1)
    return per_gpu * gpus_per_server * dtype_bytes


def device_perm_from_slots(
    slot_perm: np.ndarray, slots_per_device: int
) -> np.ndarray | None:
    """Collapse an expert-slot permutation to a device-level wire permutation.

    A ControlPlane placement plan permutes virtual expert slots; when the
    permutation moves whole device blocks, the wire chunks themselves can be
    re-addressed (``CommSpec.reconfigure``).  Returns ``None`` when slots
    cross device boundaries — those plans are realized by the router-side
    re-addressing instead, and the wire layout stays put.
    """
    slot_perm = np.asarray(slot_perm)
    if slot_perm.size % slots_per_device != 0:
        return None
    blocks = slot_perm.reshape(-1, slots_per_device)
    devs = blocks // slots_per_device
    if not (devs == devs[:, :1]).all():
        return None  # a device's slots scatter across devices
    within = blocks % slots_per_device
    if not (within == np.arange(slots_per_device)[None, :]).all():
        return None  # reordered within the block: not a pure device move
    return devs[:, 0].astype(np.int64)


# ---------------------------------------------------------------------------
# CollectiveOp protocol + ops
# ---------------------------------------------------------------------------


@runtime_checkable
class CollectiveOp(Protocol):
    """What every runtime collective carries (DESIGN.md §7).

    ``__call__``   — the executable shard_map lowering (per-device view).
    ``bytes_on_link`` — analytic per-device wire bytes by link class.
    ``cost``       — phase latency priced against a Fabric's link rates
                     (the function netsim consumes).
    ``reconfigure`` — install a ControlPlane plan's wire re-addressing.
    """

    spec: CommSpec

    def __call__(self, x, **kwargs): ...

    def bytes_on_link(self, nbytes: float) -> LinkBytes: ...

    def cost(self, fabric, *args, **kwargs) -> float: ...

    def reconfigure(self, dest_perm=None, src_perm=None) -> "CollectiveOp": ...


@dataclasses.dataclass(frozen=True)
class _OpBase:
    spec: CommSpec

    def reconfigure(self, dest_perm=None, src_perm=None):
        """Reconfiguration hook: same op, re-addressed wire chunks."""
        return dataclasses.replace(
            self, spec=self.spec.reconfigure(dest_perm, src_perm)
        )

    def _perms(self, dest_perm, src_perm):
        if dest_perm is None and self.spec.dest_perm is not None:
            dest_perm = jnp.asarray(self.spec.dest_perm)
        if src_perm is None and self.spec.src_perm is not None:
            src_perm = jnp.asarray(self.spec.src_perm)
        return dest_perm, src_perm


def _ids_to_lanes(ids: jax.Array, dtype) -> jax.Array:
    """Encode int32 metadata into exact small-integer lanes of the payload
    dtype.  Deliberately numeric, NOT a bitcast: arbitrary id bit patterns
    form float NaNs (e.g. the -1 sentinel -> 0xFFFF) which XLA backends may
    canonicalize in transit.  Byte-sized lanes are exact in every >=8-bit
    significand float.  Ids must lie in [-1, 2**16 - 2] for 16-bit payload
    dtypes ([-1, 2**24 - 2] for 32-bit)."""
    dtype = jnp.dtype(dtype)
    enc = ids + 1  # shift the -1 sentinel into the unsigned range
    if dtype.itemsize == 4:
        return enc.astype(dtype)[..., None]
    lo = (enc & 0xFF).astype(dtype)
    hi = ((enc >> 8) & 0xFF).astype(dtype)
    return jnp.stack([lo, hi], axis=-1)


def _lanes_to_ids(lanes: jax.Array, dtype) -> jax.Array:
    dtype = jnp.dtype(dtype)
    if dtype.itemsize == 4:
        return lanes[..., 0].astype(jnp.int32) - 1
    lo = lanes[..., 0].astype(jnp.int32)
    hi = lanes[..., 1].astype(jnp.int32)
    return lo + (hi << 8) - 1


def fuse_pack(payload: jax.Array, ids: jax.Array) -> jax.Array | None:
    """Pack int32 metadata into trailing exact lanes of ``payload``'s dtype
    (one wire tensor for a staged transfer).  Returns ``None`` when the
    payload dtype has no exact lane encoding (itemsize not 2/4) — callers
    fall back to the unfused pair."""
    if jnp.dtype(payload.dtype).itemsize not in (2, 4):
        return None
    lanes = lax.stop_gradient(_ids_to_lanes(ids, payload.dtype))
    return jnp.concatenate([payload, lanes], axis=-1)


def fuse_unpack(packed: jax.Array, d: int) -> tuple[jax.Array, jax.Array]:
    """Inverse of :func:`fuse_pack`: split a packed wire tensor back into
    (payload ``[..., d]``, int32 ids)."""
    return packed[..., :d], _lanes_to_ids(packed[..., d:], packed.dtype)


@dataclasses.dataclass(frozen=True)
class AllToAll(_OpBase):
    """EP all-to-all: flat or hierarchical/delegation per the spec.

    Lowering: ``x`` is the per-device ``[P, ...]`` send layout, chunks
    ordered by destination; returns ``[P, ...]`` ordered by source.  The
    spec's wire perms (or per-call overrides, for traced runtime values)
    re-address chunks exactly like an OCS cross-map push.

    ``lowering`` selects the *priced* wire schedule (the analytic side the
    netsim and the autotuner search over — DESIGN.md §13):

    * ``"hier"``  — the delegation lowering (default): per-server
      aggregation amortizes per-message overheads, the server-level demand
      matrix is what the fabric schedules.  This is the lowering
      ``__call__`` executes and the historical ``cost``.
    * ``"flat"``  — no in-server delegation: the same bytes cross the
      scale-out fabric but as ``group_size``x more (and smaller)
      per-GPU messages, so every remote destination pays the per-message
      propagation latency the delegation would have amortized.
    * ``"ring"``  — store-and-forward ring: R-1 sequential neighbor hops,
      each carrying the residual full payload over one p2p link.  Only
      competitive when the payload is tiny and latency dominates; the
      autotuner is expected to reject it at training payloads.
    """

    lowering: str = "hier"

    def __post_init__(self):
        if self.lowering not in ("hier", "flat", "ring"):
            raise ValueError(
                f"unknown a2a lowering {self.lowering!r}; "
                "expected 'hier', 'flat', or 'ring'")

    def __call__(self, x, *, dest_perm=None, src_perm=None):
        dest_perm, src_perm = self._perms(dest_perm, src_perm)
        if self.spec.axis is None or self.spec.axis_size <= 1:
            if dest_perm is not None:
                x = x[dest_perm]
            if src_perm is not None:
                x = x[src_perm]
            return x
        return mixnet_all_to_all(
            x, self.spec.axis, self.spec.group_size,
            dest_perm=dest_perm, src_perm=src_perm,
        )

    def fused(self, payload, ids, *, dest_perm=None, src_perm=None):
        """ONE packed wire transfer for a payload + its int32 metadata.

        ``payload``: ``[P, C, D]`` activations; ``ids``: ``[P, C]`` int32
        (e.g. destination-expert ids riding the same a2a; range per
        :func:`_ids_to_lanes`).  The metadata travels as exact trailing
        payload-dtype lanes, so the payload bytes move bit-identically to
        the unfused pair of transfers (tested) while the wire sees a single
        phase.  Metadata lanes carry no gradient.
        """
        packed = fuse_pack(payload, ids)
        if packed is None:
            return (
                self(payload, dest_perm=dest_perm, src_perm=src_perm),
                self(ids[..., None], dest_perm=dest_perm, src_perm=src_perm)[..., 0],
            )
        out = self(packed, dest_perm=dest_perm, src_perm=src_perm)
        return fuse_unpack(out, payload.shape[-1])

    # -- staged execution (the overlap scheduler's surface) ------------------
    def stages(self) -> tuple["AllToAllStage", ...]:
        """The lowering's wire phases as separately-callable stages.

        Hierarchical/delegation specs expose the scale-up and scale-out
        halves (stage 0 applies the spec's ``dest_perm``, the last stage the
        ``src_perm``); flat/degenerate specs expose one stage that IS the
        whole op.  Composing the stages in order is bit-identical to
        ``__call__`` — the split exists so the overlap engine
        (:mod:`repro.core.overlap`) can run another chunk's compute between
        a chunk's phases.  Each stage carries its own ``bytes_on_link``;
        the stage byte totals sum to the op's.
        """
        if self.spec.hierarchical:
            return (AllToAllStage(self, 0, 2), AllToAllStage(self, 1, 2))
        return (AllToAllStage(self, 0, 1),)

    # -- analytic side ------------------------------------------------------
    def bytes_on_link(self, nbytes: float) -> LinkBytes:
        """Wire bytes for ``nbytes`` of per-device send payload."""
        p = self.spec.axis_size
        if p <= 1:
            return LinkBytes()
        if not self.spec.hierarchical:
            return LinkBytes(scale_out=nbytes * (p - 1) / p)
        h = self.spec.group_size
        g = self.spec.num_groups
        return LinkBytes(
            scale_up=nbytes * (h - 1) / h,      # stage 1: intra-group
            scale_out=nbytes * (g - 1) / g,     # stage 2: across groups
        )

    def route_demand(self, demand: np.ndarray) -> np.ndarray:
        """Physical inter-server demand after the spec's wire re-addressing:
        the chunk logically bound for ``j`` lands on ``dest_perm``'s image —
        the cost-model half of the reconfiguration hook (``src_perm`` is a
        local reorder after receipt; it moves no wire bytes)."""
        if self.spec.dest_perm is None:
            return demand
        demand = np.asarray(demand)
        perm = np.asarray(self.spec.dest_perm)
        if perm.shape[0] != demand.shape[1]:
            raise ValueError(
                f"dest_perm length {perm.shape[0]} != demand dim {demand.shape[1]}"
            )
        return demand[:, perm]

    def cost(self, fabric, demand: np.ndarray) -> float:
        """Completion seconds of one a2a phase with ``demand`` bytes between
        servers, priced on ``fabric``'s link rates under this op's
        ``lowering`` (see the class docstring)."""
        demand = np.asarray(self.route_demand(demand))
        r = demand.shape[0]
        # Wire bytes this phase moves between servers (the diagonal stays
        # local) — the per-op ledger of DESIGN.md §14.
        offdiag = float(demand.sum())
        if demand.ndim == 2 and demand.shape[0] == demand.shape[1]:
            offdiag -= float(np.trace(demand))
        record_link_bytes("a2a", LinkBytes(scale_out=max(offdiag, 0.0)))
        if self.lowering == "ring" and r > 1:
            per_hop = float(
                max(demand.sum(axis=1).max(), demand.sum(axis=0).max())
            )
            return (r - 1) * fabric.p2p_time(per_hop)
        base = fabric.alltoall_time(demand)
        if self.lowering == "flat" and r > 1:
            # Same wire bytes, group_size x more messages: each GPU pays the
            # per-message latency for every remote server it talks to.
            msgs = max(self.spec.group_size, 1) * (r - 1)
            return base + msgs * fabric.cfg.propagation_delay_s
        return base


@dataclasses.dataclass(frozen=True)
class AllToAllStage:
    """One wire phase of an :class:`AllToAll` lowering (see
    :meth:`AllToAll.stages`).

    ``index``/``count`` identify the phase: for a 2-stage delegation spec,
    stage 0 is the scale-up exchange (and applies ``dest_perm``), stage 1
    the scale-out exchange (and applies ``src_perm``).  A 1-stage tuple's
    only member runs the whole op.  Inputs and outputs keep the op's
    ``[P, ...]`` layout so stages chain without reshapes.
    """

    op: AllToAll
    index: int
    count: int

    @property
    def link_class(self) -> str:
        """Which LinkBytes class this stage's traffic rides."""
        if self.count == 2 and self.index == 0:
            return "scale_up"
        return "scale_out"

    def __call__(self, x, *, dest_perm=None, src_perm=None):
        s = self.op.spec
        if self.count == 1:
            return self.op(x, dest_perm=dest_perm, src_perm=src_perm)
        dperm, sperm = self.op._perms(dest_perm, src_perm)
        if self.index == 0:
            if dperm is not None:
                x = x[dperm]
            if s.axis is None:  # cost-only spec: no wire to exchange on
                return x
            return _a2a_scale_up(x, s.axis, s.group_size)
        y = x if s.axis is None else _a2a_scale_out(x, s.axis, s.group_size)
        if sperm is not None:
            y = y[sperm]
        return y

    def bytes_on_link(self, nbytes: float) -> LinkBytes:
        """This stage's share of the op's wire bytes — the SAME per-stage
        accounting both the trainer's overlap scheduler and netsim's event
        timeline consume."""
        full = self.op.bytes_on_link(nbytes)
        if self.count == 1:
            return full
        if self.index == 0:
            return LinkBytes(scale_up=full.scale_up)
        return LinkBytes(scale_out=full.scale_out)


@dataclasses.dataclass(frozen=True)
class AllReduce(_OpBase):
    """Hierarchical all-reduce (§5.3): reduce-scatter over the region,
    all-reduce across regions on the gateway shard, all-gather back.

    ``compress=True`` routes the reduction through the int8 codec of
    :mod:`repro.optim.compress` — quantize against a pmax-shared scale, sum
    exactly in int32 through the same reduce-scatter/ring/all-gather stages,
    one shared dequantization — cutting wire bytes by ``dtype_bytes``x
    (error feedback lives with the caller, which holds per-shard residual
    state; see ``repro.train.train_step``).  The matching
    ``compress_ratio`` on :meth:`bytes_on_link`/:meth:`cost` is how netsim
    prices the identical savings.
    """

    def __call__(
        self, x, *, scatter_dim: int = 0, mean: bool = False,
        compress: bool = False,
    ):
        s = self.spec
        if s.axis is None and s.axis_size > 1:
            # A cost-only spec (e.g. netsim's fabric-derived one) prices
            # phases but names no mesh axis to reduce over — executing it
            # would silently return unreduced (and mis-scaled) data.
            raise ValueError(
                "cost-only AllReduce spec (axis=None, axis_size>1) has no "
                "executable lowering"
            )
        if compress:
            from repro.optim.compress import compressed_hierarchical_psum

            y = compressed_hierarchical_psum(
                x, s.axis, s.outer_axis, scatter_dim=scatter_dim
            )
        elif s.axis is None:
            y = lax.psum(x, s.outer_axis) if s.outer_axis else x
        else:
            y = hierarchical_psum(x, s.axis, s.outer_axis, scatter_dim=scatter_dim)
        if mean:
            y = y / float(max(s.axis_size, 1) * max(s.outer_size, 1))
        return y

    def compressed(self, x, *, scatter_dim: int = 0, mean: bool = False):
        """Error-feedback-aware compressed reduction: returns
        ``(reduced, local_decoded)`` where ``local_decoded`` (f32) is this
        shard's own decoded contribution — what the caller's residual
        subtracts (``repro.train.train_step``'s ``dp_compress`` path)."""
        from repro.optim.compress import compressed_hierarchical_psum

        s = self.spec
        if s.axis is None and s.axis_size > 1:
            raise ValueError(
                "cost-only AllReduce spec (axis=None, axis_size>1) has no "
                "executable lowering"
            )
        total, local = compressed_hierarchical_psum(
            x, s.axis, s.outer_axis, scatter_dim=scatter_dim, with_local=True
        )
        if mean:
            total = total / float(max(s.axis_size, 1) * max(s.outer_size, 1))
        return total, local

    def bytes_on_link(
        self, nbytes: float, *, compress_ratio: float = 1.0
    ) -> LinkBytes:
        """Wire bytes for ``nbytes`` of per-device reduction payload.
        ``compress_ratio`` scales the payload for the int8 path (e.g.
        1/dtype_bytes) with the SAME accounting the trainer's compressed
        reduction realizes."""
        nbytes = nbytes * compress_ratio
        i, o = self.spec.axis_size, self.spec.outer_size
        if i <= 1 and o <= 1:
            return LinkBytes()
        if o > 1:
            inner = 2.0 * nbytes * (i - 1) / i if i > 1 else 0.0
            ring = 2.0 * (nbytes / max(i, 1)) * (o - 1) / o
            return LinkBytes(scale_up=inner, cross_region=ring)
        return LinkBytes(cross_region=2.0 * nbytes * (i - 1) / i)

    def cost(
        self, fabric, bytes_per_server: float, num_servers: int | None = None,
        *, compress_ratio: float = 1.0,
    ) -> float:
        n = num_servers or (self.spec.outer_size if self.spec.outer_size > 1 else None)
        record_link_bytes(
            "allreduce",
            self.bytes_on_link(bytes_per_server, compress_ratio=compress_ratio),
        )
        return fabric.allreduce_time(bytes_per_server * compress_ratio, n)


@dataclasses.dataclass(frozen=True)
class AllGather(_OpBase):
    """All-gather over the regional axis; ``impl='ring'`` runs the explicit
    collective_permute ring (the comm/compute-overlap building block),
    ``impl='flat'`` the single-shot ``lax.all_gather``."""

    impl: str = "ring"

    def __call__(self, x, *, axis: int = 0, tiled: bool = True):
        s = self.spec
        if s.axis is None or s.axis_size <= 1:
            return x if tiled else jnp.expand_dims(x, axis)
        if self.impl == "ring" and tiled:
            if axis == 0:
                return ring_all_gather(x, s.axis)
            return jnp.moveaxis(
                ring_all_gather(jnp.moveaxis(x, axis, 0), s.axis), 0, axis
            )
        return lax.all_gather(x, s.axis, axis=axis, tiled=tiled)

    def bytes_on_link(self, nbytes: float) -> LinkBytes:
        """Wire bytes for ``nbytes`` of local shard: the shard transits every
        ring hop once."""
        p = self.spec.axis_size
        return LinkBytes(scale_out=nbytes * max(p - 1, 0))

    def cost(self, fabric, shard_bytes: float) -> float:
        p = self.spec.axis_size
        if p <= 1:
            return 0.0
        record_link_bytes("allgather", self.bytes_on_link(shard_bytes))
        return (p - 1) * fabric.p2p_time(shard_bytes)


@dataclasses.dataclass(frozen=True)
class ReduceScatter(_OpBase):
    """Tiled reduce-scatter over the regional axis (the hierarchical
    all-reduce's first phase, exposed for overlap scheduling).
    ``impl='ring'`` runs the explicit Permute-ring stepping
    (:func:`ring_reduce_scatter` — one collective_permute hop per step, the
    overlap building block); ``impl='flat'`` the single-shot
    ``lax.psum_scatter``.  Ring summation order differs from XLA's tree, so
    cross-impl equality is allclose (exact for integer payloads)."""

    impl: str = "flat"

    def __call__(self, x, *, scatter_dim: int = 0):
        s = self.spec
        if s.axis is None or s.axis_size <= 1:
            return x
        if x.shape[scatter_dim] % s.axis_size != 0:
            raise ValueError(
                f"dim {scatter_dim} ({x.shape[scatter_dim]}) not divisible by "
                f"axis size {s.axis_size}"
            )
        if self.impl == "ring":
            return ring_reduce_scatter(x, s.axis, scatter_dim=scatter_dim)
        return lax.psum_scatter(x, s.axis, scatter_dimension=scatter_dim, tiled=True)

    def bytes_on_link(self, nbytes: float) -> LinkBytes:
        p = self.spec.axis_size
        if p <= 1:
            return LinkBytes()
        return LinkBytes(scale_out=nbytes * (p - 1) / p)

    def cost(self, fabric, nbytes: float) -> float:
        p = self.spec.axis_size
        if p <= 1:
            return 0.0
        record_link_bytes("reducescatter", self.bytes_on_link(nbytes))
        return (p - 1) * fabric.p2p_time(nbytes / p)


@dataclasses.dataclass(frozen=True)
class Permute(_OpBase):
    """Point-to-point wire re-address with the SAME gather semantics as
    :class:`AllToAll`: after the hop, device ``k`` holds the payload of
    device ``perm[k]`` (default: the previous ring neighbour, i.e. a +1 ring
    shift of the blocks).  This is the primitive a ControlPlane plan
    actuates when it relocates whole device payloads (PP hops and
    expert-state migration ride it) — one ``dest_perm`` means one routing
    across the whole op family."""

    def __call__(self, x, *, perm=None):
        s = self.spec
        if s.axis is None or s.axis_size <= 1:
            return x
        if perm is None:
            perm = (
                s.dest_perm
                if s.dest_perm is not None
                else tuple((i - 1) % s.axis_size for i in range(s.axis_size))
            )
        # ppermute pairs are (source, dest): device k receives from perm[k].
        pairs = [(int(srcdev), k) for k, srcdev in enumerate(perm)]
        return lax.ppermute(x, s.axis, pairs)

    def bytes_on_link(self, nbytes: float) -> LinkBytes:
        if self.spec.axis_size <= 1:
            return LinkBytes()
        return LinkBytes(scale_out=nbytes)

    def cost(self, fabric, nbytes: float) -> float:
        if self.spec.axis_size <= 1:
            return 0.0
        record_link_bytes("permute", self.bytes_on_link(nbytes))
        return fabric.p2p_time(nbytes)
