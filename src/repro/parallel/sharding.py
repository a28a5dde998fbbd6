"""Sharding rules: how each architecture maps onto the
``("pod", "data", "model")`` production mesh (DESIGN.md §5).

* batch            -> ("pod", "data")            (DP)
* params/opt-state -> "data" (+"pod")            (FSDP / ZeRO-3)
* "model" axis     -> the regional high-bandwidth domain:
    - dense layers: Megatron TP (heads / d_ff) when divisible,
      sequence-parallel activations between layers;
    - MoE layers: EP over (virtual) experts — the MixNet domain;
    - attention for head counts not divisible by the axis: sequence-sharded
      queries with gathered KV;
    - decode KV caches: sequence-sharded (flash-decoding style).
"""

from __future__ import annotations

import dataclasses

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = ["ShardingPlan", "make_plan", "virtual_experts"]


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    batch_axes: tuple  # axes sharding the batch dim, e.g. ("pod", "data")
    model_axis: str | None  # TP/EP axis name (None on single-device)
    model_size: int
    fsdp_axis: str | None  # params sharded over this axis too (ZeRO-3)
    data_size: int = 1

    # -- helpers used by layer init --------------------------------------
    def heads_axis(self, num_heads: int) -> str | None:
        """Shard a heads dim over the model axis only when divisible."""
        if self.model_axis and num_heads % max(self.model_size, 1) == 0:
            return self.model_axis
        return None

    def dim_axis(self, dim: int) -> str | None:
        if self.model_axis and dim % max(self.model_size, 1) == 0:
            return self.model_axis
        return None

    def fsdp_for(self, dim: int) -> str | None:
        if self.fsdp_axis and dim % max(self.data_size, 1) == 0:
            return self.fsdp_axis
        return None

    # -- activation specs ---------------------------------------------------
    def activation_spec(self, seq_shardable: bool = True) -> P:
        """Residual-stream spec [B, S, D]: batch over DP axes, seq over model
        (sequence parallelism) when the model axis exists."""
        seq = self.model_axis if seq_shardable else None
        return P(self.batch_axes or None, seq, None)

    def batch_spec(self) -> P:
        return P(self.batch_axes or None)

    def tokens_spec(self) -> P:
        return P(self.batch_axes or None, self.model_axis)

    def kv_cache_spec(self) -> P:
        """[B, S, Hkv, dh] — S sharded for flash-decoding."""
        return P(self.batch_axes or None, self.model_axis, None, None)

    def logits_spec(self) -> P:
        return P(self.batch_axes or None, None, self.model_axis)


def make_plan(mesh: Mesh | None, *, fsdp: bool = True) -> ShardingPlan:
    """Derive the plan from a mesh's named axes (or a no-op plan for None)."""
    if mesh is None or not mesh.axis_names:
        return ShardingPlan((), None, 1, None, 1)
    names = mesh.axis_names
    sizes = dict(zip(names, mesh.devices.shape))
    batch_axes = tuple(a for a in ("pod", "data") if a in names)
    model_axis = "model" if "model" in names else None
    data_size = 1
    for a in batch_axes:
        data_size *= sizes[a]
    return ShardingPlan(
        batch_axes=batch_axes,
        model_axis=model_axis,
        model_size=sizes.get("model", 1),
        fsdp_axis=("data" if (fsdp and "data" in names) else None),
        data_size=sizes.get("data", 1),
    )


def virtual_experts(num_experts: int, model_size: int) -> tuple[int, int]:
    """(virtual expert count, replication factor r).

    When E < model axis size, each expert is split into r = axis/E tensor
    shards ("virtual experts") so the expert dim shards exactly; tokens are
    dispatched to all r shards and the combine sums the partial products
    (row-split matmul identity).  When E >= axis, r = 1.
    """
    if model_size <= 1 or num_experts >= model_size:
        return num_experts, 1
    if model_size % num_experts != 0:
        raise ValueError(
            f"cannot factor {num_experts} experts over model axis {model_size}"
        )
    r = model_size // num_experts
    return num_experts * r, r


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def constrain(x, mesh: Mesh | None, spec: P):
    """with_sharding_constraint that degrades to identity without a mesh."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
