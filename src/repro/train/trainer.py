"""Training loop wiring the whole system together:

  data -> jit(train_step) -> MoE telemetry -> MixNet control plane
  (observe -> end_step -> plan -> apply, repro.core.controlplane)
  -> checkpoint/restart -> straggler watchdog.

The control loop is the paper's runtime reconfiguration (Fig 20) at the
framework level, driven through the shared :class:`ControlPlane` engine:
every step the trainer feeds the realized per-layer expert loads to the
engine's monitor; every ``reconfig_every`` steps it asks for a *per-layer*
placement plan (the regional per-layer OCS cross-maps of §5.2, DESIGN.md
§3) and — only for layers whose predicted gain clears the permute cost —
gathers that layer's stacked expert weights into their new slots and
updates the router's per-layer slot map.  Training math is unchanged (the
paper: "MixNet does not alter the parallelization strategies... and does
not affect training accuracy").
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.controlplane import (
    ControlPlane,
    LayerPlan,
    PlacementApplier,
    permute_expert_weights,
)
from repro.obs import metrics, trace
from repro.models.config import ModelConfig
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import ShardingPlan, virtual_experts
from repro.train import checkpoint as ckpt
from repro.train.pp_step import make_pp_train_step
from repro.train.train_step import init_all, init_ef_residual, make_train_step

# permute_expert_weights moved to repro.core.controlplane (it is shared with
# the serving engine, DESIGN.md §9); re-exported here for API stability.
__all__ = ["TrainerConfig", "Trainer", "permute_expert_weights"]


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    log_every: int = 10
    ckpt_every: int = 0  # 0 = disabled
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_keep: int = 3
    ckpt_async: bool = True
    # MixNet runtime reconfiguration
    reconfig_every: int = 0  # 0 = disabled (paper-faithful needs >0)
    reconfig_min_gain: float = 0.05
    # DP gradient reduction: "auto" (XLA sharding propagation) or "runtime"
    # (explicit CommRuntime hierarchical all-reduce inside shard_map over the
    # batch axes — requires a DP-only mesh and an fsdp=False plan; see the
    # repro.train.train_step module docstring).
    dp_comm: str = "auto"
    # int8 + error-feedback gradient compression through the runtime
    # reduction (requires dp_comm="runtime"); the trainer carries the
    # per-shard residual state across steps.
    dp_compress: bool = False
    # Pipeline parallelism (DESIGN.md §13): pp_stages > 1 stacks block
    # repeats on a 'stage' mesh axis and runs the GPipe schedule
    # (repro.train.pp_step) with the MoE data plane live inside each stage;
    # params/checkpoints/placement stay in the canonical [repeats, ...]
    # layout.  num_microbatches also drives gradient accumulation for the
    # non-PP step.
    pp_stages: int = 1
    num_microbatches: int = 1
    # Cached autotuner (repro.core.autotune, DESIGN.md §13): when both are
    # set and the key is present in the cache file, the trainer replaces the
    # constant comm knobs (MoE overlap_chunks / dispatch mode, dp_compress
    # where the mesh allows) with the tuned winners before building the
    # step.  A cache miss keeps the configured constants and is surfaced as
    # a one-line warning plus an ``autotune.cache_miss`` counter — tuning is
    # done offline by the benchmark/netsim side, which shares the cache file.
    autotune_cache: str = ""
    autotune_key: str = ""
    # Straggler watchdog: warn when a step exceeds ema * factor.
    straggler_factor: float = 3.0


class Trainer:
    def __init__(
        self,
        cfg: ModelConfig,
        opt_cfg: AdamWConfig,
        tcfg: TrainerConfig,
        plan: ShardingPlan,
        *,
        mesh=None,
        seed: int = 0,
    ):
        if tcfg.autotune_cache and tcfg.autotune_key:
            from repro.core import autotune

            tuned = autotune.load_cached(tcfg.autotune_cache, tcfg.autotune_key)
            if tuned is not None:
                cfg, tcfg = autotune.apply_to_trainer(cfg, tcfg, tuned)
            else:
                metrics.counter("autotune.cache_miss").inc()
                print(
                    f"[trainer] autotune cache miss: key {tcfg.autotune_key!r} "
                    f"not in {tcfg.autotune_cache} — using configured constants"
                )
        self.cfg = cfg
        self.opt_cfg = opt_cfg
        self.tcfg = tcfg
        self.plan = plan
        self.mesh = mesh
        key = jax.random.PRNGKey(seed)
        self.params, self.specs, self.opt_state = init_all(
            key, cfg, plan, opt_cfg, mesh=mesh
        )
        if tcfg.pp_stages > 1:
            if tcfg.dp_comm != "auto" or tcfg.dp_compress:
                raise ValueError(
                    "pp_stages > 1 composes with dp_comm='auto' only (the "
                    "runtime DP reduction needs a DP-only mesh)"
                )
            step = make_pp_train_step(
                cfg, plan, opt_cfg, mesh,
                pp_stages=tcfg.pp_stages,
                microbatches=tcfg.num_microbatches,
                block_specs=self.specs["blocks"],
            )
        else:
            step = make_train_step(
                cfg, plan, opt_cfg, mesh=mesh,
                microbatches=tcfg.num_microbatches, dp_comm=tcfg.dp_comm,
                dp_compress=tcfg.dp_compress,
            )
        self.step_fn = jax.jit(step, donate_argnums=(0, 1))
        self.ef_residual = (
            init_ef_residual(self.params, plan) if tcfg.dp_compress else None
        )
        self.step = 0
        self.metrics_log: list[dict] = []
        self._ema_step_time: float | None = None
        self.straggler_events = 0
        self._tr = trace.default()
        self._tid: int | None = None
        _m = metrics.default()
        self._m_steps = _m.counter("train.steps")
        self._m_tokens = _m.counter("train.tokens")
        self._m_stragglers = _m.counter("train.stragglers")
        self._m_step_time = _m.histogram("train.step_time_s")

        # MixNet control plane (only meaningful for MoE archs).
        self.controlplane: ControlPlane | None = None
        self._applier: PlacementApplier | None = None
        self.expert_perm = None
        if cfg.is_moe and tcfg.reconfig_every:
            ev, r = virtual_experts(cfg.moe.num_experts, plan.model_size)
            self.controlplane = ControlPlane(
                num_layers=cfg.pattern_repeats,
                num_experts=cfg.moe.num_experts,
                num_devices=max(plan.model_size, 1),
                replication=r,
                min_gain_fraction=tcfg.reconfig_min_gain,
            )
            self._applier = PlacementApplier(
                self.controlplane, model_size=max(plan.model_size, 1)
            )
            self.expert_perm = self.controlplane.perm_stack()
        self.reconfig_count = 0

    # Wire-level re-addressing state: [L, P] per-layer device maps for plans
    # realized on the a2a wire instead of by weight gathers (lives on the
    # shared PlacementApplier, DESIGN.md §3/§9).
    @property
    def wire_perm(self) -> np.ndarray | None:
        return self._applier.wire_perm if self._applier is not None else None

    @property
    def wire_reconfig_count(self) -> int:
        return self._applier.wire_reconfig_count if self._applier is not None else 0

    # -- checkpoint/restart ---------------------------------------------------
    def maybe_restore(self) -> bool:
        last = ckpt.latest_step(self.tcfg.ckpt_dir)
        if last is None:
            return False
        state = ckpt.restore(
            self.tcfg.ckpt_dir, last, {"params": self.params, "opt": self.opt_state}
        )
        self.params, self.opt_state = state["params"], state["opt"]
        # Placement state rides the manifest: restore it WITH the weights or
        # the router would address pre-reconfiguration slots (DESIGN.md §9).
        extra = ckpt.load_extra(self.tcfg.ckpt_dir, last)
        if self._applier is not None and "placement" in extra:
            self._applier.load_state_dict(extra["placement"])
            self.expert_perm = self.controlplane.perm_stack()
            self.reconfig_count = self.controlplane.reconfig_count
        self.step = last
        return True

    def _checkpoint(self):
        tree = {"params": self.params, "opt": self.opt_state}
        extra = (
            {"placement": self._applier.state_dict()}
            if self._applier is not None
            else None
        )
        if self.tcfg.ckpt_async:
            ckpt.save_async(
                self.tcfg.ckpt_dir, self.step, tree, keep=self.tcfg.ckpt_keep,
                extra=extra,
            )
        else:
            ckpt.save(
                self.tcfg.ckpt_dir, self.step, tree, keep=self.tcfg.ckpt_keep,
                extra=extra,
            )

    # -- observability ---------------------------------------------------------
    def _track_id(self) -> int:
        if self._tid is None:
            self._tid = self._tr.track("trainer")
        return self._tid

    # -- MixNet reconfiguration ------------------------------------------------
    def _wire_capable(self) -> bool:
        """Wire re-addressing needs the mixnet data plane and a control-plane
        device space that IS the model axis (one slot block per device)."""
        cp = self.controlplane
        p = max(self.plan.model_size, 1)
        return (
            self.cfg.moe is not None
            and self.cfg.moe.backend == "mixnet"
            and p > 1
            and cp is not None
            and cp.num_devices == p
        )

    def _apply_layer_plans(self, plans: list[LayerPlan]) -> bool:
        """Actuate per-layer placement plans through the shared
        :class:`PlacementApplier` (wire re-address for whole-device-block
        plans, weight gather otherwise — DESIGN.md §3)."""
        # Rebind when the engine was swapped after construction (tests inject
        # custom-region ControlPlanes directly onto the trainer).
        if self._applier is None or self._applier.cp is not self.controlplane:
            self._applier = PlacementApplier(
                self.controlplane, model_size=max(self.plan.model_size, 1)
            )
        ap = self._applier
        # Re-evaluated per call: tests toggle _wire_capable on the instance.
        ap.wire_capable = self._wire_capable()
        tid = self._track_id() if self._tr.enabled else None
        with self._tr.span(
            "train.reconfig", tid=tid, cat="reconfig", step=self.step
        ) as sp:
            # Adam moments move with their expert weights, so re-placement
            # leaves the optimizer math unchanged.
            self.params, changed = ap.apply(
                self.params, plans,
                states=(self.opt_state["mu"], self.opt_state["nu"]),
            )
            sp.set(
                applied=bool(changed),
                plans=sum(1 for p in plans if p.reconfigure),
            )
        if changed:
            self.expert_perm = self.controlplane.perm_stack()
            self.reconfig_count = self.controlplane.reconfig_count
        return changed

    def _reconfigure_step(self, expert_load: np.ndarray):
        """Drive one step of the Fig 20 loop through the shared engine.

        ``expert_load``: [repeats, E] realized loads from the last step.
        """
        cp = self.controlplane
        for layer in range(expert_load.shape[0]):
            cp.observe(layer, expert_load[layer])
        cp.end_step()
        if self.step % self.tcfg.reconfig_every:
            return
        self._apply_layer_plans([cp.plan(layer) for layer in range(cp.num_layers)])

    def fail_device(self, device: int) -> None:
        """§5.4 failover: re-home the failed device's experts onto backup
        slots through the identical decide/apply path as a routine
        reconfiguration; subsequent plans keep only cold experts there."""
        if self.controlplane is None:
            raise RuntimeError(
                "no control plane configured (MoE arch + reconfig_every > 0 required)"
            )
        self._apply_layer_plans(self.controlplane.fail_device(device))

    def restore_device(self, device: int) -> None:
        if self.controlplane is None:
            raise RuntimeError(
                "no control plane configured (MoE arch + reconfig_every > 0 required)"
            )
        self.controlplane.restore_device(device)

    # -- main loop ---------------------------------------------------------------
    def train(self, data_iter) -> list[dict]:
        t = self.tcfg
        while self.step < t.total_steps:
            batch_np = next(data_iter)
            batch = {
                "tokens": jnp.asarray(batch_np.tokens),
                "labels": jnp.asarray(batch_np.labels),
            }
            perm = (
                jnp.asarray(self.expert_perm)
                if self.expert_perm is not None
                else None
            )
            wire = (
                jnp.asarray(self.wire_perm, jnp.int32)
                if self.wire_perm is not None
                else None
            )
            tid = self._track_id() if self._tr.enabled else None
            t0 = time.perf_counter()
            with self._tr.span(
                "train.step", tid=tid, cat="train", step=self.step + 1
            ) as sp:
                if self.tcfg.dp_compress:
                    self.params, self.opt_state, step_metrics, self.ef_residual = (
                        self.step_fn(
                            self.params, self.opt_state, batch, perm, wire,
                            self.ef_residual,
                        )
                    )
                else:
                    self.params, self.opt_state, step_metrics = self.step_fn(
                        self.params, self.opt_state, batch, perm, wire
                    )
                step_metrics = {
                    k: np.asarray(v) for k, v in step_metrics.items()
                }
                sp.set(loss=float(step_metrics.get("loss", 0.0)))
            dt = time.perf_counter() - t0
            self._m_steps.inc()
            self._m_tokens.inc(float(batch_np.tokens.size))
            self._m_step_time.observe(dt)
            # Straggler watchdog (mitigation = flag + report; a real cluster
            # deployment feeds this to the job scheduler for hot-sparing).
            if self._ema_step_time is not None and dt > t.straggler_factor * self._ema_step_time:
                self.straggler_events += 1
                self._m_stragglers.inc()
                if self._tr.enabled:
                    self._tr.instant(
                        "train.straggler", tid=tid, cat="train",
                        step=self.step + 1, dt_s=dt, ema_s=self._ema_step_time,
                    )
            self._ema_step_time = (
                dt if self._ema_step_time is None else 0.9 * self._ema_step_time + 0.1 * dt
            )
            self.step += 1
            step_metrics["step"] = self.step
            step_metrics["step_time_s"] = dt
            self.metrics_log.append(step_metrics)

            if self.controlplane is not None and "expert_load" in step_metrics:
                self._reconfigure_step(np.asarray(step_metrics["expert_load"]))
            if t.ckpt_every and self.step % t.ckpt_every == 0:
                self._checkpoint()
        ckpt.wait_pending()
        return self.metrics_log
