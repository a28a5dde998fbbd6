"""Pipeline-parallel MoE training over CommRuntime: a 2-stage x 4-way
expert-parallel mesh on 8 forced host devices, with a live mid-run
reconfiguration (expert->slot perm + wire re-address) flowing through the
stage pipe.

Every step's loss is parity-checked against the flat (non-PP) train step
running the same schedule — the PP composition is a scheduling change, not
a math change (DESIGN.md §13).

    python examples/train_pp.py [--steps 4]

(no PYTHONPATH needed; the script forces 8 host devices before jax loads.)
"""

import argparse
import os
import sys

sys.path.insert(0, "src")

# Must happen before jax is imported anywhere.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.config import ModelConfig, MoEConfig
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import make_plan, virtual_experts
from repro.train.pp_step import make_pp_train_step
from repro.train.train_step import init_all, make_train_step

STAGES, EP = 2, 4

CFG = ModelConfig(
    name="pp-demo-moe",
    family="moe",
    num_layers=4,
    d_model=32,
    num_heads=3,
    num_kv_heads=1,
    d_ff=0,
    vocab_size=64,
    head_dim=8,
    dtype="float32",
    remat="none",
    moe=MoEConfig(num_experts=4, top_k=2, d_ff=32, capacity_factor=2.0,
                  backend="mixnet", overlap_chunks=2),
)
OPT = AdamWConfig(lr=1e-3)
B, T = 4, 16


def batch_for(step):
    k = jax.random.PRNGKey(step)
    tok = jax.random.randint(k, (B, T), 0, CFG.vocab_size)
    return {"tokens": tok, "labels": jnp.roll(tok, -1, axis=1)}


def plan_for_step(step):
    """A toy control-plane: from step 2 on, apply a per-layer expert->slot
    perm plus a rotate-by-one wire re-address (what ControlPlane.apply
    pushes during real training)."""
    if step < 2:
        return None, None
    reps = CFG.pattern_repeats
    ev, _ = virtual_experts(CFG.moe.num_experts, EP)
    rng = np.random.RandomState(step)
    perm = jnp.asarray(
        np.stack([rng.permutation(ev) for _ in range(reps)]), jnp.int32)
    wire = jnp.asarray(
        np.stack([np.roll(np.arange(EP), l % EP) for l in range(reps)]),
        jnp.int32)
    return perm, wire


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()
    if jax.device_count() < STAGES * EP:
        raise SystemExit(
            f"needs {STAGES * EP} devices, have {jax.device_count()} "
            "(is XLA_FLAGS already set in the environment?)")

    pp_mesh = make_mesh((STAGES, EP), ("stage", "model"))
    pp_plan = make_plan(pp_mesh, fsdp=False)
    ref_mesh = make_mesh((EP,), ("model",))
    ref_plan = make_plan(ref_mesh)

    params, _, opt_state = init_all(
        jax.random.PRNGKey(0), CFG, make_plan(None), OPT)
    ref_params, ref_opt = jax.tree.map(jnp.copy, (params, opt_state))

    print(f"== PP(S={STAGES}) x EP({EP}) on {jax.device_count()} host "
          f"devices, microbatches=2, vs the flat EP({EP}) step ==")
    with jax.set_mesh(pp_mesh):
        pp_step = jax.jit(make_pp_train_step(
            CFG, pp_plan, OPT, pp_mesh, pp_stages=STAGES, microbatches=2))
    with jax.set_mesh(ref_mesh):
        ref_step = jax.jit(make_train_step(
            CFG, ref_plan, OPT, mesh=ref_mesh, microbatches=2))

    for step in range(args.steps):
        batch = batch_for(step)
        perm, wire = plan_for_step(step)
        with jax.set_mesh(pp_mesh):
            params, opt_state, m = pp_step(
                params, opt_state, batch, perm, wire)
        with jax.set_mesh(ref_mesh):
            ref_params, ref_opt, rm = ref_step(
                ref_params, ref_opt, batch, perm, wire)
        loss, ref_loss = float(m["loss"]), float(rm["loss"])
        np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
        tag = "  [reconfigured: perm+wire applied]" if perm is not None else ""
        print(f"step {step}: pp_loss={loss:.6f}  ref_loss={ref_loss:.6f}{tag}")

    # The whole trajectories agree, not just the scalar losses.
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
    print(f"PARITY_OK: {args.steps} steps, params match to 1e-5 "
          "across a live reconfiguration")


if __name__ == "__main__":
    main()
