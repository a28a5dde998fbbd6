"""Pipeline-parallel train step (repro.train.pp_step): gradient parity
against the non-PP step for S in {1,2,4} x P, with and without an applied
placement plan (expert/wire perms), on 8 fake devices.

The acceptance bar (DESIGN.md §13): the PP step's *math* is bit-identical
to the non-PP step — asserted by running both steps un-jitted (op-by-op
execution, no cross-program fusion) and requiring exact equality of the
updated params, loss, and gate telemetry.  Two sources of 1-ulp noise are
outside the math's control and get their own (far tighter than repo
standard) bars:

* whole-program jit: XLA fuses the two differently-shaped programs
  differently (reductions folded into different producers), perturbing
  single elements at the 1-ulp level -> jitted cross-checks use the
  repo's 1e-5 tolerance;
* P > 1 meshes: changing the stage count changes the device layout the
  model-axis reductions run over (the reduce-scatter adjoint of the
  sequence all_gather reassociates differently), perturbing ~1 element
  in a few thousand at ~1e-11 abs even un-jitted -> the P>1 tiers use
  rtol=1e-6/atol=1e-10 ("tight": two decades below repo tolerance, two
  above the observed noise floor).  P = 1 is the true bitwise tier.
"""

_COMMON = """
import jax, jax.numpy as jnp, numpy as np
from repro.models.config import ModelConfig, MoEConfig
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import make_plan
from repro.train.train_step import init_all, make_train_step
from repro.train.pp_step import make_pp_train_step
from repro.launch.mesh import make_mesh

# heads deliberately NOT divisible by the model axis (attention inside a PP
# stage computes replicated on the gathered sequence; keep the non-PP
# reference on the same no-TP-attention path).
CFG = ModelConfig('tiny-moe', 'moe', 4, 32, 3, 1, 0, 64, head_dim=8,
                  dtype='float32', remat='none',
                  moe=MoEConfig(num_experts=4, top_k=2, d_ff=32,
                                capacity_factor=2.0, backend='mixnet',
                                overlap_chunks=2))
OPT = AdamWConfig(lr=1e-3)
B, T = 4, 16

def batch_for(seed=0):
    k = jax.random.PRNGKey(seed)
    tok = jax.random.randint(k, (B, T), 0, CFG.vocab_size)
    lab = jnp.roll(tok, -1, axis=1)
    return {'tokens': tok, 'labels': lab}

def fresh_state():
    return init_all(jax.random.PRNGKey(0), CFG, make_plan(None), OPT)[::2]

def run_pp(s, p, m, perm=None, wire=None, seed=0, jit=False):
    mesh = make_mesh((s, p), ('stage', 'model'))
    plan = make_plan(mesh, fsdp=False)
    params, opt_state = fresh_state()
    with jax.set_mesh(mesh):
        step = make_pp_train_step(
            CFG, plan, OPT, mesh, pp_stages=s, microbatches=m)
        if jit:
            step = jax.jit(step)
        out = step(params, opt_state, batch_for(seed), perm, wire)
        out = jax.tree.map(np.asarray, out)
    return out

def run_ref(p, m=1, perm=None, wire=None, seed=0, jit=False):
    mesh = make_mesh((p,), ('model',))
    plan = make_plan(mesh)
    params, opt_state = fresh_state()
    with jax.set_mesh(mesh):
        step = make_train_step(CFG, plan, OPT, mesh=mesh, microbatches=m)
        if jit:
            step = jax.jit(step)
        out = step(params, opt_state, batch_for(seed), perm, wire)
        out = jax.tree.map(np.asarray, out)
    return out

def check(tag, a, b, mode):
    # mode: 'exact' (bitwise), 'tight' (1-ulp mesh-layout noise only), or
    # 'close' (repo tolerance, for jitted cross-checks).
    pa, _, ma = a
    pb, _, mb = b
    la, lb = jax.tree.leaves(pa), jax.tree.leaves(pb)
    assert len(la) == len(lb)
    if mode == 'exact':
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(x, y, err_msg=tag)
        np.testing.assert_array_equal(ma['loss'], mb['loss'], err_msg=tag)
        np.testing.assert_array_equal(ma['expert_load'], mb['expert_load'],
                                      err_msg=tag)
    else:
        rtol, atol = (1e-6, 1e-10) if mode == 'tight' else (1e-5, 1e-6)
        for x, y in zip(la, lb):
            np.testing.assert_allclose(x, y, rtol=rtol, atol=atol,
                                       err_msg=tag)
        np.testing.assert_allclose(ma['loss'], mb['loss'], rtol=rtol,
                                   err_msg=tag)
        np.testing.assert_allclose(ma['expert_load'], mb['expert_load'],
                                   rtol=rtol, atol=max(atol, 1e-6),
                                   err_msg=tag)
    print(tag, 'ok')
"""

# ---- Tier A: P = 1, bitwise vs the non-PP step for S in {1, 2, 4} ----------
P1 = _COMMON + """
ref = run_ref(1)
for s in (1, 2, 4):
    check(f'pp(S={s},P=1) == ref', run_pp(s, 1, 1), ref, 'exact')
# the jitted production path stays on the same answer to float tolerance
check('jit pp(S=2,P=1) ~= jit ref', run_pp(2, 1, 1, jit=True),
      run_ref(1, jit=True), 'close')
print('PP_P1_OK')
"""


def test_pp_bitwise_vs_ref_p1(multidevice):
    out = multidevice(P1, devices=8, timeout=900)
    assert "PP_P1_OK" in out


# ---- Tier B: S x P over 8 devices; PP(S,P) matches PP(1,P) and the
# auto-sharded non-PP reference to the tight (near-bit) bar ----------------
TP = _COMMON + """
for s, p in ((2, 4), (4, 2)):
    pp = run_pp(s, p, 1)
    check(f'pp(S={s},P={p}) ~= pp(S=1,P={p})', pp, run_pp(1, p, 1), 'tight')
    check(f'pp(S={s},P={p}) ~= ref(P={p})', pp, run_ref(p), 'tight')
check('jit pp(S=2,P=4) ~= jit ref(P=4)', run_pp(2, 4, 1, jit=True),
      run_ref(4, jit=True), 'close')
print('PP_TP_OK')
"""


def test_pp_bitwise_vs_pp1_and_ref_tp(multidevice):
    out = multidevice(TP, devices=8, timeout=900)
    assert "PP_TP_OK" in out


# ---- Tier C: microbatched schedule (M > S, M = S, warmup/drain live) and a
# forced placement plan: expert perm + wire re-address through the pipe ----
PERMS = _COMMON + """
# M=4 microbatches: the full pipeline (warmup + steady + drain) matches the
# S=1 schedule (same single value_and_grad over the whole batch).
check('pp(S=4,P=2,M=4) ~= pp(S=1,P=2,M=4)',
      run_pp(4, 2, 4), run_pp(1, 2, 4), 'tight')

# Applied placement plan: per-layer expert->slot perms + wire device maps
# must flow through the stage pipe exactly as through the flat step.
from repro.parallel.sharding import virtual_experts
reps, p = CFG.pattern_repeats, 4
ev, _ = virtual_experts(CFG.moe.num_experts, p)
rng = np.random.RandomState(0)
perm = jnp.asarray(np.stack([rng.permutation(ev) for _ in range(reps)]),
                   jnp.int32)
wire = jnp.asarray(np.stack([np.roll(np.arange(p), l % p)
                             for l in range(reps)]), jnp.int32)
pp = run_pp(2, p, 1, perm=perm, wire=wire)
check('pp(S=2,P=4,perm+wire) ~= pp(S=1,P=4,perm+wire)',
      pp, run_pp(1, p, 1, perm=perm, wire=wire), 'tight')
check('pp(S=2,P=4,perm+wire) ~= ref(P=4,perm+wire)',
      pp, run_ref(p, perm=perm, wire=wire), 'tight')
print('PP_PERMS_OK')
"""


def test_pp_microbatches_and_placement_plan(multidevice):
    out = multidevice(PERMS, devices=8, timeout=900)
    assert "PP_PERMS_OK" in out


def test_pp_misconfigurations_rejected():
    import jax

    from repro.models.config import ModelConfig, MoEConfig
    from repro.optim.adamw import AdamWConfig
    from repro.parallel.sharding import make_plan
    from repro.train.pp_step import make_pp_train_step
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = ModelConfig(
        "tiny-moe", "moe", 4, 32, 4, 2, 0, 64, dtype="float32", remat="none",
        moe=MoEConfig(num_experts=4, top_k=2, d_ff=32, backend="mixnet"),
    )
    opt = AdamWConfig(lr=1e-3)
    plan = make_plan(None)

    # no stage axis on the mesh
    try:
        make_pp_train_step(cfg, plan, opt, None, pp_stages=2)
        raise AssertionError("expected ValueError (no mesh)")
    except ValueError:
        pass
    # einsum backend has no per-device local body
    from jax.sharding import Mesh

    mesh = Mesh(jax.devices()[:1], ("stage",))
    import dataclasses

    bad = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, backend="einsum")
    )
    try:
        make_pp_train_step(bad, plan, opt, mesh, pp_stages=1)
        raise AssertionError("expected NotImplementedError (einsum)")
    except NotImplementedError:
        pass
    # repeats not divisible by stages
    try:
        make_pp_train_step(cfg, plan, opt, mesh, pp_stages=3)
        raise AssertionError("expected ValueError (3 stages, 4 repeats)")
    except ValueError:
        pass
    # expert replication (E < model axis) has no stage-body lowering
    import dataclasses as _dc

    rep_plan = _dc.replace(plan, model_axis="model", model_size=8)
    try:
        make_pp_train_step(cfg, rep_plan, opt, mesh, pp_stages=1)
        raise AssertionError("expected NotImplementedError (replication)")
    except NotImplementedError:
        pass
    # Trainer: PP composes with dp_comm='auto' only
    try:
        Trainer(cfg, opt, TrainerConfig(pp_stages=2, dp_comm="runtime"),
                plan, mesh=None)
        raise AssertionError("expected ValueError (dp_comm)")
    except ValueError:
        pass
