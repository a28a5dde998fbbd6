"""Pipeline parallelism x the MixNet control plane: a mid-run expert
re-placement (perm + wire re-address) applied to a PP(S=2) trainer matches
the flat trainer applying the SAME plan, and the PP trainer's checkpoint
round-trips params + placement state (DESIGN.md §13).

Both tests force the reconfiguration with a fixed load matrix (the
injection pattern from test_train.py) so the two trainers compare the same
plan rather than two independently-observed ones."""

_COMMON = """
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import make_mesh
from repro.models.config import ModelConfig, MoEConfig
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import make_plan
from repro.train.trainer import Trainer, TrainerConfig

CFG = ModelConfig('tiny-moe', 'moe', 4, 32, 3, 1, 0, 64, head_dim=8,
                  dtype='float32', remat='none',
                  moe=MoEConfig(num_experts=4, top_k=2, d_ff=32,
                                capacity_factor=2.0, backend='mixnet',
                                overlap_chunks=2))
OPT = AdamWConfig(lr=1e-3)
# A co-located hot pair per layer (device 0 holds experts {0,1}, device 1
# holds {2,3} at identity placement) -> splitting it is a real gain; the
# hot device alternates so adjacent layers get different perms.
LOADS = np.array([[40.0, 40.0, 1.0, 1.0],
                  [1.0, 1.0, 40.0, 40.0],
                  [40.0, 40.0, 1.0, 1.0],
                  [1.0, 1.0, 40.0, 40.0]])

def make_trainer(pp, tmp=None, **tkw):
    if pp > 1:
        mesh = make_mesh((pp, 2), ('stage', 'model'))
        plan = make_plan(mesh, fsdp=False)
    else:
        mesh = make_mesh((2,), ('model',))
        plan = make_plan(mesh)
    kw = dict(total_steps=2, num_microbatches=2, reconfig_every=1000,
              reconfig_min_gain=0.01, pp_stages=pp, ckpt_every=0)
    if tmp:
        kw.update(ckpt_every=2, ckpt_dir=tmp, ckpt_async=False)
    kw.update(tkw)
    tcfg = TrainerConfig(**kw)
    tr = Trainer(CFG, OPT, tcfg, plan, mesh=mesh, seed=0)
    return tr, mesh

def run_chunk(tr, mesh, data, upto):
    tr.tcfg = dataclasses.replace(tr.tcfg, total_steps=upto)
    with jax.set_mesh(mesh):
        tr.train(data)
    return [float(m['loss']) for m in tr.metrics_log]

def force_reconfig(tr):
    # align the modulo gate, push the fixed plan, restore the step counter
    saved, tr.step = tr.step, tr.tcfg.reconfig_every
    tr._reconfigure_step(LOADS)
    tr.step = saved
"""

PARITY = _COMMON + """
tr_pp, mesh_pp = make_trainer(2)
tr_ref, mesh_ref = make_trainer(1)

d_pp = iter(SyntheticLM(CFG.vocab_size, 16, 4, seed=0))
d_ref = iter(SyntheticLM(CFG.vocab_size, 16, 4, seed=0))

run_chunk(tr_pp, mesh_pp, d_pp, 2)
run_chunk(tr_ref, mesh_ref, d_ref, 2)

force_reconfig(tr_pp)
force_reconfig(tr_ref)
# the forced plan actually moved experts, identically on both trainers
assert tr_pp.reconfig_count + tr_pp.wire_reconfig_count >= 1
assert tr_pp.reconfig_count == tr_ref.reconfig_count
assert tr_pp.wire_reconfig_count == tr_ref.wire_reconfig_count
perm_pp = np.asarray(tr_pp.expert_perm)
np.testing.assert_array_equal(perm_pp, np.asarray(tr_ref.expert_perm))
moved = (perm_pp != np.arange(CFG.moe.num_experts)).any()
wired = (tr_pp.wire_perm is not None
         and (np.asarray(tr_pp.wire_perm) != np.arange(2)).any())
assert moved or wired, (perm_pp, tr_pp.wire_perm)

l_pp = run_chunk(tr_pp, mesh_pp, d_pp, 4)
l_ref = run_chunk(tr_ref, mesh_ref, d_ref, 4)
np.testing.assert_allclose(l_pp, l_ref, rtol=1e-5)
for a, b in zip(jax.tree.leaves(tr_pp.params), jax.tree.leaves(tr_ref.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)
print('PP_RECONFIG_PARITY_OK')
"""


def test_pp_trainer_matches_flat_across_forced_reconfig(multidevice):
    out = multidevice(PARITY, devices=4, timeout=900)
    assert "PP_RECONFIG_PARITY_OK" in out


CKPT = _COMMON + """
import os, tempfile
tmp = tempfile.mkdtemp()

tr, mesh = make_trainer(2, tmp=tmp)
data = iter(SyntheticLM(CFG.vocab_size, 16, 4, seed=0))
run_chunk(tr, mesh, data, 2)
force_reconfig(tr)
run_chunk(tr, mesh, data, 4)  # checkpoints at steps 2 and 4
assert tr.reconfig_count + tr.wire_reconfig_count >= 1

tr2, mesh2 = make_trainer(2, tmp=tmp)
assert tr2.maybe_restore()
assert tr2.step == 4
# params AND placement state ride the same manifest (stage-stacking is a
# runtime view; the checkpoint stays in the canonical [repeats, ...] layout)
for a, b in zip(jax.tree.leaves(tr.params), jax.tree.leaves(tr2.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
np.testing.assert_array_equal(np.asarray(tr.expert_perm),
                              np.asarray(tr2.expert_perm))
assert tr2.reconfig_count == tr.reconfig_count
if tr.wire_perm is not None:
    np.testing.assert_array_equal(np.asarray(tr.wire_perm),
                                  np.asarray(tr2.wire_perm))

# one more step from the SAME restored state produces the same trajectory
d1 = iter(SyntheticLM(CFG.vocab_size, 16, 4, seed=7))
d2 = iter(SyntheticLM(CFG.vocab_size, 16, 4, seed=7))
l1 = run_chunk(tr, mesh, d1, 5)[-1]
l2 = run_chunk(tr2, mesh2, d2, 5)[-1]
np.testing.assert_allclose(l1, l2, rtol=1e-6)
print('PP_CKPT_OK')
"""


def test_pp_trainer_checkpoint_roundtrip_with_placement(multidevice):
    out = multidevice(CKPT, devices=4, timeout=900)
    assert "PP_CKPT_OK" in out
