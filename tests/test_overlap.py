"""Overlap engine (repro.core.overlap, DESIGN.md §8): scheduler invariants,
the staged CollectiveOp surface, chunked-MoE bit-parity sweeps, the FSDP
prefetch path, netsim's hidden/exposed accounting, gradient compression
through the runtime, and wire-level re-addressing in the trainer."""

import dataclasses

import numpy as np
import pytest

from repro.core import overlap


# ---------------------------------------------------------------------------
# scheduler unit tests
# ---------------------------------------------------------------------------


def test_chunk_count_divisor():
    assert overlap.chunk_count(32, 4) == 4
    assert overlap.chunk_count(32, 5) == 4  # nearest divisor below
    assert overlap.chunk_count(30, 4) == 3
    assert overlap.chunk_count(7, 16) == 7
    assert overlap.chunk_count(7, 3) == 1
    assert overlap.chunk_count(8, 1) == 1


def test_software_pipeline_dataflow_and_order():
    """Each chunk flows through all stages in order; the global issue order
    is the skewed tick order with later stages drained first."""
    issued = []

    def stage(s):
        def run(prev, k):
            issued.append((s, k))
            return (prev or ()) + (s,)

        return run

    out = overlap.software_pipeline(3, [stage(0), stage(1), stage(2)])
    assert out == [(0, 1, 2)] * 3
    # tick t issues stage s of chunk t-s, deepest stage first
    assert issued == [
        (0, 0),
        (1, 0), (0, 1),
        (2, 0), (1, 1), (0, 2),
        (2, 1), (1, 2),
        (2, 2),
    ]
    assert overlap.software_pipeline(2, []) == [None, None]


def test_pipelined_phase_serial_equals_additive():
    total, exposed = overlap.pipelined_phase(3.0, 5.0, 2.0, 1, serial_prefix=1.0)
    assert total == pytest.approx(1.0 + 3.0 + 5.0 + 2.0)
    assert exposed == pytest.approx(5.0)  # all comm exposed


def test_pipelined_phase_invariants_sweep():
    rng = np.random.default_rng(0)
    for _ in range(200):
        d, e, cb, pre = rng.random(4) * 10
        serial = pre + d + e + cb
        prev_total = None
        for c in (1, 2, 4, 8, 16):
            total, exposed = overlap.pipelined_phase(d, e, cb, c, serial_prefix=pre)
            comm = d + cb
            # never exceeds the serial estimate, never undercuts either
            # resource's busy time
            assert total <= serial + 1e-9, (c, d, e, cb, pre)
            assert total >= pre + max(e, comm) - 1e-9
            assert -1e-9 <= exposed <= comm + 1e-9
            # hidden + exposed == comm by construction
            hidden = comm - exposed
            assert -1e-9 <= hidden <= comm + 1e-9
            if prev_total is not None:
                assert total <= prev_total + 1e-9  # more chunks never slower
            prev_total = total


def test_pipelined_phase_hides_comm_under_compute():
    # compute-dominated phase: almost all comm hides once chunked
    total, exposed = overlap.pipelined_phase(1.0, 10.0, 1.0, 8)
    assert exposed < 0.5
    assert total < 12.0 - 1.0  # strictly better than serial


# ---------------------------------------------------------------------------
# staged CollectiveOp surface
# ---------------------------------------------------------------------------


def test_a2a_stage_bytes_sum_to_op_bytes():
    from repro.core import commruntime as cr

    op = cr.AllToAll(cr.CommSpec(axis="model", axis_size=8, group_size=4))
    stages = op.stages()
    assert len(stages) == 2
    assert stages[0].link_class == "scale_up"
    assert stages[1].link_class == "scale_out"
    b = 4096.0
    full = op.bytes_on_link(b)
    s0 = stages[0].bytes_on_link(b)
    s1 = stages[1].bytes_on_link(b)
    assert s0.scale_up == pytest.approx(full.scale_up)
    assert s0.scale_out == 0.0
    assert s1.scale_out == pytest.approx(full.scale_out)
    assert s0.total + s1.total == pytest.approx(full.total)
    # flat spec: one stage that IS the op
    flat = cr.AllToAll(cr.CommSpec(axis="model", axis_size=8)).stages()
    assert len(flat) == 1
    assert flat[0].bytes_on_link(b).total == pytest.approx(
        cr.AllToAll(cr.CommSpec(axis="model", axis_size=8)).bytes_on_link(b).total
    )
    # cost-only hierarchical spec (netsim's) still exposes both stages
    cost_only = cr.AllToAll(cr.CommSpec(axis=None, axis_size=32, group_size=8))
    assert len(cost_only.stages()) == 2


STAGED_A2A = """
import itertools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.commruntime import AllToAll, CommSpec
from repro.launch.mesh import make_mesh

PDEV = 8
mesh = make_mesh((PDEV,), ('model',))
x = jax.random.normal(jax.random.PRNGKey(0), (PDEV * PDEV, 4))
rng = np.random.default_rng(1)
perms = [None, tuple(rng.permutation(PDEV).tolist())]

for g, dp, sp in itertools.product((1, 2, 4), perms, perms):
    op = AllToAll(CommSpec(axis='model', axis_size=PDEV, group_size=g,
                           dest_perm=dp, src_perm=sp))
    def whole(v):
        return op(v.reshape(PDEV, 4)).reshape(1, PDEV * 4)
    def staged(v):
        y = v.reshape(PDEV, 4)
        for s in op.stages():
            y = s(y)
        return y.reshape(1, PDEV * 4)
    run = lambda f: np.asarray(jax.shard_map(f, mesh=mesh, in_specs=P('model'),
                                         out_specs=P('model'))(x))
    np.testing.assert_array_equal(run(staged), run(whole)), (g, dp, sp)
print('STAGED_A2A_OK')
"""


def test_a2a_stages_compose_bit_identical(multidevice):
    """Composing AllToAll.stages() in order == the whole lowering, bitwise,
    for group sizes {1,2,4} x non-identity dest/src wire perms."""
    out = multidevice(STAGED_A2A, devices=8, timeout=900)
    assert "STAGED_A2A_OK" in out


RING_RS = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.commruntime import ReduceScatter, CommSpec, ring_reduce_scatter
from repro.launch.mesh import make_mesh

for p in (2, 4, 8):
    mesh = make_mesh((p,), ('model',))
    # integer payload: the ring's hop-ordered sum must be EXACTLY psum_scatter
    xi = jnp.arange(p * p * 3, dtype=jnp.int32).reshape(p * p, 3)
    ring_op = ReduceScatter(CommSpec(axis='model', axis_size=p), impl='ring')
    flat_op = ReduceScatter(CommSpec(axis='model', axis_size=p), impl='flat')
    run = lambda op, v: np.asarray(jax.shard_map(
        lambda u: op(u), mesh=mesh, in_specs=P('model'), out_specs=P('model'),
        check_vma=False)(v))
    np.testing.assert_array_equal(run(ring_op, xi), run(flat_op, xi)), p
    # f32: allclose (ring order vs XLA tree order)
    xf = jax.random.normal(jax.random.PRNGKey(p), (p * p * 2, 3))
    np.testing.assert_allclose(run(ring_op, xf), run(flat_op, xf),
                               rtol=1e-5, atol=1e-5)
    # non-zero scatter_dim: per-device distinct [2, 2p] inputs reduced over
    # the axis and scattered along dim 1
    xt = jax.random.normal(jax.random.PRNGKey(p + 10), (p * 2, 2 * p))
    a = np.asarray(jax.shard_map(lambda u: ring_reduce_scatter(u, 'model', scatter_dim=1),
                             mesh=mesh, in_specs=P('model', None),
                             out_specs=P(None, 'model'), check_vma=False)(xt))
    b = np.asarray(jax.shard_map(
        lambda u: jax.lax.psum_scatter(u, 'model', scatter_dimension=1, tiled=True),
        mesh=mesh, in_specs=P('model', None), out_specs=P(None, 'model'),
        check_vma=False)(xt))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
print('RING_RS_OK')
"""


def test_ring_reduce_scatter_matches_psum_scatter(multidevice):
    """The Permute-ring ReduceScatter stepping == lax.psum_scatter (exact for
    ints, allclose for f32) across axis sizes and scatter dims."""
    out = multidevice(RING_RS, devices=8, timeout=900)
    assert "RING_RS_OK" in out


# ---------------------------------------------------------------------------
# chunked MoE parity (tentpole acceptance)
# ---------------------------------------------------------------------------


def test_moe_chunked_parity_single_device():
    """P=1 leg of the sweep: overlap_chunks {1,2,4} x dropless/capacity are
    bit-identical to the serial path."""
    import jax
    import jax.numpy as jnp

    from repro.models import moe as moe_mod
    from repro.models.config import ModelConfig, MoEConfig
    from repro.parallel.sharding import make_plan

    plan = make_plan(None)
    for dispatch in ("dropless", "capacity"):
        cfg = ModelConfig(
            "t", "moe", 2, 32, 4, 2, 64, 128, dtype="float32",
            moe=MoEConfig(num_experts=4, top_k=2, d_ff=48, capacity_factor=4.0,
                          a2a_group=2, dispatch=dispatch),
        )
        params, _ = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, plan)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
        base, st0 = moe_mod.moe_apply(params, x, cfg, plan, backend="mixnet")
        for c in (2, 4):
            cfg_c = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, overlap_chunks=c)
            )
            out, st = moe_mod.moe_apply(params, x, cfg_c, plan, backend="mixnet")
            np.testing.assert_array_equal(np.asarray(base), np.asarray(out))
            assert float(st.dropped_fraction) == float(st0.dropped_fraction)


CHUNK_SWEEP = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.models import moe as moe_mod
from repro.models.config import ModelConfig, MoEConfig
from repro.parallel.sharding import make_plan
from repro.launch.mesh import make_mesh

# P sweep over model sizes {2, 4, 8} on 8 forced devices (P=1 runs
# in-process in the test file); chunks {1, 2, 4} x dropless/capacity,
# hierarchical a2a groups, and a non-identity wire perm on the P=4 mesh.
for shape, axes in (((4, 2), ('data', 'model')),
                    ((2, 4), ('data', 'model')),
                    ((8,), ('model',))):
    mesh = make_mesh(shape, axes)
    plan = make_plan(mesh)
    P_ = plan.model_size
    for dispatch in ('dropless', 'capacity'):
        cfg = ModelConfig('t', 'moe', 2, 32, 4, 2, 64, 128, dtype='float32',
                          moe=MoEConfig(num_experts=8, top_k=2, d_ff=48,
                                        capacity_factor=8.0, a2a_group=2,
                                        dispatch=dispatch))
        params, _ = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, plan)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
        with jax.set_mesh(mesh):
            base, st0 = jax.jit(lambda p, v: moe_mod.moe_apply(
                p, v, cfg, plan, mesh=mesh, backend='mixnet'))(params, x)
            for c in (2, 4):
                cfg_c = dataclasses.replace(
                    cfg, moe=dataclasses.replace(cfg.moe, overlap_chunks=c))
                out, st = jax.jit(lambda p, v: moe_mod.moe_apply(
                    p, v, cfg_c, plan, mesh=mesh, backend='mixnet'))(params, x)
                assert (np.asarray(base) == np.asarray(out)).all(), (P_, dispatch, c)
                assert float(st.dropped_fraction) == float(st0.dropped_fraction)

# Non-identity wire perm leg: physical weights laid out for device map D,
# logical placement identity; every chunk count must match the einsum
# reference AND the serial wire path bitwise.
mesh = make_mesh((2, 4), ('data', 'model'))
plan = make_plan(mesh)
plan1 = make_plan(None)
for dispatch in ('dropless', 'capacity'):
    cfg = ModelConfig('t', 'moe', 2, 32, 4, 2, 64, 128, dtype='float32',
                      moe=MoEConfig(num_experts=8, top_k=2, d_ff=48,
                                    capacity_factor=8.0, a2a_group=2,
                                    dispatch=dispatch))
    params, _ = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, plan)
    params1, _ = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, plan1)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
    ref, _ = moe_mod.moe_apply(params1, x, cfg, plan1, backend='einsum')
    D = np.array([2, 3, 1, 0])
    Dinv = np.argsort(D)
    ev, epd = 8, 2
    t_ = np.arange(ev)
    inv_phi = Dinv[t_ // epd] * epd + t_ % epd
    pw = dict(params)
    for wname in ('w_in', 'w_gate', 'w_out'):
        pw[wname] = params[wname][inv_phi]
    wire = jnp.asarray(D, jnp.int32)
    with jax.set_mesh(mesh):
        outs = {}
        for c in (1, 2, 4):
            cfg_c = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, overlap_chunks=c))
            ow, _ = jax.jit(lambda p, v, w: moe_mod.moe_apply(
                p, v, cfg_c, plan, mesh=mesh, backend='mixnet',
                wire_perm=w))(pw, x, wire)
            outs[c] = np.asarray(ow)
            assert float(jnp.max(jnp.abs(ow - ref))) < 1e-5, (dispatch, c)
        assert (outs[2] == outs[1]).all() and (outs[4] == outs[1]).all(), dispatch
    # decode after a wire-level reconfig: S=1 auto-routes to dense_decode,
    # which must compose the wire perm into the slot addressing to hit the
    # physically-resident weights (the analogue of PR 2's decode fix).
    x1 = x[:, :1]
    ref1, _ = moe_mod.moe_apply(params1, x1, cfg, plan1, backend='einsum')
    with jax.set_mesh(mesh):
        od, _ = jax.jit(lambda p, v, w: moe_mod.moe_apply(
            p, v, cfg, plan, mesh=mesh, backend='mixnet', mode='decode',
            wire_perm=w))(pw, x1, wire)
    assert float(jnp.max(jnp.abs(od - ref1))) < 1e-5, dispatch
print('CHUNK_SWEEP_OK')
"""


def test_moe_chunked_parity_multidevice_sweep(multidevice):
    """Acceptance sweep: overlap_chunks {1,2,4} x dropless/capacity x
    P {2,4,8} x non-identity wire perms, bit-identical to the serial path."""
    out = multidevice(CHUNK_SWEEP, devices=8, timeout=900)
    assert "CHUNK_SWEEP_OK" in out


# ---------------------------------------------------------------------------
# netsim event timeline
# ---------------------------------------------------------------------------


def _sim(model, *, chunks, seed=7, delay=0.025):
    from repro.core.fabric import FabricConfig, make_fabric
    from repro.core.netsim import GateTraceGenerator, simulate_iteration

    m = dataclasses.replace(model, overlap_chunks=chunks)
    fab = make_fabric(
        "mixnet",
        FabricConfig(num_servers=16, link_gbps=400, reconfig_delay_s=delay),
    )
    trace = GateTraceGenerator(m.layers_per_stage, m.num_experts, seed=seed)
    return simulate_iteration(m, fab, trace, num_servers_region=4)


def test_netsim_hidden_plus_exposed_equals_additive_a2a():
    """Cross-check: the overlap split partitions the old additive a2a total
    exactly, at every chunk count."""
    from repro.configs.paper_models import MIXTRAL_8X7B

    model = dataclasses.replace(MIXTRAL_8X7B, num_blocks=8)
    for chunks in (1, 2, 4, 8):
        res = _sim(model, chunks=chunks)
        assert res.hidden_comm + res.exposed_comm == pytest.approx(res.a2a)
        assert res.hidden_comm >= 0 and res.exposed_comm >= 0
        bd = res.breakdown()
        assert "hidden_comm" in bd and "exposed_comm" in bd


def test_netsim_serial_chunks_reproduce_additive_schedule():
    """overlap_chunks=1 IS the pre-overlap additive model: zero hidden comm
    and total == compute + a2a composition (the old formula)."""
    from repro.configs.paper_models import MIXTRAL_8X7B

    model = dataclasses.replace(MIXTRAL_8X7B, num_blocks=8)
    res = _sim(model, chunks=1)
    assert res.hidden_comm == pytest.approx(0.0)
    assert res.exposed_comm == pytest.approx(res.a2a)
    m, p = model.num_microbatches, model.pp_degree
    stretch = (m + p - 1) / m
    compute = m * 3.0 * (
        model.attention_time() + model.expert_time()
    )
    expected = stretch * compute + res.a2a + res.reconfig_blocked + res.dp_allreduce
    assert res.total == pytest.approx(expected, rel=1e-9)


def test_netsim_overlap_hides_comm_and_never_exceeds_serial():
    """Acceptance: nonzero hidden_comm for a production-shape model at 25 ms
    OCS, and the overlapped total never exceeds the serial estimate."""
    from repro.configs.paper_models import MIXTRAL_8X7B

    model = dataclasses.replace(MIXTRAL_8X7B, num_blocks=8)
    serial = _sim(model, chunks=1)
    for chunks in (2, 4, 8):
        res = _sim(model, chunks=chunks)
        assert res.hidden_comm > 0.0, chunks
        assert res.total <= serial.total * (1 + 1e-9), chunks
    assert _sim(model, chunks=4).total < serial.total


def test_netsim_stage_bytes_match_trainer_scheduler_accounting():
    """The per-link bytes netsim reports come from the identical
    AllToAllStage.bytes_on_link the trainer-side scheduler consumes."""
    from repro.configs.paper_models import MIXTRAL_8X7B
    from repro.core import commruntime as cr
    from repro.core.fabric import FabricConfig, make_fabric

    model = dataclasses.replace(MIXTRAL_8X7B, num_blocks=8, overlap_chunks=4)
    res = _sim(model, chunks=4)
    fab = make_fabric("mixnet", FabricConfig(num_servers=16, link_gbps=400))
    op = cr.AllToAll(cr.CommSpec.from_fabric(fab, 4))
    phase = model.a2a_bytes_total() / 4
    expect = {}
    for st in op.stages():
        lb = st.bytes_on_link(phase)
        expect[st.link_class] = expect.get(st.link_class, 0.0) + getattr(
            lb, st.link_class
        )
    assert res.a2a_link_bytes == pytest.approx(expect)
    assert set(res.a2a_link_bytes) == {"scale_up", "scale_out"}


def test_netsim_dp_compress_prices_byte_savings():
    """dp_compress halves (bf16) the priced DP wire bytes through the same
    AllReduce accounting the trainer's compressed reduction uses."""
    from repro.configs.paper_models import MIXTRAL_8X7B
    from repro.core import commruntime as cr

    from repro.core.fabric import FabricConfig, make_fabric

    model = dataclasses.replace(MIXTRAL_8X7B, num_blocks=8)
    base = _sim(model, chunks=1)
    comp = _sim(dataclasses.replace(model, dp_compress=True), chunks=1)
    assert comp.dp_allreduce < base.dp_allreduce
    # the priced savings ARE the op's compress_ratio accounting: cost with
    # ratio r == cost of r x the bytes (int8 wire = 1/dtype_bytes)
    fab = make_fabric("mixnet", FabricConfig(num_servers=16, link_gbps=400))
    dp_op = cr.AllReduce(cr.CommSpec(
        axis=None, axis_size=8, group_size=8, outer_size=16
    ))
    dp_bytes = model.dp_gradient_bytes_per_server(8)
    ratio = 1.0 / model.dtype_bytes
    assert comp.dp_allreduce == pytest.approx(
        0.5 * dp_op.cost(fab, dp_bytes, compress_ratio=ratio)
    )
    assert dp_op.cost(fab, dp_bytes, compress_ratio=ratio) == pytest.approx(
        dp_op.cost(fab, dp_bytes * ratio)
    )
    # op-level: bytes_on_link scales identically
    op = cr.AllReduce(cr.CommSpec(axis="data", axis_size=8, group_size=8,
                                  outer_axis="pod", outer_size=4))
    b = 1e9
    assert op.bytes_on_link(b, compress_ratio=0.5).total == pytest.approx(
        0.5 * op.bytes_on_link(b).total
    )


# ---------------------------------------------------------------------------
# FSDP prefetch
# ---------------------------------------------------------------------------


FSDP_PREFETCH = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.data.pipeline import SyntheticLM
from repro.models.config import ModelConfig, MoEConfig
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import make_plan
from repro.train.train_step import init_all, make_train_step
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ('data', 'model'))
plan = make_plan(mesh)  # fsdp axis = data
opt = AdamWConfig(lr=1e-3)
data = SyntheticLM(64, 16, 8, seed=0)
b = next(data)
batch = {'tokens': jnp.asarray(b.tokens), 'labels': jnp.asarray(b.labels)}

# MoE (mixnet) and dense configs both run the double-buffered ring gather.
cfgs = [
    ModelConfig('moe', 'moe', 2, 32, 4, 2, 0, 64, dtype='float32', remat='none',
                moe=MoEConfig(num_experts=8, top_k=2, d_ff=32,
                              capacity_factor=2.0, backend='mixnet',
                              a2a_group=2)),
    ModelConfig('dense', 'dense', 2, 32, 4, 2, 64, 64, dtype='float32',
                remat='none'),
]
for cfg in cfgs:
    cfg_p = dataclasses.replace(cfg, fsdp_prefetch=True)
    params, specs, opt_state = init_all(jax.random.PRNGKey(0), cfg, plan, opt)
    opt_state2 = jax.tree.map(lambda a: a, opt_state)
    with jax.set_mesh(mesh):
        s0 = jax.jit(make_train_step(cfg, plan, opt, mesh=mesh))
        s1 = jax.jit(make_train_step(cfg_p, plan, opt, mesh=mesh))
        p0, o0, m0 = s0(params, opt_state, batch)
        p1, o1, m1 = s1(params, opt_state2, batch)
    np.testing.assert_allclose(float(m0['loss']), float(m1['loss']), rtol=1e-5)
    for a, r in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(r, np.float64),
                                   rtol=5e-4, atol=1e-5)
print('FSDP_PREFETCH_OK')
"""


def test_fsdp_prefetch_matches_auto_gather(multidevice):
    """The double-buffered ring prefetch of block l+1's FFN weights computes
    the same step as XLA's on-demand FSDP gather (MoE and dense)."""
    out = multidevice(FSDP_PREFETCH, devices=8, timeout=900)
    assert "FSDP_PREFETCH_OK" in out


# ---------------------------------------------------------------------------
# gradient compression through the runtime
# ---------------------------------------------------------------------------


DP_COMPRESS = """
import jax, jax.numpy as jnp, numpy as np
from repro.data.pipeline import SyntheticLM
from repro.models.config import ModelConfig, MoEConfig
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import make_plan
from repro.train.train_step import init_all, init_ef_residual, make_train_step
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ('data',))
plan = make_plan(mesh, fsdp=False)
cfg = ModelConfig('tiny-moe', 'moe', 2, 32, 4, 2, 0, 64, dtype='float32',
                  remat='none',
                  moe=MoEConfig(num_experts=4, top_k=2, d_ff=32,
                                capacity_factor=2.0, backend='einsum',
                                balance_loss=0.0, router_z_loss=0.0))
opt = AdamWConfig(lr=1e-3)
params, _, opt_state = init_all(jax.random.PRNGKey(0), cfg, plan, opt)
opt_state2 = jax.tree.map(lambda a: a, opt_state)
res = init_ef_residual(params, plan)
data = SyntheticLM(cfg.vocab_size, 16, 8, seed=0)
b = next(data)
batch = {'tokens': jnp.asarray(b.tokens), 'labels': jnp.asarray(b.labels)}
with jax.set_mesh(mesh):
    base = jax.jit(make_train_step(cfg, plan, opt, mesh=mesh, dp_comm='runtime'))
    comp = jax.jit(make_train_step(cfg, plan, opt, mesh=mesh, dp_comm='runtime',
                                   dp_compress=True))
    pb, ob, mb = base(params, opt_state, batch)
    pc, oc, mc, new_res = comp(params, opt_state2, batch, None, None, res)
# identical forward loss; params close (int8 mean with exact int32 sums)
np.testing.assert_allclose(float(mb['loss']), float(mc['loss']), rtol=1e-5)
for a, r in zip(jax.tree.leaves(pb), jax.tree.leaves(pc)):
    np.testing.assert_allclose(np.asarray(a, np.float64), np.asarray(r, np.float64),
                               rtol=2e-2, atol=2e-3)
# the residual captured this step's quantization error (nonzero, bounded)
rmax = max(float(jnp.max(jnp.abs(l))) for l in jax.tree.leaves(new_res))
assert 0.0 < rmax < 1.0, rmax

# error feedback keeps the long-run mean unbiased: iterate the compressed
# step on a FIXED batch and compare the parameter drift direction
p1, o1, r1 = params, jax.tree.map(lambda a: a, opt_state), res
with jax.set_mesh(mesh):
    for _ in range(3):
        p1, o1, m1, r1 = comp(p1, o1, batch, None, None, r1)
assert np.isfinite(float(m1['loss']))

# misconfigurations fail loudly
try:
    make_train_step(cfg, plan, opt, mesh=mesh, dp_compress=True)
    raise SystemExit('expected ValueError (compress without runtime)')
except ValueError:
    pass
try:
    make_train_step(cfg, plan, opt, mesh=mesh, dp_comm='runtime',
                    dp_compress=True, microbatches=2)
    raise SystemExit('expected ValueError (compress with microbatches)')
except ValueError:
    pass
print('DP_COMPRESS_OK')
"""


def test_dp_compress_through_runtime(multidevice):
    """Satellite: int8 + error-feedback gradient compression rides the
    runtime AllReduce's reduce-scatter stage."""
    out = multidevice(DP_COMPRESS, devices=8, timeout=900)
    assert "DP_COMPRESS_OK" in out


def test_compressed_hierarchical_psum_single_device_identity():
    import jax.numpy as jnp

    from repro.optim.compress import compressed_hierarchical_psum

    x = jnp.arange(8.0)
    out = compressed_hierarchical_psum(x, None, None)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    out, local = compressed_hierarchical_psum(x, None, None, with_local=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(local), np.asarray(x))


# ---------------------------------------------------------------------------
# wire-level re-addressing in the trainer
# ---------------------------------------------------------------------------


WIRE_TRAINER = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.core.controlplane import LayerPlan
from repro.data.pipeline import SyntheticLM
from repro.models.config import ModelConfig, MoEConfig
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import make_plan
from repro.train.train_step import loss_fn
from repro.train.trainer import Trainer, TrainerConfig
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ('data', 'model'))
plan = make_plan(mesh)
cfg = ModelConfig('tiny-moe8', 'moe', 2, 32, 4, 2, 0, 64, dtype='float32',
                  remat='none',
                  moe=MoEConfig(num_experts=8, top_k=2, d_ff=32,
                                capacity_factor=8.0, backend='mixnet',
                                a2a_group=2))
opt = AdamWConfig(lr=1e-3)
tcfg = TrainerConfig(total_steps=1, reconfig_every=1)

def make():
    return Trainer(cfg, opt, tcfg, plan, mesh=mesh, seed=0)

# two plans: layer 0 moves WHOLE device blocks (wire-eligible),
# layer 1 swaps slots across a block boundary (weight path)
block_perm = np.array([2, 3, 0, 1, 6, 7, 4, 5])   # devices 0<->1, 2<->3
slot_perm = np.array([2, 1, 0, 3, 4, 5, 6, 7])    # slot 0 <-> 2 (not a block move)
plans = [LayerPlan(0, True, perm=block_perm.copy()),
         LayerPlan(1, True, perm=slot_perm.copy())]

tr = make()
w0_before = np.asarray(tr.params['blocks']['0_global']['moe']['w_in'][0])
assert tr._wire_capable()
tr._apply_layer_plans(plans)
# layer 0 realized on the wire: weights untouched, device map installed
w0_after = np.asarray(tr.params['blocks']['0_global']['moe']['w_in'][0])
np.testing.assert_array_equal(w0_before, w0_after)
assert tr.wire_perm is not None
assert (tr.wire_perm[0] != np.arange(4)).any()
assert (tr.wire_perm[1] == np.arange(4)).all()
assert tr.wire_reconfig_count == 1

# reference trainer: identical plans, forced through the weight path
ref = make()
ref._wire_capable = lambda: False
ref._apply_layer_plans([LayerPlan(0, True, perm=block_perm.copy()),
                        LayerPlan(1, True, perm=slot_perm.copy())])
assert ref.wire_perm is None
np.testing.assert_array_equal(np.asarray(tr.expert_perm), np.asarray(ref.expert_perm))

data = SyntheticLM(cfg.vocab_size, 16, 4, seed=0)
b = next(data)
batch = {'tokens': jnp.asarray(b.tokens), 'labels': jnp.asarray(b.labels)}

def loss_of(t):
    wire = jnp.asarray(t.wire_perm, jnp.int32) if t.wire_perm is not None else None
    with jax.set_mesh(mesh):
        l, _ = jax.jit(lambda p, bt, pm, wr: loss_fn(
            p, bt, cfg, plan, mesh, pm, wr))(
            t.params, batch, jnp.asarray(t.expert_perm), wire)
    return float(l)

lw = loss_of(tr)
lr = loss_of(ref)
np.testing.assert_allclose(lw, lr, rtol=1e-5)

# a later NON-block plan on layer 0 must flush the wire perm into the gather
flush_perm = np.array([1, 0, 2, 3, 4, 5, 6, 7])   # slot 0 <-> 1, within a block
tr._apply_layer_plans([LayerPlan(0, True, perm=flush_perm.copy())])
ref._apply_layer_plans([LayerPlan(0, True, perm=flush_perm.copy())])
assert (tr.wire_perm[0] == np.arange(4)).all()   # flushed
np.testing.assert_array_equal(np.asarray(tr.expert_perm), np.asarray(ref.expert_perm))
np.testing.assert_allclose(loss_of(tr), loss_of(ref), rtol=1e-5)

# and training still runs through the installed wire perms
tr2 = make()
tr2._apply_layer_plans([LayerPlan(0, True, perm=block_perm.copy())])
with jax.set_mesh(mesh):
    log = tr2.train(iter(SyntheticLM(cfg.vocab_size, 16, 4, seed=0)))
assert np.isfinite([float(m['loss']) for m in log]).all()
print('WIRE_TRAINER_OK')
"""


def test_trainer_wire_readdressing_both_branches(multidevice):
    """Satellite: whole-device-block plans install wire perms (no weight
    gather); other plans gather weights, flushing any pending wire perm —
    both branches compute the same function."""
    out = multidevice(WIRE_TRAINER, devices=8, timeout=900)
    assert "WIRE_TRAINER_OK" in out
