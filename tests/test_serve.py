"""ServeEngine (DESIGN.md §9): workload determinism, decode-time
reconfiguration parity (bit-identical generation, single- and multi-device,
dropless and capacity), CommRuntime-consistent a2a accounting, checkpointed
placement state, and the priced netsim serving scenario."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core import commruntime as comm
from repro.core.controlplane import ControlPlane, LayerPlan
from repro.models.config import ModelConfig, MoEConfig
from repro.models.transformer import init_model
from repro.parallel.sharding import make_plan
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.workload import MIXES, WorkloadGenerator

PLAN = make_plan(None)


def moe_cfg(dispatch="dropless", decode_backend="dense", cf=8.0):
    return ModelConfig(
        "srv", "moe", 2, 32, 4, 2, 0, 64, dtype="float32", remat="none",
        moe=MoEConfig(num_experts=8, top_k=2, d_ff=32, capacity_factor=cf,
                      backend="mixnet", a2a_group=2, dispatch=dispatch,
                      decode_backend=decode_backend),
    )


def small_requests(gen, n, *, prompt_cap=20, out_cap=6):
    return [
        dataclasses.replace(
            r, prompt_len=min(r.prompt_len, prompt_cap),
            max_new_tokens=min(r.max_new_tokens, out_cap),
        )
        for r in gen.generate(n)
    ]


def build_engine(params, cfg, *, reconfig, prefill_chunk=0, num_devices=4,
                 reconfig_every=3):
    scfg = ServeConfig(
        slots=2, max_len=40, prefill_chunk=prefill_chunk,
        reconfig_every=(reconfig_every if reconfig else 0),
        reconfig_min_gain=0.0, num_devices=num_devices,
    )
    return ServeEngine(jax.tree.map(lambda a: a, params), cfg, PLAN, scfg)


# ---------------------------------------------------------------------------
# workload generator
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_workload_generator_valid_and_deterministic(mix):
    gen = WorkloadGenerator(mix, seed=7)
    reqs = gen.generate(64)
    m = MIXES[mix]
    arr = [r.arrival_s for r in reqs]
    assert arr == sorted(arr) and arr[0] >= 0.0
    for r in reqs:
        assert m.prompt_min <= r.prompt_len <= m.prompt_max
        assert m.out_min <= r.max_new_tokens <= m.out_max
        assert 0 <= r.region < m.num_regions
    # deterministic in seed, including prompt materialization
    reqs2 = WorkloadGenerator(mix, seed=7).generate(64)
    assert reqs == reqs2
    np.testing.assert_array_equal(
        gen.prompt_tokens(reqs[0]), WorkloadGenerator(mix, seed=7).prompt_tokens(reqs2[0])
    )
    # a different seed moves the stream
    assert WorkloadGenerator(mix, seed=8).generate(64) != reqs


def test_workload_region_prefix_encoded():
    gen = WorkloadGenerator("chat", seed=1, vocab_size=97)
    for r in gen.generate(16):
        assert gen.prompt_tokens(r)[0] == r.region % 97


# ---------------------------------------------------------------------------
# decode-time reconfiguration parity (the acceptance criterion)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dispatch", ["dropless", "capacity"])
def test_engine_reconfig_parity_single_device(dispatch):
    """A mixed workload served to completion with decode-time
    reconfiguration enabled generates BIT-identical tokens to the
    reconfiguration-off run under identical seeds."""
    cfg = moe_cfg(dispatch=dispatch)
    params, _ = init_model(jax.random.PRNGKey(0), cfg, PLAN)
    gen = WorkloadGenerator("chat", seed=3, vocab_size=cfg.vocab_size)
    reqs = small_requests(gen, 6)

    eng_on = build_engine(params, cfg, reconfig=True)
    rep_on = eng_on.run(reqs, gen)
    eng_off = build_engine(params, cfg, reconfig=False)
    rep_off = eng_off.run(reqs, gen)

    assert rep_on.completed == len(reqs) == rep_off.completed
    assert rep_on.reconfig_count > 0, "control loop never reconfigured"
    toks_on = {r.rid: r.out for r in eng_on.batcher.finished}
    toks_off = {r.rid: r.out for r in eng_off.batcher.finished}
    assert toks_on == toks_off
    # the placement actually moved experts
    assert (eng_on.controlplane.perm_stack() != eng_off.batcher.expert_perm).any() or (
        eng_on.controlplane.perm_stack()
        != np.tile(np.arange(8, dtype=np.int32), (2, 1))
    ).any()


def test_engine_chunked_prefill_reconfig_parity():
    """Chunked prefill interleaved into decode ticks preserves the parity
    guarantee (prefill chunks run under the same perm state)."""
    cfg = moe_cfg()
    params, _ = init_model(jax.random.PRNGKey(0), cfg, PLAN)
    gen = WorkloadGenerator("agentic", seed=5, vocab_size=cfg.vocab_size)
    reqs = small_requests(gen, 5)
    eng_on = build_engine(params, cfg, reconfig=True, prefill_chunk=8)
    eng_on.run(reqs, gen)
    eng_off = build_engine(params, cfg, reconfig=False, prefill_chunk=8)
    eng_off.run(reqs, gen)
    assert eng_on.controlplane.reconfig_count > 0
    assert {r.rid: r.out for r in eng_on.batcher.finished} == {
        r.rid: r.out for r in eng_off.batcher.finished
    }


SPARSE_SWEEP = """
import dataclasses
import jax, numpy as np
from repro.core.controlplane import LayerPlan
from repro.models.config import ModelConfig, MoEConfig
from repro.models.transformer import init_model
from repro.parallel.sharding import make_plan
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.workload import WorkloadGenerator
from repro.launch.mesh import make_mesh as _mm

P = %(P)d
mesh = _mm((P,), ("model",))
plan = make_plan(mesh)

for dispatch in ("dropless", "capacity"):
    cfg = ModelConfig(
        "srv", "moe", 2, 32, 4, 2, 0, 64, dtype="float32", remat="none",
        moe=MoEConfig(num_experts=8, top_k=2, d_ff=32, capacity_factor=8.0,
                      backend="mixnet", a2a_group=2, dispatch=dispatch,
                      decode_backend="sparse"),
    )
    params, _ = init_model(jax.random.PRNGKey(0), cfg, plan)
    gen = WorkloadGenerator("chat", seed=3, vocab_size=cfg.vocab_size)
    reqs = [dataclasses.replace(r, prompt_len=12, max_new_tokens=4)
            for r in gen.generate(3)]

    def run(reconfig):
        scfg = ServeConfig(slots=2, max_len=32,
                           reconfig_every=(2 if reconfig else 0),
                           reconfig_min_gain=0.0, num_devices=P)
        eng = ServeEngine(jax.tree.map(lambda a: a, params), cfg, plan, scfg,
                          mesh=mesh)
        with jax.set_mesh(mesh):
            if reconfig:
                # Force one whole-device-block plan: realized as a WIRE
                # re-address on the decode a2a (weights never move).
                epd = 8 // P
                block = np.arange(8).reshape(P, epd)
                block[[0, 1]] = block[[1, 0]]
                eng.apply_plans([
                    LayerPlan(l, True, perm=block.reshape(-1).copy())
                    for l in range(cfg.pattern_repeats)
                ])
                assert eng.applier.wire_reconfig_count > 0, "wire path not taken"
            rep = eng.run(reqs, gen)
        assert rep.completed == len(reqs)
        return eng, rep

    eng_on, rep_on = run(True)
    eng_off, rep_off = run(False)
    assert rep_on.reconfig_count > 0
    a = {r.rid: r.out for r in eng_on.batcher.finished}
    b = {r.rid: r.out for r in eng_off.batcher.finished}
    assert a == b, (dispatch, a, b)
    # sparse decode accounted nonzero a2a bytes through the CommRuntime
    assert rep_on.a2a_bytes > 0
print("SPARSE_SWEEP_OK_P%(P)d")
"""


@pytest.mark.parametrize("p", [2, 4, 8])
def test_sparse_decode_reconfig_parity_multidevice(multidevice, p):
    """P-device EP-sharded decode (the mixnet a2a runs every tick, wire
    perms re-address it): reconfiguration on vs off is bit-identical for
    dropless AND capacity dispatch."""
    out = multidevice(SPARSE_SWEEP % {"P": p}, devices=8, timeout=900)
    assert f"SPARSE_SWEEP_OK_P{p}" in out


# ---------------------------------------------------------------------------
# a2a accounting cross-check (engine <-> CommRuntime <-> netsim)
# ---------------------------------------------------------------------------


def test_engine_a2a_bytes_match_commruntime_accounting():
    cfg = moe_cfg()
    params, _ = init_model(jax.random.PRNGKey(0), cfg, PLAN)
    gen = WorkloadGenerator("chat", seed=2, vocab_size=cfg.vocab_size)
    reqs = small_requests(gen, 4)
    eng = build_engine(params, cfg, reconfig=True, prefill_chunk=4)
    rep = eng.run(reqs, gen)
    dtype_bytes = np.dtype(cfg.dtype).itemsize
    moe_layers = cfg.pattern_repeats  # one MoE block per repeat here
    expected = sum(
        moe_layers * comm.ep_alltoall_bytes(
            t.live + t.prefill_tokens, cfg.moe.top_k, cfg.d_model, dtype_bytes
        )
        for t in eng.tick_log
    )
    assert rep.a2a_bytes == expected > 0


def test_netsim_serving_a2a_bytes_match_commruntime_accounting():
    """The priced scenario's byte total is exactly the CommRuntime formula
    applied to every routed token (prefill + decode) — no private model."""
    from repro.configs.paper_models import MIXTRAL_8X7B
    from repro.core.fabric import FabricConfig, make_fabric
    from repro.core.netsim import simulate_serving

    model = dataclasses.replace(MIXTRAL_8X7B, num_blocks=8)
    fab = make_fabric("fat-tree", FabricConfig(num_servers=16, link_gbps=400))
    reqs = WorkloadGenerator("chat", seed=4).generate(12)
    res = simulate_serving(
        model, fab, mix="chat", num_requests=12, use_reconfig=False, seed=4
    )
    assert res.completed == len(reqs)
    routed = sum(r.prompt_len for r in reqs) + (res.tokens_out - len(reqs))
    expected = model.layers_per_stage * comm.ep_alltoall_bytes(
        routed, model.top_k, model.d_model, model.dtype_bytes
    )
    np.testing.assert_allclose(res.a2a_bytes_total, expected, rtol=1e-9)


# ---------------------------------------------------------------------------
# checkpointed placement state
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip_mid_reconfig_bit_identical(tmp_path):
    """Save mid-reconfiguration, restore into a FRESH server: the restored
    perm stack composes with the restored (permuted) weights, so the next
    tokens are bit-identical to the original server's."""
    cfg = moe_cfg()
    params, _ = init_model(jax.random.PRNGKey(0), cfg, PLAN)
    gen = WorkloadGenerator("chat", seed=9, vocab_size=cfg.vocab_size)
    warm = small_requests(gen, 3)

    eng = build_engine(params, cfg, reconfig=True, reconfig_every=2)
    eng.run(warm, gen)
    assert eng.controlplane.reconfig_count > 0
    stack = eng.controlplane.perm_stack()
    assert (stack != np.tile(np.arange(8, dtype=np.int32), (2, 1))).any()
    step = eng.save_checkpoint(str(tmp_path))

    fresh = build_engine(params, cfg, reconfig=True, reconfig_every=10**9)
    fresh.restore_checkpoint(str(tmp_path), step)
    np.testing.assert_array_equal(fresh.batcher.expert_perm, stack)

    probe = small_requests(WorkloadGenerator("chat", seed=11,
                                             vocab_size=cfg.vocab_size), 2)
    gen11 = WorkloadGenerator("chat", seed=11, vocab_size=cfg.vocab_size)
    # original server (reconfig loop frozen so no further plans land)
    eng.scfg.reconfig_every = 10**9
    eng.run(probe, gen11)
    fresh.run(probe, gen11)
    a = {r.rid: r.out for r in eng.batcher.finished if r.rid in {p.rid for p in probe}}
    b = {r.rid: r.out for r in fresh.batcher.finished}
    assert a == b

    # restoring placement into an engine without a control plane is an error
    bare = build_engine(params, cfg, reconfig=False)
    with pytest.raises(RuntimeError):
        bare.restore_checkpoint(str(tmp_path), step)


def test_controlplane_state_dict_validation():
    cp = ControlPlane(num_layers=2, num_experts=8, num_devices=4,
                      use_copilot=False)
    cp.apply(LayerPlan(0, True, perm=np.array([1, 0, 2, 3, 4, 5, 6, 7])))
    state = cp.state_dict()
    cp2 = ControlPlane(num_layers=2, num_experts=8, num_devices=4,
                      use_copilot=False)
    cp2.load_state_dict(state)
    np.testing.assert_array_equal(cp2.perm_stack(), cp.perm_stack())
    assert cp2.reconfig_count == cp.reconfig_count
    bad = dict(state, layer_perms=[[0, 0, 2, 3, 4, 5, 6, 7]] * 2)
    with pytest.raises(ValueError):
        cp2.load_state_dict(bad)
    with pytest.raises(ValueError):
        cp2.load_state_dict(dict(state, layer_perms=[[0, 1]]))


# ---------------------------------------------------------------------------
# priced serving scenario
# ---------------------------------------------------------------------------


def _serving(fabric_name, reconfig, **kw):
    from repro.configs.paper_models import MIXTRAL_8X7B
    from repro.core.fabric import FabricConfig, make_fabric
    from repro.core.netsim import simulate_serving

    model = dataclasses.replace(MIXTRAL_8X7B, num_blocks=8, overlap_chunks=4)
    fab = make_fabric(fabric_name, FabricConfig(num_servers=128, link_gbps=400))
    return simulate_serving(
        model, fab, mix="agentic", num_requests=32, use_reconfig=reconfig,
        seed=1, **kw,
    )


def test_netsim_serving_goodput_per_dollar_gate():
    """The acceptance gate: reconfigured-fabric goodput-per-dollar >= the
    static EPS baseline, with the 25 ms OCS fully amortized at the default
    serving cadence."""
    r_mix = _serving("mixnet", True)
    r_eps = _serving("fat-tree", False)
    assert r_mix.completed == r_mix.requests
    assert r_mix.reconfig_count > 0 and r_eps.reconfig_count == 0
    assert r_mix.goodput_per_mdollar >= r_eps.goodput_per_mdollar
    assert r_mix.reconfig_blocked_s == 0.0  # hidden in the window's compute
    for r in (r_mix, r_eps):
        assert 0.0 <= r.exposed_comm_fraction <= 1.0
        assert r.ttft_p99_s >= r.ttft_p50_s >= 0.0
        assert r.tpot_p99_s >= r.tpot_p50_s > 0.0


def test_netsim_serving_chunked_prefill_widens_hide_window():
    """Interleaved prefill compute joins the hideable window: with chunked
    overlap, a LARGER prefill budget never increases the exposed fraction."""
    lo = _serving("mixnet", False, prefill_chunk_tokens=32)
    hi = _serving("mixnet", False, prefill_chunk_tokens=512)
    assert hi.exposed_comm_fraction <= lo.exposed_comm_fraction + 1e-9


def test_netsim_serving_aggressive_reconfig_pays_blocking():
    """Fig 28's logic at serving cadence: reconfiguring every few ticks
    cannot hide the 25 ms OCS and stalls the pipe."""
    calm = _serving("mixnet", True)  # default cadence: fully hidden
    hot = _serving("mixnet", True, reconfig_every_ticks=4)
    assert calm.reconfig_blocked_s == 0.0
    assert hot.reconfig_blocked_s > 0.0
    assert hot.tpot_p50_s >= calm.tpot_p50_s
