"""Speculative decoding over the paged KV pool (DESIGN.md §11): draft/verify
tick parity vs serial decode (greedy AND sampled, dropless/capacity, reconfig
on/off, single- and multi-device), EOS landing at every position of a span,
draft-truncation page reclaim with a no-leak check after every tick, and the
netsim acceptance-vs-goodput/$ pricing."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.models import moe as moe_mod
from repro.models import routing
from repro.models.config import ModelConfig, MoEConfig
from repro.models.transformer import init_model
from repro.parallel.sharding import make_plan
from repro.serve.batching import Request
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.paged import PageAllocator

PLAN = make_plan(None)


def _dense_toy():
    cfg = ModelConfig("sp", "dense", 2, 32, 4, 2, 64, 64, dtype="float32",
                      remat="none")
    params, _ = init_model(jax.random.PRNGKey(0), cfg, PLAN)
    return cfg, params


def _moe_toy(dispatch="dropless", shared=1):
    cfg = ModelConfig(
        "sps", "moe", 2, 32, 4, 2, 0, 64, dtype="float32", remat="none",
        moe=MoEConfig(num_experts=8, top_k=2, d_ff=32,
                      num_shared_experts=shared, capacity_factor=8.0,
                      backend="mixnet", a2a_group=2, dispatch=dispatch),
    )
    params, _ = init_model(jax.random.PRNGKey(1), cfg, PLAN)
    return cfg, params


def _prompts(vocab, seed=3, sizes=(5, 9, 12, 7)):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab - 1, size=int(n)).astype(np.int32)
            for n in sizes]


def _serve(params, cfg, prompts, *, spec_k, sample=False, sample_seed=0,
           eos=None, max_new=8, reconfig=False, page_size=8, max_len=48,
           leak_check=True):
    scfg = ServeConfig(
        slots=2, max_len=max_len, prefill_chunk=0, paged=True,
        page_size=page_size, spec_k=spec_k, sample=sample,
        sample_seed=sample_seed,
        reconfig_every=(3 if reconfig else 0),
        reconfig_min_gain=0.0, num_devices=4,
    )
    eng = ServeEngine(jax.tree.map(lambda a: a, params), cfg, PLAN, scfg)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=100 + i, prompt=np.asarray(p, np.int32),
                           max_new_tokens=max_new, eos_id=eos))
    while eng.batcher.busy:
        eng.step()
        if leak_check:
            # satellite: the page pool must balance after EVERY tick —
            # truncation returns pages immediately, never strands them.
            eng.batcher.alloc.check_leaks()
    rep = eng.report(1.0)
    outs = {r.rid: list(r.out) for r in eng.batcher.finished}
    assert len(outs) == len(prompts)
    return outs, rep, eng


# ---------------------------------------------------------------------------
# draft-mode plumbing (config/routing level)
# ---------------------------------------------------------------------------


def test_effective_top_k_and_resolve():
    assert routing.effective_top_k(2, "off") == 2
    assert routing.effective_top_k(2, "topk1") == 1
    assert routing.effective_top_k(1, "topk1") == 1
    assert routing.effective_top_k(2, "shared_only") == 0
    dense_cfg, _ = _dense_toy()
    assert moe_mod.resolve_draft_mode(dense_cfg, "auto") == "off"
    shared_cfg, _ = _moe_toy(shared=1)
    assert moe_mod.resolve_draft_mode(shared_cfg, "auto") == "shared_only"
    plain_cfg = ModelConfig(
        "spt", "moe", 2, 32, 4, 2, 0, 64, dtype="float32", remat="none",
        moe=MoEConfig(8, 2, 32, capacity_factor=8.0, backend="mixnet",
                      a2a_group=2),
    )
    assert moe_mod.resolve_draft_mode(plain_cfg, "auto") == "topk1"
    dc = moe_mod.draft_config(shared_cfg, "auto")
    assert dc.moe.draft_mode == "shared_only"
    assert shared_cfg.moe.draft_mode == "off"  # original untouched
    with pytest.raises(ValueError):
        routing.compute_routing(
            jax.numpy.zeros((4, 8)), top_k=2, num_virtual=8, replication=1,
            draft_mode="shared_only")


# ---------------------------------------------------------------------------
# allocator: truncation returns pages immediately (satellite)
# ---------------------------------------------------------------------------


def test_allocator_truncate_frees_pages_and_restores_reservation():
    al = PageAllocator(slots=2, page_size=4, max_pages=6, num_pages=12,
                       prefix_cache=False)
    assert al.admit(0, np.arange(6), 8, 24) is not None
    al.ensure(0, 0, 14)  # 4 pages mapped (ceil(14/4))
    free_before = len(al._free)
    reserved_before = al._reserved[0]
    freed = al.truncate(0, 7)  # back to 2 pages
    assert freed == 2 and al.pages_reclaimed == 2 and al.draft_truncations == 1
    assert len(al._free) == free_before + 2
    assert al._reserved[0] == reserved_before + 2  # reservation restored
    assert (al.table[0, 2:] == -1).all() and (al.table[0, :2] >= 0).all()
    al.check_leaks()
    # the freed headroom is immediately re-mappable
    al.ensure(0, 7, 14)
    assert (al.table[0, :4] >= 0).all()
    al.check_leaks()
    # truncation inside the same page frees nothing but still counts
    assert al.truncate(0, 13) == 0
    assert al.draft_truncations == 2
    al.check_leaks()


# ---------------------------------------------------------------------------
# engine parity: spec vs serial, single device
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sample", [False, True])
def test_spec_parity_dense(sample):
    """Dense toy (draft == full model): spec emits the exact serial stream,
    greedy and sampled, with the pool balancing after every tick."""
    cfg, params = _dense_toy()
    prompts = _prompts(cfg.vocab_size)
    base, _, _ = _serve(params, cfg, prompts, spec_k=0, sample=sample)
    spec, rep, _ = _serve(params, cfg, prompts, spec_k=4, sample=sample)
    assert spec == base
    assert rep.spec_k == 4 and rep.spec_drafted > 0
    assert rep.spec_accepted > 0 and rep.spec_acceptance > 0.5


@pytest.mark.parametrize("dispatch", ["dropless", "capacity"])
@pytest.mark.parametrize("reconfig", [False, True])
def test_spec_parity_moe(dispatch, reconfig):
    """MoE (shared_only draft): bit-exact acceptance means the spec engine's
    output is token-for-token the serial stream even when the draft is wrong
    most of the time, across dispatch modes and under decode-time
    reconfiguration."""
    cfg, params = _moe_toy(dispatch)
    prompts = _prompts(cfg.vocab_size, seed=9)
    base, rep_b, _ = _serve(params, cfg, prompts, spec_k=0, reconfig=reconfig)
    spec, rep_s, _ = _serve(params, cfg, prompts, spec_k=3, reconfig=reconfig)
    assert spec == base, (dispatch, reconfig)
    assert rep_s.spec_drafted > 0
    if reconfig:
        assert rep_s.reconfig_count > 0


def test_spec_parity_moe_topk1_sampled():
    """No shared expert: the draft narrows to top-1 routing; sampled decode
    still reproduces the serial stream via the per-(row, position) keys."""
    cfg, params = _moe_toy("capacity", shared=0)
    assert moe_mod.resolve_draft_mode(cfg, "auto") == "topk1"
    prompts = _prompts(cfg.vocab_size, seed=13)
    base, _, _ = _serve(params, cfg, prompts, spec_k=3, sample=True,
                        sample_seed=7)
    spec, _, _ = _serve(params, cfg, prompts, spec_k=0, sample=True,
                        sample_seed=7)
    assert spec == base


def test_spec_sampled_seed_discipline():
    """Same seed -> identical sampled streams (spec and serial); a different
    seed draws a different stream (the keys really are threaded)."""
    cfg, params = _dense_toy()
    prompts = _prompts(cfg.vocab_size, seed=21, sizes=(6, 11))
    a, _, _ = _serve(params, cfg, prompts, spec_k=4, sample=True,
                     sample_seed=5)
    b, _, _ = _serve(params, cfg, prompts, spec_k=0, sample=True,
                     sample_seed=5)
    c, _, _ = _serve(params, cfg, prompts, spec_k=4, sample=True,
                     sample_seed=6)
    assert a == b
    assert a != c


def test_spec_truncation_reclaims_pages():
    """A draft the verifier mostly rejects: truncation fires, crosses page
    boundaries (page_size=4 < K+1), and the reclaimed pages are visible in
    the report — with the pool balancing after every tick."""
    cfg, params = _moe_toy("dropless")
    prompts = _prompts(cfg.vocab_size, seed=17)
    base, _, _ = _serve(params, cfg, prompts, spec_k=0, page_size=4)
    spec, rep, _ = _serve(params, cfg, prompts, spec_k=4, page_size=4)
    assert spec == base
    assert rep.draft_truncations > 0, "random-weight draft never rejected?"
    assert rep.pages_reclaimed > 0, "rejection never crossed a page boundary"


# ---------------------------------------------------------------------------
# EOS inside a span (satellite)
# ---------------------------------------------------------------------------


def test_spec_eos_at_every_span_position():
    """Place EOS at every position 0..K of the FIRST K=4 span: the spec
    engine stops exactly where serial decode stops and discards the
    speculated tail beyond EOS."""
    cfg, params = _dense_toy()
    # need the first K+1 tokens distinct so eos==stream[j] stops AT j:
    # scan prompt seeds for a stream whose first span has no repeats
    for seed in range(29, 40):
        prompts = _prompts(cfg.vocab_size, seed=seed, sizes=(8,))
        ref, _, _ = _serve(params, cfg, prompts, spec_k=0, max_new=10)
        stream = ref[100]
        if len(set(stream[:5])) == 5:
            break
    else:
        pytest.fail(f"no seed gave 5 distinct first-span tokens: {stream[:5]}")
    for j in range(5):
        eos = stream[j]
        b, _, _ = _serve(params, cfg, prompts, spec_k=0, max_new=10, eos=eos)
        s, rep, _ = _serve(params, cfg, prompts, spec_k=4, max_new=10, eos=eos)
        assert s == b, f"eos at span position {j}"
        assert s[100] == stream[: j + 1], f"eos at span position {j}"
        assert rep.completed == 1


# ---------------------------------------------------------------------------
# netsim pricing: acceptance curve must cross 1.0 (tentpole, priced side)
# ---------------------------------------------------------------------------


def test_netsim_spec_decode_pricing():
    from repro.configs.paper_models import MIXTRAL_8X7B
    from repro.core.fabric import FabricConfig, make_fabric
    from repro.core.netsim import simulate_serving

    model = dataclasses.replace(MIXTRAL_8X7B, num_blocks=8, overlap_chunks=4)
    fab = make_fabric("mixnet", FabricConfig(num_servers=128, link_gbps=400))
    mix = dataclasses.replace(
        __import__("repro.serve.workload", fromlist=["MIXES"]).MIXES[
            "agentic_shared"],
        rate_rps=500.0, arrival="poisson", num_regions=1)
    base = simulate_serving(model, fab, mix=mix, num_requests=24, slots=64,
                            use_reconfig=True, seed=1)
    lo = simulate_serving(model, fab, mix=mix, num_requests=24, slots=64,
                          use_reconfig=True, seed=1, spec_decode=(4, 0.05))
    hi = simulate_serving(model, fab, mix=mix, num_requests=24, slots=64,
                          use_reconfig=True, seed=1, spec_decode=(4, 0.95))
    assert base.spec_k == 0 and lo.spec_k == 4 and hi.spec_k == 4
    assert 0.0 < lo.spec_acceptance < hi.spec_acceptance <= 1.0
    assert hi.spec_tokens_per_round > lo.spec_tokens_per_round > 1.0
    # the draft pass is priced: junk drafts LOSE goodput/$, good drafts win
    assert lo.goodput_per_mdollar < base.goodput_per_mdollar
    assert hi.goodput_per_mdollar > base.goodput_per_mdollar
    # inter-token latency falls monotonically with acceptance
    assert hi.tpot_p50_s < base.tpot_p50_s
    # an acceptance MODEL (callable K -> expected accepted) is also accepted
    fn = simulate_serving(model, fab, mix=mix, num_requests=16, slots=64,
                          use_reconfig=True, seed=1,
                          spec_decode=(4, lambda k: 0.9 * k))
    assert fn.spec_tokens_per_round == pytest.approx(1.0 + 0.9 * 4)


# ---------------------------------------------------------------------------
# multi-device sweep: P x dispatch x reconfig, spec == serial
# ---------------------------------------------------------------------------


SPEC_SWEEP = """
import dataclasses
import jax, numpy as np
from repro.core.controlplane import LayerPlan
from repro.models.config import ModelConfig, MoEConfig
from repro.models.transformer import init_model
from repro.parallel.sharding import make_plan
from repro.serve.batching import Request
from repro.serve.engine import ServeConfig, ServeEngine
from repro.launch.mesh import make_mesh as _mm

P = %(P)d
mesh = _mm((P,), ("model",))
plan = make_plan(mesh)

for dispatch, shared in (("dropless", 1), ("capacity", 0)):
    cfg = ModelConfig(
        "sps", "moe", 2, 32, 4, 2, 0, 64, dtype="float32", remat="none",
        moe=MoEConfig(num_experts=8, top_k=2, d_ff=32,
                      num_shared_experts=shared, capacity_factor=8.0,
                      backend="mixnet", a2a_group=2, dispatch=dispatch),
    )
    params, _ = init_model(jax.random.PRNGKey(1), cfg, plan)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 63, size=int(n)).astype(np.int32)
               for n in (6, 11, 9)]

    def run(spec_k, reconfig):
        scfg = ServeConfig(slots=2, max_len=48, paged=True, page_size=8,
                           spec_k=spec_k,
                           reconfig_every=(3 if reconfig else 0),
                           reconfig_min_gain=0.0, num_devices=P)
        eng = ServeEngine(jax.tree.map(lambda a: a, params), cfg, plan, scfg,
                          mesh=mesh)
        with jax.set_mesh(mesh):
            if reconfig:
                perm = np.arange(8)
                perm[[0, 1]] = perm[[1, 0]]
                eng.apply_plans([
                    LayerPlan(l, True, perm=perm.copy())
                    for l in range(cfg.pattern_repeats)
                ])
            for i, p in enumerate(prompts):
                eng.submit(Request(rid=100 + i, prompt=p, max_new_tokens=5))
            while eng.batcher.busy:
                eng.step()
                eng.batcher.alloc.check_leaks()
        rep = eng.report(1.0)
        assert rep.completed == len(prompts)
        return {r.rid: list(r.out) for r in eng.batcher.finished}, rep

    for reconfig in (False, True):
        a, rep_s = run(3, reconfig)
        b, rep_b = run(0, reconfig)
        assert a == b, (dispatch, reconfig, a, b)
        assert rep_s.spec_drafted > 0
        if reconfig:
            assert rep_s.reconfig_count > 0
print("SPEC_SWEEP_OK_P%(P)d")
"""


@pytest.mark.parametrize("p", [2, 4, 8])
def test_spec_parity_multidevice(multidevice, p):
    """P-device EP-sharded serving: speculative decode is token-for-token
    the serial stream for shared_only AND topk1 drafts, dropless and
    capacity dispatch, reconfiguration on and off."""
    out = multidevice(SPEC_SWEEP % {"P": p}, devices=8, timeout=900)
    assert f"SPEC_SWEEP_OK_P{p}" in out
