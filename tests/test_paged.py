"""Paged KV cache (DESIGN.md §10): PageAllocator lifecycle (allocation,
eviction, copy-on-write, dirty-page reuse), paged-vs-dense bit-identical
generation through the continuous batcher and the serving engine (single- and
multi-device, dropless and capacity, reconfiguration on and off), and the
prefix-registry reuse path."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.models.config import ModelConfig, MoEConfig
from repro.models.transformer import init_model, paged_supported
from repro.parallel.sharding import make_plan
from repro.serve.batching import ContinuousBatcher, Request
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.paged import PageAllocator
from repro.serve.workload import MIXES, WorkloadGenerator

PLAN = make_plan(None)


def _toy(name="pg"):
    cfg = ModelConfig(name, "dense", 2, 32, 4, 2, 64, 64, dtype="float32",
                      remat="none")
    params, _ = init_model(jax.random.PRNGKey(0), cfg, PLAN)
    return cfg, params


# ---------------------------------------------------------------------------
# allocator lifecycle (host-side policy, no device work)
# ---------------------------------------------------------------------------


def test_allocator_slot_churn_recycles_pages():
    """Admit/release churn across slots: pages cycle through the free list,
    the table row is fully cleared on release, and residency never exceeds
    what the live slots actually map."""
    al = PageAllocator(slots=4, page_size=4, max_pages=4, num_pages=8,
                      prefix_cache=False)
    rng = np.random.default_rng(0)
    for round_ in range(20):
        slot = round_ % 4
        prompt = rng.integers(0, 97, size=int(rng.integers(3, 12)))
        plan = al.admit(slot, prompt, 4, 16)
        if plan is None:  # pool busy: release an older slot and retry
            al.release((slot + 1) % 4)
            plan = al.admit(slot, prompt, 4, 16)
            assert plan is not None
        assert plan.reuse_len == 0 and plan.start == 0
        al.ensure(slot, 0, len(prompt))
        mapped = (al.table[slot] >= 0).sum()
        assert mapped == -(-len(prompt) // 4)
        assert al.resident_pages() <= 8
        al.release(slot)
        assert (al.table[slot] == -1).all()
    # all pages returned
    assert al.resident_pages() - len(al._registry) == 0
    assert al.allocs > 0


def test_allocator_reservation_blocks_oversubscription():
    """Admission reserves every page the request can touch; a second request
    the pool cannot also cover is refused instead of deadlocking mid-decode."""
    al = PageAllocator(slots=2, page_size=4, max_pages=4, num_pages=4,
                      prefix_cache=False)
    assert al.admit(0, np.arange(8), 8, 16) is not None  # reserves all 4 pages
    assert al.admit(1, np.arange(8), 8, 16) is None  # pool cannot cover it
    # the refused admission left no state behind
    assert (al.table[1] == -1).all() and al._reserved[1] == 0
    al.release(0)
    assert al.admit(1, np.arange(8), 8, 16) is not None


def test_allocator_prefix_reuse_and_cow_fork():
    """A second request with the same prompt maps the registry's pages
    read-only; a write into the shared range copy-on-write forks."""
    al = PageAllocator(slots=4, page_size=4, max_pages=4, num_pages=16)
    prompt = np.arange(9)  # 2 full pages + 1 partial
    p0 = al.admit(0, prompt, 4, 16)
    assert p0.reuse_len == 0
    al.ensure(0, 0, 9)
    al.register_prefix(0, prompt)
    owner_pages = [int(al.table[0, j]) for j in range(3)]

    p1 = al.admit(1, prompt, 4, 16)
    assert p1.reuse_len == 8  # the two FULL pages, never the partial third
    assert p1.start == 8
    assert list(p1.reused_pages) == owner_pages[:2]
    assert al.prefix_hit_pages == 2
    assert [int(al.table[1, j]) for j in range(2)] == owner_pages[:2]
    # continuing the prompt at position 8 allocates a private third page
    forks = al.ensure(1, 8, 9)
    assert forks == [] and int(al.table[1, 2]) not in owner_pages
    # a write into the SHARED range forks: new page, old one still mapped by
    # slot 0 and the registry
    forks = al.ensure(1, 4, 8)
    assert len(forks) == 1 and al.cow_forks == 1
    src, dst = forks[0]
    assert src == owner_pages[1] and int(al.table[1, 1]) == dst != src
    assert int(al.table[0, 1]) == src and al.refcount[src] >= 2


def test_allocator_full_reuse_forks_for_first_token():
    """A prompt whose pages are ALL in the registry re-runs its last token:
    admission reserves the extra page and ensure() forks the shared page the
    re-run writes."""
    al = PageAllocator(slots=2, page_size=4, max_pages=4, num_pages=16)
    prompt = np.arange(8)  # exactly 2 full pages
    al.admit(0, prompt, 4, 16)
    al.ensure(0, 0, 8)
    al.register_prefix(0, prompt)
    p1 = al.admit(1, prompt, 4, 16)
    assert p1.reuse_len == 8 and p1.start == 7  # re-run the last token
    forks = al.ensure(1, 7, 8)
    assert len(forks) == 1 and forks[0][0] == int(al.table[0, 1])


def test_allocator_evicts_registry_pages_oldest_first():
    """Registry-only pages are the eviction victims (LRU): allocation
    pressure evicts the oldest prefix, and its hash stops hitting."""
    al = PageAllocator(slots=2, page_size=4, max_pages=2, num_pages=4)
    a, b = np.arange(4), np.arange(4) + 50
    for p in (a, b):  # publish a first (oldest), then b
        al.admit(0, p, 4, 8)
        al.ensure(0, 0, 4)
        al.register_prefix(0, p)
        al.release(0)  # page survives, held by the registry
    assert al.resident_pages() == 2
    # a live slot takes the two free pages; the next allocation must evict
    al.admit(0, np.arange(8) + 100, 0, 8)
    al.ensure(0, 0, 8)
    al.admit(1, np.arange(4) + 200, 0, 8)
    al.ensure(1, 0, 4)
    assert al.evictions == 1
    al.release(1)
    assert al.admit(1, b, 0, 8).reuse_len == 4  # newer prefix survived
    al.release(1)
    assert al.admit(1, a, 0, 8).reuse_len == 0  # oldest was the victim


def test_allocator_dirty_page_reuse_after_retirement():
    """Freed pages go back verbatim (no clearing) and get reallocated; the
    free list is exercised by churning one slot."""
    al = PageAllocator(slots=1, page_size=4, max_pages=4, num_pages=4,
                      prefix_cache=False)
    al.admit(0, np.arange(16), 0, 16)
    al.ensure(0, 0, 16)
    first = [int(x) for x in al.table[0]]
    al.release(0)
    al.admit(0, np.arange(16) + 7, 0, 16)
    al.ensure(0, 0, 16)
    assert sorted(int(x) for x in al.table[0]) == sorted(first)


# ---------------------------------------------------------------------------
# paged vs dense bit parity through the batcher (P=1)
# ---------------------------------------------------------------------------


def _run_batcher(params, cfg, prompts, **kw):
    cb = ContinuousBatcher(params, cfg, PLAN, slots=2, max_len=32, **kw)
    for i, p in enumerate(prompts):
        cb.submit(Request(rid=i, prompt=p, max_new_tokens=6))
    done = cb.run()
    return cb, {r.rid: r.out for r in done}


@pytest.mark.parametrize("prefill_chunk", [0, 5])
def test_batcher_paged_matches_dense_bitwise(prefill_chunk):
    """Unique prompts (no prefix sharing in play): the paged batcher emits
    BIT-identical tokens to the dense ring-buffer batcher, for whole-prompt
    AND chunked prefill."""
    cfg, params = _toy()
    assert paged_supported(cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 17, 9, 23)]
    _, dense = _run_batcher(params, cfg, prompts, paged=False,
                            prefill_chunk=prefill_chunk)
    cb, paged = _run_batcher(params, cfg, prompts, paged=True,
                             prefill_chunk=prefill_chunk)
    assert cb.paged and paged == dense
    assert cb.kv_resident_pages_peak > 0


def test_batcher_prefix_reuse_skips_prefill_and_matches():
    """Identical prompts: the second request admits with a prefix hit (pages
    mapped, only the tail recomputed) and still generates the same tokens as
    the dense path."""
    cfg, params = _toy()
    rng = np.random.default_rng(5)
    p = rng.integers(0, cfg.vocab_size, size=21).astype(np.int32)
    prompts = [p, p.copy(), p.copy()]
    _, dense = _run_batcher(params, cfg, prompts, paged=False)
    cb, paged = _run_batcher(params, cfg, prompts, paged=True)
    assert cb.alloc.prefix_hit_pages > 0, "prefix registry never hit"
    assert paged == dense
    # all three identical requests decode identically
    assert paged[0] == paged[1] == paged[2]


def test_batcher_paged_pool_pressure_queues_not_corrupts():
    """A pool sized for ~1 sequence forces requests to wait for pages; every
    request still completes with the dense-path tokens."""
    cfg, params = _toy()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (9, 13, 11)]
    _, dense = _run_batcher(params, cfg, prompts, paged=False)
    cb, paged = _run_batcher(params, cfg, prompts, paged=True, num_pages=3,
                             prefix_cache=False)
    assert cb.num_pages == 3  # 3 pages of 16 = 48 tokens for 2 slots of 32
    assert paged == dense


# ---------------------------------------------------------------------------
# shared-prefix workload (satellite: agentic traces)
# ---------------------------------------------------------------------------


def test_workload_shared_prefix_mix():
    gen = WorkloadGenerator("agentic_shared", seed=11, vocab_size=128)
    reqs = gen.generate(64)
    m = MIXES["agentic_shared"]
    carriers = [r for r in reqs if r.prefix_len > 0]
    assert 0.7 < len(carriers) / len(reqs) <= 1.0  # ratio ~0.9
    by_region: dict = {}
    for r in carriers:
        assert r.prefix_len == min(m.shared_prefix_tokens, r.prompt_len)
        toks = gen.prompt_tokens(r)
        assert toks[0] == r.region % 128
        key = r.region
        if key in by_region:
            np.testing.assert_array_equal(toks[:r.prefix_len],
                                          by_region[key][:r.prefix_len])
        else:
            by_region[key] = toks
    # non-carriers keep the old per-rid stream
    plain = [r for r in reqs if r.prefix_len == 0]
    if plain:
        assert gen.prompt_tokens(plain[0]).shape == (plain[0].prompt_len,)
    # determinism
    assert WorkloadGenerator("agentic_shared", seed=11,
                             vocab_size=128).generate(64) == reqs


# ---------------------------------------------------------------------------
# engine parity: paged vs dense x dropless/capacity x reconfig on/off
# ---------------------------------------------------------------------------


def _moe_cfg(dispatch):
    return ModelConfig(
        "pgs", "moe", 2, 32, 4, 2, 0, 64, dtype="float32", remat="none",
        moe=MoEConfig(num_experts=8, top_k=2, d_ff=32, capacity_factor=8.0,
                      backend="mixnet", a2a_group=2, dispatch=dispatch),
    )


@pytest.mark.parametrize("dispatch", ["dropless", "capacity"])
@pytest.mark.parametrize("reconfig", [False, True])
def test_engine_paged_parity_single_device(dispatch, reconfig):
    cfg = _moe_cfg(dispatch)
    params, _ = init_model(jax.random.PRNGKey(0), cfg, PLAN)
    gen = WorkloadGenerator("chat", seed=3, vocab_size=cfg.vocab_size)
    reqs = [dataclasses.replace(r, prompt_len=min(r.prompt_len, 20),
                                max_new_tokens=min(r.max_new_tokens, 5))
            for r in gen.generate(4)]

    def run(paged):
        scfg = ServeConfig(slots=2, max_len=32, paged=paged,
                           reconfig_every=(3 if reconfig else 0),
                           reconfig_min_gain=0.0, num_devices=4)
        eng = ServeEngine(jax.tree.map(lambda a: a, params), cfg, PLAN, scfg)
        rep = eng.run(reqs, gen)
        assert rep.completed == len(reqs)
        return eng, rep

    eng_p, rep_p = run(True)
    eng_d, rep_d = run(False)
    assert rep_p.kv_paged and not rep_d.kv_paged
    assert rep_p.kv_resident_pages_peak > 0
    a = {r.rid: r.out for r in eng_p.batcher.finished}
    b = {r.rid: r.out for r in eng_d.batcher.finished}
    assert a == b, (dispatch, reconfig)
    if reconfig:
        assert rep_p.reconfig_count > 0


PAGED_SWEEP = """
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
        "pgs", "moe", 2, 32, 4, 2, 0, 64, dtype="float32", remat="none",
        moe=MoEConfig(num_experts=8, top_k=2, d_ff=32, capacity_factor=8.0,
                      backend="mixnet", a2a_group=2, dispatch=dispatch),
    )
    params, _ = init_model(jax.random.PRNGKey(0), cfg, plan)
    gen = WorkloadGenerator("chat", seed=3, vocab_size=cfg.vocab_size)
    reqs = [dataclasses.replace(r, prompt_len=12, max_new_tokens=4)
            for r in gen.generate(3)]

    def run(paged, reconfig):
        scfg = ServeConfig(slots=2, max_len=32, paged=paged,
                           reconfig_every=(2 if reconfig else 0),
                           reconfig_min_gain=0.0, num_devices=P)
        eng = ServeEngine(jax.tree.map(lambda a: a, params), cfg, plan, scfg,
                          mesh=mesh)
        with jax.set_mesh(mesh):
            if reconfig:
                # Force one expert-weight permutation so the paged decode
                # path provably runs under a moved placement (the control
                # plane may find no gainful move on a 3-request workload).
                perm = np.arange(8)
                perm[[0, 1]] = perm[[1, 0]]
                eng.apply_plans([
                    LayerPlan(l, True, perm=perm.copy())
                    for l in range(cfg.pattern_repeats)
                ])
            rep = eng.run(reqs, gen)
        assert rep.completed == len(reqs)
        return {r.rid: r.out for r in eng.batcher.finished}, rep

    for reconfig in (False, True):
        a, rep_p = run(True, reconfig)
        b, rep_d = run(False, reconfig)
        assert rep_p.kv_paged and not rep_d.kv_paged
        assert a == b, (dispatch, reconfig, a, b)
        if reconfig:
            assert rep_p.reconfig_count > 0
print("PAGED_SWEEP_OK_P%(P)d")
"""


@pytest.mark.parametrize("p", [2, 4, 8])
def test_engine_paged_parity_multidevice(multidevice, p):
    """P-device EP-sharded serving: paged vs dense generation is
    bit-identical for dropless AND capacity dispatch, with decode-time
    reconfiguration on and off."""
    out = multidevice(PAGED_SWEEP % {"P": p}, devices=8, timeout=900)
    assert f"PAGED_SWEEP_OK_P{p}" in out
