"""Shared routing core (repro.models.routing): sort-based plan invariants,
backend parity through the one routing engine (dropless + capacity), the
dense-decode ``expert_perm`` regression, and the two-stage drop telemetry."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.placement import apply_placement
from repro.models import moe as moe_mod
from repro.models import routing
from repro.models.config import ModelConfig, MoEConfig
from repro.parallel.sharding import make_plan

KEY = jax.random.PRNGKey(0)
PLAN = make_plan(None)
BACKENDS = ("einsum", "mixnet", "dense_decode")


def make_cfg(num_experts=8, top_k=2, cf=8.0, dispatch="dropless"):
    return ModelConfig(
        "t", "moe", 2, 32, 4, 2, 64, 128, dtype="float32",
        moe=MoEConfig(num_experts=num_experts, top_k=top_k, d_ff=48,
                      capacity_factor=cf, a2a_group=2, dispatch=dispatch),
    )


# ---------------------------------------------------------------------------
# plan-level invariants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("buckets,n", [(4, 64), (8, 17), (16, 256)])
def test_bucket_ranks_match_cumsum_semantics(seed, buckets, n):
    """Stable argsort ranks == the historical one_hot+cumsum ranks."""
    dest = jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, buckets)
    rank, counts = routing.bucket_ranks(dest, buckets)
    oh = jax.nn.one_hot(dest, buckets, dtype=jnp.int32)
    pos = jnp.cumsum(oh, axis=0) - oh
    expect = jnp.sum(pos * oh, axis=1)
    assert bool(jnp.all(rank == expect))
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(np.asarray(dest), minlength=buckets)
    )


@pytest.mark.parametrize("seed", [0, 3])
@pytest.mark.parametrize("buckets,n,block", [(4, 64, 8), (8, 100, 16), (3, 9, 4)])
def test_dropless_plan_places_every_choice(seed, buckets, n, block):
    dest = jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, buckets)
    rank, counts = routing.bucket_ranks(dest, buckets)
    plan = routing.dropless_plan(dest, rank, counts, None, buckets, block)
    slot = np.asarray(plan.slot)
    src = np.asarray(plan.src)
    be = np.asarray(plan.block_experts)
    # dropless: every choice placed, in a unique row, and invertible
    assert (slot >= 0).all() and int(plan.kept) == n
    assert len(set(slot.tolist())) == n
    assert plan.num_rows % block == 0 and (src >= -1).all()
    for i in range(n):
        assert src[slot[i]] == i
        # the owning block's expert matches the choice's destination
        assert be[slot[i] // block] == dest[i]
    # empty rows are marked empty
    assert (np.delete(src, slot) == -1).all()


def test_capacity_plan_drops_overflow_in_order():
    dest = jnp.array([0, 0, 0, 1, 0, 1], dtype=jnp.int32)
    rank, _ = routing.bucket_ranks(dest, 2)
    plan = routing.capacity_plan(dest, rank, None, 2, 2)
    # first-come (token-order) keeps, like the historical cumsum ranks
    np.testing.assert_array_equal(
        np.asarray(plan.slot), np.array([0, 1, -1, 2, -1, 3])
    )
    assert int(plan.kept) == 4


# ---------------------------------------------------------------------------
# backend parity through the shared core
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dispatch", ["dropless", "capacity"])
@pytest.mark.parametrize("top_k", [1, 2])
def test_backend_parity_seeded_sweep(dispatch, top_k):
    """einsum, mixnet and dense_decode agree through the shared routing core
    (generous capacity in capacity mode so no backend drops)."""
    for seed in (0, 1, 2):
        cfg = make_cfg(top_k=top_k, dispatch=dispatch)
        params, _ = moe_mod.init_moe(jax.random.PRNGKey(seed), cfg, PLAN)
        x = jax.random.normal(jax.random.PRNGKey(seed + 100), (2, 16, 32))
        outs, loads = {}, {}
        for backend in BACKENDS:
            out, st = moe_mod.moe_apply(params, x, cfg, PLAN, backend=backend)
            outs[backend], loads[backend] = out, st.expert_load
        for backend in BACKENDS[1:]:
            err = float(jnp.max(jnp.abs(outs["einsum"] - outs[backend])))
            assert err < 1e-5, (backend, dispatch, top_k, seed, err)
            np.testing.assert_allclose(
                np.asarray(loads["einsum"]), np.asarray(loads[backend])
            )


@pytest.mark.parametrize("dispatch", ["dropless", "capacity"])
def test_backend_parity_with_expert_perm(dispatch):
    """Non-identity expert->slot permutation (permuted weights + perm passed)
    preserves the math on every backend."""
    cfg = make_cfg(dispatch=dispatch)
    params, _ = moe_mod.init_moe(KEY, cfg, PLAN)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    perm = jnp.array([3, 1, 4, 0, 6, 2, 7, 5], dtype=jnp.int32)
    permuted = {
        k: (apply_placement(v, np.asarray(perm)) if k in ("w_in", "w_gate", "w_out") else v)
        for k, v in params.items()
    }
    for backend in BACKENDS:
        base, _ = moe_mod.moe_apply(params, x, cfg, PLAN, backend=backend)
        out, _ = moe_mod.moe_apply(
            permuted, x, cfg, PLAN, backend=backend, expert_perm=perm
        )
        err = float(jnp.max(jnp.abs(base - out)))
        assert err < 1e-5, (backend, dispatch, err)


def test_dropless_invariant_exact_combine():
    """Dropless = exact: every token contributes exactly top_k·r combine
    terms, so the MoE output equals the brute-force per-token gate-weighted
    expert sum."""
    cfg = make_cfg(num_experts=4, top_k=2)
    params, _ = moe_mod.init_moe(KEY, cfg, PLAN)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 32))
    xt = x.reshape(-1, 32)
    logits = xt @ params["router"]
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), 2)
    w = w / w.sum(-1, keepdims=True)

    def one_expert(e, tok):
        h = tok @ params["w_in"][e]
        g = jax.nn.silu(tok @ params["w_gate"][e])
        return (g * h) @ params["w_out"][e]

    expect = jnp.stack([
        sum(w[t, k] * one_expert(idx[t, k], xt[t]) for k in range(2))
        for t in range(xt.shape[0])
    ]).reshape(x.shape)
    for backend in BACKENDS:
        out, stats = moe_mod.moe_apply(params, x, cfg, PLAN, backend=backend)
        assert float(jnp.max(jnp.abs(out - expect))) < 1e-5, backend
        assert float(stats.dropped_fraction) == 0.0, backend


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------


def test_dense_decode_honors_expert_perm():
    """Regression: decode after a runtime reconfiguration (physically
    permuted expert weights + the layer's perm) must match the
    pre-reconfiguration output — dense_decode used to ignore the perm."""
    cfg = make_cfg(num_experts=8, top_k=2)
    params, _ = moe_mod.init_moe(KEY, cfg, PLAN)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 1, 32))  # S=1 decode
    perm = jnp.array([5, 0, 3, 7, 2, 6, 1, 4], dtype=jnp.int32)
    permuted = {
        k: (apply_placement(v, np.asarray(perm)) if k in ("w_in", "w_gate", "w_out") else v)
        for k, v in params.items()
    }
    base, _ = moe_mod.moe_apply(params, x, cfg, PLAN, backend="dense_decode")
    # via the auto decode switch (mixnet backend, S=1) AND explicitly
    for backend in ("mixnet", "dense_decode"):
        out, _ = moe_mod.moe_apply(
            permuted, x, cfg, PLAN, backend=backend, expert_perm=perm
        )
        assert float(jnp.max(jnp.abs(base - out))) < 1e-5, backend


def test_mixnet_drop_telemetry_counts_pack_stage():
    """Regression: stage-2 (pack-by-expert) drops must show up in
    ``dropped_fraction``.  A heavily skewed router overflows the per-expert
    pack buffers while the stage-1 device send buffer (single device) never
    drops — the old telemetry reported 0 here."""
    cfg = make_cfg(num_experts=4, top_k=1, cf=1.0, dispatch="capacity")
    params, _ = moe_mod.init_moe(KEY, cfg, PLAN)
    # Bias the router so (almost) all tokens pick expert 0.
    params = dict(params)
    params["router"] = params["router"].at[:, 0].set(50.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 32))
    _, stats = moe_mod.moe_apply(params, x, cfg, PLAN, backend="mixnet")
    assert float(stats.dropped_fraction) > 0.2
    # and the einsum backend agrees about the realized loss
    _, st_e = moe_mod.moe_apply(params, x, cfg, PLAN, backend="einsum")
    assert abs(float(stats.dropped_fraction) - float(st_e.dropped_fraction)) < 0.26


# ---------------------------------------------------------------------------
# multi-device: virtual experts (r > 1) + perm through the shared core
# ---------------------------------------------------------------------------


MULTIDEV = """
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.models.config import ModelConfig, MoEConfig
from repro.models import moe as moe_mod
from repro.parallel.sharding import make_plan
from repro.core.placement import apply_placement

from repro.launch.mesh import make_mesh as _mk
mesh = _mk((2, 4), ('data', 'model'))
plan = make_plan(mesh)
plan1 = make_plan(None)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))

for dispatch in ('dropless', 'capacity'):
    # virtual experts: E=2 over model=4 (r=2), top_k=1
    cfg = ModelConfig('t', 'moe', 2, 32, 4, 2, 64, 128, dtype='float32',
                      moe=MoEConfig(num_experts=2, top_k=1, d_ff=48,
                                    capacity_factor=8.0, a2a_group=2,
                                    dispatch=dispatch))
    params, _ = moe_mod.init_moe(jax.random.PRNGKey(2), cfg, plan)
    with jax.set_mesh(mesh):
        o_m, st_m = jax.jit(lambda p, v: moe_mod.moe_apply(
            p, v, cfg, plan, mesh=mesh, backend='mixnet'))(params, x)
        o_e, st_e = jax.jit(lambda p, v: moe_mod.moe_apply(
            p, v, cfg, plan, mesh=mesh, backend='einsum'))(params, x)
    assert float(jnp.max(jnp.abs(o_m - o_e))) < 1e-5, dispatch
    np.testing.assert_allclose(np.asarray(st_m.expert_load),
                               np.asarray(st_e.expert_load))

    # r=2 + non-identity perm over the 4 virtual slots
    perm = np.array([2, 0, 3, 1], dtype=np.int32)
    pp = {k: (apply_placement(v, perm) if k in ('w_in', 'w_gate', 'w_out') else v)
          for k, v in params.items()}
    with jax.set_mesh(mesh):
        o_p, _ = jax.jit(lambda p, v: moe_mod.moe_apply(
            p, v, cfg, plan, mesh=mesh, backend='mixnet',
            expert_perm=jnp.asarray(perm)))(pp, x)
    assert float(jnp.max(jnp.abs(o_p - o_m))) < 1e-5, dispatch
print('ROUTING_MULTIDEV_OK')
"""


def test_routing_multidevice_virtual_experts(multidevice):
    out = multidevice(MULTIDEV, devices=8, timeout=900)
    assert "ROUTING_MULTIDEV_OK" in out
