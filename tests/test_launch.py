"""Launch-layer units: trip-aware HLO analyzer, roofline math, sharding
plan rules, data pipeline determinism."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import Batch, SyntheticLM
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.roofline import HW, analytic_hbm_bytes, roofline_from_counts
from repro.parallel.sharding import ShardingPlan, make_plan


def test_analyzer_counts_scan_trips():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    sds = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = jax.jit(f).lower(sds, sds).compile()
    r = analyze_hlo(compiled.as_text(), 1)
    assert r["flops"] == pytest.approx(10 * 2 * 64**3, rel=0.01)


def test_analyzer_collective_formulas():
    hlo = """
HloModule test, entry_computation_layout={()->f32[16,16]{1,0}}

ENTRY %main (p: f32[16,16]) -> f32[16,16] {
  %p = f32[16,16]{1,0} parameter(0)
  %ag = f32[16,16]{1,0} all-gather(%p), replica_groups=[2,4]<=[8], dimensions={0}
  ROOT %ar = f32[16,16]{1,0} all-reduce(%ag), replica_groups=[2,4]<=[8], to_apply=%add
}
"""
    r = analyze_hlo(hlo, 8)
    size = 16 * 16 * 4
    assert r["collectives"]["all-gather"] == pytest.approx(size * 3 / 4)
    assert r["collectives"]["all-reduce"] == pytest.approx(2 * size * 3 / 4)


def test_roofline_terms_and_bottleneck():
    t = roofline_from_counts(
        arch="a", shape="s", mesh="16x16", chips=256,
        hlo_flops=256 * 197e12,  # exactly 1 second of compute
        hlo_bytes=256 * 819e9 * 0.5,
        collective_bytes=256 * 50e9 * 2.0,
        model_flops=256 * 197e12 * 0.8,
        per_device_hbm_peak=8e9,
    )
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(0.5)
    assert t.collective_s == pytest.approx(2.0)
    assert t.bottleneck == "collective"
    assert t.useful_ratio == pytest.approx(0.8)
    assert t.roofline_fraction == pytest.approx(0.4)


def test_analytic_hbm_scales_with_kind():
    from repro.configs import get_config
    from repro.configs.shapes import SHAPES

    cfg = get_config("deepseek-7b")
    train = analytic_hbm_bytes(cfg, SHAPES["train_4k"])
    prefill = analytic_hbm_bytes(cfg, SHAPES["prefill_32k"])
    decode = analytic_hbm_bytes(cfg, SHAPES["decode_32k"])
    assert train > prefill > 0
    # decode traffic is dominated by weights + KV reads, far below train
    assert decode < train


def test_sharding_plan_rules():
    plan = ShardingPlan(("pod", "data"), "model", 16, "data", data_size=16)
    assert plan.heads_axis(96) == "model"
    assert plan.heads_axis(8) is None
    assert plan.dim_axis(28672) == "model"
    assert plan.dim_axis(1500) is None
    assert plan.fsdp_for(4096) == "data"
    none_plan = make_plan(None)
    assert none_plan.model_axis is None and none_plan.model_size == 1


def test_synthetic_data_deterministic_and_sharded():
    a = next(iter(SyntheticLM(256, 32, 8, seed=3)))
    b = next(iter(SyntheticLM(256, 32, 8, seed=3)))
    np.testing.assert_array_equal(a.tokens, b.tokens)
    # labels are next-token shifted
    np.testing.assert_array_equal(a.labels[:, :-1], a.tokens[:, 1:])
    # host sharding: two hosts see disjoint streams, each half the batch
    h0 = next(iter(SyntheticLM(256, 32, 8, seed=3, host_id=0, num_hosts=2)))
    h1 = next(iter(SyntheticLM(256, 32, 8, seed=3, host_id=1, num_hosts=2)))
    assert h0.tokens.shape == (4, 32)
    assert not np.array_equal(h0.tokens, h1.tokens)


def test_input_specs_no_allocation():
    """input_specs must be pure ShapeDtypeStructs (never device arrays)."""
    from repro.configs import get_config
    from repro.configs.shapes import SHAPES
    from repro.launch.specs import input_specs
    from jax.sharding import Mesh

    from repro.launch.mesh import make_mesh as _compat_make_mesh

    mesh = _compat_make_mesh((1, 1), ("data", "model"))
    plan = make_plan(mesh)
    for arch in ("whisper-base", "qwen2-vl-72b", "mamba2-1.3b"):
        cfg = get_config(arch)
        for shape in SHAPES.values():
            specs = input_specs(cfg, shape, mesh, plan)
            for leaf in jax.tree.leaves(specs):
                assert isinstance(leaf, jax.ShapeDtypeStruct), (arch, shape.name)


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir_is_fixed(env_dir, tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache goes to
    the fixed .jax_cache/ at the checkout root."""
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(compile_cache.CHECKOUT_CACHE_DIR)
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert compile_cache.enable_compile_cache() == want
        expect_cfg = before if env_dir else want
        assert jax.config.jax_compilation_cache_dir == expect_cfg
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert str(compile_cache.CHECKOUT_CACHE_DIR) == os.path.join(repo, ".jax_cache")
