"""Flow-level simulator + cost model properties, anchored to the paper's
headline claims (validated numerically in benchmarks; sanity-tested here)."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.paper_models import MIXTRAL_8X7B, SIM_MODELS
from repro.core import cost as costm
from repro.core.fabric import FabricConfig, make_fabric
from repro.core.netsim import GateTraceGenerator, SimModel, simulate_training


def mean_iter(model, fabric_name, gbps, iters=4, servers=128, **cfg_kw):
    cfg = FabricConfig(num_servers=servers, link_gbps=gbps, **cfg_kw)
    fab = make_fabric(fabric_name, cfg)
    res = simulate_training(
        model, fab, iterations=iters, use_copilot=(fabric_name == "mixnet")
    )
    return float(np.mean([r.total for r in res[1:]]))


def test_more_bandwidth_never_slower():
    t100 = mean_iter(MIXTRAL_8X7B, "mixnet", 100)
    t400 = mean_iter(MIXTRAL_8X7B, "mixnet", 400)
    assert t400 <= t100 * 1.001


def test_mixnet_close_to_fat_tree_and_beats_oversub():
    tm = mean_iter(MIXTRAL_8X7B, "mixnet", 400)
    tf = mean_iter(MIXTRAL_8X7B, "fat-tree", 400)
    to = mean_iter(MIXTRAL_8X7B, "oversub-fat-tree", 400)
    assert tm <= tf * 1.25  # "comparable to non-blocking fat-tree" (§7.3)
    assert tm < to  # outperforms the over-subscribed fabric


def test_mixnet_beats_topoopt():
    """§7.3: MixNet outperforms TopoOpt's static topology."""
    tm = mean_iter(MIXTRAL_8X7B, "mixnet", 100, iters=6)
    tt = mean_iter(MIXTRAL_8X7B, "topoopt", 100, iters=6)
    assert tt / tm > 1.1


def test_cost_efficiency_headline():
    """Fig 13: MixNet cost-efficiency vs fat-tree grows with link bandwidth
    and clears 1.2x at 100G / 1.9x at 400G for Mixtral 8x7B."""
    ratios = {}
    for gbps in (100, 400):
        tm = mean_iter(MIXTRAL_8X7B, "mixnet", gbps, iters=5)
        tf = mean_iter(MIXTRAL_8X7B, "fat-tree", gbps, iters=5)
        cm = costm.fabric_cost("mixnet", 128, gbps)
        cf = costm.fabric_cost("fat-tree", 128, gbps)
        ratios[gbps] = costm.cost_efficiency(tm, cm) / costm.cost_efficiency(tf, cf)
    assert ratios[100] > 1.2, ratios
    assert ratios[400] > 1.9, ratios
    assert ratios[400] > ratios[100]


def test_reconfig_latency_cliff_fig28():
    """25 ms OCS is hidden; second-scale reconfiguration degrades."""
    fast = mean_iter(MIXTRAL_8X7B, "mixnet", 400, reconfig_delay_s=0.025)
    micro = mean_iter(MIXTRAL_8X7B, "mixnet", 400, reconfig_delay_s=1e-5)
    slow = mean_iter(MIXTRAL_8X7B, "mixnet", 400, reconfig_delay_s=10.0)
    assert fast <= micro * 1.1  # ms-scale already fully hidden
    assert slow > fast * 1.5  # the Fig 28 cliff


def test_failure_resilience_fig14():
    """OCS link failure on one server costs only a few percent (EPS fallback)."""
    cfg = FabricConfig(num_servers=128, link_gbps=400)
    fab = make_fabric("mixnet", cfg)
    healthy = simulate_training(MIXTRAL_8X7B, fab, iterations=4)
    t_healthy = float(np.mean([r.total for r in healthy[1:]]))
    fab.fail_server_ocs(0)
    failed = simulate_training(MIXTRAL_8X7B, fab, iterations=4, seed=1)
    t_failed = float(np.mean([r.total for r in failed[1:]]))
    assert t_failed < t_healthy * 1.35
    assert t_failed >= t_healthy * 0.95


@given(seed=st.integers(0, 100))
@settings(max_examples=10, deadline=None)
def test_trace_generator_is_valid_distribution(seed):
    g = GateTraceGenerator(4, 16, seed=seed)
    loads = g.step()
    assert loads.shape == (4, 16)
    assert np.allclose(loads.sum(axis=1), 1.0, atol=1e-6)
    assert (loads >= 0).all()
    dem = g.device_demand(loads[0], MIXTRAL_8X7B, 4)
    assert (np.diag(dem) == 0).all()
    assert (dem >= 0).all()


def test_cost_table_prices_loaded():
    for gbps in (100, 200, 400, 800):
        c = costm.fabric_cost("mixnet", 128, gbps)
        f = costm.fabric_cost("fat-tree", 128, gbps)
        assert 0 < c < f  # Fig 11: MixNet always cheaper than fat-tree
