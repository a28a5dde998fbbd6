"""Algorithm 1 (greedy OCS reconfiguration) properties — hypothesis-driven."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import topology as topo


def random_demand(rng, e):
    d = rng.random((e, e)) * 1e9
    np.fill_diagonal(d, 0.0)
    return d


@given(
    n_servers=st.sampled_from([2, 4, 8]),
    alpha=st.integers(min_value=1, max_value=8),
    seed=st.integers(min_value=0, max_value=1000),
)
@settings(max_examples=40, deadline=None)
def test_degree_and_symmetry_invariants(n_servers, alpha, seed):
    rng = np.random.default_rng(seed)
    demand = random_demand(rng, n_servers * 2)
    t = topo.reconfigure_ocs(demand, alpha=alpha, num_servers=n_servers)
    # symmetric circuit matrix, zero diagonal
    assert (t.circuits == t.circuits.T).all()
    assert (np.diag(t.circuits) == 0).all()
    # optical degree never exceeded
    for s in range(n_servers):
        assert t.links_of(s) <= alpha
    # NIC map consistent with the circuit matrix
    pair_counts = {}
    for i, _, j, _ in t.nic_map:
        pair_counts[(i, j)] = pair_counts.get((i, j), 0) + 1
    for (i, j), c in pair_counts.items():
        assert t.circuits[i, j] == c
    # no NIC used twice per server
    used = {}
    for i, ni, j, nj in t.nic_map:
        assert (i, ni) not in used and (j, nj) not in used
        used[(i, ni)] = used[(j, nj)] = True


@given(seed=st.integers(min_value=0, max_value=500))
@settings(max_examples=30, deadline=None)
def test_greedy_beats_uniform_on_skewed_demand(seed):
    """The demand-aware allocation completes skewed a2a no slower than the
    demand-oblivious round-robin topology."""
    rng = np.random.default_rng(seed)
    n = 8
    demand = random_demand(rng, n)
    # Skew: one hot pair dominates.
    demand[0, 1] = demand[1, 0] = demand.max() * 10
    solved = topo.reconfigure_ocs(demand, alpha=6, num_servers=n, experts_per_server=1)
    uniform = topo.uniform_topology(n, 6)
    pair = np.triu(demand + demand.T, 1)
    t_solved = topo.topology_completion_time(solved.circuits, pair, 1.0, 0.25)
    t_uniform = topo.topology_completion_time(uniform, pair, 1.0, 0.25)
    assert t_solved <= t_uniform * 1.0001


def test_monotone_in_alpha():
    rng = np.random.default_rng(7)
    demand = random_demand(rng, 8)
    pair = np.triu(demand + demand.T, 1)
    times = []
    for alpha in (1, 2, 4, 6, 8):
        t = topo.reconfigure_ocs(demand, alpha=alpha, num_servers=8, experts_per_server=1)
        times.append(topo.topology_completion_time(t.circuits, pair, 1.0, 0.25))
    # More optical degree never slows the all-to-all (Fig 27).
    for a, b in zip(times, times[1:]):
        assert b <= a * 1.0001


def test_server_demand_fold():
    e = np.arange(16, dtype=float).reshape(4, 4)
    d = topo.calculate_server_demand(e, experts_per_server=2)
    assert d.shape == (2, 2)
    # upper triangular with TX+RX folded
    assert d[1, 0] == 0.0
    block_up = e[:2, 2:].sum()
    block_down = e[2:, :2].sum()
    assert d[0, 1] == pytest.approx(block_up + block_down)
