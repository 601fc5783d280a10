"""Weighted-core extension (paper §6 future work): fixpoint vs peeling
oracle, maintenance under random weighted edits."""
import numpy as np
import pytest

from repro.core.weighted import (
    WeightedCoreMaintainer,
    weighted_core_oracle,
)
from repro.graph.generators import erdos_renyi


def _setup(n, m, seed):
    g = erdos_renyi(n, m, seed=seed)
    edges = g.edge_array()
    rng = np.random.default_rng(seed)
    weights = rng.integers(1, 6, size=edges.shape[0]).astype(np.int32)
    return g, edges, weights


@pytest.mark.parametrize("seed", range(4))
def test_weighted_fixpoint_matches_oracle(seed):
    g, edges, weights = _setup(80, 300, seed)
    m = WeightedCoreMaintainer(g.n, edges, weights)
    expect = weighted_core_oracle(g.n, edges, weights)
    np.testing.assert_array_equal(m.cores(), expect)


@pytest.mark.parametrize("seed", range(3))
def test_weighted_maintenance_matches_recompute(seed):
    g, edges, weights = _setup(60, 220, seed + 10)
    m = WeightedCoreMaintainer(g.n, edges, weights, capacity=2048)
    rng = np.random.default_rng(seed)
    live = {tuple(e): int(w) for e, w in zip(edges.tolist(), weights)}

    for step in range(6):
        if rng.random() < 0.5:
            batch, ws = [], []
            while len(batch) < 5:
                u, v = rng.integers(0, g.n, size=2)
                key = (int(min(u, v)), int(max(u, v)))
                if u == v or key in live or key in batch:
                    continue
                batch.append(key)
                ws.append(int(rng.integers(1, 6)))
            m.insert_edges(np.asarray(batch), np.asarray(ws))
            live.update(dict(zip(batch, ws)))
        else:
            keys = sorted(live)
            take = rng.choice(len(keys), size=min(5, len(keys)),
                              replace=False)
            batch = [keys[i] for i in take]
            m.remove_edges(np.asarray(batch))
            for k in batch:
                live.pop(k)
        cur_edges = np.asarray(sorted(live), dtype=np.int64)
        cur_w = np.asarray([live[tuple(e)] for e in cur_edges.tolist()],
                           dtype=np.int32)
        expect = weighted_core_oracle(g.n, cur_edges, cur_w)
        np.testing.assert_array_equal(m.cores(), expect)


def test_unit_weights_reduce_to_unweighted_cores():
    from repro.core.oracle import bz_from_csr

    g, edges, _ = _setup(70, 260, 42)
    ones = np.ones(edges.shape[0], np.int32)
    m = WeightedCoreMaintainer(g.n, edges, ones)
    np.testing.assert_array_equal(m.cores(), bz_from_csr(g))


def _heavy_maintainer(w_big):
    """A 4-cycle with one edge of weight ``w_big``, on the unified
    weighted engine."""
    from repro.core.api import maintainer_from_edges

    edges = np.array([[0, 1], [1, 2], [2, 3], [0, 3]])
    return maintainer_from_edges(4, edges, weighted=True,
                                 weights=[w_big, 1, 1, 1], capacity=64)


def test_from_graph_refuses_strengths_past_int32():
    from repro.core.api import STRENGTH_MAX

    m = _heavy_maintainer(STRENGTH_MAX - 1)  # vertex 0: STRENGTH_MAX
    assert m.strength_ub == STRENGTH_MAX
    with pytest.raises(ValueError, match="weighted degree"):
        _heavy_maintainer(STRENGTH_MAX)
    with pytest.raises(ValueError, match="weighted degree"):
        _heavy_maintainer(2**31 + 5)  # would wrap in an int32 cast


def test_apply_batch_refuses_weight_past_int32_atomically():
    from repro.core.api import STRENGTH_MAX

    big = STRENGTH_MAX // 2
    m = _heavy_maintainer(big)
    core0, slots0 = m.cores(), dict(m.edge_slot)
    # one more edge of that weight onto vertex 0 fits; two do not
    with pytest.raises(ValueError, match="could exceed"):
        m.apply_batch(insert_edges=[[0, 2], [1, 3]],
                      remove_edges=[[1, 2]], insert_weights=[big, big])
    np.testing.assert_array_equal(m.cores(), core0)
    assert m.edge_slot == slots0  # nothing applied, removals included
    # removing the heavy edge first brings the exact bound down: the
    # same heavy insertion is then taken, not refused
    m.apply_batch(remove_edges=[[0, 1]])
    m.apply_batch(insert_edges=[[0, 2]], insert_weights=[big + 2])
    m.apply_batch(insert_edges=[[0, 1]], insert_weights=[big - 5])
    assert m.strength_ub <= STRENGTH_MAX
    np.testing.assert_array_equal(
        m.cores(),
        weighted_core_oracle(4, np.array([[1, 2], [2, 3], [0, 3], [0, 2],
                                          [0, 1]]),
                             np.array([1, 1, 1, big + 2, big - 5])))
