"""The weighted h-index by one sort (``graph_ops.sorted_h_index``) against
the lockstep bisection it replaces on layouts without a mesh axis: equal
integer for integer on every case below, and a fixpoint on it reaches
``weighted_core_oracle``'s cores. The engines: the unified program sorts,
the edge-sharded and halo programs still bisect, and all three agree; the
unweighted program's lowered text does not move."""
import hashlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph_ops as G
from repro.core.api import STRENGTH_MAX, CoreMaintainer
from repro.core.engine import apply_batch
from repro.core.remove import weighted_core_fixpoint_pass
from repro.core.weighted import weighted_core_oracle
from repro.graph.generators import erdos_renyi
from repro.graph.stream import churn_stream


def _table(n, m, seed, w_lo=1, w_hi=9, invalid=0.25):
    """A slot table of ``m`` distinct edges on ``n`` vertices plus
    invalid slots (share ``invalid``) holding stale endpoints and
    weights."""
    rng = np.random.default_rng(seed)
    g = erdos_renyi(n, m, seed=seed)
    e = g.edge_array()
    n_bad = int(invalid * len(e))
    src = np.concatenate([e[:, 0], rng.integers(0, n, n_bad)])
    dst = np.concatenate([e[:, 1], rng.integers(0, n, n_bad)])
    valid = np.arange(len(src)) < len(e)
    w = rng.integers(w_lo, w_hi, len(src))
    perm = rng.permutation(len(src))
    return (n, src[perm].astype(np.int32), dst[perm].astype(np.int32),
            valid[perm], w[perm].astype(np.int32), rng)


def _strength(n, src, dst, valid, w):
    s = np.zeros(n, np.int64)
    np.add.at(s, src[valid], w[valid])
    np.add.at(s, dst[valid], w[valid])
    return s


def _case(name):
    """``(n, src, dst, valid, w, core, upper)`` of one named case."""
    if name.startswith("random"):
        seed = int(name.rsplit("_", 1)[1])
        n, src, dst, valid, w, rng = _table(40, 150, seed)
        core = rng.integers(0, 40, n)
        # half the cases cap at the core, half above it
        upper = core + (rng.integers(0, 30, n) if seed % 2 else 0)
        return n, src, dst, valid, w, core, upper
    n, src, dst, valid, w, rng = _table(40, 150, 7)
    core = rng.integers(0, 60, n)
    if name == "upper_eq_core":
        return n, src, dst, valid, w, core, core
    if name == "upper_gt_core":
        return n, src, dst, valid, w, core, core + 1000
    if name == "upper_zero":
        return n, src, dst, valid, w, core, np.zeros(n, np.int64)
    if name == "isolated":
        # vertices 30..39 touch no valid slot (invalid slots still may)
        keep = valid & (src < 30) & (dst < 30)
        return n, src, dst, keep, w, core, core + 5
    if name == "equal_neighbour_cores":
        return n, src, dst, valid, w, np.full(n, 9), np.full(n, 40)
    if name == "total_weight_past_2_31":
        n, src, dst, valid, w, rng = _table(
            64, 300, 3, w_lo=1 << 24, w_hi=(1 << 24) + 4096)
        s = _strength(n, src, dst, valid, w)
        assert w[valid].astype(np.int64).sum() > 2**31
        assert s.max() <= STRENGTH_MAX
        return n, src, dst, valid, w, s, s
    raise KeyError(name)


CASES = [f"random_{i}" for i in range(6)] + [
    "upper_eq_core", "upper_gt_core", "upper_zero", "isolated",
    "equal_neighbour_cores", "total_weight_past_2_31",
]


@pytest.mark.parametrize("name", CASES)
def test_sorted_h_index_equals_bisection_and_reaches_the_oracle(name):
    n, src, dst, valid, w, core, upper = _case(name)
    args = tuple(map(jnp.asarray, (src, dst, valid, w)))
    core_j = jnp.asarray(core, jnp.int32)
    upper_j = jnp.asarray(upper, jnp.int32)
    entries = G.hindex_entries(*args, n)
    got = G.sorted_h_index(entries, core_j, upper_j)
    want, steps = G.bisect_h_index(*args, core_j, upper_j, n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the dispatch takes the sort where the layout has no mesh axis
    h, k = G.weighted_h_index(*args, core_j, upper_j, n)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(want))
    assert int(k) == 0
    if name in ("random_1", "upper_gt_core"):
        assert int(steps) > 0 and (np.asarray(want) > 0).any()

    # a fixpoint on it, from the weighted degree, gives the exact cores
    strength = _strength(n, src, dst, valid, w)
    cores, rounds, _, steps, sorts = weighted_core_fixpoint_pass(
        *args, jnp.asarray(strength, jnp.int32), n)
    live = valid.astype(bool)
    edges = np.stack([src[live], dst[live]], axis=1)
    np.testing.assert_array_equal(
        np.asarray(cores), weighted_core_oracle(n, edges, w[live]))
    assert int(steps) == 0 and int(sorts) == int(rounds) > 0


def _weighted_stream(**config):
    """A seeded weighted churn stream on one configuration: a hash of
    every batch's cores, labels, rounds, ``n_promoted`` and
    ``n_dropped``, the batches' ``BatchStats``, and the final cores
    against the oracle's."""
    g = erdos_renyi(40, 160, seed=3)
    rng = np.random.default_rng(7)
    w0 = rng.integers(1, 9, g.m)
    m = CoreMaintainer.from_graph(g, capacity=4 * g.m + 64, weighted=True,
                                  weights=w0, **config)
    h = hashlib.sha256(np.asarray(m.cores(), np.int64).tobytes())
    stats = []
    for ev in churn_stream(g, 4, 12, seed=5, p_reinsert=0.5):
        st = m.apply_batch(insert_edges=ev.edges, remove_edges=ev.removals,
                           insert_weights=rng.integers(1, 9, len(ev.edges)))
        for a in (m.cores(), m.labels(),
                  [int(st.remove_rounds), int(st.insert_rounds),
                   int(st.n_promoted), int(st.n_dropped)]):
            h.update(np.asarray(a, np.int64).tobytes())
        stats.append(st)
    wcol = np.asarray(m.w)
    slots = sorted(m.edge_slot.items())
    edges = np.asarray([e for e, _ in slots], np.int64).reshape(-1, 2)
    want = weighted_core_oracle(g.n, edges, wcol[[s for _, s in slots]])
    np.testing.assert_array_equal(m.cores(), want)
    return h.hexdigest(), stats


# the bisection path's record of ``_weighted_stream()`` on the unified
# engine, taken before the unified engine sorted
BISECTION_TRAJECTORY = (
    "f2d3bee9c73d054c733eaaa0dfa6b30c52ad4970ddf5eb82baadbf7d5b90897f")


@pytest.mark.parametrize("bisecting", [
    dict(engine="sharded"),
    dict(engine="sharded", vertex_sharding="halo"),
], ids=["sharded", "halo"])
def test_unified_engine_sorts_and_matches_the_bisecting_engines(bisecting):
    digest, stats = _weighted_stream()
    assert digest == BISECTION_TRAJECTORY
    for st in stats:
        assert int(st.hindex_sorts) == \
            int(st.remove_rounds) + int(st.insert_rounds) > 0
        assert int(st.remove_bisect_steps) == 0
        assert int(st.insert_bisect_steps) == 0
    # a layout with a mesh axis keeps the bisection, round for round
    other, ostats = _weighted_stream(**bisecting)
    assert other == digest
    for st, ot in zip(stats, ostats):
        assert int(ot.hindex_sorts) == 0
        assert int(ot.remove_bisect_steps) + int(ot.insert_bisect_steps) \
            > 0
        assert (int(ot.remove_rounds), int(ot.insert_rounds)) == \
            (int(st.remove_rounds), int(st.insert_rounds))


# sha256 of the unified unweighted program's lowered text at the small
# shape of ``test_unweighted_program_text_is_unchanged``. A change meant
# for the unweighted program updates it; a weighted-only change may not.
UNWEIGHTED_TEXT = (
    "589edfda3457898ffd57f4b379d00bbdd7b485850e5768d51988c0e52e6041de")


def test_unweighted_program_text_is_unchanged():
    n, cap, lanes = 48, 256, 8
    lane_i = jnp.zeros(lanes, jnp.int32)
    lane_ok = jnp.zeros(lanes, bool)
    text = apply_batch.lower(
        jnp.zeros(cap, jnp.int32), jnp.zeros(cap, jnp.int32),
        jnp.zeros(cap, bool), jnp.zeros(n, jnp.int32),
        jnp.zeros(n, jnp.int64), jnp.int32(0),
        lane_i, lane_i, lane_ok, lane_i, lane_i, lane_ok,
        n=n, n_levels=4, active_cap=128,
    ).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == UNWEIGHTED_TEXT
