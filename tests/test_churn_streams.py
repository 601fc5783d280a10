"""Cross-engine churn harness: long balanced insert/remove/re-insert
streams through EVERY engine configuration — host / unified / sharded,
plus the sharded engine's range-sharded vertex layout, hierarchical
free-list, and sparse frontier-exchange variants, and the fused Pallas
stat-kernel backend on both device engines — pinned bit-identical
to each other and to the sequential oracle. This is the differential
lockdown of the in-program free-list slot recycler, the per-shard
high-water window, and the vertex-layout layer (sparse frontier
overflow fallback included — see the triangle boundary test).

The claims under test (docs/DESIGN.md §4.1–§4.2):

* heavy recycled-slot traffic (just-removed re-insertion, same-batch
  remove+re-insert, duplicate dirt) never desynchronizes cores OR
  k-order labels between any two engine configurations — including
  ``vertex_sharding="range"``, whose per-round exchanges are owned
  stat slices + bitmasks rather than full vertex arrays;
* the hierarchical free-list ranking (one scalar per shard instead of
  the windowed dead-mask all_gather) allocates the IDENTICAL LIVE EDGE
  SET — and, core numbers never depending on slot positions, identical
  cores and labels — as the interleaved ranking;
* with flat live edges, capacity never grows after warm-up and the slot
  high-water mark is bounded by the running max of the live count (the
  recycling invariant) — host-side defrag never fires on device engines;
* ``validate=False`` masked rows consume no slots and leave
  ``live_edges`` / ``BatchStats`` untouched;
* a save -> load round trip after recycling (tombstones + free-list +
  per-shard high-water marks, all carried by the ``valid`` mask)
  restores an equivalent maintainer on 1 and 8 forced host devices;
* a batch that must defrag AND grow places the sharded buffers exactly
  once (regression: the old compact-then-grow path placed them twice);
* the weighted h-index configs ride the same dirty stream: on the unit
  weights ``apply_batch`` defaults to they match every unweighted
  config's cores, and under RANDOM integer weights both weighted
  configs stay pinned to ``weighted_core_oracle`` (first-occurrence
  duplicate weights, live re-insert no-ops, same-batch remove+insert
  roundtrips committing the new weight) on 1 and 8 forced devices.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

try:  # the fuzz variant needs hypothesis; the deterministic harness not
    from hypothesis import HealthCheck, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.api import CoreMaintainer
from repro.core.oracle import OrderCoreMaintainer, bz_from_csr
from repro.core.weighted import weighted_core_oracle
from repro.graph.csr import build_csr
from repro.graph.generators import erdos_renyi
from repro.graph.stream import churn_stream

ENGINES = ("host", "unified", "sharded")

# every engine CONFIGURATION the differential harness pins bit-identical:
# the three engines plus the sharded engine's vertex-layout / free-list /
# frontier-exchange variants (CoreMaintainer kwargs per name)
CONFIGS = {
    "host": dict(engine="host"),
    "unified": dict(engine="unified"),
    "sharded": dict(engine="sharded"),
    "vertex_range": dict(engine="sharded", vertex_sharding="range"),
    "freelist_hier": dict(engine="sharded", freelist="hierarchical"),
    "frontier_sparse": dict(engine="sharded", vertex_sharding="range",
                            frontier_exchange="sparse"),
    # the 2-axis halo layout (edge x vertex mesh; degenerate (1, 1) on a
    # single device) — the owner-range working set plus halo must stay
    # bit-identical to every flat layout
    "vertex_halo": dict(engine="sharded", vertex_sharding="halo"),
    # the fused Pallas stat kernels (kernels/coremaint.py) — interpret
    # mode off-TPU, so this runs (and must stay bit-identical) everywhere
    "pallas": dict(engine="unified", kernel_backend="pallas"),
    "pallas_sharded": dict(engine="sharded", kernel_backend="pallas"),
    # the weighted h-index engine: on the unit weights apply_batch
    # defaults to, weighted coreness degenerates to plain coreness, so
    # these rows ride the SAME dirty stream and must match every other
    # config's CORES. Labels are compared only among the weighted
    # configs: weighted maintenance freezes labels through the fixpoints
    # and renumbers once per batch, a deliberately different (equally
    # valid) k-order schedule than the order-based engines'.
    "weighted": dict(engine="unified", weighted=True),
    "weighted_sharded": dict(engine="sharded", weighted=True),
}

# configs whose labels follow the weighted renumber-once-per-batch
# schedule rather than the order-based one
WEIGHTED_CONFIGS = ("weighted", "weighted_sharded")


def _norm(edges) -> list:
    """Normalized (lo, hi) tuples of an [k, 2] edge array."""
    return [
        (int(min(a, b)), int(max(a, b))) for a, b in np.asarray(edges)
    ]


def _effective_delta(live, ins, rm):
    """Replay one dirty event with apply_batch semantics on a host-side
    live-set mirror: removals first, then first-occurrence-deduped
    insertions. Returns the clean (inserted, removed) lists the
    sequential oracle (which rejects duplicate edits) can consume."""
    removed = []
    for e in _norm(rm):
        if e in live:
            live.discard(e)
            removed.append(e)
    inserted = []
    for e in _norm(ins):
        if e[0] != e[1] and e not in live:
            live.add(e)
            inserted.append(e)
    return inserted, removed


def _run_churn_differential(m0, graph_seed, stream_seed, n_batches,
                            batch_size, p_reinsert):
    """Every engine sees the same dirty churn events; after every event
    all three agree bit-exactly (cores AND labels) with each other, with
    BZ from scratch, and with the sequential order-based oracle fed the
    clean effective delta."""
    n = 24
    g = erdos_renyi(n, m0, seed=graph_seed)
    cap = 4 * g.m + 64
    ms = {
        e: CoreMaintainer.from_graph(g, capacity=cap, **kw)
        for e, kw in CONFIGS.items()
    }
    caps0 = {e: m.capacity for e, m in ms.items()}
    oracle = OrderCoreMaintainer(n, g.edge_array())
    live = set(_norm(g.edge_array()))
    hwm_bound = len(live)  # running max of the live count
    for ev in churn_stream(g, n_batches, batch_size, seed=stream_seed,
                           p_reinsert=p_reinsert):
        stats = {
            e: m.apply_batch(insert_edges=ev.edges, remove_edges=ev.removals)
            for e, m in ms.items()
        }
        inserted, removed = _effective_delta(live, ev.edges, ev.removals)
        oracle.remove_batch(np.asarray(removed).reshape(-1, 2))
        oracle.insert_batch(np.asarray(inserted).reshape(-1, 2))
        hwm_bound = max(hwm_bound, len(live))
        expect = bz_from_csr(
            build_csr(n, np.asarray(sorted(live), dtype=np.int64))
        )
        u = ms["unified"]
        np.testing.assert_array_equal(u.cores(), expect)
        np.testing.assert_array_equal(u.cores(), oracle.core)
        for e in CONFIGS:
            if e == "unified":
                continue
            np.testing.assert_array_equal(u.cores(), ms[e].cores(), e)
            if e not in WEIGHTED_CONFIGS:
                np.testing.assert_array_equal(u.labels(), ms[e].labels(), e)
        # the weighted configs' labels follow their own (shared)
        # renumber-once-per-batch schedule — identical to each other
        np.testing.assert_array_equal(
            ms["weighted"].labels(), ms["weighted_sharded"].labels()
        )
        for e, st_ in stats.items():
            assert int(st_.n_inserted) == len(inserted), e
            assert int(st_.n_removed) == len(removed), e
        # the order-based engines run the same promotion passes: equal
        # FORWARD / EVICT wave counts across engines, layouts and kernel
        # backends; the weighted engines run no such waves
        for e, st_ in stats.items():
            got = (int(st_.forward_waves), int(st_.evict_waves))
            if e in WEIGHTED_CONFIGS:
                assert got == (0, 0), e
            else:
                want = stats["unified"]
                assert got == (int(want.forward_waves),
                               int(want.evict_waves)), e
        # the recycling invariant: the slot high-water mark never outruns
        # the running max of the live count (holes are filled first)
        assert int(stats["unified"].high_water) <= hwm_bound
        assert int(u.n_edges) == u.live_edges == len(live)
        # both free-list rankings allocate the identical live set (slot
        # POSITIONS may differ across shards; the keys may not)
        for e in ("sharded", "vertex_range", "freelist_hier",
                  "frontier_sparse", "vertex_halo", "pallas_sharded",
                  "weighted", "weighted_sharded"):
            assert ms[e].edge_slot.keys() == u.edge_slot.keys(), e
    # balanced stream + generous initial capacity: nothing may grow
    for e, m in ms.items():
        assert m.capacity == caps0[e], e


@pytest.mark.parametrize(
    "params",
    [
        # (m0, graph_seed, stream_seed, n_batches, batch_size, p_reinsert)
        (60, 0, 1, 4, 12, 0.6),   # mixed fresh/recycled traffic
        (45, 7, 3, 3, 8, 1.0),    # every insert re-inserts a removal
        (90, 2, 9, 3, 16, 0.3),   # denser graph, mostly fresh inserts
    ],
)
def test_churn_engines_bit_identical(params):
    _run_churn_differential(*params)


if HAVE_HYPOTHESIS:

    @st.composite
    def churn_params(draw):
        # n is held fixed so the whole hypothesis run shares one jit
        # cache per (batch-bucket, window-bucket) pair; the graph, the
        # stream shape, and the dirt all vary through the seeds
        m0 = draw(st.integers(min_value=40, max_value=90))
        graph_seed = draw(st.integers(min_value=0, max_value=2**31 - 1))
        stream_seed = draw(st.integers(min_value=0, max_value=2**31 - 1))
        n_batches = draw(st.integers(min_value=2, max_value=4))
        batch_size = draw(st.sampled_from([8, 12, 16]))
        p_reinsert = draw(st.sampled_from([0.3, 0.6, 1.0]))
        return m0, graph_seed, stream_seed, n_batches, batch_size, p_reinsert

    @given(churn_params())
    @settings(
        max_examples=8,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow,
                               HealthCheck.data_too_large],
    )
    def test_churn_engines_bit_identical_fuzz(params):
        _run_churn_differential(*params)


def _weighted_oracle_state(n, live):
    """Exact weighted cores of a (lo, hi) -> weight live-set mirror."""
    if not live:
        return np.zeros(n, dtype=np.int64)
    edges = np.asarray(sorted(live), dtype=np.int64)
    weights = np.asarray([live[tuple(e)] for e in edges], dtype=np.int64)
    return weighted_core_oracle(n, edges, weights)


def _run_weighted_churn_differential(m0, graph_seed, stream_seed,
                                     n_batches, batch_size, max_w):
    """The weighted twin of ``_run_churn_differential``: both weighted
    engine configs see the same dirty churn stream with RANDOM integer
    weights on every insert list; after every event their cores match
    the numpy peeling oracle on a host-side live-set mirror that pins
    the engine's weight semantics — removals first, first occurrence
    of an in-batch duplicate wins, re-inserting a live edge keeps the
    stored weight, and remove+re-insert in ONE batch lands the new
    weight (the same-batch roundtrip path)."""
    n = 24
    g = erdos_renyi(n, m0, seed=graph_seed)
    rng = np.random.default_rng(stream_seed + 1)
    w0 = rng.integers(1, max_w + 1, g.m)
    cap = 4 * g.m + 64
    ms = {
        e: CoreMaintainer.from_graph(g, capacity=cap, weights=w0,
                                     **CONFIGS[e])
        for e in WEIGHTED_CONFIGS
    }
    live = {e: int(w) for e, w in zip(_norm(g.edge_array()), w0)}
    np.testing.assert_array_equal(
        ms["weighted"].cores(), _weighted_oracle_state(n, live)
    )
    for ev in churn_stream(g, n_batches, batch_size, seed=stream_seed):
        iw = rng.integers(1, max_w + 1, len(ev.edges))
        stats = {
            e: m.apply_batch(insert_edges=ev.edges,
                             remove_edges=ev.removals,
                             insert_weights=iw)
            for e, m in ms.items()
        }
        # host mirror of the engine's batch semantics: removals first,
        # then insertions in order with duplicate/live rows skipped (so
        # the first occurrence's weight sticks and a same-batch
        # remove+insert roundtrip commits the new weight)
        removed = 0
        for e in _norm(ev.removals):
            if live.pop(e, None) is not None:
                removed += 1
        inserted = 0
        for e, w in zip(_norm(ev.edges), iw):
            if e[0] != e[1] and e not in live:
                live[e] = int(w)
                inserted += 1
        expect = _weighted_oracle_state(n, live)
        u = ms["weighted"]
        np.testing.assert_array_equal(u.cores(), expect)
        np.testing.assert_array_equal(
            u.cores(), ms["weighted_sharded"].cores()
        )
        np.testing.assert_array_equal(
            u.labels(), ms["weighted_sharded"].labels()
        )
        for e, st_ in stats.items():
            assert int(st_.n_inserted) == inserted, e
            assert int(st_.n_removed) == removed, e
        # the same rounds on both: the unified program takes each
        # round's h-index from one sort, the sharded one bisects
        assert [(int(st_.remove_rounds), int(st_.insert_rounds))
                for st_ in stats.values()] == [
            (int(stats["weighted"].remove_rounds),
             int(stats["weighted"].insert_rounds))] * len(stats)
        uw, sw = stats["weighted"], stats["weighted_sharded"]
        assert int(uw.hindex_sorts) == \
            int(uw.remove_rounds) + int(uw.insert_rounds)
        assert int(uw.remove_bisect_steps) == int(uw.insert_bisect_steps) \
            == 0
        assert int(sw.hindex_sorts) == 0
        assert int(sw.remove_bisect_steps) + int(sw.insert_bisect_steps) \
            > 0
        assert ms["weighted_sharded"].edge_slot.keys() == \
            u.edge_slot.keys()
        # the stored weight column mirrors the live map exactly
        wcol = np.asarray(u.w)
        for e, slot in u.edge_slot.items():
            assert int(wcol[slot]) == live[e], e


@pytest.mark.parametrize(
    "params",
    [
        # (m0, graph_seed, stream_seed, n_batches, batch_size, max_w)
        (60, 0, 1, 4, 12, 7),   # mixed traffic, spread weights
        (45, 7, 3, 3, 8, 1),    # all-unit weights == unweighted cores
        (90, 2, 9, 3, 16, 13),  # denser graph, heavier weights
    ],
)
def test_weighted_churn_engines_match_oracle(params):
    _run_weighted_churn_differential(*params)


def test_weighted_duplicate_and_same_batch_roundtrip():
    """Pin the weight-commit rules one at a time (against the oracle,
    on both weighted configs): in-batch duplicates keep the FIRST
    occurrence's weight, re-inserting a live edge is a no-op that keeps
    the stored weight, and remove + re-insert in the SAME batch (the
    slot-recycling roundtrip) commits the NEW weight."""
    n = 8
    e0 = np.asarray([[0, 1], [1, 2], [2, 0], [3, 4]], dtype=np.int64)
    w0 = np.asarray([2, 3, 4, 5], dtype=np.int64)
    for config in WEIGHTED_CONFIGS:
        g = build_csr(n, e0)
        m = CoreMaintainer.from_graph(
            g, capacity=64, weights=w0, **CONFIGS[config]
        )
        # weights align with g.edge_array() (build_csr normalizes and
        # sorts), so mirror from the canonical row order
        live = {e: int(w) for e, w in zip(_norm(g.edge_array()), w0)}
        # in-batch duplicate: first occurrence wins
        m.apply_batch(insert_edges=[[4, 5], [4, 5]], insert_weights=[6, 9])
        live[(4, 5)] = 6
        # re-insert of a live edge: no-op, stored weight kept
        m.apply_batch(insert_edges=[[0, 1]], insert_weights=[9])
        # same-batch remove + re-insert: the NEW weight lands
        m.apply_batch(insert_edges=[[1, 2]], remove_edges=[[1, 2]],
                      insert_weights=[7])
        live[(1, 2)] = 7
        wcol = np.asarray(m.w)
        for e, slot in m.edge_slot.items():
            assert int(wcol[slot]) == live[e], (config, e)
        np.testing.assert_array_equal(
            m.cores(), _weighted_oracle_state(n, live), config
        )


if HAVE_HYPOTHESIS:

    @st.composite
    def weighted_churn_params(draw):
        # same shape discipline as churn_params (fixed n, pow2 lane
        # buckets shared across examples); weights draw from three
        # regimes — unit (degenerates to plain coreness), narrow, wide
        m0 = draw(st.integers(min_value=40, max_value=90))
        graph_seed = draw(st.integers(min_value=0, max_value=2**31 - 1))
        stream_seed = draw(st.integers(min_value=0, max_value=2**31 - 1))
        n_batches = draw(st.integers(min_value=2, max_value=3))
        batch_size = draw(st.sampled_from([8, 12, 16]))
        max_w = draw(st.sampled_from([1, 5, 13]))
        return m0, graph_seed, stream_seed, n_batches, batch_size, max_w

    @given(weighted_churn_params())
    @settings(
        max_examples=6,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow,
                               HealthCheck.data_too_large],
    )
    def test_weighted_churn_engines_match_oracle_fuzz(params):
        _run_weighted_churn_differential(*params)


@pytest.mark.parametrize("config", tuple(CONFIGS))
def test_capacity_flat_under_balanced_churn(config):
    """Acceptance: >= 50 balanced 50/50 batches on a TIGHT table. After
    warm-up, capacity never grows on any engine configuration; on the
    device engines the in-program recycler absorbs every batch without a
    single host-side defrag, and the high-water mark stays pinned at the
    live count."""
    engine = CONFIGS[config]["engine"]
    g = erdos_renyi(60, 240, seed=2)
    cap = int(g.m * 1.4) + 32  # far less than the stream's gross inserts
    m = CoreMaintainer.from_graph(g, capacity=cap, **CONFIGS[config])
    cap_after_warmup = None
    defrags = 0
    orig = CoreMaintainer._defrag_to

    def counting(self, new_cap):
        nonlocal defrags
        defrags += 1
        return orig(self, new_cap)

    live = set(_norm(g.edge_array()))
    events = list(churn_stream(g, 52, 16, seed=7))
    try:
        CoreMaintainer._defrag_to = counting
        for i, ev in enumerate(events):
            m.apply_batch(insert_edges=ev.edges, remove_edges=ev.removals)
            _effective_delta(live, ev.edges, ev.removals)
            if i == 1:
                cap_after_warmup = m.capacity
                defrags = 0
            if cap_after_warmup is not None:
                assert m.capacity == cap_after_warmup, f"grew at batch {i}"
    finally:
        CoreMaintainer._defrag_to = orig
    if engine != "host":
        # flat live edges -> the free-list recycles every tombstone
        # in-program; the host reclaim path never runs
        assert defrags == 0
        assert int(m.last_batch_stats.high_water) <= len(live) + 1
        assert int(m.n_edges) == len(live)
    assert m.live_edges == len(live)
    expect = bz_from_csr(build_csr(m.n, np.asarray(sorted(live),
                                                   dtype=np.int64)))
    np.testing.assert_array_equal(m.cores(), expect)


@pytest.mark.parametrize(
    "config", ("unified", "sharded", "vertex_range", "frontier_sparse")
)
def test_masked_rows_consume_nothing(config):
    """validate=False drops out-of-range rows BEFORE they can touch the
    device: no slot is consumed, live_edges and n_edges are unchanged,
    and the batch stats count only the surviving rows."""
    g = erdos_renyi(40, 120, seed=5)
    m = CoreMaintainer.from_graph(g, capacity=512, **CONFIGS[config],
                                  validate=False)
    live0 = m.live_edges
    ne0 = int(m.n_edges)
    core0 = m.cores().copy()
    # all rows masked -> the batch degenerates to the empty-batch path
    st_ = m.apply_batch(insert_edges=[[5, 9999], [-1, 3]],
                        remove_edges=[[40, 0], [2, -7]])
    assert int(st_.n_inserted) == 0 and int(st_.n_removed) == 0
    assert int(st_.n_recycled) == 0
    assert m.live_edges == live0 and int(m.n_edges) == ne0
    np.testing.assert_array_equal(m.cores(), core0)
    # mixed batch: only the in-range row lands
    ins = [[0, 39], [0, 40], [-1, 1]]
    already = (0, 39) in m.edge_slot
    st_ = m.apply_batch(insert_edges=ins)
    assert int(st_.n_inserted) == (0 if already else 1)
    assert m.live_edges == live0 + int(st_.n_inserted)
    assert int(m.n_edges) == m.live_edges


@pytest.mark.parametrize("n_triangles", (3, 4, 5))
def test_frontier_sparse_across_overflow_boundary(n_triangles):
    """ACCEPTANCE: the sparse frontier exchange straddling its overflow
    fallback. Removing one edge from each of T disjoint triangles makes
    the FIRST removal round drop exactly 2T vertices (both endpoints of
    every removed edge; the third vertex follows in round 2, and the
    terminating rounds of both fixpoints have EMPTY frontiers). With the
    cap forced to 8, T = 3 / 4 / 5 puts that round's frontier below /
    exactly at / above the cap — the overflowing round takes the
    in-program bitmask fallback — and every regime must stay
    bit-identical (cores AND labels) to the unified engine and the BZ
    oracle, through the re-inserting promotion batch too."""
    T = n_triangles
    n = 3 * T
    edges = np.asarray(
        [e for t in range(T)
         for e in ((3 * t, 3 * t + 1), (3 * t, 3 * t + 2),
                   (3 * t + 1, 3 * t + 2))],
        dtype=np.int64,
    )
    g = build_csr(n, edges)
    mk = dict(capacity=4 * len(edges) + 16)
    mu = CoreMaintainer.from_graph(g, **mk)
    mf = CoreMaintainer.from_graph(
        g, engine="sharded", vertex_sharding="range",
        frontier_exchange="sparse", frontier_cap=8, **mk,
    )
    rm = np.asarray([(3 * t, 3 * t + 1) for t in range(T)], dtype=np.int64)
    for m in (mu, mf):
        m.apply_batch(remove_edges=rm)
    np.testing.assert_array_equal(mu.cores(), mf.cores())
    np.testing.assert_array_equal(mu.labels(), mf.labels())
    gone = set(map(tuple, rm.tolist()))
    live = np.asarray(
        [e for e in map(tuple, edges.tolist()) if e not in gone],
        dtype=np.int64,
    )
    np.testing.assert_array_equal(mu.cores(), bz_from_csr(build_csr(n, live)))
    # re-insert: T whole triangles promote back 1 -> 2 (3T candidates —
    # above the cap again at T=3 already), same bit-identity demands
    for m in (mu, mf):
        m.apply_batch(insert_edges=rm)
    np.testing.assert_array_equal(mu.cores(), mf.cores())
    np.testing.assert_array_equal(mu.labels(), mf.labels())
    np.testing.assert_array_equal(mu.cores(), bz_from_csr(build_csr(n, edges)))


def test_save_load_after_recycling_roundtrip(tmp_path):
    """Tombstones, the implicit free-list, and the high-water bookkeeping
    all ride in the ``valid`` mask: a reload mid-churn (holes present)
    restores an equivalent maintainer under every engine configuration
    and continues bit-identically. The second leg saves FROM the
    range-sharded reader — its padded, vertex-sharded core/label must
    checkpoint unpadded and reload under any layout."""
    g = erdos_renyi(50, 180, seed=1)
    m = CoreMaintainer.from_graph(g, capacity=1024)
    live = set(_norm(g.edge_array()))
    events = list(churn_stream(g, 4, 12, seed=4))
    for ev in events[:3]:
        m.apply_batch(insert_edges=ev.edges, remove_edges=ev.removals)
        _effective_delta(live, ev.edges, ev.removals)
    # punch extra unrecycled holes so the saved state is fragmented
    holes = np.asarray(sorted(live), dtype=np.int64)[:7]
    m.apply_batch(remove_edges=holes)
    _effective_delta(live, np.zeros((0, 2), np.int64), holes)
    p = str(tmp_path / "churned.npz")
    m.save(p)
    loaded = {e: CoreMaintainer.load(p, **kw) for e, kw in CONFIGS.items()}
    val = np.asarray(m.valid)
    hwm = int(np.nonzero(val)[0].max()) + 1
    for e, m2 in loaded.items():
        assert m2.live_ub == len(live), e
        assert m2.hwm_ub == hwm, e  # recomputed exactly from the mask
        assert m2.edge_slot == m.edge_slot, e
    # fragmented save FROM range-sharded vertex state, reload replicated
    p2 = str(tmp_path / "churned_vs.npz")
    loaded["vertex_range"].save(p2)
    loaded["reload_of_vs"] = CoreMaintainer.load(p2)
    assert loaded["reload_of_vs"].core.shape == (g.n,)  # pad stripped
    # everyone (original + reloads) continues identically
    ev = events[3]
    for m2 in (m, *loaded.values()):
        m2.apply_batch(insert_edges=ev.edges, remove_edges=ev.removals)
    _effective_delta(live, ev.edges, ev.removals)
    expect = bz_from_csr(build_csr(m.n, np.asarray(sorted(live),
                                                   dtype=np.int64)))
    np.testing.assert_array_equal(m.cores(), expect)
    for e, m2 in loaded.items():
        np.testing.assert_array_equal(m.cores(), m2.cores(), e)
        if e not in WEIGHTED_CONFIGS:
            np.testing.assert_array_equal(m.labels(), m2.labels(), e)
        assert m2.live_edges == len(live), e
    # the weighted reloads (unit weights recovered from the unweighted
    # checkpoint) share the renumber-once-per-batch label schedule
    np.testing.assert_array_equal(
        loaded["weighted"].labels(), loaded["weighted_sharded"].labels()
    )


def test_compact_then_grow_places_sharded_buffers_once():
    """Regression: one apply_batch that must BOTH defrag and grow used to
    place the sharded buffers twice (_compact placed, then _grow placed
    again). _ensure_capacity now fuses them into a single re-layout."""
    g = erdos_renyi(40, 150, seed=9)
    m = CoreMaintainer.from_graph(g, capacity=g.m + 12, engine="sharded")
    placements = 0
    orig = CoreMaintainer._place_sharded

    def counting(self):
        nonlocal placements
        placements += 1
        return orig(self)

    cap0 = m.capacity
    big = np.asarray(
        [[u, v] for u in range(6) for v in range(u + 1, 40)
         if (u, v) not in m.edge_slot][:40],
        dtype=np.int64,
    )
    try:
        CoreMaintainer._place_sharded = counting
        m.apply_batch(insert_edges=big)  # cannot fit: defrag + grow
    finally:
        CoreMaintainer._place_sharded = orig
    assert m.capacity > cap0
    assert placements == 1, f"sharded buffers placed {placements}x"
    live = set(_norm(g.edge_array())) | set(_norm(big))
    expect = bz_from_csr(build_csr(m.n, np.asarray(sorted(live),
                                                   dtype=np.int64)))
    np.testing.assert_array_equal(m.cores(), expect)
    assert m.live_edges == len(live)


def test_pure_defrag_keeps_capacity():
    """When live edges shrink but the high-water mark stays pinned high
    (a live edge stuck in a top slot above a sea of holes), the
    escalation path defrags WITHOUT growing — _compact demoted to a rare
    defrag, not the reclaim path."""
    g = erdos_renyi(40, 150, seed=3)
    m = CoreMaintainer.from_graph(g, capacity=g.m + 24)
    edges = g.edge_array()
    # remove most edges: live collapses but the top slots stay occupied,
    # so high_water stays ~m while the table is mostly holes
    m.apply_batch(remove_edges=edges[: g.m - 10])
    hw = int(m.last_batch_stats.high_water)
    assert hw == g.m  # top slot still live above the holes
    live = set(_norm(edges[g.m - 10:]))
    # a batch too big for the window above the pinned high-water mark:
    # the exact-bound refresh still crosses the threshold, so the
    # escalation must defrag — but a packed table leaves plenty of room,
    # so capacity must NOT grow
    fresh = []
    for u in range(40):
        for v in range(u + 1, 40):
            if (u, v) not in live and len(fresh) < 30:
                fresh.append((u, v))
    fresh = np.asarray(fresh, dtype=np.int64)
    defrags = 0
    orig = CoreMaintainer._defrag_to

    def counting(self, new_cap):
        nonlocal defrags
        defrags += 1
        return orig(self, new_cap)

    cap0 = m.capacity
    try:
        CoreMaintainer._defrag_to = counting
        m.apply_batch(insert_edges=fresh)
    finally:
        CoreMaintainer._defrag_to = orig
    live |= set(_norm(fresh))
    assert defrags == 1
    assert m.capacity == cap0
    assert int(m.last_batch_stats.high_water) <= len(live)
    expect = bz_from_csr(build_csr(m.n, np.asarray(sorted(live),
                                                   dtype=np.int64)))
    np.testing.assert_array_equal(m.cores(), expect)
    assert m.live_edges == len(live)


_ROUNDTRIP_8DEV = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax

    import repro  # enables x64
    from repro.core.api import CoreMaintainer
    from repro.core.oracle import bz_from_csr
    from repro.graph.csr import build_csr
    from repro.graph.generators import erdos_renyi
    from repro.graph.stream import churn_stream

    assert len(jax.devices()) == 8, jax.devices()
    g = erdos_renyi(83, 320, seed=1)  # n % 8 != 0: vertex pad in play
    ms = CoreMaintainer.from_graph(g, capacity=645, engine="sharded")
    mu = CoreMaintainer.from_graph(g, capacity=645, engine="unified")
    mv = CoreMaintainer.from_graph(g, capacity=645, engine="sharded",
                                   vertex_sharding="range")
    mh = CoreMaintainer.from_graph(g, capacity=645, engine="sharded",
                                   freelist="hierarchical")
    # sparse frontier exchange with a deliberately TINY forced cap: the
    # per-round frontiers of a 24-edit churn batch straddle it, so the
    # stream exercises both cond arms on a real 8-shard mesh
    mf = CoreMaintainer.from_graph(g, capacity=645, engine="sharded",
                                   vertex_sharding="range",
                                   frontier_exchange="sparse",
                                   frontier_cap=4)
    # the fused Pallas stat kernels under a REAL 8-shard mesh (interpret
    # mode off-TPU): local partials swap in, the collective schedule does
    # not change, so cores AND labels must track the lax engines exactly
    mp = CoreMaintainer.from_graph(g, capacity=645, engine="sharded",
                                   kernel_backend="pallas")
    # 2-axis halo meshes: both proper edge x vertex factorizations of the
    # same 8 devices (one on each kernel backend) plus BOTH degenerate
    # shapes — (1, 8) is pure vertex sharding, (8, 1) pure edge sharding
    # — all of which must track the flat engines bit-exactly
    mh42 = CoreMaintainer.from_graph(g, capacity=645, engine="sharded",
                                     vertex_sharding="halo",
                                     mesh_shape=(4, 2))
    mh24 = CoreMaintainer.from_graph(g, capacity=645, engine="sharded",
                                     vertex_sharding="halo",
                                     mesh_shape=(2, 4),
                                     kernel_backend="pallas")
    mh18 = CoreMaintainer.from_graph(g, capacity=645, engine="sharded",
                                     vertex_sharding="halo",
                                     mesh_shape=(1, 8))
    mh81 = CoreMaintainer.from_graph(g, capacity=645, engine="sharded",
                                     vertex_sharding="halo",
                                     mesh_shape=(8, 1))
    halos = (mh42, mh24, mh18, mh81)
    assert ms.capacity % 8 == 0, ms.capacity
    assert mv.core.shape == (88,)  # padded to the shard multiple

    def norm(edges):
        return [(int(min(a, b)), int(max(a, b))) for a, b in edges]

    live = set(norm(g.edge_array()))
    events = list(churn_stream(g, 8, 24, seed=5))
    for ev in events[:6]:
        waves = set()
        for m in (ms, mu, mv, mh, mf, mp, *halos):
            st = m.apply_batch(insert_edges=ev.edges,
                               remove_edges=ev.removals)
            waves.add((int(st.insert_rounds), int(st.forward_waves),
                       int(st.evict_waves)))
        # the wave counters count the same passes on every layout, mesh
        # and kernel backend
        assert len(waves) == 1, waves
        for e in norm(ev.removals):
            live.discard(e)
        for e in norm(ev.edges):
            if e[0] != e[1]:
                live.add(e)
        # range-sharded vertex state, the hierarchical free-list, and
        # the overflow-straddling sparse frontier exchange stay
        # bit-identical to the replicated interleaved engine mid-stream
        np.testing.assert_array_equal(mu.cores(), mv.cores())
        np.testing.assert_array_equal(mu.labels(), mv.labels())
        np.testing.assert_array_equal(mu.cores(), mh.cores())
        np.testing.assert_array_equal(mu.labels(), mh.labels())
        np.testing.assert_array_equal(mu.cores(), mf.cores())
        np.testing.assert_array_equal(mu.labels(), mf.labels())
        np.testing.assert_array_equal(mu.cores(), mp.cores())
        np.testing.assert_array_equal(mu.labels(), mp.labels())
        for hm in halos:
            np.testing.assert_array_equal(mu.cores(), hm.cores())
            np.testing.assert_array_equal(mu.labels(), hm.labels())
        # hierarchical ranks (shard, slot): slot POSITIONS may differ
        # from the interleaved engines, the LIVE SET may not
        assert mh.edge_slot.keys() == mu.edge_slot.keys()
    # flat live edges on a tight table: nobody grew, slots recycled
    assert ms.capacity == 648 and mu.capacity == 645
    assert int(ms.last_batch_stats.n_recycled) > 0
    assert int(mh.last_batch_stats.n_recycled) > 0
    # per-shard window bound: densest shard stays far under local cap
    assert int(ms.last_batch_stats.high_water) <= -(-len(live) // 8) + 24

    p = "/tmp/churn_8dev_roundtrip.npz"
    ms.save(p)
    pv = "/tmp/churn_8dev_roundtrip_vs.npz"
    mv.save(pv)  # fragmented save FROM range-sharded (padded) state
    m2 = CoreMaintainer.load(p, engine="sharded")   # re-strided over 8
    m3 = CoreMaintainer.load(p, engine="unified")
    m4 = CoreMaintainer.load(pv, engine="sharded", vertex_sharding="range")
    m5 = CoreMaintainer.load(pv, engine="unified")
    assert m5.core.shape == (g.n,)  # the phantom pad never leaks out
    assert m2.edge_slot.keys() == m3.edge_slot.keys() == {
        tuple(e) for e in live
    }
    assert m4.edge_slot.keys() == m5.edge_slot.keys() == {
        tuple(e) for e in live
    }
    for ev in events[6:]:
        for m in (ms, mu, mv, mh, mf, mp, *halos, m2, m3, m4, m5):
            m.apply_batch(insert_edges=ev.edges, remove_edges=ev.removals)
        for e in norm(ev.removals):
            live.discard(e)
        for e in norm(ev.edges):
            if e[0] != e[1]:
                live.add(e)
    expect = bz_from_csr(build_csr(g.n, np.asarray(sorted(live),
                                                   dtype=np.int64)))
    for name, m in (("sharded", ms), ("unified", mu),
                    ("vertex-range", mv), ("freelist-hier", mh),
                    ("frontier-sparse", mf), ("pallas-sharded", mp),
                    ("halo-4x2", mh42), ("halo-2x4-pallas", mh24),
                    ("halo-1x8", mh18), ("halo-8x1", mh81),
                    ("reload-sharded", m2), ("reload-unified", m3),
                    ("reload-vertex-range", m4), ("reload-vs-unified", m5)):
        np.testing.assert_array_equal(m.cores(), expect, err_msg=name)
        np.testing.assert_array_equal(m.labels(), ms.labels(), err_msg=name)
        assert m.live_edges == len(live), name
    print("churn-roundtrip-8dev OK")
    """
)


_WEIGHTED_8DEV = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax

    import repro  # enables x64
    from repro.core.api import CoreMaintainer
    from repro.core.weighted import weighted_core_oracle
    from repro.graph.csr import build_csr
    from repro.graph.generators import erdos_renyi
    from repro.graph.stream import churn_stream

    assert len(jax.devices()) == 8, jax.devices()
    n = 40
    g = erdos_renyi(n, 150, seed=4)
    rng = np.random.default_rng(11)
    w0 = rng.integers(1, 9, g.m)
    cap = 4 * g.m + 64
    mk = dict(capacity=cap, weighted=True, weights=w0)
    engines = {
        "unified": CoreMaintainer.from_graph(g, **mk),
        "pallas": CoreMaintainer.from_graph(g, kernel_backend="pallas",
                                            **mk),
        "sharded": CoreMaintainer.from_graph(g, engine="sharded", **mk),
        "range_sparse": CoreMaintainer.from_graph(
            g, engine="sharded", vertex_sharding="range",
            frontier_exchange="sparse", frontier_cap=8, **mk),
        "halo_2x4": CoreMaintainer.from_graph(
            g, engine="sharded", vertex_sharding="halo",
            mesh_shape=(2, 4), **mk),
    }

    def norm(edges):
        return [(int(min(a, b)), int(max(a, b))) for a, b in edges]

    def oracle_state(live):
        if not live:
            return np.zeros(n, dtype=np.int64)
        e = np.asarray(sorted(live), dtype=np.int64)
        w = np.asarray([live[tuple(r)] for r in e], dtype=np.int64)
        return weighted_core_oracle(n, e, w)

    live = {e: int(w) for e, w in zip(norm(g.edge_array()), w0)}
    events = list(churn_stream(g, 6, 16, seed=8))
    for ev in events[:4]:
        iw = rng.integers(1, 9, len(ev.edges))
        for m in engines.values():
            m.apply_batch(insert_edges=ev.edges, remove_edges=ev.removals,
                          insert_weights=iw)
        for e in norm(ev.removals):
            live.pop(e, None)
        for e, w in zip(norm(ev.edges), iw):
            if e[0] != e[1] and e not in live:
                live[e] = int(w)
        expect = oracle_state(live)
        ref = engines["unified"]
        np.testing.assert_array_equal(ref.cores(), expect)
        for name, m in engines.items():
            np.testing.assert_array_equal(ref.cores(), m.cores(),
                                          err_msg=name)
            np.testing.assert_array_equal(ref.labels(), m.labels(),
                                          err_msg=name)
    # save FROM the sharded weighted table mid-churn (holes present),
    # reload under both engines: the weight column rides the checkpoint
    p = "/tmp/weighted_churn_8dev.npz"
    engines["sharded"].save(p)
    engines["reload_unified"] = CoreMaintainer.load(p, weighted=True)
    engines["reload_sharded"] = CoreMaintainer.load(p, weighted=True,
                                                    engine="sharded")
    for ev in events[4:]:
        iw = rng.integers(1, 9, len(ev.edges))
        for m in engines.values():
            m.apply_batch(insert_edges=ev.edges, remove_edges=ev.removals,
                          insert_weights=iw)
        for e in norm(ev.removals):
            live.pop(e, None)
        for e, w in zip(norm(ev.edges), iw):
            if e[0] != e[1] and e not in live:
                live[e] = int(w)
    expect = oracle_state(live)
    ref = engines["unified"]
    np.testing.assert_array_equal(ref.cores(), expect)
    for name, m in engines.items():
        np.testing.assert_array_equal(ref.cores(), m.cores(), err_msg=name)
        np.testing.assert_array_equal(ref.labels(), m.labels(),
                                      err_msg=name)
        wcol = np.asarray(m.w)
        for e, slot in m.edge_slot.items():
            assert int(wcol[slot]) == live[e], (name, e)
    print("weighted-churn-8dev OK")
    """
)


@pytest.mark.slow
def test_weighted_churn_oracle_8dev(tmp_path):
    """8 forced host devices: the weighted engine matrix (unified lax +
    pallas, sharded replicated, range+sparse, 2x4 halo) under random
    integer weights stays pinned to the peeling oracle — cores AND
    labels — through dirty churn and a mid-churn save/load whose
    checkpoint carries the weight column."""
    script = tmp_path / "weighted8.py"
    script.write_text(_WEIGHTED_8DEV)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    out = subprocess.run(
        [sys.executable, str(script)],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "weighted-churn-8dev OK" in out.stdout


@pytest.mark.slow
def test_churn_save_load_roundtrip_8dev(tmp_path):
    """8 forced host devices: recycled-slot churn on a genuinely sharded
    table, then a save -> load round trip (sharded AND unified readers)
    that must keep tracking BZ and the original engines bit-exactly."""
    script = tmp_path / "roundtrip8.py"
    script.write_text(_ROUNDTRIP_8DEV)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    out = subprocess.run(
        [sys.executable, str(script)],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "churn-roundtrip-8dev OK" in out.stdout
