"""The benchmark's generators, stream and plain reference, on the CPU."""
import hashlib
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(REPO))

from benchmarks.chip import harness, reference  # noqa: E402

BENCH = harness.Bench.load(REPO)


def _cfg(name, **over):
    cfg = BENCH.config(name)
    cfg.update(over)
    return cfg


def _gen(cfg, seed):
    return BENCH.generator(cfg["generator"]).generate(
        cfg, np.random.default_rng(seed))


@pytest.mark.parametrize("cfg", [_cfg("rmat16", scale=9),
                                 _cfg("ba16", n=600)],
                         ids=["rmat", "ba"])
def test_generator_is_deterministic_and_canonical(cfg):
    n, e = _gen(cfg, 11)
    n2, e2 = _gen(cfg, 11)
    _, e3 = _gen(cfg, 12)
    assert n == n2 and np.array_equal(e, e2)
    assert not np.array_equal(e, e3)
    key = e[:, 0] * n + e[:, 1]
    assert (e[:, 0] < e[:, 1]).all() and (np.diff(key) > 0).all()
    assert e.min() >= 0 and e.max() < n


def test_rmat_is_the_programs_rmat():
    from repro.graph.generators import rmat

    from benchmarks.chip.generators import rmat as chip_rmat

    cfg = _cfg("rmat16", scale=10)
    n, src, dst = chip_rmat.kronecker(cfg, np.random.default_rng(5))
    g = rmat(10, 16 << 10, seed=5)
    assert n == g.n
    assert np.array_equal(chip_rmat.canonical(n, src, dst), g.edge_array())
    # the Graph500 relabelling: the same graph up to vertex labels, with
    # the hubs no longer at the lowest ids
    _, e = _gen(cfg, 5)
    deg = np.bincount(e.ravel(), minlength=n)
    kdeg = np.bincount(g.edge_array().ravel(), minlength=n)
    assert e.shape == g.edge_array().shape
    assert np.array_equal(np.sort(deg), np.sort(kdeg))
    assert not np.array_equal(deg, kdeg)
    assert np.argmax(kdeg) == 0 and np.argmax(deg) != 0


def test_ba_puts_nearly_every_vertex_at_core_k():
    cfg = _cfg("ba16", n=3000)
    n, e = _gen(cfg, 3)
    k = cfg["edges_per_vertex"]
    assert e.shape[0] == k * (n - k)  # k distinct targets per vertex
    core = reference.core_numbers(n, e[:, 0] * n + e[:, 1])
    assert core.max() == k
    assert np.count_nonzero(core == k) >= 0.99 * n


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_matches_the_programs_oracle(seed, weighted):
    from repro.core.oracle import bz_from_csr
    from repro.core.weighted import weighted_core_oracle
    from repro.graph.csr import build_csr

    n, e = _gen(_cfg("rmat16", scale=9), seed)
    rng = np.random.default_rng(seed)
    keep = rng.random(e.shape[0]) < 0.5
    e = e[keep]
    keys = e[:, 0] * n + e[:, 1]
    if not weighted:
        got = reference.core_numbers(n, keys)
        assert np.array_equal(got, bz_from_csr(build_csr(n, e)))
        return
    w = rng.integers(1, 9, size=e.shape[0])
    got = reference.weighted_core_numbers(n, keys, w)
    assert np.array_equal(got, weighted_core_oracle(n, e, w))
    assert got.max() > reference.core_numbers(n, keys).max()
    # unit weights give the classic cores
    assert np.array_equal(reference.weighted_core_numbers(
        n, keys, np.ones_like(w)), reference.core_numbers(n, keys))


SHAPES = {
    "remove_reinsert": {"remove": 40, "remove_from": "live", "insert": 40,
                        "insert_from": "removed", "cycle": 5},
    "insert_only": {"remove": 0, "insert": 40, "insert_from": "absent",
                    "bursts": 12},
    "remove_only": {"remove": 40, "remove_from": "live", "insert": 0,
                    "bursts": 12},
    "expiry": {"remove": 40, "remove_from": "oldest", "insert": 40,
               "insert_from": "absent", "bursts": 12},
}


def _keys(n, e):
    return e[:, 0] * n + e[:, 1]


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
def test_stream_bursts_are_applicable_and_cycle(weighted):
    _check_stream("remove_reinsert", weighted)


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
@pytest.mark.parametrize("shape", ["insert_only", "remove_only", "expiry"])
def test_stream_of_another_shape_is_applicable_and_tracked(shape, weighted):
    _check_stream(shape, weighted)


def _weighted_stream(n, e, traffic, perm, w):
    """The stream over ``e`` with weights ``w``, as the harness builds it;
    fresh pairs draw weights 100 and up, apart from the graph's."""
    draw = np.random.default_rng(7)
    return BENCH.stream("bursts").build(
        n, e, traffic, np.random.default_rng(9), perm, weights=w,
        draw_weights=lambda k: draw.integers(100, 200, size=k))


def _check_stream(shape, weighted=False):
    traffic = dict(SHAPES[shape], stream="bursts")
    n, e = _gen(_cfg("rmat16", scale=9), 4)
    perm = np.random.default_rng(3).permutation(n)
    if weighted:
        w = np.random.default_rng(5).integers(1, 9, size=e.shape[0])
        st = _weighted_stream(n, e, traffic, perm, w)
        e, w = harness.canonical(n, perm[e], w)
        weight = dict(zip(_keys(n, e).tolist(), w.tolist()))
    else:
        st = BENCH.stream("bursts").build(n, e, traffic,
                                          np.random.default_rng(9), perm)
        e = harness.canonical(n, perm[e])  # the graph as the device gets it
    cur = _keys(n, e)

    def check_weights(keys, ins_w):
        """Each key keeps one weight: a graph edge its own, a fresh pair
        the one it first came with, from the fresh law."""
        if not weighted:
            assert ins_w is None
            return
        assert ins_w.shape == keys.shape and (ins_w >= 1).all()
        for key, x in zip(keys.tolist(), ins_w.tolist()):
            assert weight.setdefault(key, x) == x
            if key not in graph:
                assert 100 <= x < 200
        live, live_w = st.live_after(i, weights=True)
        assert np.array_equal(live_w, [weight[k] for k in live.tolist()])

    graph = set(cur.tolist())
    ins, rm, ins_w = st.warmup
    assert (len(rm), len(ins)) == (traffic["remove"], traffic["insert"])
    assert np.isin(_keys(n, rm), cur).all()
    assert not np.isin(_keys(n, ins), cur).any()
    i = -1
    check_weights(_keys(n, ins), ins_w)
    cur = np.union1d(np.setdiff1d(cur, _keys(n, rm)), _keys(n, ins))
    assert np.array_equal(cur, st.live_after(-1))
    for i in range(12):  # past the end of a cycle
        ins, rm, ins_w = st.burst_edges(i)
        ik, rk = _keys(n, ins), _keys(n, rm)
        assert len(np.unique(ik)) == traffic["insert"]
        assert len(np.unique(rk)) == traffic["remove"]
        assert np.isin(rk, cur).all() and not np.isin(ik, cur).any()
        if shape == "expiry":  # the oldest go first: none that came after
            assert not np.isin(rk, _keys(n, st.warmup[0])).any()
        check_weights(ik, ins_w)
        cur = np.union1d(np.setdiff1d(cur, rk), ik)
        assert np.array_equal(cur, st.live_after(i))
        assert cur.size <= st.max_live
    assert st.n_bursts == (None if "cycle" in traffic else 12)


def test_weights_leave_the_unweighted_draws_as_they_are():
    traffic = dict(SHAPES["expiry"], stream="bursts")
    n, e = _gen(_cfg("rmat16", scale=9), 4)
    perm = np.random.default_rng(3).permutation(n)
    a = BENCH.stream("bursts").build(n, e, traffic,
                                     np.random.default_rng(9), perm)
    b = _weighted_stream(n, e, traffic, perm, np.ones(e.shape[0], int))
    for i in range(12):
        for x, y in zip(a.burst_edges(i)[:2], b.burst_edges(i)[:2]):
            assert np.array_equal(x, y)


# sha256 of the relabelled graph, the warm-up and the first three bursts of
# each committed cell under one seed: an edit to the generators, the stream
# or the relabelling that moves a cell's work fails here
PINNED = {
    "rmat16.burst25k":
        "516a2afcba25706180eb5cd3ca78ca085090d4495f81114cb8b9c8f0a68b42e4",
    "ba16.burst25k":
        "caeb534e32f09757bc04966ec6041d6d7e554a5c51abfebed894d9f9ff12fd5c",
}


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_committed_cells_send_the_pinned_bursts(cell):
    c = BENCH.cell(cell)
    cfg, traffic = BENCH.config(c["config"]), BENCH.traffic(c["traffic"])
    n, e = _gen(cfg, cfg["graph_seed"])
    perm = harness.seeded(2**31 + 12345, 2)[0].permutation(n)
    st = BENCH.stream(traffic["stream"]).build(
        n, e, traffic, np.random.default_rng(traffic["stream_seed"]), perm)
    h = hashlib.sha256()
    parts = [harness.canonical(n, perm[e]), *st.warmup[:2]]
    for i in range(3):
        ins, rm, ins_w = st.burst_edges(i)
        assert ins_w is None
        parts += [ins, rm]
    for a in parts:
        h.update(np.ascontiguousarray(a, dtype=np.int64).tobytes())
    assert st.warmup[2] is None and h.hexdigest() == PINNED[cell]


def test_stream_is_the_same_work_under_other_labels():
    traffic = dict(SHAPES["remove_reinsert"], stream="bursts")
    n, e = _gen(_cfg("rmat16", scale=9), 4)
    ident = np.arange(n)
    perm = np.random.default_rng(3).permutation(n)
    a = BENCH.stream("bursts").build(n, e, traffic,
                                     np.random.default_rng(9), ident)
    b = BENCH.stream("bursts").build(n, e, traffic,
                                     np.random.default_rng(9), perm)
    for i in range(-1, 7):
        got = b.live_after(i)
        want = np.sort(_keys(n, harness.canonical(
            n, perm[np.stack([a.live_after(i) // n,
                              a.live_after(i) % n], axis=1)])))
        assert np.array_equal(got, want)
    for i in range(7):
        for x, y in zip(a.burst_edges(i)[:2], b.burst_edges(i)[:2]):
            assert np.array_equal(np.sort(perm[x], axis=1), np.sort(y, axis=1))


def test_stream_refuses_a_burst_the_graph_cannot_hold():
    test_stream_refuses_traffic_it_cannot_send(
        {"remove": 10**6, "insert": 10**6, "insert_from": "removed",
         "cycle": 4})


@pytest.mark.parametrize("traffic", [
    {"remove": 5, "insert": 6, "insert_from": "removed", "bursts": 3},
    {"remove": 5, "insert": 5, "insert_from": "absent", "cycle": 3},
    {"remove": 0, "insert": 0, "bursts": 3},
], ids=["reinsert_mismatch", "cycle_cannot_close", "empty"])
def test_stream_refuses_traffic_it_cannot_send(traffic):
    n, e = _gen(_cfg("rmat16", scale=6), 0)
    with pytest.raises(ValueError):
        BENCH.stream("bursts").build(n, e, traffic, np.random.default_rng(0),
                                     np.arange(n))


def test_reference_readings_count_each_fault():
    n = 6
    live = np.array([0 * n + 1, 1 * n + 2, 0 * n + 2, 3 * n + 4])
    core = reference.core_numbers(n, live)
    assert core.tolist() == [2, 2, 2, 1, 1, 0]
    lo, hi = live // n, live % n
    label = np.arange(n)
    state = {"src": lo, "dst": hi, "valid": np.ones(4, bool), "core": core,
             "label": label, "n_edges": 4}
    ok = reference.readings(n, state, live, core, 0,
                            probe=(core, label, live, core))
    assert reference.verdict(ok) and set(ok) == set(reference.LIMITS)
    r = reference.readings(n, state, live, core, 0,
                           probe=(core - 1, label, live, core))
    assert r["probe_core_mismatch"] == n and not reference.verdict(r)
    bad = dict(state, valid=np.array([True, True, True, False]), n_edges=3)
    r = reference.readings(n, bad, live, core, 2)
    assert r["slot_table_diff"] == 1 and r["n_edges_diff"] == 1
    assert r["burst_count_diff"] == 2 and not reference.verdict(r)
    r = reference.readings(n, dict(state, core=core + 1), live, core, 0)
    assert r["core_mismatch"] == n
    # cores of 0 with neighbours: every dout exceeds its core
    r = reference.readings(n, dict(state, core=np.zeros(n, np.int64)),
                           live, core, 0)
    assert r["certificate_violations"] > 0


def test_readings_are_the_named_checks_and_weigh_the_slot_table():
    n = 6
    live = np.array([0 * n + 1, 0 * n + 2, 1 * n + 2, 3 * n + 4])
    live_w = np.array([3, 1, 2, 5])
    core = reference.weighted_core_numbers(n, live, live_w)
    assert core.tolist() == [3, 3, 3, 5, 5, 0]
    lo, hi = live // n, live % n
    state = {"src": hi, "dst": lo, "valid": np.ones(4, bool), "core": core,
             "label": np.arange(n), "n_edges": 4, "w": live_w}
    names = ("core_mismatch", "slot_table_diff", "n_edges_diff",
             "burst_count_diff", "probe_core_mismatch")
    ok = reference.readings(n, state, live, core, 0, checks=names,
                            live_w=live_w,
                            probe=(core, state["label"], live, core))
    assert tuple(ok) == names and reference.verdict(ok)
    # the right edge under a wrong weight: one pair extra, one missing
    r = reference.readings(n, dict(state, w=live_w + [0, 0, 1, 0]), live,
                           core, 0, checks=names, live_w=live_w)
    assert r["slot_table_diff"] == 2 and "probe_core_mismatch" not in r
    # an edge held twice, under its own weight and another
    two = {k: np.concatenate([v, v[:1]]) for k, v in state.items()
           if k in ("src", "dst", "valid")}
    r = reference.readings(n, dict(state, **two, w=np.append(live_w, 9)),
                           live, core, 0, checks=names, live_w=live_w)
    assert r["slot_table_diff"] == 2
    # unweighted, the weight column is not read
    r = reference.readings(n, dict(state, w=live_w + 1), live, core, 0,
                           checks=names)
    assert r["slot_table_diff"] == 0
