"""The round statistics of ``core/graph_ops.py`` against a NumPy loop over
the slot table written from their definitions (docs/DESIGN.md §2): each
valid slot is one undirected edge and counts once from each endpoint;
an invalid slot counts for nothing, whatever endpoints it still holds.

Per vertex v, over the other endpoint u of every valid slot at v:
  mcd         u with core[u] >= core[v]                 (Def 3.8)
  hi          u with core[u] >  core[v]
  dout_same   u with core[u] == core[v], label[u] > label[v]
  same_in     u with core[u] == core[v] and mask[u]
  din         u with core[u] == core[v], label[u] < label[v] and rp[u]
  wsupport    sum of the slot weights of u with core[u] >= thresh[v]
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph_ops as G


def _random_slot_table(seed, n=150, cap=512):
    """A random COO slot table shaped like the engines': dead slots,
    self-edge-free random endpoints, maintenance-like core/label state,
    a vertex mask, slot weights and per-vertex thresholds."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=cap).astype(np.int32)
    dst = rng.integers(0, n, size=cap).astype(np.int32)
    dst = np.where(dst == src, (dst + 1) % n, dst).astype(np.int32)
    valid = rng.random(cap) < 0.7
    core = rng.integers(0, 6, size=n).astype(np.int32)
    label = rng.integers(0, 1 << 40, size=n).astype(np.int64)
    aux = rng.random(n) < 0.4
    w = rng.integers(1, 9, size=cap).astype(np.int32)
    thresh = (core + rng.integers(-1, 3, size=n)).astype(np.int32)
    return dict(src=src, dst=dst, valid=valid, core=core, label=label,
                aux=aux, w=w, thresh=thresh, n=n)


def _reference(t):
    """Every statistic of the module docstring, by a loop over slots."""
    n = t["n"]
    core, label, aux = t["core"], t["label"], t["aux"]
    out = {k: np.zeros(n, np.int64)
           for k in ("mcd", "hi", "dout", "same_in", "din", "wsupport")}
    for s, d, ok, w in zip(t["src"], t["dst"], t["valid"], t["w"]):
        if not ok:
            continue
        for v, u in ((s, d), (d, s)):
            same = core[u] == core[v]
            out["mcd"][v] += core[u] >= core[v]
            out["hi"][v] += core[u] > core[v]
            out["dout"][v] += same and label[u] > label[v]
            out["same_in"][v] += same and aux[u]
            out["din"][v] += same and label[u] < label[v] and aux[u]
            out["wsupport"][v] += w if core[u] >= t["thresh"][v] else 0
    return out


def _lax(name, t):
    """``(got, want keys)``: the lax statistic's outputs, and the names
    of the reference columns they must equal."""
    j = {k: jnp.asarray(v) for k, v in t.items() if k != "n"}
    n = t["n"]
    src, dst, valid, core, label = (j["src"], j["dst"], j["valid"],
                                    j["core"], j["label"])
    if name == "count_ge":
        return (G.count_ge(src, dst, valid, core, n),), ("mcd",)
    if name == "mcd_hi_dout":
        return (G.mcd_hi_dout(src, dst, valid, core, label, n),
                ("mcd", "hi", "dout"))
    if name == "hi_and_dout_same":
        return (G.hi_and_dout_same(src, dst, valid, core, label, n),
                ("hi", "dout"))
    if name == "count_same_level_in":
        return ((G.count_same_level_in(src, dst, valid, core, j["aux"], n),),
                ("same_in",))
    if name == "din_and_expand":
        din, grow = G.din_and_expand(src, dst, valid, core, label, j["aux"],
                                     n)
        return (din, grow), ("din", "din>0")
    if name == "weighted_support":
        return ((G.weighted_support(src, dst, valid, j["w"], core,
                                    j["thresh"], n),), ("wsupport",))
    raise KeyError(name)


STATS = ("count_ge", "mcd_hi_dout", "hi_and_dout_same",
         "count_same_level_in", "din_and_expand", "weighted_support")


def _check(name, t):
    want = _reference(t)
    got, keys = _lax(name, t)
    assert len(got) == len(keys)
    for g, k in zip(got, keys):
        w = want["din"] > 0 if k == "din>0" else want[k]
        assert np.asarray(g).shape == (t["n"],), (name, k)
        np.testing.assert_array_equal(np.asarray(g), w, err_msg=f"{name}:{k}")
    return want


@pytest.mark.parametrize("seed", [0, 3, 7])
@pytest.mark.parametrize("name", STATS)
def test_stat_matches_the_definition(name, seed):
    want = _check(name, _random_slot_table(seed))
    # the case is not degenerate: every column counts something
    assert all(v.any() for v in want.values()), {
        k: int(v.sum()) for k, v in want.items()}


def test_empty_table_gives_zeros():
    n = 9
    t = _random_slot_table(1, n=n, cap=0)
    for name in STATS:
        got, _ = _lax(name, t)
        for g in got:
            assert np.asarray(g).shape == (n,), name
            assert not np.asarray(g).any(), name


def test_tombstones_and_padding_count_for_nothing():
    """n and capacity of no power of two; the invalid slots keep stale
    endpoints. They contribute nothing: with them the statistics equal
    the definition over the valid slots, and a table of only such slots
    gives zeros."""
    t = _random_slot_table(11, n=77, cap=300)
    assert (~t["valid"]).sum() > 50
    for name in STATS:
        _check(name, t)
    dead = dict(t, valid=np.zeros_like(t["valid"]))
    for name in STATS:
        got, _ = _lax(name, dead)
        for g in got:
            assert not np.asarray(g).any(), name
