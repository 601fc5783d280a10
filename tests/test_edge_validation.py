"""Edit-path hardening: out-of-range endpoints must never reach the slot
table (where per-edge stat scatters would clamp them onto vertex n-1 and
``live_edges`` would count a phantom slot), and the engines must refuse
to run with x64 disabled (int64 labels / 1<<62 key sentinels corrupt
silently under x32)."""
import numpy as np
import pytest

import jax

from repro.core.api import CoreMaintainer
from repro.core.oracle import bz_from_csr
from repro.graph.generators import erdos_renyi

ENGINES = ("unified", "host", "sharded")


@pytest.fixture(scope="module")
def small_graph():
    return erdos_renyi(8, 12, seed=0)


@pytest.mark.parametrize("engine", ENGINES)
def test_out_of_range_insert_raises(small_graph, engine):
    m = CoreMaintainer.from_graph(small_graph, capacity=64, engine=engine)
    before = m.cores().copy()
    live0 = m.live_edges
    with pytest.raises(ValueError, match="out of range"):
        m.apply_batch(insert_edges=[[5, 999]])
    # no phantom slot, no state corruption
    assert m.live_edges == live0
    np.testing.assert_array_equal(m.cores(), before)
    np.testing.assert_array_equal(m.cores(), bz_from_csr(small_graph))


@pytest.mark.parametrize("engine", ENGINES)
def test_negative_remove_raises(small_graph, engine):
    m = CoreMaintainer.from_graph(small_graph, capacity=64, engine=engine)
    live0 = m.live_edges
    with pytest.raises(ValueError, match="out of range"):
        m.apply_batch(remove_edges=[[-3, 2]])
    with pytest.raises(ValueError, match="out of range"):
        m.remove_edges([[0, 8]])  # n == 8: first out-of-range vertex id
    assert m.live_edges == live0


@pytest.mark.parametrize("engine", ENGINES)
def test_validate_false_masks_instead(small_graph, engine):
    """validate=False drops the offending rows; valid rows in the same
    batch still apply."""
    m = CoreMaintainer.from_graph(
        small_graph, capacity=64, engine=engine, validate=False
    )
    live0 = m.live_edges
    st = m.apply_batch(insert_edges=[[5, 999]], remove_edges=[[-3, 2]])
    assert int(st.n_inserted) == 0
    assert int(st.n_removed) == 0
    assert m.live_edges == live0
    np.testing.assert_array_equal(m.cores(), bz_from_csr(small_graph))
    # mixed good/bad batch: only the good row lands
    g = small_graph
    absent = None
    for u in range(g.n):
        for v in range(u + 1, g.n):
            if not g.has_edge(u, v):
                absent = (u, v)
                break
        if absent:
            break
    st = m.apply_batch(insert_edges=[[7, 100], list(absent)])
    assert int(st.n_inserted) == 1
    assert m.live_edges == live0 + 1
    assert absent in m.edge_slot


@pytest.mark.parametrize("engine", ENGINES)
def test_rejected_mixed_batch_is_atomic(small_graph, engine):
    """A batch with an invalid insert and a VALID removal must be rejected
    whole: the host path applies removals first, so validation has to run
    for both halves before any state changes."""
    m = CoreMaintainer.from_graph(small_graph, capacity=64, engine=engine)
    before = m.cores().copy()
    live0 = m.live_edges
    rm = small_graph.edge_array()[:1]
    with pytest.raises(ValueError, match="out of range"):
        m.apply_batch(insert_edges=[[0, 999]], remove_edges=rm)
    assert m.live_edges == live0  # the valid removal was NOT committed
    assert (int(rm[0, 0]), int(rm[0, 1])) in m.edge_slot
    np.testing.assert_array_equal(m.cores(), before)


def test_host_insert_path_validates(small_graph):
    m = CoreMaintainer.from_graph(small_graph, capacity=64, engine="host")
    with pytest.raises(ValueError, match="out of range"):
        m.insert_edges([[5, 999]])
    assert (5, 999) not in m.edge_slot


def test_x64_guard_fires_loudly(small_graph):
    """Disabling x64 after import must raise with a clear message, not
    silently corrupt the int64 label space."""
    m = CoreMaintainer.from_graph(small_graph, capacity=64)
    try:
        jax.config.update("jax_enable_x64", False)
        with pytest.raises(RuntimeError, match="x64"):
            m.apply_batch(insert_edges=[[0, 1]])
        with pytest.raises(RuntimeError, match="x64"):
            CoreMaintainer.from_graph(small_graph, capacity=64)
        mh = m
        mh.engine = "host"
        with pytest.raises(RuntimeError, match="x64"):
            mh.insert_edges([[0, 1]])
        with pytest.raises(RuntimeError, match="x64"):
            mh.remove_edges(small_graph.edge_array()[:1])
    finally:
        jax.config.update("jax_enable_x64", True)


def test_kernel_backend_accepts_only_lax(small_graph):
    """``from_graph`` still takes ``kernel_backend``, which older
    configurations pass, and allows only the one statistics path: any
    other value is refused by name, and the maintainer keeps no field."""
    m = CoreMaintainer.from_graph(small_graph, capacity=64,
                                  kernel_backend="lax")
    np.testing.assert_array_equal(m.cores(), bz_from_csr(small_graph))
    assert not hasattr(m, "kernel_backend")
    with pytest.raises(ValueError, match="kernel_backend='pallas'"):
        CoreMaintainer.from_graph(small_graph, capacity=64,
                                  kernel_backend="pallas")
