"""Pallas kernels vs pure-jnp oracles, interpret=True, shape/dtype sweeps."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import graph_ops as G
from repro.kernels import ref
from repro.kernels.segment_ell import ell_aggregate, ell_stat
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fm_interaction import fm_interaction
from repro.graph.csr import ell_from_csr
from repro.graph.generators import erdos_renyi


def _random_ell(n, max_deg, seed):
    rng = np.random.default_rng(seed)
    deg = rng.integers(0, max_deg + 1, size=n)
    nbrs = np.full((n, max_deg), n, dtype=np.int32)
    for v in range(n):
        nbrs[v, : deg[v]] = rng.integers(0, n, size=deg[v])
    return jnp.asarray(nbrs)


@pytest.mark.parametrize("n,max_deg", [(64, 8), (300, 17), (1024, 33), (7, 3)])
@pytest.mark.parametrize("op", ["count_ge", "count_gt", "sum", "max"])
def test_ell_stat_sweep(n, max_deg, op):
    nbrs = _random_ell(n, max_deg, seed=n + max_deg)
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.integers(0, 50, size=n), dtype=jnp.int32)
    got = ell_stat(nbrs, vals, vals, op=op, interpret=True)
    want = ref.ell_stat_ref(nbrs, vals, vals, op=op)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("op", ["sum", "max"])
def test_ell_aggregate_sweep(dtype, op):
    n, max_deg, f = 200, 12, 16
    nbrs = _random_ell(n, max_deg, seed=5)
    rng = np.random.default_rng(1)
    feats = jnp.asarray(rng.normal(size=(n, f)), dtype=dtype)
    got = ell_aggregate(nbrs, feats, op=op, interpret=True)
    want = ref.ell_aggregate_ref(nbrs, feats, op=op)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(want, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-6,
        atol=1e-2 if dtype == jnp.bfloat16 else 1e-6,
    )


def test_ell_stat_mcd_matches_real_graph():
    """mcd via the kernel == mcd by definition on a real graph."""
    g = erdos_renyi(150, 600, seed=3)
    ell = ell_from_csr(g)
    rng = np.random.default_rng(2)
    core = rng.integers(0, 10, size=g.n).astype(np.int32)
    got = ell_stat(
        jnp.asarray(ell.nbrs), jnp.asarray(core), jnp.asarray(core),
        op="count_ge", interpret=True,
    )
    want = np.array(
        [
            sum(1 for w in g.neighbors(v) if core[w] >= core[v])
            for v in range(g.n)
        ],
        dtype=np.int32,
    )
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize(
    "b,h,hkv,s,d", [(2, 4, 4, 256, 64), (1, 8, 2, 512, 64), (2, 4, 1, 128, 128)]
)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, h, hkv, s, d, causal):
    rng = np.random.default_rng(b * 100 + h)
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), dtype=jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype=jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype=jnp.float32)
    got = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          interpret=True)
    want = ref.mha_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), dtype=dtype)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), dtype=dtype)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), dtype=dtype)
    got = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                          interpret=True)
    want = ref.mha_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(want, np.float32),
        rtol=3e-2 if dtype == jnp.bfloat16 else 2e-3,
        atol=3e-2 if dtype == jnp.bfloat16 else 2e-3,
    )


@pytest.mark.parametrize("b,f,d", [(64, 39, 10), (1000, 26, 16), (3, 5, 4)])
def test_fm_interaction_sweep(b, f, d):
    rng = np.random.default_rng(b)
    emb = jnp.asarray(rng.normal(size=(b, f, d)), dtype=jnp.float32)
    got = fm_interaction(emb, block_b=256, interpret=True)
    want = ref.fm_interaction_ref(emb)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
    )


# -- segment_ell regressions ------------------------------------------------

@pytest.mark.parametrize("n,max_deg", [(0, 8), (64, 0), (0, 0)])
@pytest.mark.parametrize("op", ["count_ge", "sum", "max"])
def test_ell_stat_zero_grid(n, max_deg, op):
    """Regression: n == 0 or max_deg == 0 used to launch a zero-sized
    grid, returning an UNINITIALIZED output buffer. Both entry points
    must short-circuit to explicit zeros."""
    nbrs = jnp.full((n, max_deg), n, dtype=jnp.int32)
    vals = jnp.zeros((n,), dtype=jnp.int32)
    got = ell_stat(nbrs, vals, vals, op=op, interpret=True)
    assert got.shape == (n,)
    np.testing.assert_array_equal(np.asarray(got), np.zeros(n, np.int32))


@pytest.mark.parametrize("n,max_deg", [(0, 8), (64, 0), (0, 0)])
@pytest.mark.parametrize("op", ["sum", "max"])
def test_ell_aggregate_zero_grid(n, max_deg, op):
    nbrs = jnp.full((n, max_deg), n, dtype=jnp.int32)
    feats = jnp.zeros((n, 16), dtype=jnp.float32)
    got = ell_aggregate(nbrs, feats, op=op, interpret=True)
    assert got.shape == (n, 16)
    np.testing.assert_array_equal(
        np.asarray(got), np.zeros((n, 16), np.float32)
    )


def test_ell_stat_max_isolated_vertex_is_zero():
    """Regression: op="max" rows with NO live neighbor slots used to leak
    the running-max sentinel (INT32_MIN) instead of the documented
    identity 0. Negative values make any leak (sentinel OR a stale
    accumulator) visible."""
    n, max_deg = 96, 8
    nbrs = np.full((n, max_deg), n, dtype=np.int32)  # all padding
    nbrs[0, :3] = [1, 2, 3]  # one connected row as a control
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.integers(-50, -1, size=n), dtype=jnp.int32)
    got = np.asarray(ell_stat(jnp.asarray(nbrs), vals, vals, op="max",
                              interpret=True))
    want = np.asarray(ref.ell_stat_ref(jnp.asarray(nbrs), vals, vals,
                                       op="max"))
    np.testing.assert_array_equal(got, want)
    assert got[0] == max(int(vals[i]) for i in (1, 2, 3))
    np.testing.assert_array_equal(got[1:], np.zeros(n - 1, np.int32))


def test_ell_aggregate_max_isolated_vertex_is_zero():
    n, max_deg, f = 80, 6, 8
    nbrs = np.full((n, max_deg), n, dtype=np.int32)
    nbrs[0, :2] = [1, 2]
    rng = np.random.default_rng(1)
    feats = jnp.asarray(-1.0 - rng.random((n, f)), dtype=jnp.float32)
    got = np.asarray(ell_aggregate(jnp.asarray(nbrs), feats, op="max",
                                   interpret=True))
    want = np.asarray(ref.ell_aggregate_ref(jnp.asarray(nbrs), feats,
                                            op="max"))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got[1:], np.zeros((n - 1, f), np.float32))
    np.testing.assert_array_equal(
        got[0], np.maximum(np.asarray(feats)[1], np.asarray(feats)[2])
    )


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("op", ["count_ge", "count_gt"])
def test_ell_stat_matches_graph_ops_on_random_graphs(op, seed):
    """Differential: the ELL kernel's count stats == the COO
    segment-sum path (core/graph_ops.py) on random graphs — the two
    traversal layouts must agree on every vertex."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(20, 180))
    m = int(rng.integers(n, 4 * n))
    g = erdos_renyi(n, m, seed=seed + 10)
    ell = ell_from_csr(g)
    edges = g.edge_array()
    src = jnp.asarray(edges[:, 0].astype(np.int32))
    dst = jnp.asarray(edges[:, 1].astype(np.int32))
    valid = jnp.ones((edges.shape[0],), dtype=bool)
    vals = jnp.asarray(rng.integers(0, 12, size=n), dtype=jnp.int32)
    got = ell_stat(jnp.asarray(ell.nbrs), vals, vals, op=op, interpret=True)
    fn = G.count_ge if op == "count_ge" else G.count_gt
    want = fn(src, dst, valid, vals, g.n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
