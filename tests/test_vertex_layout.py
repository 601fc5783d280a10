"""Unit + traffic tests of the vertex-layout layer (core/vertex_layout.py).

Two kinds of claims:

* algebraic — ``HaloShardedVertices`` round-trips owned state through
  the halo working set exactly (bind, regather, stat completion, sparse
  refresh with its overflow fallback — bit-identical at every frontier
  size), and ``ReplicatedVertices`` off a mesh is the identity, so
  layout-generic fixpoint code degenerates to the original
  single-device program verbatim;

* traffic — per FIXPOINT ROUND the halo layout's collectives are one
  bounded all_gather of halo-domain partial stats (O(d_v * halo_cap)
  words), the O(n_owned) ring placement ppermutes, and halo refreshes
  that are either sparse compacted-index gathers (O(cap * d_v) words)
  or a dense reduce_scatter regather (O(halo_cap)); the replicated
  layout psums the full [n]-sized stats to every device. Asserted from
  the trace-time accounting (``record_traffic``): a ``lax.while_loop``
  body traces exactly once, so the records ARE the per-round collective
  budget — the acceptance check of the §4.3/§4.4 traffic model, run
  without executing a single batch. The 8-shard and 2-axis numbers are
  pinned by the slow subprocess test below.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.analysis import cross_check_round, primitive_names
from repro.analysis.programs import trace_removal_round
from repro.core.vertex_layout import (
    HaloShardedVertices,
    ReplicatedVertices,
    make_layout,
    record_traffic,
)


def test_replicated_layout_is_identity_off_mesh():
    lay = ReplicatedVertices(7)
    x = jnp.arange(7, dtype=jnp.int32)
    m = x > 3
    assert lay.complete(x) is x
    assert lay.own(x) is x
    assert lay.gather_mask(m) is m
    assert lay.gather_state(x) is x
    assert bool(lay.any_owned(m))
    np.testing.assert_array_equal(
        np.asarray(lay.add_at(lay.zeros(), jnp.array([1, 1, 6]),
                              jnp.array([2, 3, 4], jnp.int32))),
        np.array([0, 5, 0, 0, 0, 0, 4], np.int32),
    )


def test_make_layout_factory():
    assert make_layout("replicated", 5, None).kind == "replicated"
    lay = make_layout("range", 10, "data", 4)
    assert isinstance(lay, HaloShardedVertices)
    assert lay.kind == "halo" and lay.n_owned == 3 and lay.n_pad == 12
    assert lay.frontier_cap is None and lay.edge_axes == ()
    assert make_layout("range", 10, "data", 4, 8).frontier_cap == 8
    two = make_layout("halo", 10, "data", 2, None, ("edge",))
    assert two.edge_axes == ("edge",) and two.n_owned == 5
    with pytest.raises(ValueError):
        make_layout("range", 5, None)
    with pytest.raises(ValueError):
        make_layout("diagonal", 5, "data")


def test_make_layout_rejects_misconfiguration_at_construction():
    """The replicated layout has no shard ranges and no frontier: a
    silently ignored n_shards/frontier_cap would hide a caller that
    believes it built a sharded or sparse layout — both raise HERE, not
    three layers down at trace time. Same for the range/halo split: the
    1-axis range layout must refuse pure-edge axes, and the 2-axis halo
    layout must refuse to run without them."""
    with pytest.raises(ValueError, match="n_shards"):
        make_layout("replicated", 10, "data", 8)
    with pytest.raises(ValueError, match="frontier_cap"):
        make_layout("replicated", 10, "data", 1, 16)
    with pytest.raises(ValueError, match="edge_axes"):
        make_layout("replicated", 10, "data", 1, None, ("edge",))
    # the sparse bucket must be able to hold at least one index
    with pytest.raises(ValueError, match="frontier_cap"):
        make_layout("range", 10, "data", 2, 0)
    with pytest.raises(ValueError, match="frontier_cap"):
        make_layout("range", 10, "data", 2, -4)
    # range <-> halo are the edge_axes=()/edge_axes=(...) halves
    with pytest.raises(ValueError, match="halo"):
        make_layout("range", 10, "data", 2, None, ("edge",))
    with pytest.raises(ValueError, match="edge axes"):
        make_layout("halo", 10, "data", 2)


def _full_halo_ids(n: int, n_pad: int, hcap: int) -> jnp.ndarray:
    """A 1-shard halo covering every vertex, sentinel-padded to hcap."""
    return jnp.concatenate([
        jnp.arange(n, dtype=jnp.int32),
        jnp.full((hcap - n,), n_pad, dtype=jnp.int32),
    ])


def test_record_traffic_nesting_raises_and_outer_survives():
    """Nested record_traffic() used to silently steal the outer
    context's records; now the inner entry raises and the outer log
    keeps accumulating afterwards, intact."""
    lay = make_layout("range", 16, "data", 1)
    mesh = jax.make_mesh((1,), ("data",))

    def kernel(ids, owned):
        return lay.bind(ids).gather_values(owned)

    sm = shard_map(kernel, mesh=mesh, in_specs=(P(), P("data")),
                   out_specs=P(), check_vma=False)
    ids = _full_halo_ids(16, 16, 16)
    with record_traffic() as outer:
        jax.make_jaxpr(sm)(ids, jnp.zeros(16, jnp.int32))
        n_before = len(outer)
        assert [t.op for t in outer] == ["gather_halo", "regather"]
        with pytest.raises(RuntimeError, match="nest"):
            with record_traffic():
                pass  # pragma: no cover — entry must raise
        # the outer context still owns the log: more records land in it
        # (a different dtype forces a genuinely fresh trace — an
        # identical call could be served from the trace cache)
        jax.make_jaxpr(sm)(ids, jnp.zeros(16, jnp.int64))
        assert len(outer) == n_before + 2
    # fully unwound: a fresh context starts empty and records again
    # (again a fresh dtype, to dodge the trace cache)
    with record_traffic() as log2:
        jax.make_jaxpr(sm)(ids, jnp.zeros(16, jnp.float32))
    assert [t.op for t in log2] == ["gather_halo", "regather"]


def test_halo_session_roundtrips_one_shard():
    """Bind/regather/complete bookkeeping on a 1-shard mesh with n not
    a pow2: halo values are exact images of the owned state, halo-domain
    partial stats complete back to the exact owned sums, and the
    owner-drop scatter-add lands replicated contributions correctly."""
    mesh = jax.make_mesh((1,), ("data",))
    n, hcap = 13, 16
    lay = make_layout("range", n, "data", 1)
    assert lay.n_owned == 13 and lay.n_pad == 13
    all_ids = jnp.arange(n, dtype=jnp.int32)

    def kernel(ids, core, mask):
        sess = lay.bind(ids)
        core_h = sess.gather_values(core)
        pos = sess.locate(all_ids)
        # halo-domain partials: vertex i contributes i at its halo slot
        stats = jnp.zeros(hcap, jnp.int32).at[pos].add(all_ids)
        owned_stats = sess.complete(stats)
        halo_mask, ovf = sess.refresh_mask(mask)
        delta = sess.add_at(sess.zeros(), jnp.array([0, 12, 12]),
                            jnp.array([5, 1, 1], jnp.int32))
        return (core_h, pos, owned_stats, halo_mask, ovf, delta,
                sess.any_owned(mask))

    f = shard_map(
        kernel, mesh=mesh, in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P(), P("data"), P(), P(), P("data"), P()),
        check_vma=False,
    )
    ids = _full_halo_ids(n, lay.n_pad, hcap)
    core = jnp.arange(n, dtype=jnp.int32) * 7 - 3
    mask = (jnp.arange(n) % 3) == 0
    core_h, pos, owned_stats, halo_mask, ovf, delta, some = (
        jax.jit(f)(ids, core, mask))
    np.testing.assert_array_equal(
        np.asarray(core_h)[np.asarray(pos)], np.asarray(core))
    np.testing.assert_array_equal(np.asarray(owned_stats),
                                  np.arange(n, dtype=np.int32))
    np.testing.assert_array_equal(
        np.asarray(halo_mask)[np.asarray(pos)], np.asarray(mask))
    assert not bool(ovf)  # dense refresh never overflows
    assert int(delta[0]) == 5 and int(delta[12]) == 2
    assert bool(some)


def test_per_round_traffic_replicated_vs_range():
    """The acceptance traffic model on a 1-shard mesh: the replicated
    layout psums the full [n, 3] stats each round; the halo layout pays
    a one-time per-batch setup (halo-membership gather + entry
    regathers) and then, per round, ONE bounded halo-stat gather, the
    O(n_owned) ring placement, and a dense O(halo_cap) value regather —
    no [n]-replicated buffer anywhere. (The 8-shard and 2-axis byte
    counts are pinned by the subprocess test.)"""
    n, cap = 24, 32
    mesh = jax.make_mesh((1,), ("data",))

    rep_log, rep_jx = trace_removal_round("replicated", n, cap, mesh)
    rng_log, rng_jx = trace_removal_round("range", n, cap, mesh)
    rep_prims = primitive_names(rep_jx)
    rng_prims = primitive_names(rng_jx)

    # replicated: exactly one vertex collective per round — the [n, 3]
    # int32 psum, every device receiving the full completed stats
    assert [t.op for t in rep_log] == ["psum"]
    assert rep_log[0].recv_bytes == n * 3 * 4
    assert "reduce_scatter" not in rep_prims

    # halo (hcap = n_pad = 24 on this toy: the pow2 bucket clamps to n):
    # setup = membership gather + core/label entry regathers, then the
    # round: stat gather, 5 ring ppermutes, dense core/label refresh,
    # scalar continue-vote
    lay = make_layout("range", n, "data", 1)
    hcap = 24
    assert [t.op for t in rng_log] == (
        ["gather_halo", "regather", "regather"]          # per-batch setup
        + ["gather_stats"] + ["ppermute"] * 5            # round: stats+ring
        + ["regather", "regather", "psum_scalar"]        # round: refresh
    )
    setup, main = rng_log[:3], rng_log[3:]
    assert setup[0].recv_bytes == 1 * hcap * 4           # d_v * hcap ids
    assert (setup[1].recv_bytes, setup[2].recv_bytes) == (
        hcap * 4, hcap * 8)                              # core, label
    assert main[0].recv_bytes == 1 * hcap * 3 * 4        # d_v * hcap * 3
    assert (main[6].recv_bytes, main[7].recv_bytes) == (
        hcap * 4, hcap * 8)                              # dense refresh
    assert all(t.recv_bytes <= lay.n_owned * 2 * 4
               for t in main if t.op == "ppermute")
    # the collective-count cross-check straight off the jaxpr: the halo
    # program really lowers to all_gather + reduce_scatter + ppermute,
    # and contains no full-stat [n]-psum (the only psum is the scalar
    # continue-vote)
    assert {"reduce_scatter", "all_gather", "ppermute"} <= rng_prims
    # and the trace-time accounting above describes the REAL program,
    # collective by collective (op mapping + payload bytes)
    assert cross_check_round(rng_log, rng_jx) == []


@pytest.mark.parametrize("k_mode", ["empty", "cap-1", "cap", "cap+1", "all"])
def test_sparse_refresh_roundtrip_across_overflow_boundary(k_mode):
    """The sparse halo refresh reproduces the dense result EXACTLY at
    every frontier size — empty, below, exactly at, and above the cap
    (where the in-program lax.cond falls back to the dense regather):
    masks AND (core, label) value refreshes, bit for bit."""
    mesh = jax.make_mesh((1,), ("data",))
    n, cap, hcap = 13, 4, 16
    k = {"empty": 0, "cap-1": cap - 1, "cap": cap,
         "cap+1": cap + 1, "all": n}[k_mode]
    lay = make_layout("range", n, "data", 1, frontier_cap=cap)
    all_ids = jnp.arange(n, dtype=jnp.int32)

    def kernel(ids, old_core, old_label, new_core, new_label, changed):
        sess = lay.bind(ids)
        pos = sess.locate(all_ids)
        # stale halo = exact image of the pre-commit state
        core_h = sess.gather_values(old_core)
        label_h = sess.gather_values(old_label)
        halo_mask, m_ovf = sess.refresh_mask(changed)
        core_h, label_h, v_ovf = sess.refresh_values(
            new_core, new_label, changed, core_h, label_h)
        return pos, halo_mask, core_h, label_h, m_ovf, v_ovf

    f = jax.jit(shard_map(
        kernel, mesh=mesh,
        in_specs=(P(),) + (P("data"),) * 5,
        out_specs=(P(), P(), P(), P(), P(), P()), check_vma=False,
    ))
    rng = np.random.default_rng(3 + k)
    changed = np.zeros(n, dtype=bool)
    changed[rng.choice(n, size=k, replace=False)] = True
    old_core = rng.integers(0, 50, n).astype(np.int32)
    old_label = rng.integers(0, 1 << 40, n).astype(np.int64)
    new_core = np.where(changed, old_core + 1, old_core).astype(np.int32)
    new_label = np.where(changed, old_label + 7, old_label).astype(np.int64)

    ids = _full_halo_ids(n, lay.n_pad, hcap)
    pos, halo_mask, core_h, label_h, m_ovf, v_ovf = f(
        ids, jnp.asarray(old_core), jnp.asarray(old_label),
        jnp.asarray(new_core), jnp.asarray(new_label),
        jnp.asarray(changed))
    pos = np.asarray(pos)
    np.testing.assert_array_equal(np.asarray(halo_mask)[pos], changed,
                                  err_msg=f"frontier={k}")
    # the refreshed halo is an exact image of the committed state —
    # sparse path and overflow fallback alike
    np.testing.assert_array_equal(np.asarray(core_h)[pos], new_core,
                                  err_msg=f"frontier={k}")
    np.testing.assert_array_equal(np.asarray(label_h)[pos], new_label,
                                  err_msg=f"frontier={k}")
    assert bool(m_ovf) == (k > cap)
    assert bool(v_ovf) == (k > cap)


def test_per_round_traffic_sparse_frontier():
    """ACCEPTANCE (docs/DESIGN.md §4.3): a sparse halo removal round
    refreshes with THREE O(cap * d_v)-word compacted-index gathers —
    count-prefixed ids, cores, labels — and the dense O(halo_cap)
    regather exists only inside the overflow arm of the per-round
    lax.cond (branch="overflow"); nothing [n]-sized ever moves.
    (The 8-shard byte counts are pinned by the subprocess test.)"""
    n, cap, fcap = 24, 32, 8
    mesh = jax.make_mesh((1,), ("data",))
    log, jaxpr = trace_removal_round("range", n, cap, mesh,
                                     frontier_cap=fcap)
    prims = primitive_names(jaxpr)

    hcap = 24
    main = [t for t in log if t.branch != "overflow"]
    fallback = [t for t in log if t.branch == "overflow"]
    # setup + non-overflow round budget: stats by bounded gather, the
    # refresh as count-prefixed indices — O(cap * d_v) words,
    # n-independent
    assert [t.op for t in main] == (
        ["gather_halo", "regather", "regather"]
        + ["gather_stats"] + ["ppermute"] * 5
        + ["gather_frontier"] * 3 + ["psum_scalar"]
    )
    gi, gc, gl = [t for t in main if t.op == "gather_frontier"]
    assert gi.recv_bytes == 1 * (fcap + 1) * 4  # d_v * (cap+1) words
    assert gc.recv_bytes == 1 * fcap * 4        # d_v * cap int32 cores
    assert gl.recv_bytes == 1 * fcap * 8        # d_v * cap int64 labels
    # the ONLY dense halo regather lives on the overflow branch
    assert [t.op for t in fallback] == ["regather", "regather"]
    assert (fallback[0].recv_bytes, fallback[1].recv_bytes) == (
        hcap * 4, hcap * 8)
    # jaxpr cross-check: all_gathers + reduce_scatters, and the traffic
    # notes match the program collective-by-collective (branch
    # attribution included — the dense regather must sit on the cond's
    # overflow arm in the jaxpr too)
    assert {"reduce_scatter", "all_gather"} <= prims
    assert cross_check_round(log, jaxpr) == []


_TRAFFIC_8DEV = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax

    import repro  # enables x64
    from repro.analysis import cross_check_round
    from repro.analysis.programs import trace_removal_round
    from repro.launch.mesh import make_edge_vertex_mesh

    n, cap, d, fcap, w = 2048, 4096, 8, 8, 16
    hcap = 64  # pow2(2*w + 2*lanes_total) = pow2(64), lanes=8
    mesh = jax.make_mesh((8,), ("data",))
    rep_log, rep_jx = trace_removal_round("replicated", n, cap, mesh,
                                          window=w)
    rng_log, rng_jx = trace_removal_round("range", n, cap, mesh,
                                          window=w)
    sp_log, sp_jx = trace_removal_round("range", n, cap, mesh,
                                        frontier_cap=fcap, window=w)
    # the SAME 8 devices factored as 4 edge shards x 2 vertex ranges
    mesh42 = make_edge_vertex_mesh(8, (4, 2), axis="data",
                                   edge_axis="edge")
    h_log, h_jx = trace_removal_round("halo", n, cap, mesh42, window=w)

    [psum] = rep_log
    # replicated: O(n) received per device, O(n * d) mesh-wide
    assert psum.recv_bytes == n * 3 * 4, psum

    def split(log):
        setup, main, over = log[:3], [], []
        for t in log[3:]:
            (over if t.branch == "overflow" else main).append(t)
        return setup, main, over

    # range on the shared axis: d_v = 8 vertex ranges
    setup, main, over = split(rng_log)
    assert [t.op for t in setup] == ["gather_halo", "regather",
                                     "regather"], setup
    assert setup[0].recv_bytes == d * hcap * 4, setup
    assert [t.op for t in main] == (
        ["gather_stats"] + ["ppermute"] * 5
        + ["regather", "regather", "psum_scalar"]), main
    assert main[0].recv_bytes == d * hcap * 3 * 4, main
    assert over == [], over
    # the whole per-round working set is O(n/d + hcap * d): every round
    # collective undercuts the replicated [n]-psum per device ...
    assert all(t.recv_bytes < psum.recv_bytes for t in main), main
    # ... and so does the round total, mesh-wide
    assert sum(t.recv_bytes for t in main) * d < psum.recv_bytes * d

    # 2-axis halo (d_e, d_v) = (4, 2): the halo-stat gather spans the
    # OWNER axis only — its payload shrinks from d*hcap to d_v*hcap
    # words — and the edge partials complete with one psum over the
    # pure-edge axis of the OWNED slice (n/d_v, never n)
    hsetup, hmain, hover = split(h_log)
    d_v = 2
    assert hsetup[0].recv_bytes == d_v * hcap * 4, hsetup
    assert [t.op for t in hmain[:2]] == ["gather_stats", "psum_edge"], hmain
    assert hmain[0].recv_bytes == d_v * hcap * 3 * 4, hmain
    assert hmain[1].recv_bytes == (n // d_v) * 3 * 4, hmain
    assert hover == [], hover

    # sparse frontier exchange (docs/DESIGN.md S4.3): the non-overflow
    # refresh is THREE O(cap * d)-word compacted gathers, INDEPENDENT
    # of n; the dense O(hcap) regather only moves on the overflow arm
    ssetup, smain, sover = split(sp_log)
    gf = [t for t in smain if t.op == "gather_frontier"]
    assert [t.recv_bytes for t in gf] == [
        d * (fcap + 1) * 4, d * fcap * 4, d * fcap * 8], gf
    assert [t.op for t in sover] == ["regather", "regather"], sover
    assert [t.recv_bytes for t in sover] == [hcap * 4, hcap * 8], sover

    # the accounting above must describe the traced programs exactly
    # (op mapping, payload bytes, overflow-branch attribution) at 8
    # shards and on the 2-axis mesh too, not just the 1-shard fast path
    for log, jx in ((rep_log, rep_jx), (rng_log, rng_jx),
                    (sp_log, sp_jx), (h_log, h_jx)):
        mismatches = cross_check_round(log, jx)
        assert mismatches == [], mismatches
    print("traffic-8dev OK",
          psum.recv_bytes, sum(t.recv_bytes for t in main),
          sum(t.recv_bytes for t in hmain))
    """
)


@pytest.mark.slow
def test_per_round_traffic_8_shards(tmp_path):
    """8 forced host devices: the per-round byte counts of the
    replicated, range, sparse, and 2-axis halo layouts, asserted from
    trace-time accounting (no batch is executed)."""
    script = tmp_path / "traffic8.py"
    script.write_text(_TRAFFIC_8DEV)
    env = dict(os.environ)
    here = os.path.dirname(__file__)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.abspath(os.path.join(here, "..", "src")),
         os.path.abspath(here)]
    )
    out = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "traffic-8dev OK" in out.stdout


def test_vertex_sharding_needs_sharded_engine():
    from repro.core.api import CoreMaintainer
    from repro.graph.generators import erdos_renyi

    g = erdos_renyi(20, 40, seed=0)
    with pytest.raises(ValueError, match="vertex_sharding"):
        CoreMaintainer.from_graph(g, capacity=128, engine="unified",
                                  vertex_sharding="range")
    with pytest.raises(ValueError, match="freelist"):
        CoreMaintainer.from_graph(g, capacity=128, engine="unified",
                                  freelist="magic")
    # hierarchical ranking only differs across shards: accepting it on
    # the other engines would silently do nothing, so it must raise too
    with pytest.raises(ValueError, match="hierarchical"):
        CoreMaintainer.from_graph(g, capacity=128, engine="unified",
                                  freelist="hierarchical")


def test_engine_config_matrix_rejected_at_construction():
    """Every invalid engine-configuration combination raises a
    construction-time ValueError NAMING the offending field — none may
    survive to a deep trace-time error or be silently ignored."""
    from repro.core.api import CoreMaintainer
    from repro.graph.generators import erdos_renyi

    g = erdos_renyi(20, 40, seed=0)
    bad = [
        (dict(engine="warp"), "engine"),
        (dict(vertex_sharding="diagonal"), "vertex_sharding"),
        (dict(freelist="magic"), "freelist"),
        (dict(frontier_exchange="rle"), "frontier_exchange"),
        # a mesh passed to an engine that never reads it
        (dict(engine="unified", mesh=jax.make_mesh((1,), ("data",))),
         "mesh"),
        (dict(engine="host", mesh=jax.make_mesh((1,), ("data",))),
         "mesh"),
        # combinations whose silent acceptance would do nothing
        (dict(engine="unified", vertex_sharding="range"),
         "vertex_sharding"),
        (dict(engine="host", freelist="hierarchical"), "hierarchical"),
        (dict(engine="sharded", frontier_exchange="sparse"),
         "frontier_exchange"),  # sparse without range vertex state
        (dict(engine="unified", frontier_exchange="sparse"),
         "frontier_exchange"),
        (dict(engine="sharded", vertex_sharding="range",
              frontier_cap=64), "frontier_cap"),  # cap without sparse
        (dict(engine="sharded", vertex_sharding="range",
              frontier_exchange="sparse", frontier_cap=-2),
         "frontier_cap"),
    ]
    for kw, field in bad:
        with pytest.raises(ValueError, match=field):
            CoreMaintainer.from_graph(g, capacity=128, **kw)
    # the valid corners of the matrix still construct
    CoreMaintainer.from_graph(g, capacity=128, engine="sharded",
                              vertex_sharding="range",
                              frontier_exchange="sparse")
    CoreMaintainer.from_graph(g, capacity=128, engine="sharded",
                              vertex_sharding="range",
                              frontier_exchange="sparse", frontier_cap=16)


def test_make_sharded_apply_rejects_bad_frontier_config():
    from repro.core.sharded import make_sharded_apply

    mesh = jax.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="frontier_exchange"):
        make_sharded_apply(mesh, 16, 18, frontier_exchange="rle")
    with pytest.raises(ValueError, match="frontier_exchange"):
        make_sharded_apply(mesh, 16, 18, frontier_exchange="sparse")
    with pytest.raises(ValueError, match="frontier_cap"):
        make_sharded_apply(mesh, 16, 18, vertex_sharding="range",
                           frontier_exchange="sparse", frontier_cap=0)
    # a cap the bitmask exchange would silently ignore must raise too
    with pytest.raises(ValueError, match="frontier_cap"):
        make_sharded_apply(mesh, 16, 18, vertex_sharding="range",
                           frontier_cap=64)


def test_local_active_window_cannot_outrun_the_shard():
    """An oversized per-shard window (e.g. sized from the GLOBAL
    high-water mark) used to slice past the local shard and silently
    splice a SHORT slot table back together; it must raise loudly at
    the window boundary instead. The exact-boundary window still runs."""
    from repro.core.sharded import make_sharded_apply

    mesh = jax.make_mesh((1,), ("data",))
    n, cap = 8, 16

    def fresh_args():  # the engine donates its buffers — one set per call
        b = jnp.zeros(4, jnp.int32)
        ok = jnp.zeros(4, bool)
        return (jnp.zeros(cap, jnp.int32), jnp.zeros(cap, jnp.int32),
                jnp.zeros(cap, bool), jnp.zeros(n, jnp.int32),
                jnp.zeros(n, jnp.int64), jnp.int32(0),
                b, b, ok, b, b, ok)

    # window == per-shard capacity: legal, runs
    fn = make_sharded_apply(mesh, n, n + 2, local_active=cap)
    out = fn(*fresh_args())
    assert out[0].shape == (cap,)

    # one past the shard: loud ValueError naming the misconfiguration
    fn = make_sharded_apply(mesh, n, n + 2, local_active=cap + 1)
    with pytest.raises(ValueError, match="local_active"):
        fn(*fresh_args())
