"""Batch-parallel edge removal maintenance (paper Algorithm 6, TPU form).

The lock-based mcd cascade becomes a decrease-only fixpoint over dense
per-vertex state:

    round:  mcd[v] = |{u in N(v) : core[u] >= core[v]}|      (CheckMCD)
            drop   = mcd < core                              (DoMCD)
            core  -= drop                                    (<= 1 per round,
                                                              the paper's
                                                              Theorem bound)

Every round handles ALL affected levels of ALL removed edges at once —
the paper's conditional-lock concurrency collapses into simultaneity:
because all of a round's droppers still count each other in mcd, any
intra-round append order at the new level keeps the k-order certificate
``dout(v) <= core(v)`` valid (proof in docs/DESIGN.md §2.1).

The fixpoint provably converges to the exact core numbers of the edited
graph from any state that upper-bounds them (Lü et al. style argument;
tests/test_jax_core.py property-checks this against the oracle).

``removal_fixpoint`` is the reusable building block: the unified
mixed-batch engine (core/engine.py) runs it back-to-back with the
promotion rounds in one compiled program, reusing the terminating round's
packed (hi, dout_same) statistics to seed the promotion phase.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from . import graph_ops as G
from .order import place_block, place_block_ring
from .vertex_layout import (
    HaloSession,
    ReplicatedVertices,
    VertexLayout,
    _note,
)

Array = jax.Array


class RemoveStats(NamedTuple):
    rounds: Array        # number of fixpoint rounds executed
    n_dropped: Array     # |V*| — vertices whose core number decreased
    max_frontier: Array  # max per-shard drop-mask count over all rounds


@jax.named_scope("coremaint.remove.stats")
def removal_fixpoint(
    src: Array,
    dst: Array,
    valid: Array,
    core: Array,
    label: Array,
    n: int,
    n_levels: int,
    share_stats: bool = True,
    layout: VertexLayout | None = None,
) -> Tuple[Array, Array, Array, Array, Array, Array]:
    """Run the decrease-only mcd fixpoint on an already-tombstoned table.

    Returns ``(core, label, rounds, hi, dout_same, max_frontier)``;
    ``max_frontier`` is the max per-shard drop-mask count observed over
    all rounds (``layout.frontier_peak`` — the datum the sparse
    ``frontier_cap`` planner is tuned from). With ``share_stats``
    the (hi, dout_same) statistics come from the same packed scatter as
    the terminating mcd check, so they describe the FINAL state exactly
    (the last round drops nothing and therefore leaves core/label
    untouched) — the unified engine seeds its promotion phase from them
    for free. Removal-only callers pass ``share_stats=False`` to scatter
    just the 1-column mcd (the returned hi/dout_same stay zero, and are
    OWNED-sized under a range-sharded layout).

    With a ``layout`` the edge arrays are shard_map-local shards of the
    slot table and every statistic is completed by the layout: a psum
    over the mesh axis for replicated vertex state (every device sees
    the full statistic), a reduce_scatter for range-sharded state (each
    device sees only its owned vertex range and decides drops there; the
    drop mask is all_gathered — bit-packed, or as compacted frontier
    indices with an in-program overflow fallback when the layout carries
    a ``frontier_cap`` (docs/DESIGN.md §4.3) — so the commit — core -1
    and the label tail placement — replays identically everywhere).
    Either way the working core/label stay replicated values, so all
    devices run the loop in lockstep.
    """
    if layout is None:
        layout = ReplicatedVertices(n)

    def cond(state):
        return state[2]

    def body(state):
        core, label, _, rounds, hi, dout_same, fmax = state
        if share_stats:
            mcd, hi, dout_same = G.mcd_hi_dout(
                src, dst, valid, core, label, n, layout,
            )
        else:
            mcd = G.count_ge(src, dst, valid, core, n, layout)
        core_own = layout.own(core)
        drop = layout.gather_mask((mcd < core_own) & (core_own > 0))
        new_core = core - drop.astype(jnp.int32)
        fmax = jnp.maximum(fmax, layout.frontier_peak(drop))
        # place this round's droppers at the tail of their new level
        label = place_block(new_core, label, drop, at_head=False,
                            n_levels=n_levels)
        return (new_core, label, jnp.any(drop), rounds + 1, hi, dout_same,
                fmax)

    z = layout.zeros()
    # rounds counts body executions (the final one observes no drops)
    core, label, _, rounds, hi, dout_same, fmax = jax.lax.while_loop(
        cond, body,
        (core, label, jnp.bool_(True), jnp.int32(0), z, z, jnp.int32(0)),
    )
    return core, label, rounds, hi, dout_same, fmax


@jax.named_scope("coremaint.remove.stats")
def removal_fixpoint_halo(
    src_h: Array,
    dst_h: Array,
    valid: Array,
    core_own: Array,
    label_own: Array,
    core_h: Array,
    label_h: Array,
    session: HaloSession,
    n_levels: int,
):
    """The removal fixpoint on a halo working set — no [n] buffer.

    ``src_h``/``dst_h`` are the windowed edge endpoints as HALO positions
    (``session.locate``); ``core_h``/``label_h`` are the current halo
    values, ``core_own``/``label_own`` the owned slices. Per round: one
    halo-domain stats pass completed into owned by the session (bounded
    all_gather + owner scatter + edge-axis psum), the drop decision on
    the owned slice, the ring ``place_block_ring`` label commit, and ONE
    changed-restricted halo value refresh (sparse indices under a
    ``frontier_cap``, dense O(halo_cap) regather otherwise / on
    overflow) — every step bit-identical to ``removal_fixpoint`` on the
    assembled global state.

    Returns ``(core_own, label_own, core_h, label_h, rounds, hi,
    dout_same, max_frontier, n_overflow)``; ``hi``/``dout_same`` are the
    terminating round's OWNED promotion-seeding stats, ``max_frontier``
    the LOCAL running per-round owned drop count (the engine completes
    it with one pmax at batch end), ``n_overflow`` the number of rounds
    whose sparse refresh fell back to the dense regather.
    """
    hcap = session.halo_cap
    d_v = session.layout.n_shards

    def cond(state):
        return state[4]

    def body(state):
        (core_own, label_own, core_h, label_h, _, rounds, hi, dout_same,
         fmax, n_ovf) = state
        mcd, hi, dout_same = G.mcd_hi_dout(
            src_h, dst_h, valid, core_h, label_h, hcap, session,
        )
        drop = (mcd < core_own) & (core_own > 0)
        fmax = jnp.maximum(fmax, session.frontier_peak(drop))
        new_core = core_own - drop.astype(jnp.int32)
        label_own = place_block_ring(
            new_core, label_own, drop, at_head=False, n_levels=n_levels,
            axis=session.axis, n_shards=d_v, note=_note,
        )
        core_h, label_h, ovf = session.refresh_values(
            new_core, label_own, drop, core_h, label_h
        )
        cont = session.any_owned(drop)
        return (new_core, label_own, core_h, label_h, cont, rounds + 1,
                hi, dout_same, fmax, n_ovf + ovf.astype(jnp.int32))

    z = session.zeros()
    (core_own, label_own, core_h, label_h, _, rounds, hi, dout_same,
     fmax, n_ovf) = jax.lax.while_loop(
        cond, body,
        (core_own, label_own, core_h, label_h, jnp.bool_(True),
         jnp.int32(0), z, z, jnp.int32(0), jnp.int32(0)),
    )
    return (core_own, label_own, core_h, label_h, rounds, hi, dout_same,
            fmax, n_ovf)


def weighted_core_fixpoint_pass(
    src: Array,
    dst: Array,
    valid: Array,
    w: Array,
    core: Array,
    n: int,
    layout: VertexLayout | None = None,
) -> Tuple[Array, Array, Array, Array, Array]:
    """Decrease-only weighted h-index fixpoint (Zhou et al., WWW'21):
    per round ``core <- min(core, H_w(core))`` where ``H_w`` is the
    per-vertex weighted h-index (graph_ops.weighted_h_index), until no
    vertex moves. Converges to the exact weighted cores from
    ANY state upper-bounding them — both engine phases use it: removal
    starts from the current cores, promotion from ``core + W`` (W the
    batch's total inserted weight — docs/DESIGN.md §4.5 derives why the
    per-vertex incident bound is NOT sound).

    Where the layout has no mesh axis each round's h-index comes from
    one sort over the table's entries, built once here
    (graph_ops.hindex_entries); under a mesh axis from the lockstep
    bisection. Labels are FROZEN throughout: the weighted fixpoint has
    no per-level append order to maintain (levels are unbounded in
    maxW, so the bucketed ``place_block`` does not apply); the engine
    commits ONE bucket-free renumber per batch instead. Returns
    ``(core, rounds, max_frontier, steps, sorts)``: ``steps`` the
    bisection steps (support passes) summed over the rounds, ``sorts``
    the rounds whose h-index came from the sort. Replicated/plain
    layouts only — the halo twin is ``weighted_core_fixpoint_pass_halo``."""
    if layout is None:
        layout = ReplicatedVertices(n)
    entries = (G.hindex_entries(src, dst, valid, w, n)
               if G.completes_locally(layout) else None)
    sorted_round = jnp.int32(entries is not None)

    def cond(state):
        return state[1]

    def body(state):
        core, _, rounds, fmax, steps, sorts = state
        h, k = G.weighted_h_index(src, dst, valid, w, core, core, n,
                                  layout, entries=entries)
        new_core = jnp.minimum(core, h)
        changed = new_core < core
        fmax = jnp.maximum(fmax, layout.frontier_peak(changed))
        return (new_core, jnp.any(changed), rounds + 1, fmax, steps + k,
                sorts + sorted_round)

    core, _, rounds, fmax, steps, sorts = jax.lax.while_loop(
        cond, body,
        (core, jnp.bool_(True), jnp.int32(0), jnp.int32(0), jnp.int32(0),
         jnp.int32(0)),
    )
    return core, rounds, fmax, steps, sorts


def _weighted_h_index_halo(src_h, dst_h, valid, w, core_own, core_h,
                           session: HaloSession):
    """Lockstep owned+halo weighted h-index bisection. ``(lo, hi)`` live
    in BOTH domains: the owned pair is authoritative, the halo pair is
    its exact image (the per-step ``ok`` verdict crosses the mesh as a
    dense int32 ``gather_values`` — bisection masks flip for ~half the
    vertices per step, so the sparse frontier path would overflow every
    step; dense is the right exchange here). Continuation is carried in
    the loop STATE (one ``any_owned`` psum per step) so the while cond
    stays collective-free and every shard runs the same trip count.
    Returns ``(lo_own, lo_halo, steps)`` — the h-index, its halo image
    and the loop's iteration count."""
    hcap = session.halo_cap
    lo_o = jnp.zeros_like(core_own)
    hi_o = jnp.maximum(core_own, 0)
    lo_h = jnp.zeros_like(core_h)
    hi_h = jnp.maximum(core_h, 0)

    def cond(state):
        return state[4]

    def body(state):
        lo_o, hi_o, lo_h, hi_h, _, steps = state
        mid_o = (lo_o + hi_o + 1) // 2
        mid_h = (lo_h + hi_h + 1) // 2
        s = G.weighted_support(src_h, dst_h, valid, w, core_h, mid_h,
                               hcap, session)
        ok_o = s >= mid_o
        ok_h = session.gather_values(ok_o.astype(jnp.int32)) > 0
        lo_o = jnp.where(ok_o, mid_o, lo_o)
        hi_o = jnp.where(ok_o, hi_o, mid_o - 1)
        lo_h = jnp.where(ok_h, mid_h, lo_h)
        hi_h = jnp.where(ok_h, hi_h, mid_h - 1)
        cont = session.any_owned(lo_o < hi_o)
        return lo_o, hi_o, lo_h, hi_h, cont, steps + 1

    cont0 = session.any_owned(lo_o < hi_o)
    lo_o, _, lo_h, _, _, steps = jax.lax.while_loop(
        cond, body, (lo_o, hi_o, lo_h, hi_h, cont0, jnp.int32(0))
    )
    return lo_o, lo_h, steps


def weighted_core_fixpoint_pass_halo(
    src_h: Array,
    dst_h: Array,
    valid: Array,
    w: Array,
    core_own: Array,
    core_h: Array,
    session: HaloSession,
):
    """``weighted_core_fixpoint_pass`` on a halo working set. The halo
    core image stays current WITHOUT ``refresh_values``: each round's
    commit is ``min`` against the bisection result, whose halo copy
    (``lo_h``) is already the exact image of the owned one — so the halo
    update is the same local ``min`` (sentinel rows hold 0 and stay 0;
    no valid edge references them). Labels are frozen (see the plain
    twin); the engine runs one ring renumber per batch afterwards.
    Returns ``(core_own, core_h, rounds, max_frontier, steps)`` with
    ``max_frontier`` the LOCAL running per-round owned change count
    (completed by the engine's batch-end pmax); every shard runs the
    same trip counts, so ``steps`` needs no collective."""

    def cond(state):
        return state[2]

    def body(state):
        core_own, core_h, _, rounds, fmax, steps = state
        lo_o, lo_h, k = _weighted_h_index_halo(
            src_h, dst_h, valid, w, core_own, core_h, session,
        )
        new_o = jnp.minimum(core_own, lo_o)
        new_h = jnp.minimum(core_h, lo_h)
        changed = new_o < core_own
        fmax = jnp.maximum(fmax, session.frontier_peak(changed))
        cont = session.any_owned(changed)
        return new_o, new_h, cont, rounds + 1, fmax, steps + k

    core_own, core_h, _, rounds, fmax, steps = jax.lax.while_loop(
        cond, body,
        (core_own, core_h, jnp.bool_(True), jnp.int32(0), jnp.int32(0),
         jnp.int32(0)),
    )
    return core_own, core_h, rounds, fmax, steps


@partial(jax.jit, static_argnames=("n", "n_levels"))
def remove_batch(
    src: Array,
    dst: Array,
    valid: Array,
    core: Array,
    label: Array,
    slots: Array,
    n: int,
    n_levels: int,
) -> Tuple[Array, Array, Array, RemoveStats]:
    """Remove the edges in ``slots`` (int32, -1 entries are padding) and
    restore core numbers + k-order labels.

    Returns (valid, core, label, stats).
    """
    ok = slots >= 0
    safe = jnp.where(ok, slots, 0)
    # commutative scatter-max: padding entries (ok=False) are no-ops even
    # when they collide with a real removal of slot 0
    rm = jnp.zeros(valid.shape[0], dtype=bool).at[safe].max(ok)
    valid = valid & ~rm

    core0 = core
    core, label, rounds, _, _, fmax = removal_fixpoint(
        src, dst, valid, core, label, n, n_levels, share_stats=False
    )
    stats = RemoveStats(
        rounds=rounds, n_dropped=jnp.sum(core != core0, dtype=jnp.int32),
        max_frontier=fmax,
    )
    return valid, core, label, stats
