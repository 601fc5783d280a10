"""Batch-parallel edge insertion maintenance (paper Algorithm 5, TPU form).

Round structure (all levels of all inserted edges processed together — the
bulk-synchronous analogue of one-lock-per-vertex worker concurrency):

  1. SEED      — k-order roots of the pending edges (order-min endpoints),
                 plus last round's promoted vertices (cross-round cascades),
                 plus any vertex violating the certificate dout > core
                 (self-healing seeds; see docs/DESIGN.md §2).
  2. FORWARD   — masked wave expansion along same-level k-order-increasing
                 edges, gated by the optimistic candidate test
                 ``hi + dout_same + din_reached > core`` (paper's Forward;
                 the gating is provably reach-complete: every true candidate
                 has a forward path from a seed through passing vertices).
  3. EVICT     — exact candidate fixpoint on the reached set (paper's
                 Backward collapsed into iterative pruning): evict v while
                 ``hi(v) + |same-level candidate nbrs| <= core(v)``.
  4. COMMIT    — survivors' core += 1; moved to the head of O_{K+1} in old
                 label order (required to preserve the k-order certificate).

Rounds repeat until no promotion happens (a batch can raise a core by more
than one; each round applies the paper's +1-per-edge theorem to the whole
batch).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from . import graph_ops as G
from ..kernels import coremaint
from .order import place_block, place_block_ring
from .remove import (
    weighted_core_fixpoint_pass,
    weighted_core_fixpoint_pass_halo,
)
from .vertex_layout import (
    HaloSession,
    ReplicatedVertices,
    VertexLayout,
    _note,
)

Array = jax.Array


class InsertStats(NamedTuple):
    rounds: Array        # outer promotion rounds
    n_promoted: Array    # |V*| over the whole batch
    v_plus: Array        # |V+| — vertices ever reached by FORWARD
    max_frontier: Array  # max per-shard exchanged-mask count over all rounds
    forward_waves: Array  # FORWARD wave iterations, summed over rounds
    evict_waves: Array    # EVICT iterations, summed over rounds


def freelist_alloc(
    valid: Array,
    iok: Array,
    axis: str | None = None,
    hierarchical: bool = False,
) -> Tuple[Array, Array]:
    """Recycling slot allocator: every dead slot IS the free-list.

    Dead slots (``~valid``) are ranked in (local slot, shard) order and
    the batch's kept inserts (``iok``, rank by cumsum) are assigned
    one-to-one to the lowest-ranked free slots. Filling the lowest local
    indices first — interleaved ACROSS shards, not shard-by-shard — does
    two jobs at once: the per-shard slot high-water mark only grows when
    every shard is hole-free below it (so steady-state churn recycles
    tombstones entirely in-program and host-side ``_compact`` becomes a
    rare defrag), and fresh-ground allocation round-robins the shards,
    keeping the densest shard's high-water mark — the quantity that
    sizes the per-shard active window — near ``live / n_shards``.
    Ranking by (shard, slot) instead would funnel every insert into the
    lowest shard's tail before touching the next shard's holes,
    ratcheting that shard up to full local capacity (docs/DESIGN.md
    §4.1). On one shard both orders degenerate to ascending slot id, so
    the unified and 1-device sharded engines still pick identical slots.

    With ``axis`` (shard_map) each device ranks its own dead slots from
    one ``all_gather`` of the [window]-sized dead masks, writes the
    batch ranks that land in its shard, and drops the rest via the
    sentinel position — the same OOB-drop trick as the stat scatters.

    ``hierarchical`` replaces that O(n_shards * window) mask exchange
    with an all_gather of ONE scalar per shard (the per-shard free
    count): each device already knows its local dead ranks, and the
    exclusive prefix sum of the gathered counts offsets them into a
    global ranking. The ranking becomes (shard, local slot) —
    shard-by-shard instead of interleaved — so it gives up the
    §4.1 shard-balance property (fresh ground fills the lowest shard's
    window before touching the next) in exchange for O(n_shards) bytes
    per batch; the LIVE EDGE SET and the maintained core/label state are
    unaffected (core numbers never depend on slot positions), which the
    churn harness pins by running both rankings against each other. On
    one shard both paths are ascending slot id, i.e. identical.

    Returns ``(lpos, iok)``: ``lpos[b]`` is this shard's local slot for
    insert lane ``b`` (``== capacity`` when the lane is masked or owned
    by another shard — out-of-bounds, so ``.at[lpos].set(mode="drop")``
    skips it), and ``iok`` narrowed by the free-exhaustion guard (an
    insert with no free slot anywhere is dropped rather than miscounted;
    the host's capacity planning makes that unreachable).
    """
    capacity = valid.shape[0]
    b = iok.shape[0]
    dead = ~valid
    if axis is None:
        total_free = jnp.sum(dead, dtype=jnp.int32)
        drank = jnp.cumsum(dead.astype(jnp.int32), dtype=jnp.int32) - 1
    elif hierarchical:
        my_free = jnp.sum(dead, dtype=jnp.int32)
        counts = jax.lax.all_gather(my_free, axis)  # [n_shards] scalars
        me = jax.lax.axis_index(axis)
        total_free = jnp.sum(counts, dtype=jnp.int32)
        # my dead slot with local free-rank r has global rank
        # (free slots on shards before me) + r: (shard, slot) order
        base = (jnp.cumsum(counts, dtype=jnp.int32) - counts)[me]
        drank = base + jnp.cumsum(dead.astype(jnp.int32),
                                  dtype=jnp.int32) - 1
    else:
        all_dead = jax.lax.all_gather(dead, axis)  # [n_shards, capacity]
        me = jax.lax.axis_index(axis)
        col = jnp.sum(all_dead, axis=0, dtype=jnp.int32)  # dead per index
        total_free = jnp.sum(col, dtype=jnp.int32)
        # free rank of MY dead slot i = all dead slots at indices < i
        # (any shard) + dead slots at index i on shards before me
        col_before = jnp.cumsum(col, dtype=jnp.int32) - col
        row_before = (
            jnp.cumsum(all_dead.astype(jnp.int32), axis=0) - all_dead
        )[me]
        drank = col_before + row_before
    rank = jnp.cumsum(iok.astype(jnp.int32), dtype=jnp.int32) - 1
    iok = iok & (rank < total_free)
    # ranks past the batch can never be targets (rank < b always), so
    # their dead slots park on the scatter sentinel
    spos = jnp.where(dead & (drank < b), drank, b)
    slot_of_rank = jnp.full((b,), capacity, dtype=jnp.int32).at[spos].set(
        jnp.arange(capacity, dtype=jnp.int32), mode="drop"
    )
    lpos = jnp.where(iok, slot_of_rank[jnp.maximum(rank, 0)], capacity)
    return lpos, iok


def write_edge_slots(
    src: Array,
    dst: Array,
    valid: Array,
    n_edges: Array,
    new_src: Array,
    new_dst: Array,
    new_ok: Array,
) -> Tuple[Array, Array, Array, Array]:
    """Bump slot allocation via ``cumsum`` + masked table writes — the
    seed path behind ``engine="host"``, where ``n_edges`` is the bump
    pointer (slot high-water mark) and tombstones are reclaimed only by
    host-side ``_compact``. The device engines allocate with
    ``freelist_alloc`` instead.

    Padding lanes are parked on the LAST slot (they rewrite its current
    values, a no-op); callers must guarantee that slot is never a real
    allocation target (n_edges + batch + 1 <= table size).
    Returns the updated ``(src, dst, valid, n_edges)``.
    """
    slot = n_edges + jnp.cumsum(new_ok.astype(jnp.int32), dtype=jnp.int32) - 1
    slot = jnp.where(new_ok, slot, src.shape[0] - 1)
    src = src.at[slot].set(jnp.where(new_ok, new_src, src[slot]))
    dst = dst.at[slot].set(jnp.where(new_ok, new_dst, dst[slot]))
    valid = valid.at[slot].set(jnp.where(new_ok, True, valid[slot]))
    return src, dst, valid, n_edges + jnp.sum(new_ok, dtype=jnp.int32)


@jax.named_scope("coremaint.promote.seed")
def promotion_fixpoint(
    src: Array,
    dst: Array,
    valid: Array,
    core: Array,
    label: Array,
    new_src: Array,
    new_dst: Array,
    new_ok: Array,
    hi: Array,
    dout_same: Array,
    n: int,
    n_levels: int,
    layout: VertexLayout | None = None,
    kernel_backend: str = "lax",
) -> Tuple[Array, Array, Array, Array, Array]:
    """Promotion rounds for pending edges already written into the table.

    ``hi``/``dout_same`` must describe the CURRENT (core, label, valid)
    state including the pending edges; each round recomputes them after its
    commit, so the caller-provided pair is consumed exactly once. This is
    how the unified engine shares one statistics pass between the removal
    fixpoint and the first promotion round. Under a range-sharded layout
    the pair is OWNED-sized (the caller completed it with the layout).

    With a ``layout`` the table arrays are shard_map-local edge shards and
    all neighborhood statistics are completed by it (psum for replicated
    vertex state, reduce_scatter to owned vertex ranges for
    range-sharded); candidacy/eviction decisions then run on the owned
    slices and come back as all_gathered masks — bit-packed, or sparse
    compacted indices with a per-round overflow fallback when the layout
    carries a ``frontier_cap`` (docs/DESIGN.md §4.3); this code only
    ever sees ``layout.gather_mask``. The pending-edge
    arrays (``new_src``/``new_dst``/``new_ok``) and the working
    core/label stay replicated values, so the seed scatter and the label
    placement need no collective.

    Returns ``(core, label, rounds, v_plus_mask, max_frontier,
    forward_waves, evict_waves)``; ``max_frontier`` is the max per-shard
    count over every exchanged mask (``layout.frontier_peak``) — the
    observed datum the sparse ``frontier_cap`` planner is tuned from
    (docs/DESIGN.md §4.3); the wave counts are the iterations of the
    FORWARD and EVICT loops summed over the rounds, each one a pass over
    the slot table.

    Every op runs under a ``coremaint.*`` named scope (the round's seed
    here, ``promote.forward`` / ``promote.evict`` / ``labels`` in the
    helpers, ``promote.stats`` for the closing statistics pass), so a
    profile splits device time by phase.

    With the lax backend a round's FORWARD and EVICT waves read the
    round's per-edge ``G.WaveMasks`` instead of gathering core and label
    on every wave: (core, label, valid) hold through the round, so the
    masks are built once before the first round and then by each round's
    closing statistics pass, from the gathers that pass already makes,
    and carried to the next round.

    ``kernel_backend="pallas"`` runs every wave/evict/terminating
    statistic through the fused COO kernels (kernels/coremaint.py) —
    bit-identical partials, fewer launches; where the layout completes
    locally the terminating violator check folds into the same launch
    as its statistics (``fused_promotion_stats``). The fused kernels
    make their own gathers, so that backend carries no masks.
    """
    if layout is None:
        layout = ReplicatedVertices(n)
    fuse_decision = (
        kernel_backend == "pallas" and G.completes_locally(layout)
    )
    masked = kernel_backend == "lax"

    def round_cond(state):
        return state[2]

    def round_body(state):
        (core, label, _, promoted_prev, rounds, v_plus, hi, dout_same,
         masks, fmax, fwd, ev) = state

        # SEED: roots of pending edges (order-min endpoint at current state)
        e_src_lt = (core[new_src] < core[new_dst]) | (
            (core[new_src] == core[new_dst]) & (label[new_src] < label[new_dst])
        )
        root = jnp.where(e_src_lt, new_src, new_dst)
        seed = (
            jnp.zeros(n, dtype=jnp.int32).at[root].add(new_ok.astype(jnp.int32))
            > 0
        )
        # certificate violators are potential hidden roots (the stats live
        # on their owners; only the violator bitmask crosses the mesh)
        viol = layout.gather_mask((hi + dout_same) > layout.own(core))
        fmax = jnp.maximum(fmax, layout.frontier_peak(viol))
        seed = seed | viol | promoted_prev

        reach, passing, wave_fmax, fwd_waves = _forward_reach(
            src, dst, valid, core, label, seed, hi, dout_same, n, layout,
            kernel_backend=kernel_backend, masks=masks,
        )
        cand0 = reach & passing
        cand, evict_round, ev_fmax, ev_waves = _evict_fixpoint(
            src, dst, valid, core, cand0, hi, n, layout,
            kernel_backend=kernel_backend, masks=masks,
        )
        fmax = jnp.maximum(fmax, jnp.maximum(wave_fmax, ev_fmax))

        new_core = core + cand.astype(jnp.int32)
        # promoted -> head of O_{K+1} in old-label order
        label = place_block(new_core, label, cand, at_head=True,
                            n_levels=n_levels)
        # Backward-evicted -> tail of O_K in (eviction round, old label)
        # order; restores the dout <= core certificate (docs/DESIGN.md §2)
        evicted = cand0 & ~cand
        label = place_block(new_core, label, evicted, at_head=False,
                            n_levels=n_levels, round_key=evict_round)
        # fused (hi, dout_same) for the NEXT round — one scatter-add (C1).
        # Continue only while the k-order certificate is violated somewhere:
        # the passing-set fixpoint bootstraps from ``hi + dout_same > core``
        # vertices, so with none of them the next round provably finds no
        # candidates (docs/DESIGN.md §2.3) — this skips the seed
        # implementation's trailing confirm round (a full forward + evict
        # + stats pass) entirely.
        with jax.named_scope("coremaint.promote.stats"):
            if fuse_decision:
                # ONE pallas_call: stats + the violator threshold mask
                # that decides fixpoint termination
                new_hi, new_dout, viol_next = (
                    coremaint.fused_promotion_stats(
                        src, dst, valid, new_core, label, n
                    )
                )
                changed = jnp.any(viol_next)
            elif masked:
                # the next round's masks come from this pass's gathers
                new_hi, new_dout, masks = G.hi_dout_same_and_masks(
                    src, dst, valid, new_core, label, n, layout,
                )
                changed = layout.any_owned(
                    (new_hi + new_dout) > layout.own(new_core)
                )
            else:
                new_hi, new_dout = G.hi_and_dout_same(
                    src, dst, valid, new_core, label, n, layout,
                    backend=kernel_backend,
                )
                changed = layout.any_owned(
                    (new_hi + new_dout) > layout.own(new_core)
                )
        return (
            new_core,
            label,
            changed,
            cand,
            rounds + 1,
            v_plus | reach,
            new_hi,
            new_dout,
            masks,
            fmax,
            fwd + fwd_waves,
            ev + ev_waves,
        )

    masks = G.wave_masks(src, dst, valid, core, label) if masked else None
    z = jnp.int32(0)
    (core, label, _, _, rounds, v_plus, _, _, _, fmax, fwd,
     ev) = jax.lax.while_loop(
        round_cond,
        round_body,
        (core, label, jnp.bool_(True), jnp.zeros(n, dtype=bool),
         z, jnp.zeros(n, dtype=bool), hi, dout_same, masks, z, z, z),
    )
    return core, label, rounds, v_plus, fmax, fwd, ev


@jax.named_scope("coremaint.promote.seed")
def promotion_fixpoint_halo(
    src_h: Array,
    dst_h: Array,
    valid: Array,
    core_own: Array,
    label_own: Array,
    core_h: Array,
    label_h: Array,
    new_src: Array,
    new_dst: Array,
    u_pos: Array,
    v_pos: Array,
    new_ok: Array,
    hi: Array,
    dout_same: Array,
    session: HaloSession,
    n_levels: int,
    kernel_backend: str = "lax",
):
    """The promotion rounds on a halo working set — no [n] buffer.

    The mirror of ``promotion_fixpoint`` with every mask and decision in
    the OWNED domain and every edge-pass input in the HALO domain:
    ``src_h``/``dst_h`` index the halo (``session.locate`` of the
    post-insert window), ``u_pos``/``v_pos`` are the pending lanes' halo
    positions (every lane endpoint is in every device's halo by
    construction, so the root selection replays identically everywhere),
    and ``new_src``/``new_dst`` stay global ids for the owned seed
    scatter. Wave/evict masks cross the owner axis as changed-restricted
    sparse refreshes (dense O(halo_cap) regather on overflow); the
    commits run ``order.place_block_ring``. Bit-identical cores AND
    labels to ``promotion_fixpoint`` on the assembled global state.

    Returns ``(core_own, label_own, core_h, label_h, rounds, v_plus_own,
    max_frontier, n_overflow, forward_waves, evict_waves)`` —
    ``max_frontier`` is the LOCAL running per-round owned frontier count
    (engine completes with one pmax), ``n_overflow`` counts sparse
    exchanges that fell back dense, and the wave counts are the halo
    loops' own iterations (replicated: every shard runs the same trip
    count).
    """
    hcap = session.halo_cap
    d_v = session.layout.n_shards

    def round_cond(state):
        return state[4]

    def round_body(state):
        (core_own, label_own, core_h, label_h, _, promoted_prev, rounds,
         v_plus, hi, dout_same, fmax, n_ovf, fwd, ev) = state

        # SEED: roots of pending edges at the current state — the lane
        # endpoints' halo values are identical on every device, so the
        # owned scatter of the replicated root ids needs no collective
        cu, cv = core_h[u_pos], core_h[v_pos]
        e_src_lt = (cu < cv) | (
            (cu == cv) & (label_h[u_pos] < label_h[v_pos])
        )
        root = jnp.where(e_src_lt, new_src, new_dst)
        seed = session.add_at(
            session.zeros(), root, new_ok.astype(jnp.int32)
        ) > 0
        viol = (hi + dout_same) > core_own
        fmax = jnp.maximum(fmax, session.frontier_peak(viol))
        seed = seed | viol | promoted_prev

        (reach, passing, wave_fmax, wave_ovf,
         fwd_waves) = _forward_reach_halo(
            src_h, dst_h, valid, core_own, core_h, label_h, seed,
            hi, dout_same, session, kernel_backend=kernel_backend,
        )
        cand0 = reach & passing
        cand, evict_round, ev_fmax, ev_ovf, ev_waves = _evict_fixpoint_halo(
            src_h, dst_h, valid, core_own, core_h, cand0, hi, session,
            kernel_backend=kernel_backend,
        )
        fmax = jnp.maximum(fmax, jnp.maximum(wave_fmax, ev_fmax))

        new_core = core_own + cand.astype(jnp.int32)
        # promoted -> head of O_{K+1} in old-label order
        label_own = place_block_ring(
            new_core, label_own, cand, at_head=True, n_levels=n_levels,
            axis=session.axis, n_shards=d_v, note=_note,
        )
        # Backward-evicted -> tail of O_K in (eviction round, old label)
        # order (docs/DESIGN.md §2)
        evicted = cand0 & ~cand
        label_own = place_block_ring(
            new_core, label_own, evicted, at_head=False,
            n_levels=n_levels, axis=session.axis, n_shards=d_v,
            round_key=evict_round, note=_note,
        )
        # cand0 covers every vertex whose core OR label just changed
        # (promoted: both; evicted: label) — the changed-restricted
        # halo refresh the next round's edge pass reads
        core_h, label_h, ovf = session.refresh_values(
            new_core, label_own, cand0, core_h, label_h
        )
        with jax.named_scope("coremaint.promote.stats"):
            new_hi, new_dout = G.hi_and_dout_same(
                src_h, dst_h, valid, core_h, label_h, hcap, session,
                backend=kernel_backend,
            )
            changed = session.any_owned((new_hi + new_dout) > new_core)
        return (
            new_core, label_own, core_h, label_h, changed, cand,
            rounds + 1, v_plus | reach, new_hi, new_dout, fmax,
            n_ovf + wave_ovf + ev_ovf + ovf.astype(jnp.int32),
            fwd + fwd_waves, ev + ev_waves,
        )

    zmask = jnp.zeros(session.n_owned, dtype=bool)
    z = jnp.int32(0)
    (core_own, label_own, core_h, label_h, _, _, rounds, v_plus, _, _,
     fmax, n_ovf, fwd, ev) = jax.lax.while_loop(
        round_cond, round_body,
        (core_own, label_own, core_h, label_h, jnp.bool_(True), zmask,
         z, zmask, hi, dout_same, z, z, z, z),
    )
    return (core_own, label_own, core_h, label_h, rounds, v_plus, fmax,
            n_ovf, fwd, ev)


@jax.named_scope("coremaint.promote.forward")
def _forward_reach_halo(
    src_h: Array,
    dst_h: Array,
    valid: Array,
    core_own: Array,
    core_h: Array,
    label_h: Array,
    seed: Array,
    hi: Array,
    dout_same: Array,
    session: HaloSession,
    kernel_backend: str = "lax",
):
    """``_forward_reach`` with OWNED loop masks and a per-wave halo
    refresh of the reached-and-passing frontier. Returns ``(reach,
    passing, max_frontier, n_overflow, waves)`` — owned masks."""
    hcap = session.halo_cap

    def cond(state):
        return state[2]

    def body(state):
        reach, passing, _, fmax, n_ovf, waves = state
        rp = reach & passing
        rp_h, ovf = session.refresh_mask(rp)
        din, grow = G.din_and_expand(
            src_h, dst_h, valid, core_h, label_h, rp_h, hcap, session,
            backend=kernel_backend,
        )
        new_passing = (hi + dout_same + din) > core_own
        new_reach = reach | grow
        fmax = jnp.maximum(fmax, jnp.maximum(
            session.frontier_peak(new_passing),
            session.frontier_peak(grow),
        ))
        changed = session.any_owned(
            (new_reach != reach) | (new_passing != passing)
        )
        return (new_reach, new_passing, changed, fmax,
                n_ovf + ovf.astype(jnp.int32), waves + 1)

    init_pass = (hi + dout_same) > core_own
    reach, passing, _, fmax, n_ovf, waves = jax.lax.while_loop(
        cond, body,
        (seed, init_pass, jnp.bool_(True),
         session.frontier_peak(init_pass), jnp.int32(0), jnp.int32(0)),
    )
    return reach, passing, fmax, n_ovf, waves


@jax.named_scope("coremaint.promote.evict")
def _evict_fixpoint_halo(
    src_h: Array,
    dst_h: Array,
    valid: Array,
    core_own: Array,
    core_h: Array,
    cand: Array,
    hi: Array,
    session: HaloSession,
    kernel_backend: str = "lax",
):
    """``_evict_fixpoint`` with OWNED candidate masks and a per-round
    halo refresh. Returns ``(cand, evict_round, max_frontier,
    n_overflow, waves)`` — owned arrays."""
    hcap = session.halo_cap

    def cond(state):
        return state[3]

    def body(state):
        cand, evict_round, rnd, _, fmax, n_ovf = state
        cand_h, ovf = session.refresh_mask(cand)
        support = hi + G.count_same_level_in(
            src_h, dst_h, valid, core_h, cand_h, hcap, session,
            backend=kernel_backend,
        )
        keep = support > core_own
        fmax = jnp.maximum(fmax, session.frontier_peak(keep))
        new_cand = cand & keep
        newly_evicted = cand & ~new_cand
        evict_round = jnp.where(newly_evicted, rnd, evict_round)
        changed = session.any_owned(new_cand != cand)
        return (new_cand, evict_round, rnd + 1, changed, fmax,
                n_ovf + ovf.astype(jnp.int32))

    # the round counter starts at 1, so it ends one past the iterations
    cand, evict_round, rnd, _, fmax, n_ovf = jax.lax.while_loop(
        cond, body,
        (cand, jnp.zeros(session.n_owned, dtype=jnp.int32),
         jnp.int32(1), jnp.bool_(True), jnp.int32(0), jnp.int32(0)),
    )
    return cand, evict_round, fmax, n_ovf, rnd - 1


@jax.named_scope("coremaint.promote.forward")
def _forward_reach(
    src: Array,
    dst: Array,
    valid: Array,
    core: Array,
    label: Array,
    seed: Array,
    hi: Array,
    dout_same: Array,
    n: int,
    layout: VertexLayout | None = None,
    kernel_backend: str = "lax",
    masks: G.WaveMasks | None = None,
) -> Tuple[Array, Array, Array, Array]:
    """Monotone fixpoint of gated forward expansion.

    Returns (reach, passing, max_frontier, waves) — boolean masks (full
    [n], replicated), the max per-shard count over the exchanged wave
    masks, and the number of waves run. ``passing`` uses the optimistic
    test with din counted over reached-and-passing predecessors only.
    Under a range-sharded layout each wave moves one reduce_scatter
    (din, owned) plus the two wave bitmasks; the loop state stays
    full/replicated so the edge pass can index it at arbitrary
    endpoints. With the round's ``masks`` (lax backend) a wave gathers
    only ``rp``; without them (pallas) the fused kernel gathers core and
    label itself.
    """
    if layout is None:
        layout = ReplicatedVertices(n)
    core_own = layout.own(core)

    def cond(state):
        return state[2]

    def body(state):
        reach, passing, _, fmax, waves = state
        rp = reach & passing
        # one fused scatter per wave: din and frontier growth (C1)
        if masks is None:
            din, grow = G.din_and_expand(src, dst, valid, core, label, rp,
                                         n, layout, backend=kernel_backend)
        else:
            din, grow = G.din_and_expand_masked(masks, rp, src, dst, n,
                                                layout)
        new_passing = layout.gather_mask(
            (hi + dout_same + din) > core_own
        )
        grow_full = layout.gather_mask(grow)
        fmax = jnp.maximum(fmax, jnp.maximum(
            layout.frontier_peak(new_passing), layout.frontier_peak(grow_full)
        ))
        new_reach = reach | grow_full
        changed = jnp.any(new_reach != reach) | jnp.any(new_passing != passing)
        return new_reach, new_passing, changed, fmax, waves + 1

    init_pass = layout.gather_mask((hi + dout_same) > core_own)
    reach, passing, _, fmax, waves = jax.lax.while_loop(
        cond, body,
        (seed, init_pass, jnp.bool_(True), layout.frontier_peak(init_pass),
         jnp.int32(0)),
    )
    return reach, passing, fmax, waves


@jax.named_scope("coremaint.promote.evict")
def _evict_fixpoint(
    src: Array,
    dst: Array,
    valid: Array,
    core: Array,
    cand: Array,
    hi: Array,
    n: int,
    layout: VertexLayout | None = None,
    kernel_backend: str = "lax",
    masks: G.WaveMasks | None = None,
) -> Tuple[Array, Array, Array, Array]:
    """Greatest fixpoint of the candidate support test (sound + complete
    for any starting superset of V*).

    Returns (surviving candidates, eviction round per vertex,
    max_frontier, waves), masks full [n]. The round numbers order the Backward
    tail placement (never-evicted keep 0); they are maintained
    replicated from the gathered candidate masks, so no integer array
    crosses the mesh. With the round's ``masks`` (lax backend) a wave
    gathers only ``cand``.
    """
    if layout is None:
        layout = ReplicatedVertices(n)
    core_own = layout.own(core)

    def cond(state):
        _, _, _, changed, _ = state
        return changed

    def body(state):
        cand, evict_round, rnd, _, fmax = state
        if masks is None:
            same_in = G.count_same_level_in(src, dst, valid, core, cand, n,
                                            layout, backend=kernel_backend)
        else:
            same_in = G.count_same_level_in_masked(masks, cand, src, dst, n,
                                                   layout)
        support = hi + same_in
        keep = layout.gather_mask(support > core_own)
        fmax = jnp.maximum(fmax, layout.frontier_peak(keep))
        new_cand = cand & keep
        newly_evicted = cand & ~new_cand
        evict_round = jnp.where(newly_evicted, rnd, evict_round)
        return (new_cand, evict_round, rnd + 1, jnp.any(new_cand != cand),
                fmax)

    # the round counter starts at 1, so it ends one past the iterations
    cand, evict_round, rnd, _, fmax = jax.lax.while_loop(
        cond,
        body,
        (cand, jnp.zeros(n, dtype=jnp.int32), jnp.int32(1), jnp.bool_(True),
         jnp.int32(0)),
    )
    return cand, evict_round, fmax, rnd - 1


def weighted_promotion_fixpoint(
    src: Array,
    dst: Array,
    valid: Array,
    w: Array,
    core: Array,
    total_w: Array,
    n: int,
    layout: VertexLayout | None = None,
    kernel_backend: str = "lax",
) -> Tuple[Array, Array, Array, Array, Array]:
    """Weighted promotion phase. The Order machinery's forward/evict
    passes have no weighted analogue of the +1-per-round theorem, so the
    promotion phase is the SAME decrease-only h-index fixpoint as the
    removal phase, started from the sound upper bound ``core +
    total_w``: a batch of total inserted weight W can raise any vertex
    by at most W — including vertices with NO inserted edge incident
    (a new path can close a cycle through them), which is why the
    per-vertex incident-weight bound is unsound (docs/DESIGN.md §4.5).
    Returns ``(core, rounds, max_frontier, steps, sorts)``."""
    return weighted_core_fixpoint_pass(
        src, dst, valid, w, core + total_w, n, layout=layout,
        kernel_backend=kernel_backend,
    )


def weighted_promotion_fixpoint_halo(
    src_h: Array,
    dst_h: Array,
    valid: Array,
    w: Array,
    core_own: Array,
    core_h: Array,
    total_w: Array,
    session: HaloSession,
    kernel_backend: str = "lax",
):
    """``weighted_promotion_fixpoint`` on a halo working set: the upper
    bound ``+ total_w`` is replicated, so the halo image stays exact by
    the same local add (sentinel rows drift to ``total_w`` — harmless,
    no valid edge references them). Returns ``(core_own, core_h, rounds,
    max_frontier, steps)``."""
    return weighted_core_fixpoint_pass_halo(
        src_h, dst_h, valid, w, core_own + total_w, core_h + total_w,
        session, kernel_backend=kernel_backend,
    )


@partial(jax.jit, static_argnames=("n", "n_levels"))
def insert_batch(
    src: Array,
    dst: Array,
    valid: Array,
    core: Array,
    label: Array,
    new_src: Array,
    new_dst: Array,
    new_ok: Array,
    n_edges: Array,
    n: int,
    n_levels: int,
) -> Tuple[Array, Array, Array, Array, Array, Array, InsertStats]:
    """Insert ``(new_src, new_dst)`` (masked by ``new_ok``) and restore core
    numbers + k-order labels.

    Returns (src, dst, valid, n_edges, core, label, stats).
    """
    src, dst, valid, n_edges = write_edge_slots(
        src, dst, valid, n_edges, new_src, new_dst, new_ok
    )

    core0 = core
    # fused (hi, dout_same) — one scatter-add / one collective (C1)
    hi, dout_same = G.hi_and_dout_same(src, dst, valid, core, label, n)
    core, label, rounds, v_plus, fmax, fwd, ev = promotion_fixpoint(
        src, dst, valid, core, label, new_src, new_dst, new_ok,
        hi, dout_same, n, n_levels,
    )
    stats = InsertStats(
        rounds=rounds,
        n_promoted=jnp.sum(core != core0, dtype=jnp.int32),
        v_plus=jnp.sum(v_plus, dtype=jnp.int32),
        max_frontier=fmax,
        forward_waves=fwd,
        evict_waves=ev,
    )
    return src, dst, valid, n_edges, core, label, stats
