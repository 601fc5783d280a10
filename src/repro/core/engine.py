"""Unified device-resident edit engine: one compiled program per mixed
insert+remove batch.

The seed implementation paid, per batch: a Python-dict dedup loop, an
``int(n_edges)`` sync, a separate jit program per edit kind, a fresh O(m)
statistics pass per phase, a ``bool(needs_renumber)`` sync, and O(capacity)
buffer copies. ``apply_batch`` moves all of it on-device:

  1. REMOVE  — vectorized slot lookup of the removal edges against the
               live ``(src, dst, valid)`` table (no host dict on the
               critical path), tombstoning, then the mcd removal fixpoint
               (remove.removal_fixpoint).
  2. DEDUP   — in-batch duplicate and self-loop masking plus a vectorized
               membership test against the *post-removal* table, so an
               edge removed and re-inserted in the same batch round-trips
               correctly.
  3. INSERT  — batch slot allocation from the in-program free-list
               (``insert.freelist_alloc``: the ``cumsum`` of kept inserts
               draws from dead slots in global slot order, recycling the
               step-1 tombstones without any host reclaim), table writes,
               and the promotion rounds (insert.promotion_fixpoint). The
               removal fixpoint's terminating round already computed (hi,
               dout_same) in its packed scatter; the new edges' O(batch)
               delta is scattered on top, so the promotion phase starts
               with exact statistics without another O(m) pass.
  4. RELABEL — the ``needs_renumber`` gate runs as a ``lax.cond`` inside
               the program (order.maybe_renumber): no dedicated
               device->host sync, and the flag is reported in the stats.

Each phase runs under a ``jax.named_scope`` with the ``coremaint.``
prefix (``table``, ``remove.stats``, ``promote.seed``,
``promote.forward``, ``promote.evict``, ``promote.stats``, ``labels``),
which reaches every compiled instruction's ``op_name``: a device profile
splits the program's time by phase. Scopes are metadata only.

``src``/``dst``/``valid``/``core``/``label``/``n_edges`` are donated, so
each batch updates the edge table in place instead of copying O(capacity)
arrays (donation is a no-op on backends without buffer aliasing, e.g.
CPU; the harmless warning is silenced below).

The host keeps only a lazily-rebuilt edge->slot mirror for queries and an
upper bound on ``n_edges`` for capacity planning — neither touches the
per-batch critical path. See docs/DESIGN.md §3.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import graph_ops as G
from .insert import (
    freelist_alloc,
    promotion_fixpoint,
    promotion_fixpoint_halo,
    weighted_promotion_fixpoint,
    weighted_promotion_fixpoint_halo,
)
from .order import maybe_renumber, maybe_renumber_ring
from .remove import (
    removal_fixpoint,
    removal_fixpoint_halo,
    weighted_core_fixpoint_pass,
    weighted_core_fixpoint_pass_halo,
)
from .vertex_layout import (
    HaloShardedVertices,
    ReplicatedVertices,
    VertexLayout,
    _note,
)

Array = jax.Array

# Positional args of the batch programs holding the persistent state —
# src, dst, valid, core, label, n_edges — donated so each batch updates
# the table in place instead of copying O(capacity) buffers. One
# constant shared by the unified jit below and the sharded jit
# (core/sharded.py), and the ground truth the donation-verifier audit
# rule (repro.analysis) checks the lowered computations against.
DONATED_STATE_ARGS = (0, 1, 2, 3, 4, 5)

# weighted twin: the slot table carries a weight column at position 3
# (src, dst, valid, w, core, label, n_edges), all donated
WEIGHTED_DONATED_STATE_ARGS = (0, 1, 2, 3, 4, 5, 6)


class BatchStats(NamedTuple):
    """Per-batch statistics of the unified engine (all device scalars)."""

    n_inserted: Array      # edges actually added (post dedup/membership)
    n_removed: Array       # live slots tombstoned
    insert_rounds: Array   # promotion rounds executed
    n_promoted: Array      # |V*| of the insertion phase
    v_plus: Array          # |V+| — vertices reached by FORWARD
    remove_rounds: Array   # removal fixpoint rounds executed
    n_dropped: Array       # |V*| of the removal phase
    renumbered: Array      # True if the in-program label renumber fired
    n_recycled: Array      # inserts that reused a tombstoned slot
    high_water: Array      # post-batch max per-shard slot high-water mark
    max_frontier: Array    # max per-shard exchanged-mask count (both phases)
    n_overflow: Array      # sparse exchanges that fell back dense (halo) /
    #                        bitmask (0 outside the sparse regimes) — the
    #                        observed-cap planner's tuning datum (§4.3)
    forward_waves: Array   # FORWARD wave iterations over all promotion
    #                        rounds (0 in weighted mode)
    evict_waves: Array     # EVICT iterations over all promotion rounds;
    #                        a batch's passes over the slot table are
    #                        remove_rounds + insert_rounds + both waves
    #                        (unweighted)
    remove_bisect_steps: Array  # weighted h-index bisection steps over
    #                             all removal rounds, one support pass
    #                             each (0 unweighted)
    insert_bisect_steps: Array  # the same over all promotion rounds
    hindex_sorts: Optional[Array] = None  # weighted fixpoint rounds,
    #                             removal plus promotion, whose h-index
    #                             came from one sort (a layout with no
    #                             mesh axis; 0 sharded and halo); None
    #                             unweighted: no output of that program


def edge_key(lo: Array, hi: Array, n: int) -> Array:
    """Canonical int64 key of a normalized (lo <= hi) undirected edge."""
    return lo.astype(jnp.int64) * jnp.int64(n) + hi.astype(jnp.int64)


def table_lookup(src: Array, dst: Array, valid: Array, n: int):
    """One sorted int64-key view of a slot table, shared by removal slot
    lookup and insert membership: O(C log C) to build, O(B log C) per
    query batch instead of the naive O(B * C) broadcast compare.

    Returns ``lookup(qkey) -> (found, slot)`` over the given table arrays
    (global slots for the unified engine; shard-local slots when called on
    a shard_map-local shard). Tombstones carry a sentinel key that sorts
    past every real key, so they can never be found.
    """
    capacity = src.shape[0]
    big = jnp.int64(1) << 62  # sentinel: tombstones sort past every real key
    tlo = jnp.minimum(src, dst)
    thi = jnp.maximum(src, dst)
    tkey = jnp.where(valid, edge_key(tlo, thi, n), big)
    torder = jnp.argsort(tkey)
    tsorted = tkey[torder]

    def lookup(qkey):
        pos = jnp.searchsorted(tsorted, qkey)
        pos = jnp.minimum(pos, capacity - 1)
        return tsorted[pos] == qkey, torder[pos]

    return lookup


def batch_dedup(ins_u: Array, ins_v: Array, ins_ok: Array, n: int):
    """Normalize orientation, drop self-loops and in-batch duplicates.

    O(B log B): sort the masked keys and keep one representative per run
    of equals — batch order is irrelevant since the whole batch commits
    simultaneously. Returns ``(ilo, ihi, iok, key)``; the key column is
    reused by the caller's membership test.
    """
    big = jnp.int64(1) << 62
    ilo = jnp.minimum(ins_u, ins_v)
    ihi = jnp.maximum(ins_u, ins_v)
    iok = ins_ok & (ilo != ihi)
    key = edge_key(ilo, ihi, n)
    ikey = jnp.where(iok, key, big)
    iperm = jnp.argsort(ikey)
    isorted = ikey[iperm]
    first = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), isorted[1:] != isorted[:-1]]
    )
    keep = jnp.zeros_like(iok).at[iperm].set(first)
    return ilo, ihi, iok & keep, key


def batch_program(
    src: Array,
    dst: Array,
    valid: Array,
    core: Array,
    label: Array,
    n_edges: Array,
    ins_u: Array,
    ins_v: Array,
    ins_ok: Array,
    rm_u: Array,
    rm_v: Array,
    rm_ok: Array,
    n: int,
    n_levels: int,
    axis: str | None = None,
    layout: VertexLayout | None = None,
    freelist: str = "interleaved",
    kernel_backend: str = "lax",
    w: Array | None = None,
    ins_w: Array | None = None,
):
    """The ONE mixed-batch program body, shared verbatim by the unified
    engine (``axis=None``: the table arrays are the global slot table)
    and the sharded engines (``axis`` = mesh axis: the table arrays are
    this device's shard_map-local shard). Sharing the body is what
    guarantees the engines cannot drift.

    ``w`` (the slot table's weight column) and ``ins_w`` (per-lane
    insert weights) switch the program into WEIGHTED mode, statically:
    with ``w=None`` (the default) no weight array exists anywhere in the
    traced program, so the unweighted jaxpr — and with it the committed
    collective/memory/donation manifests — stays byte-identical to the
    pre-weighted engine. With ``w`` both fixpoint phases run the
    decrease-only weighted h-index fixpoint (removal from the current
    cores, promotion from ``core + total batch weight`` —
    remove.weighted_core_fixpoint_pass / docs/DESIGN.md §4.5), labels
    stay frozen through the fixpoints, and ONE forced bucket-free
    renumber per batch re-canonicalizes them whenever any core moved.
    The weighted return is the 8-tuple ``(src, dst, valid, w, core,
    label, n_edges, stats)``.

    The axis parameter changes exactly three things:

    * the free-list allocator ranks dead slots globally from one
      all_gather of the windowed dead masks (O(n_shards * window)
      replicated bytes; ``freelist="hierarchical"`` shrinks that to one
      scalar per shard at the cost of the interleaved shard-balance
      property — `insert.freelist_alloc`), so the batch cumsum still
      assigns globally unique slots and foreign writes drop
      out-of-bounds;
    * reductions over found-flags / removal masks are completed by a
      psum (an edge lives in exactly one shard, so the psum of the local
      verdicts IS the global verdict — no global sort is materialized);
    * every fixpoint statistic is completed by the vertex ``layout``
      (core/vertex_layout.py): psum for replicated vertex state — the
      default, ``layout=None`` builds ``ReplicatedVertices(n, axis)`` —
      or reduce_scatter to owned vertex ranges for
      ``HaloShardedVertices``, with only changed-vertex masks crossing
      the mesh per round: bit-packed (docs/DESIGN.md §4.2) or, when the
      layout carries a ``frontier_cap``, compacted to a fixed index
      bucket with an in-program bitmask fallback on overflow (§4.3).
      The program body never sees which representation moved — it only
      calls ``layout.gather_mask`` — which is why the sparse exchange
      concentrates entirely in the layout layer.

    ``core``/``label`` are full replicated [n] working values either
    way; a range-sharded caller gathers its owned slices before calling
    and re-slices the returned arrays (core/sharded.py).
    """
    capacity = src.shape[0]  # local (windowed) shard length under shard_map
    if layout is None:
        layout = ReplicatedVertices(n, axis)

    def allsum(x):
        return x if axis is None else jax.lax.psum(x, axis)

    with jax.named_scope("coremaint.table"):
        # pre-batch LOCAL high-water mark: inserts landing below it
        # reclaimed a tombstone (the n_recycled statistic)
        hwm0 = G.slot_high_water(valid)

        # one sorted view of the (local) table serves BOTH the removal
        # slot lookup and the insert membership test
        lookup = table_lookup(src, dst, valid, n)

        # ---- 1. removals: vectorized slot lookup + tombstoning -----------
        rlo = jnp.minimum(rm_u, rm_v)
        rhi = jnp.maximum(rm_u, rm_v)
        rm_ok = rm_ok & (rlo != rhi)
        rfound, rslot = lookup(edge_key(rlo, rhi, n))
        found = rfound & rm_ok
        # commutative scatter-max: not-found rows are no-ops; each device
        # tombstones only its own slots
        rm_mask = jnp.zeros(capacity, dtype=bool).at[rslot].max(found)
        valid = valid & ~rm_mask
        n_removed = allsum(jnp.sum(rm_mask, dtype=jnp.int32))

    core_pre_rm = core
    with jax.named_scope("coremaint.remove.stats"):
        if w is not None:
            (core, rm_rounds, rm_fmax, rm_steps,
             rm_sorts) = weighted_core_fixpoint_pass(
                src, dst, valid, w, core, n, layout=layout,
                kernel_backend=kernel_backend,
            )
            hi = dout_same = layout.zeros()
        else:
            rm_steps = jnp.int32(0)
            (core, label, rm_rounds, hi, dout_same,
             rm_fmax) = removal_fixpoint(
                src, dst, valid, core, label, n, n_levels, layout=layout,
                kernel_backend=kernel_backend,
            )
        n_dropped = jnp.sum(core != core_pre_rm, dtype=jnp.int32)

    with jax.named_scope("coremaint.table"):
        # ---- 2. insert dedup + membership against the post-removal table
        ilo, ihi, iok, key = batch_dedup(ins_u, ins_v, ins_ok, n)
        # membership against the POST-removal table: the sorted view
        # predates the tombstoning, so mask out slots removed in step 1 —
        # this is what lets an edge removed and re-inserted in the same
        # batch round-trip
        ifound, islot_hit = lookup(key)
        exists = allsum(
            (ifound & ~rm_mask[islot_hit]).astype(jnp.int32)
        ) > 0
        iok = iok & ~exists

        # ---- 3. batch slot allocation from the free-list: dead slots
        # (the step-1 tombstones included) are ranked
        # lowest-local-index-first, interleaved across shards, and the
        # batch cumsum assigns insert rank r to the r-th free slot; each
        # device writes the ranks landing in its own shard and drops the
        # rest (masked lanes included) via out-of-bounds scatter
        # semantics. The host guarantees enough free slots in the active
        # window (api.py), so the slot table recycles tombstones without
        # ever syncing.
        lpos, iok = freelist_alloc(valid, iok, axis=axis,
                                   hierarchical=(freelist == "hierarchical"))
        src = src.at[lpos].set(ilo.astype(src.dtype), mode="drop")
        dst = dst.at[lpos].set(ihi.astype(dst.dtype), mode="drop")
        valid = valid.at[lpos].set(True, mode="drop")
        if w is not None:
            # the weight column rides the same allocation: dedup's stable
            # argsort keeps the FIRST occurrence of an in-batch duplicate,
            # so that lane's weight is the one written; re-inserting a
            # live edge was masked by the membership test above (old
            # weight kept)
            w = w.at[lpos].set(ins_w.astype(w.dtype), mode="drop")
        n_inserted = jnp.sum(iok, dtype=jnp.int32)
        n_recycled = allsum(jnp.sum(lpos < hwm0, dtype=jnp.int32))
        # n_edges is the LIVE edge count (not a bump pointer): removals
        # and insertions both land in it, so it tracks the paper's
        # workload size
        n_edges = n_edges - n_removed + n_inserted

    core_pre_ins = core
    if w is not None:
        with jax.named_scope("coremaint.promote.stats"):
            # total inserted batch weight: iok is a replicated verdict
            # under sharding (freelist_alloc narrows it from all-gathered
            # counts), so the sum needs no collective
            total_w = jnp.sum(jnp.where(iok, ins_w, 0), dtype=jnp.int32)
            (core, ins_rounds, ins_fmax, ins_steps,
             ins_sorts) = weighted_promotion_fixpoint(
                src, dst, valid, w, core, total_w, n, layout=layout,
                kernel_backend=kernel_backend,
            )
            v_plus = core != core_pre_ins
        fwd_waves = ev_waves = jnp.int32(0)
    else:
        ins_steps = jnp.int32(0)
        with jax.named_scope("coremaint.promote.seed"):
            # O(batch) delta keeps the shared (hi, dout_same) statistics
            # exact for the table with the new edges — same per-edge
            # predicate as the full passes (graph_ops.hi_dout_indicators);
            # the batch is replicated under sharding, so the delta needs
            # no collective (a range-sharded layout scatters each row
            # into its owner's slice and drops the rest OOB)
            hi_u, hi_v, do_u, do_v = G.hi_dout_indicators(
                core, label, ilo, ihi, iok
            )
            hi = layout.add_at(hi, ilo, hi_u.astype(jnp.int32))
            hi = layout.add_at(hi, ihi, hi_v.astype(jnp.int32))
            dout_same = layout.add_at(dout_same, ilo,
                                      do_u.astype(jnp.int32))
            dout_same = layout.add_at(dout_same, ihi,
                                      do_v.astype(jnp.int32))

        (core, label, ins_rounds, v_plus, ins_fmax, fwd_waves,
         ev_waves) = promotion_fixpoint(
            src, dst, valid, core, label, ilo, ihi, iok,
            hi, dout_same, n, n_levels, layout=layout,
            kernel_backend=kernel_backend,
        )
    with jax.named_scope("coremaint.promote.stats"):
        n_promoted = jnp.sum(core != core_pre_ins, dtype=jnp.int32)
        n_v_plus = jnp.sum(v_plus, dtype=jnp.int32)
        # observed peak per-shard frontier across both fixpoints — the
        # datum the sparse frontier_cap planner is tuned from (§4.3)
        max_frontier = jnp.maximum(rm_fmax, ins_fmax)
        # weighted mode froze the labels through both fixpoints (no
        # bucketed place_block — weighted levels are unbounded in maxW),
        # so it forces ONE bucket-free relabel whenever any core moved;
        # force=None keeps the unweighted gate byte-identical
        force = ((n_dropped > 0) | (n_promoted > 0)) if w is not None \
            else None

    # ---- 4. in-program renumber gate (no host sync) ----------------------
    label, renumbered = maybe_renumber(core, label, force=force)

    with jax.named_scope("coremaint.table"):
        # exact post-batch bound the host refreshes its sync-free window
        # planning from (max over shards of the LOCAL high-water mark)
        high_water = G.slot_high_water(valid, axis)
    stats = BatchStats(
        n_inserted=n_inserted,
        n_removed=n_removed,
        insert_rounds=ins_rounds,
        n_promoted=n_promoted,
        v_plus=n_v_plus,
        remove_rounds=rm_rounds,
        n_dropped=n_dropped,
        renumbered=renumbered,
        n_recycled=n_recycled,
        high_water=high_water,
        max_frontier=max_frontier,
        # the replicated/range paths have no per-round sparse halo
        # refresh; overflow rounds exist only in the halo program below
        n_overflow=jnp.int32(0),
        forward_waves=fwd_waves,
        evict_waves=ev_waves,
        remove_bisect_steps=rm_steps,
        insert_bisect_steps=ins_steps,
        hindex_sorts=rm_sorts + ins_sorts if w is not None else None,
    )
    if w is not None:
        return src, dst, valid, w, core, label, n_edges, stats
    return src, dst, valid, core, label, n_edges, stats


def _pow2(x: int) -> int:
    return 1 << max(x - 1, 0).bit_length()


def halo_cap_for(window: int, lanes_total: int, n_pad: int) -> int:
    """Static halo capacity of one batch program: the pow2 bucket of the
    total endpoint-candidate count — 2 per windowed slot + 2 per batch
    lane (insert and removal) — clamped to ``n_pad``. Deduplication can
    only shrink the candidate set, so overflow is structurally
    impossible: every vertex the batch can reference fits. Derived
    entirely from shapes the jit cache is already keyed on (window and
    lane counts), so the halo adds no recompile surface."""
    return min(_pow2(2 * window + 2 * lanes_total), n_pad)


def build_halo_ids(layout: HaloShardedVertices, src: Array, dst: Array,
                   ins_u: Array, ins_v: Array, rm_u: Array, rm_v: Array,
                   n: int) -> Array:
    """This shard's halo membership: sorted unique global ids referenced
    by its windowed slot prefix or any batch lane, ``n_pad``-sentinel
    padded to the static ``halo_cap_for`` bucket. Tombstoned/garbage
    slot values are still valid vertex ids after the clip — they merely
    widen the halo, never corrupt it (every statistic is gated by the
    edge ``valid`` mask)."""
    cand = jnp.concatenate([src, dst, ins_u, ins_v, rm_u, rm_v]).astype(
        jnp.int32
    )
    cand = jnp.clip(cand, 0, n - 1)
    total = int(cand.shape[0])
    hcap = halo_cap_for(int(src.shape[0]),
                        int(ins_u.shape[0]) + int(rm_u.shape[0]),
                        layout.n_pad)
    sent = jnp.int32(layout.n_pad)
    s = jnp.sort(cand)
    uniq = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), s[1:] != s[:-1]]
    )
    ids = jnp.sort(jnp.where(uniq, s, sent))
    if total >= hcap:
        # hcap == n_pad here (the pow2 bucket was clamped); unique ids
        # number at most n <= n_pad, so truncation only drops sentinels
        return ids[:hcap]
    return jnp.concatenate(
        [ids, jnp.full((hcap - total,), sent, dtype=jnp.int32)]
    )


def batch_program_halo(
    src: Array,
    dst: Array,
    valid: Array,
    core: Array,
    label: Array,
    n_edges: Array,
    ins_u: Array,
    ins_v: Array,
    ins_ok: Array,
    rm_u: Array,
    rm_v: Array,
    rm_ok: Array,
    n: int,
    n_levels: int,
    table_axis,
    layout: HaloShardedVertices,
    freelist: str = "interleaved",
    kernel_backend: str = "lax",
    w: Array | None = None,
    ins_w: Array | None = None,
):
    """``batch_program`` for halo-sharded vertex state — the same four
    phases over the same shard-local slot table, with ``core``/``label``
    as OWNED ``[n_owned]`` slices and every edge pass indexing a bounded
    HALO working set instead of a replicated [n] copy (the PR-7 entry
    gather, deleted). ``table_axis`` names ALL mesh axes the edge slots
    are sharded over (a tuple on a 2-axis mesh; its flattened device
    order at degenerate 1 x d / d x 1 shapes equals the 1-axis mesh, so
    slot allocation — hence the whole table history — is bit-identical
    to the shared-axis engines); the vertex ``layout``'s owner axis is
    one of them (1-axis) or a distinct axis (2-axis, ``edge_axes``
    nonempty). Table-membership verdicts complete over ``table_axis``
    (an edge lives in exactly one shard of the full product); vertex
    scalars complete over the owner axis only (owned slices are
    replicated along pure-edge axes). Bit-identical cores, labels, and
    stats to ``batch_program``.
    """
    capacity = src.shape[0]

    def allsum(x):  # table domain: every mesh axis
        return jax.lax.psum(x, table_axis)

    def vsum(x):    # owned-vertex domain: owner axis only
        return jax.lax.psum(x, layout.axis)

    with jax.named_scope("coremaint.table"):
        hwm0 = G.slot_high_water(valid)
        lookup = table_lookup(src, dst, valid, n)

        # ---- 1. removals: vectorized slot lookup + tombstoning -----------
        rlo = jnp.minimum(rm_u, rm_v)
        rhi = jnp.maximum(rm_u, rm_v)
        rm_ok = rm_ok & (rlo != rhi)
        rfound, rslot = lookup(edge_key(rlo, rhi, n))
        found = rfound & rm_ok
        rm_mask = jnp.zeros(capacity, dtype=bool).at[rslot].max(found)
        valid = valid & ~rm_mask
        n_removed = allsum(jnp.sum(rm_mask, dtype=jnp.int32))

        # ---- halo working set: ONE membership gather + ONE bounded value
        # regather per batch replace the deleted O(n) entry state gather
        halo_ids = build_halo_ids(layout, src, dst, ins_u, ins_v, rm_u,
                                  rm_v, n)
        session = layout.bind(halo_ids)
        core_h = session.gather_values(core)
        # weighted mode freezes labels through both fixpoints — no edge
        # pass ever reads a halo label, so the label regather is skipped
        label_h = None if w is not None else session.gather_values(label)
        src_h = session.locate(src)
        dst_h = session.locate(dst)

    core_pre_rm = core
    with jax.named_scope("coremaint.remove.stats"):
        if w is not None:
            (core, core_h, rm_rounds, rm_fmax,
             rm_steps) = weighted_core_fixpoint_pass_halo(
                src_h, dst_h, valid, w, core, core_h, session,
                kernel_backend=kernel_backend,
            )
            hi = dout_same = session.zeros()
            rm_ovf = jnp.int32(0)
        else:
            rm_steps = jnp.int32(0)
            (core, label, core_h, label_h, rm_rounds, hi, dout_same,
             rm_fmax, rm_ovf) = removal_fixpoint_halo(
                src_h, dst_h, valid, core, label, core_h, label_h, session,
                n_levels, kernel_backend=kernel_backend,
            )
        n_dropped = vsum(jnp.sum(core != core_pre_rm, dtype=jnp.int32))

    with jax.named_scope("coremaint.table"):
        # ---- 2. insert dedup + membership against the post-removal table
        ilo, ihi, iok, key = batch_dedup(ins_u, ins_v, ins_ok, n)
        ifound, islot_hit = lookup(key)
        exists = allsum(
            (ifound & ~rm_mask[islot_hit]).astype(jnp.int32)
        ) > 0
        iok = iok & ~exists

        # ---- 3. slot allocation + table writes (identical to
        # batch_program; the free-list ranks dead slots over the WHOLE
        # mesh product) ---------------------------------------------------
        lpos, iok = freelist_alloc(valid, iok, axis=table_axis,
                                   hierarchical=(freelist == "hierarchical"))
        src = src.at[lpos].set(ilo.astype(src.dtype), mode="drop")
        dst = dst.at[lpos].set(ihi.astype(dst.dtype), mode="drop")
        valid = valid.at[lpos].set(True, mode="drop")
        if w is not None:
            w = w.at[lpos].set(ins_w.astype(w.dtype), mode="drop")
        n_inserted = jnp.sum(iok, dtype=jnp.int32)
        n_recycled = allsum(jnp.sum(lpos < hwm0, dtype=jnp.int32))
        n_edges = n_edges - n_removed + n_inserted

        # the newly written slots reference only lane endpoints — already
        # in the halo by construction — so relocating the window is pure
        # local compute, no new gather
        src_h = session.locate(src)
        dst_h = session.locate(dst)

    core_pre_ins = core
    if w is not None:
        with jax.named_scope("coremaint.promote.stats"):
            total_w = jnp.sum(jnp.where(iok, ins_w, 0), dtype=jnp.int32)
            (core, core_h, ins_rounds, ins_fmax,
             ins_steps) = weighted_promotion_fixpoint_halo(
                src_h, dst_h, valid, w, core, core_h, total_w, session,
                kernel_backend=kernel_backend,
            )
            v_plus = core != core_pre_ins
        ins_ovf = fwd_waves = ev_waves = jnp.int32(0)
    else:
        ins_steps = jnp.int32(0)
        with jax.named_scope("coremaint.promote.seed"):
            u_pos = session.locate(ilo)
            v_pos = session.locate(ihi)

            # O(batch) delta on the shared (hi, dout_same): the per-edge
            # predicate reads lane endpoint values from the halo
            # (replicated verdicts), the scatter lands in each owner's
            # slice and drops OOB
            hi_u, hi_v, do_u, do_v = G.hi_dout_indicators(
                core_h, label_h, u_pos, v_pos, iok
            )
            hi = layout.add_at(hi, ilo, hi_u.astype(jnp.int32))
            hi = layout.add_at(hi, ihi, hi_v.astype(jnp.int32))
            dout_same = layout.add_at(dout_same, ilo,
                                      do_u.astype(jnp.int32))
            dout_same = layout.add_at(dout_same, ihi,
                                      do_v.astype(jnp.int32))

        (core, label, core_h, label_h, ins_rounds, v_plus, ins_fmax,
         ins_ovf, fwd_waves, ev_waves) = promotion_fixpoint_halo(
            src_h, dst_h, valid, core, label, core_h, label_h,
            ilo, ihi, u_pos, v_pos, iok, hi, dout_same, session, n_levels,
            kernel_backend=kernel_backend,
        )
    with jax.named_scope("coremaint.promote.stats"):
        n_promoted = vsum(jnp.sum(core != core_pre_ins, dtype=jnp.int32))
        n_v_plus = vsum(jnp.sum(v_plus, dtype=jnp.int32))
        # per-round peaks were tracked locally; ONE pmax completes them
        max_frontier = session.pmax_scalar(jnp.maximum(rm_fmax, ins_fmax))
        force = ((n_dropped > 0) | (n_promoted > 0)) if w is not None \
            else None

    # ---- 4. in-program renumber gate (ring relabel over owner axis) ------
    label, renumbered = maybe_renumber_ring(
        core, label, layout.axis, layout.n_shards, note=_note, force=force
    )

    with jax.named_scope("coremaint.table"):
        high_water = G.slot_high_water(valid, table_axis)
    stats = BatchStats(
        n_inserted=n_inserted,
        n_removed=n_removed,
        insert_rounds=ins_rounds,
        n_promoted=n_promoted,
        v_plus=n_v_plus,
        remove_rounds=rm_rounds,
        n_dropped=n_dropped,
        renumbered=renumbered,
        n_recycled=n_recycled,
        high_water=high_water,
        max_frontier=max_frontier,
        # overflow verdicts are replicated (gathered count columns), so
        # the local sum IS the global round count
        n_overflow=rm_ovf + ins_ovf,
        forward_waves=fwd_waves,
        evict_waves=ev_waves,
        remove_bisect_steps=rm_steps,
        insert_bisect_steps=ins_steps,
        # the halo layout has a mesh axis: its fixpoints bisect
        hindex_sorts=jnp.int32(0) if w is not None else None,
    )
    if w is not None:
        return src, dst, valid, w, core, label, n_edges, stats
    return src, dst, valid, core, label, n_edges, stats


@partial(
    jax.jit,
    static_argnames=("n", "n_levels", "active_cap", "kernel_backend"),
    donate_argnums=DONATED_STATE_ARGS,
)
def apply_batch(
    src: Array,
    dst: Array,
    valid: Array,
    core: Array,
    label: Array,
    n_edges: Array,
    ins_u: Array,
    ins_v: Array,
    ins_ok: Array,
    rm_u: Array,
    rm_v: Array,
    rm_ok: Array,
    n: int,
    n_levels: int,
    active_cap: int,
    kernel_backend: str = "lax",
) -> Tuple[Array, Array, Array, Array, Array, Array, BatchStats]:
    """Apply one mixed batch (removals first, then insertions) and restore
    core numbers + k-order labels.

    ``ins_*``/``rm_*`` are padded edge lists masked by their ``_ok``
    flags; orientation is normalized on device. ``active_cap`` is the
    host's (sync-free) power-of-two bound on the slot high-water mark
    incl. this batch: every edge pass in the program body runs over
    ``active_cap`` slots instead of the full over-provisioned capacity,
    so per-batch device work scales with the live graph, not with
    headroom. Because the free-list allocator fills the lowest holes
    first, the window also guarantees the allocator enough dead slots
    (window >= high_water + batch implies free >= batch) and the tail
    past it stays all-invalid. Returns ``(src, dst, valid, core, label,
    n_edges, stats)``.
    """
    full_src, full_dst, full_valid = src, dst, valid
    with jax.named_scope("coremaint.table"):
        src, dst, valid = (src[:active_cap], dst[:active_cap],
                           valid[:active_cap])
    src, dst, valid, core, label, n_edges, stats = batch_program(
        src, dst, valid, core, label, n_edges,
        ins_u, ins_v, ins_ok, rm_u, rm_v, rm_ok,
        n, n_levels, kernel_backend=kernel_backend,
    )
    with jax.named_scope("coremaint.table"):
        # splice the active region back into the full-capacity buffers
        # (the inactive tail is untouched: all-invalid headroom)
        src = jnp.concatenate([src, full_src[active_cap:]])
        dst = jnp.concatenate([dst, full_dst[active_cap:]])
        valid = jnp.concatenate([valid, full_valid[active_cap:]])
    return src, dst, valid, core, label, n_edges, stats


@partial(
    jax.jit,
    static_argnames=("n", "n_levels", "active_cap", "kernel_backend"),
    donate_argnums=WEIGHTED_DONATED_STATE_ARGS,
)
def apply_batch_weighted(
    src: Array,
    dst: Array,
    valid: Array,
    w: Array,
    core: Array,
    label: Array,
    n_edges: Array,
    ins_u: Array,
    ins_v: Array,
    ins_w: Array,
    ins_ok: Array,
    rm_u: Array,
    rm_v: Array,
    rm_ok: Array,
    n: int,
    n_levels: int,
    active_cap: int,
    kernel_backend: str = "lax",
):
    """``apply_batch`` with the slot table's weight column: the same
    active-window slice/splice with ``w`` riding alongside the other
    three columns, and the batch's per-lane insert weights threaded to
    the weighted program body. Returns ``(src, dst, valid, w, core,
    label, n_edges, stats)``."""
    full_src, full_dst, full_valid, full_w = src, dst, valid, w
    with jax.named_scope("coremaint.table"):
        src, dst, valid, w = (src[:active_cap], dst[:active_cap],
                              valid[:active_cap], w[:active_cap])
    src, dst, valid, w, core, label, n_edges, stats = batch_program(
        src, dst, valid, core, label, n_edges,
        ins_u, ins_v, ins_ok, rm_u, rm_v, rm_ok,
        n, n_levels, kernel_backend=kernel_backend, w=w, ins_w=ins_w,
    )
    with jax.named_scope("coremaint.table"):
        src = jnp.concatenate([src, full_src[active_cap:]])
        dst = jnp.concatenate([dst, full_dst[active_cap:]])
        valid = jnp.concatenate([valid, full_valid[active_cap:]])
        w = jnp.concatenate([w, full_w[active_cap:]])
    return src, dst, valid, w, core, label, n_edges, stats
