"""Vectorized per-vertex neighborhood statistics over COO edge slots.

These are the message-passing primitives every maintenance round is built
from. ``segment_sum`` tolerates unsorted segment ids, so the dynamic COO
slot layout needs no sorting between edit batches.

Each undirected edge is stored once; each statistic issues two LOCAL
scatter-adds (one per direction) that GSPMD combines into one all-reduce.
Round-level stats are packed into multi-column scatters where profitable
(§Perf iteration C1; a concatenated single-scatter variant measured WORSE
— the concat of two edge-sharded streams forces an all-gather reshard).

Every statistic takes an optional ``layout`` (core/vertex_layout.py):
inside a ``shard_map`` over edge slots the local segment sums are
COMPLETED by the layout — one ``psum`` over the mesh axis for
``ReplicatedVertices`` (exact global statistic on every device), one
``reduce_scatter`` for ``HaloShardedVertices`` (each device receives
only the vertex range it owns; on a 2-axis mesh the owned partials
additionally psum over the pure-edge axes first). With ``layout=None``
(single-device /
GSPMD) completion is the identity and the functions are unchanged. This
is how the sharded engines reuse the exact fixpoint code of remove.py /
insert.py regardless of where the vertex state lives.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .vertex_layout import ReplicatedVertices, VertexLayout

Array = jax.Array

def completes_locally(layout: Optional[VertexLayout]) -> bool:
    """True when ``layout.complete`` is the identity (single device /
    GSPMD): partial statistics ARE the global statistics, so a
    per-vertex result may be computed from this device's edges alone.
    Under a mesh axis it must wait for the layout's collective."""
    return layout is None or (
        isinstance(layout, ReplicatedVertices) and layout.axis is None
    )


def _complete(x: Array, layout: Optional[VertexLayout]) -> Array:
    return x if layout is None else layout.complete(x)


def _pmax(x: Array, axis: Optional[str]) -> Array:
    return x if axis is None else jax.lax.pmax(x, axis)


def slot_high_water(valid: Array, axis: Optional[str] = None) -> Array:
    """High-water mark of a slot table: 1 + the largest valid slot index
    (0 when empty). With ``axis`` (shard_map-local shard) the result is
    the max over shards of each shard's LOCAL high-water mark — the
    "densest shard" bound that sizes the per-shard active window of the
    sharded engine (docs/DESIGN.md §4.1)."""
    idx = jnp.arange(valid.shape[0], dtype=jnp.int32)
    local = jnp.max(jnp.where(valid, idx + 1, 0))
    return _pmax(local, axis)


def _seg2(data_to_src: Array, data_to_dst: Array, src: Array, dst: Array,
          n: int, layout: Optional[VertexLayout] = None) -> Array:
    """Two-direction segment sum. Two LOCAL scatter-adds + elementwise add:
    GSPMD then emits a single all-reduce for the combined [n] result.
    (A concatenated single-scatter variant was measured WORSE — the concat
    of two edge-sharded streams forces an all-gather reshard; §Perf C1.)
    Under shard_map the partial result is completed by the vertex layout
    (psum for replicated state, reduce_scatter for range-sharded)."""
    a = jax.ops.segment_sum(data_to_src, src, num_segments=n)
    b = jax.ops.segment_sum(data_to_dst, dst, num_segments=n)
    return _complete(a + b, layout)


def degree(src: Array, dst: Array, valid: Array, n: int,
           layout: Optional[VertexLayout] = None) -> Array:
    one = valid.astype(jnp.int32)
    return _seg2(one, one, src, dst, n, layout)


def count_ge(src: Array, dst: Array, valid: Array, vals: Array, n: int,
             layout: Optional[VertexLayout] = None) -> Array:
    """mcd (Def 3.8): per-vertex count of neighbors w with vals[w] >= vals[v]."""
    to_src = (valid & (vals[dst] >= vals[src])).astype(jnp.int32)
    to_dst = (valid & (vals[src] >= vals[dst])).astype(jnp.int32)
    return _seg2(to_src, to_dst, src, dst, n, layout)


def count_gt(src: Array, dst: Array, valid: Array, vals: Array, n: int,
             layout: Optional[VertexLayout] = None) -> Array:
    """Per-vertex count of neighbors w with vals[w] > vals[v]."""
    to_src = (valid & (vals[dst] > vals[src])).astype(jnp.int32)
    to_dst = (valid & (vals[src] > vals[dst])).astype(jnp.int32)
    return _seg2(to_src, to_dst, src, dst, n, layout)


def hi_dout_indicators(
    core: Array, label: Array, u: Array, v: Array, ok: Array
):
    """Per-edge indicator columns of the promotion statistics: for each
    (u, v) edge masked by ``ok``, whether it contributes to hi(u), hi(v),
    dout_same(u), dout_same(v). The single definition shared by the full
    passes below and by the unified engine's O(batch) delta update —
    keeping the statistic's tie-breaking in one place."""
    same = ok & (core[u] == core[v])
    hi_to_u = ok & (core[v] > core[u])
    hi_to_v = ok & (core[u] > core[v])
    dout_to_u = same & (label[v] > label[u])
    dout_to_v = same & (label[u] > label[v])
    return hi_to_u, hi_to_v, dout_to_u, dout_to_v


class WaveMasks(NamedTuple):
    """Per-edge masks of one promotion round's (core, label, valid): they
    hold through every FORWARD and EVICT wave of the round, so the waves
    read them instead of gathering core and label again."""
    same: Array  # valid & core[src] == core[dst]
    s2d: Array   # same & label[src] < label[dst]: src precedes dst
    d2s: Array   # same & label[dst] < label[src]: dst precedes src


def _wave_masks_of(valid: Array, indicators) -> WaveMasks:
    hi_s, hi_d, do_s, do_d = indicators
    return WaveMasks(valid & ~(hi_s | hi_d), do_s, do_d)


def wave_masks(
    src: Array, dst: Array, valid: Array, core: Array, label: Array,
) -> WaveMasks:
    """The round's ``WaveMasks``, from the same per-edge indicators as the
    (hi, dout_same) statistic (``hi_dout_indicators``)."""
    return _wave_masks_of(
        valid, hi_dout_indicators(core, label, src, dst, valid)
    )


def _hi_dout_sums(indicators, src: Array, dst: Array, n: int,
                  layout: Optional[VertexLayout]):
    hi_s, hi_d, do_s, do_d = indicators
    to_src = jnp.stack(
        [hi_s.astype(jnp.int32), do_s.astype(jnp.int32)], axis=-1
    )
    to_dst = jnp.stack(
        [hi_d.astype(jnp.int32), do_d.astype(jnp.int32)], axis=-1
    )
    out = _complete(
        jax.ops.segment_sum(to_src, src, num_segments=n)
        + jax.ops.segment_sum(to_dst, dst, num_segments=n),
        layout,
    )
    return out[:, 0], out[:, 1]


def hi_and_dout_same(
    src: Array, dst: Array, valid: Array, core: Array, label: Array, n: int,
    layout: Optional[VertexLayout] = None,
):
    """Packed (hi, dout_same) for the insertion round: one [n, 2] result
    (single collective) carries both the higher-core neighbor count and
    the same-level k-order successor count (Defs 3.6/3.7 pieces)."""
    return _hi_dout_sums(
        hi_dout_indicators(core, label, src, dst, valid), src, dst, n, layout
    )


def hi_dout_same_and_masks(
    src: Array, dst: Array, valid: Array, core: Array, label: Array, n: int,
    layout: Optional[VertexLayout] = None,
):
    """``hi_and_dout_same`` plus the ``WaveMasks`` of the same
    (core, label, valid), built from the same gathers: a promotion round's
    closing statistics pass hands the next round both."""
    ind = hi_dout_indicators(core, label, src, dst, valid)
    hi, dout_same = _hi_dout_sums(ind, src, dst, n, layout)
    return hi, dout_same, _wave_masks_of(valid, ind)


def mcd_hi_dout(
    src: Array, dst: Array, valid: Array, core: Array, label: Array, n: int,
    layout: Optional[VertexLayout] = None,
):
    """Packed (mcd, hi, dout_same) — one [n, 3] scatter carries the removal
    fixpoint's support count (Def 3.8) together with both promotion-seeding
    statistics (Defs 3.6/3.7 pieces). The unified engine runs this once per
    removal round; the terminating round's (hi, dout_same) columns are then
    reused to seed the promotion phase without a fresh O(m) pass."""
    hi_s, hi_d, do_s, do_d = hi_dout_indicators(core, label, src, dst, valid)
    to_src = jnp.stack(
        [
            (valid & (core[dst] >= core[src])).astype(jnp.int32),
            hi_s.astype(jnp.int32),
            do_s.astype(jnp.int32),
        ],
        axis=-1,
    )
    to_dst = jnp.stack(
        [
            (valid & (core[src] >= core[dst])).astype(jnp.int32),
            hi_d.astype(jnp.int32),
            do_d.astype(jnp.int32),
        ],
        axis=-1,
    )
    out = _complete(
        jax.ops.segment_sum(to_src, src, num_segments=n)
        + jax.ops.segment_sum(to_dst, dst, num_segments=n),
        layout,
    )
    return out[:, 0], out[:, 1], out[:, 2]


def count_same_level_after(
    src: Array, dst: Array, valid: Array, core: Array, label: Array, n: int,
    layout: Optional[VertexLayout] = None,
) -> Array:
    """dout within level (part of Def 3.7): neighbors with equal core and a
    larger order label (successors in the k-order DAG at the same level)."""
    same = valid & (core[src] == core[dst])
    to_src = (same & (label[dst] > label[src])).astype(jnp.int32)
    to_dst = (same & (label[src] > label[dst])).astype(jnp.int32)
    return _seg2(to_src, to_dst, src, dst, n, layout)


def count_same_level_before_in(
    src: Array,
    dst: Array,
    valid: Array,
    core: Array,
    label: Array,
    mask: Array,
    n: int,
    layout: Optional[VertexLayout] = None,
) -> Array:
    """din* (Def 3.6): same-level order-predecessors that are in ``mask``."""
    same = valid & (core[src] == core[dst])
    to_src = (same & (label[dst] < label[src]) & mask[dst]).astype(jnp.int32)
    to_dst = (same & (label[src] < label[dst]) & mask[src]).astype(jnp.int32)
    return _seg2(to_src, to_dst, src, dst, n, layout)


def count_same_level_in(
    src: Array, dst: Array, valid: Array, core: Array, mask: Array, n: int,
    layout: Optional[VertexLayout] = None,
) -> Array:
    """Per-vertex count of same-level neighbors inside ``mask``."""
    same = valid & (core[src] == core[dst])
    to_src = (same & mask[dst]).astype(jnp.int32)
    to_dst = (same & mask[src]).astype(jnp.int32)
    return _seg2(to_src, to_dst, src, dst, n, layout)


def count_same_level_in_masked(
    masks: WaveMasks, mask: Array, src: Array, dst: Array, n: int,
    layout: Optional[VertexLayout] = None,
) -> Array:
    """``count_same_level_in`` with the round's ``WaveMasks``: the only
    gathers left are the two of the boolean ``mask``."""
    to_src = (masks.same & mask[dst]).astype(jnp.int32)
    to_dst = (masks.same & mask[src]).astype(jnp.int32)
    return _seg2(to_src, to_dst, src, dst, n, layout)


def din_and_expand(
    src: Array,
    dst: Array,
    valid: Array,
    core: Array,
    label: Array,
    rp: Array,
    n: int,
    layout: Optional[VertexLayout] = None,
):
    """Fused FORWARD-wave statistics in ONE scatter-add: din counts
    reached-and-passing k-order predecessors, and frontier growth is
    exactly ``din > 0`` (a vertex is newly reachable iff it has an RP
    predecessor) — iteration C1."""
    same = valid & (core[src] == core[dst])
    fwd_to_dst = same & (label[src] < label[dst]) & rp[src]
    fwd_to_src = same & (label[dst] < label[src]) & rp[dst]
    din = _seg2(
        fwd_to_src.astype(jnp.int32), fwd_to_dst.astype(jnp.int32),
        src, dst, n, layout,
    )
    return din, din > 0


def din_and_expand_masked(
    masks: WaveMasks, rp: Array, src: Array, dst: Array, n: int,
    layout: Optional[VertexLayout] = None,
):
    """``din_and_expand`` with the round's ``WaveMasks``: the only gathers
    left are the two of the boolean ``rp``."""
    din = _seg2(
        (masks.d2s & rp[dst]).astype(jnp.int32),
        (masks.s2d & rp[src]).astype(jnp.int32),
        src, dst, n, layout,
    )
    return din, din > 0


def weighted_support(
    src: Array, dst: Array, valid: Array, w: Array, core: Array,
    thresh: Array, n: int, layout: Optional[VertexLayout] = None,
) -> Array:
    """Weighted generalization of ``count_ge``: per-vertex SUM of incident
    edge weights to neighbors u with ``core[u] >= thresh[v]`` (the inner
    statistic of the weighted h-index bisection; with unit weights and
    ``thresh == core`` this IS mcd). The weighted column rides the exact
    same two-scatter + layout-completion schedule as the unit stats, so
    the sharded collective budget is unchanged per pass."""
    wi = w.astype(jnp.int32)
    to_src = jnp.where(valid & (core[dst] >= thresh[src]), wi, 0)
    to_dst = jnp.where(valid & (core[src] >= thresh[dst]), wi, 0)
    return _seg2(to_src, to_dst, src, dst, n, layout)


class HIndexEntries(NamedTuple):
    """A slot table's directed entries, two per slot (one per endpoint),
    for ``sorted_h_index``. The table holds through a fixpoint, so one
    view serves every round of it: sorted by owner, whatever the order
    within each owner, v's entries fill the same positions."""
    owner: Array  # [2C] int32 the entry's vertex; n on an invalid slot
    nbr: Array    # [2C] int32 the other endpoint, whose core keys it
    w: Array      # [2C] int32 the edge weight; 0 on an invalid slot
    reset: Array  # [2C] int32 -strength(v) just past v's last position
    last: Array   # [n] int32 v's last position (first: last - deg + 1)
    deg: Array    # [n] int32 v's entries


def hindex_entries(src: Array, dst: Array, valid: Array, w: Array,
                   n: int) -> HIndexEntries:
    """The ``HIndexEntries`` of a slot table. Invalid slots own the
    sentinel ``n``, which sorts past every real vertex."""
    owner = jnp.concatenate([jnp.where(valid, src, n),
                             jnp.where(valid, dst, n)]).astype(jnp.int32)
    nbr = jnp.concatenate([dst, src]).astype(jnp.int32)
    wi = jnp.where(valid, w.astype(jnp.int32), 0)
    col = jnp.stack([valid.astype(jnp.int32), wi], axis=-1)
    deg_strength = _seg2(col, col, src, dst, n)
    deg, strength = deg_strength[:, 0], deg_strength[:, 1]
    last = jnp.cumsum(deg) - 1
    # an empty vertex adds its 0 at its predecessor's reset position
    reset = jnp.zeros(owner.shape[0], jnp.int32).at[last + 1].add(
        -strength, mode="drop")
    return HIndexEntries(owner, nbr, jnp.concatenate([wi, wi]), reset,
                         last, deg)


def sorted_h_index(entries: HIndexEntries, core: Array,
                   upper: Array) -> Array:
    """Per-vertex weighted h-index in closed form: sort each vertex's
    entries by neighbour core, descending, with ``W_j`` the prefix sums
    of their weights; then ``H(v) = min(upper(v), max_j min(c_j, W_j))``
    (0 without entries). Each ``min(c_j, W_j)`` is feasible, since the
    first j entries carry ``W_j`` to neighbours of core >= c_j, and the
    largest feasible h is reached at the last entry of core >= h; the
    last entry of a tie in c carries the tie's full support, and the
    feasible set is a prefix of ``[0, ...]``, so the cap is exact. Equal
    to the bisection (``bisect_h_index``), integer for integer.

    One gather of the cores, one two-key sort, two int32 cumsums and
    n-sized gathers. ``W`` is the cumsum of the weights with
    ``entries.reset`` added: each owner's strength is taken off just
    past its last entry, so the sums restart at every owner. Every
    ``W_j`` lies in ``[0, STRENGTH_MAX]``, and a partial sum that
    wraps int32 on the way (a table's total weight may pass 2^31)
    still ends there exactly. ``W_j - c_j`` never decreases within an
    owner, so ``W_j <= c_j`` holds on a prefix of it, counted by the
    second cumsum; the max is then ``W`` at the prefix's last entry or
    ``c`` at the entry after it."""
    c = core[entries.nbr].astype(jnp.int32)
    owner, neg_c, w = jax.lax.sort((entries.owner, -c, entries.w),
                                   num_keys=2, is_stable=False)
    c = -neg_c
    support = jnp.cumsum(w + entries.reset)
    below = jnp.cumsum((support <= c).astype(jnp.int32))
    last, deg = entries.last, entries.deg
    first = last - deg + 1
    at = partial(jnp.take, mode="clip")
    cnt = jnp.where(deg > 0, at(below, last) - jnp.where(
        first > 0, at(below, first - 1), 0), 0)
    pos = first + cnt
    h = jnp.maximum(jnp.where(cnt > 0, at(support, pos - 1), 0),
                    jnp.where(cnt < deg, at(c, pos), 0))
    return jnp.minimum(jnp.maximum(h, 0),
                       jnp.maximum(upper.astype(jnp.int32), 0))


def bisect_h_index(
    src: Array, dst: Array, valid: Array, w: Array, core: Array,
    upper: Array, n: int, layout: Optional[VertexLayout] = None,
) -> Tuple[Array, Array]:
    """Per-vertex weighted h-index by lockstep bisection:
    ``H_w(v) = max{h <= upper[v] : sum of weights to nbrs with
    core >= h is >= h}`` (Zhou et al., WWW'21). The feasible set is a
    prefix (the support sum is non-increasing in h), so bisection over
    ``[0, upper]`` needs O(log maxW) masked rounds, each ONE weighted
    support pass over the edge window. The invariant is lo-feasible
    (``lo = 0`` trivially so); converged lanes re-test ``mid == lo``
    and stay fixed, so the while_loop runs until the SLOWEST lane
    converges with every lane stable. Returns ``(h, steps)``: ``steps``
    is the loop's iteration count, one support pass each. Replicated/
    plain layouts only — the halo twin lives in core/remove.py next to
    its fixpoint."""
    upper = jnp.maximum(upper.astype(jnp.int32), 0)
    lo = jnp.zeros_like(upper)

    def cond(state):
        lo_, hi_, _ = state
        return jnp.any(lo_ < hi_)

    def body(state):
        lo_, hi_, steps = state
        mid = (lo_ + hi_ + 1) // 2
        s = weighted_support(src, dst, valid, w, core, mid, n, layout)
        ok = s >= mid
        return (jnp.where(ok, mid, lo_), jnp.where(ok, hi_, mid - 1),
                steps + 1)

    lo, _, steps = jax.lax.while_loop(cond, body,
                                      (lo, upper, jnp.int32(0)))
    return lo, steps


def weighted_h_index(
    src: Array, dst: Array, valid: Array, w: Array, core: Array,
    upper: Array, n: int, layout: Optional[VertexLayout] = None,
    entries: Optional[HIndexEntries] = None,
) -> Tuple[Array, Array]:
    """Per-vertex weighted h-index capped by ``upper``; returns ``(h,
    steps)``, ``steps`` the bisection's support passes. Where the layout
    has no mesh axis (``completes_locally``) every edge of a vertex is
    on this device, so one sort gives it (``sorted_h_index``, 0 steps);
    ``entries`` is the table's view from ``hindex_entries``, which a
    fixpoint builds once (built here when None). Under a mesh axis a
    vertex's support is a sum of partial sums completed by the layout,
    and a max of prefix minima does not split that way: the lockstep
    bisection runs (``bisect_h_index``)."""
    if completes_locally(layout):
        if entries is None:
            entries = hindex_entries(src, dst, valid, w, n)
        return sorted_h_index(entries, core, upper), jnp.int32(0)
    return bisect_h_index(src, dst, valid, w, core, upper, n, layout)


def expand_forward(
    src: Array,
    dst: Array,
    valid: Array,
    core: Array,
    label: Array,
    frontier: Array,
    n: int,
    layout: Optional[VertexLayout] = None,
) -> Array:
    """One wave of the Forward phase: reach same-level k-order successors of
    ``frontier`` vertices (boolean [n])."""
    same = valid & (core[src] == core[dst])
    hit_dst = same & frontier[src] & (label[src] < label[dst])
    hit_src = same & frontier[dst] & (label[dst] < label[src])
    out = _seg2(
        hit_src.astype(jnp.int32), hit_dst.astype(jnp.int32), src, dst, n,
        layout,
    )
    return out > 0
