"""k-order label maintenance — the TPU adaptation of the parallel OM
data structure (paper §3.2, ref [11]).

Vertices carry ``(core, label)`` pairs; the k-order predicate is the
lexicographic comparison ``(core[u], label[u]) < (core[v], label[v])`` —
an O(1) ``Order(x, y)`` exactly like the OM list's two-label compare.

Batch "Insert at head of O_{K+1}" / "append at tail of O_{K-1}" become
vectorized label assignments below the level minimum / above the level
maximum; the OM rebalance/split relabel collapses into a per-level (or
global) renumber that is a single ``lexsort`` — amortized O(1) per edit
with the LABEL_GAP spacing (2^20 inserts per gap before a renumber).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

Array = jax.Array
LABEL_GAP = jnp.int64(1) << 20
_NEG = jnp.int64(-(1 << 62))
_POS = jnp.int64(1 << 62)


def level_min_labels(core: Array, label: Array, exclude: Array, n_levels: int) -> Array:
    """Min label per level over vertices not in ``exclude``; _POS if empty."""
    vals = jnp.where(exclude, _POS, label)
    return jax.ops.segment_min(vals, core, num_segments=n_levels)


def level_max_labels(core: Array, label: Array, exclude: Array, n_levels: int) -> Array:
    vals = jnp.where(exclude, _NEG, label)
    return jax.ops.segment_max(vals, core, num_segments=n_levels)


@jax.named_scope("coremaint.labels")
def place_block(
    core_new: Array,
    label: Array,
    moving: Array,
    at_head: bool,
    n_levels: int,
    round_key: Array | None = None,
) -> Array:
    """Assign fresh labels to ``moving`` vertices at the head (insertion,
    O_{K+1}) or tail (removal / Backward eviction, O_{K-1} / O_K) of their
    new level.

    Within a level the moving block is ordered by ``(round_key, old label)``
    — old-label order for promotions (required to preserve the k-order
    certificate), eviction-round order for Backward-evicted vertices
    (the batched analogue of the paper's insert-after-traversal-point;
    proof in docs/DESIGN.md §2.2), and any order is valid for removal drops.
    """
    n = core_new.shape[0]
    base_min = level_min_labels(core_new, label, moving, n_levels)
    base_max = level_max_labels(core_new, label, moving, n_levels)
    base_min = jnp.where(base_min == _POS, jnp.int64(0), base_min)
    base_max = jnp.where(base_max == _NEG, jnp.int64(0), base_max)

    # order moving vertices by (new level, round_key, old label)
    sort_level = jnp.where(moving, core_new, jnp.int32(n_levels))
    if round_key is None:
        perm = jnp.lexsort((label, sort_level))
    else:
        perm = jnp.lexsort((label, round_key, sort_level))
    ranks = jnp.zeros(n, dtype=jnp.int32).at[perm].set(
        jnp.arange(n, dtype=jnp.int32)
    )
    first_rank = jax.ops.segment_min(
        jnp.where(moving, ranks, jnp.int32(2**30)), core_new,
        num_segments=n_levels,
    )
    count = jax.ops.segment_sum(
        moving.astype(jnp.int32), core_new, num_segments=n_levels
    )
    pos = ranks - first_rank[core_new]  # position within the moving block
    if at_head:
        newlab = base_min[core_new] - LABEL_GAP * (
            count[core_new] - pos
        ).astype(jnp.int64)
    else:
        newlab = base_max[core_new] + LABEL_GAP * (pos + 1).astype(jnp.int64)
    return jnp.where(moving, newlab, label)


def _local_ranks(*keys: Array) -> Array:
    """Rank of each element under the stable lexsort of ``keys`` (last
    key primary). Keys are globally duplicate-free wherever it matters
    (same-level labels are unique — place_block always assigns fresh
    labels strictly beyond the level extremes), so stability only ever
    tie-breaks sentinel rows nobody queries."""
    n = keys[0].shape[0]
    perm = jnp.lexsort(keys)
    return jnp.zeros(n, dtype=jnp.int32).at[perm].set(
        jnp.arange(n, dtype=jnp.int32)
    )


def _ring_visiting(payload, axis: str, n_shards: int, note=None):
    """One ring rotation of ``payload`` (a tuple of [n_owned] arrays)
    along ``axis``: after ``t`` applications device ``i`` holds device
    ``(i - t) mod n_shards``'s block. ``note`` (op, bytes) is the
    trace-time traffic hook (vertex_layout._note signature)."""
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    out = []
    for arr in payload:
        if note is not None:
            note("ppermute", int(arr.size) * arr.dtype.itemsize)
        out.append(jax.lax.ppermute(arr, axis, perm=perm))
    return tuple(out)


@jax.named_scope("coremaint.labels")
def place_block_ring(
    core_new: Array,
    label: Array,
    moving: Array,
    at_head: bool,
    n_levels: int,
    axis: str,
    n_shards: int,
    round_key: Array | None = None,
    note=None,
) -> Array:
    """``place_block`` on OWNED slices only — bit-identical labels,
    no [n] or [n_levels] buffer on any device.

    Every input is this device's owned range ``[n_owned]`` of the global
    arrays. The global quantities place_block reads off dense per-level
    arrays (block position, block size, level base label) are instead
    accumulated over a ring of ``n_shards - 1`` ``ppermute`` steps: each
    step a visiting block of (level, round_key, label, moving) rows
    answers three ORDER queries per owned moving vertex — visiting
    same-level movers with a smaller (round_key, label) key, visiting
    same-level movers total, and the visiting non-moving label extreme —
    all via single-key ``searchsorted`` over sorted visiting columns
    plus one combined lexsort (cross-device key ties are impossible:
    same-level labels are globally unique). Buffers stay O(n_owned).

    At ``n_shards == 1`` the ring still runs ONE (masked, zero
    contribution) step so the traced program — and the paired memory
    audit's program-point sequence — is mesh-size independent.
    """
    n_owned = core_new.shape[0]
    rkey = jnp.zeros(n_owned, dtype=jnp.int32) if round_key is None \
        else round_key.astype(jnp.int32)
    lvl_sent = jnp.int32(n_levels)
    # moving rows keyed (level, round_key, label); non-moving rows are
    # (n_levels, 0, 0) sentinels that sort past every moving key
    lvl_m = jnp.where(moving, core_new, lvl_sent)
    rk_m = jnp.where(moving, rkey, 0)
    lab_m = jnp.where(moving, label, jnp.int64(0))
    # non-moving rows keyed (level, label) for the base-label extremes
    lvl_nm = jnp.where(moving, lvl_sent, core_new)
    lab_nm = jnp.where(moving, jnp.int64(0), label)

    # local (t = 0) contributions -------------------------------------
    q = _local_ranks(lab_m, rk_m, lvl_m)   # rank among ALL owned rows
    s_lvl_m = jnp.sort(lvl_m)
    below = jnp.searchsorted(s_lvl_m, lvl_m, side="left").astype(jnp.int32)
    pos = q - below                         # rank within my level's movers
    count = (
        jnp.searchsorted(s_lvl_m, lvl_m, side="right").astype(jnp.int32)
        - below
    )

    def _extremes(v_lvl_nm, v_lab_nm):
        """(min, max) non-moving label per owned vertex's level over one
        [n_owned] block; sentinels where the level group is empty."""
        perm = jnp.lexsort((v_lab_nm, v_lvl_nm))
        s_lvl = v_lvl_nm[perm]
        s_lab = v_lab_nm[perm]
        lo = jnp.searchsorted(s_lvl, core_new, side="left")
        hi = jnp.searchsorted(s_lvl, core_new, side="right")
        found = hi > lo
        bmin = jnp.where(found, s_lab[jnp.minimum(lo, n_owned - 1)], _POS)
        bmax = jnp.where(
            found, s_lab[jnp.clip(hi - 1, 0, n_owned - 1)], _NEG
        )
        return bmin, bmax

    bmin, bmax = _extremes(lvl_nm, lab_nm)

    # ring accumulation over the other shards' blocks ------------------
    def step(carry, t):
        pos, count, bmin, bmax, pay = carry
        pay = _ring_visiting(pay, axis, n_shards, note=note)
        v_lvl_m, v_rk_m, v_lab_m, v_lvl_nm, v_lab_nm = pay
        live = (t < n_shards).astype(jnp.int32)  # masks the 1-shard step
        # visiting movers with key strictly below mine, any level: my
        # combined rank minus my local rank (stability keeps my rows in
        # local order; visiting sentinels sort past every moving key)
        p = _local_ranks(
            jnp.concatenate([lab_m, v_lab_m]),
            jnp.concatenate([rk_m, v_rk_m]),
            jnp.concatenate([lvl_m, v_lvl_m]),
        )[:n_owned]
        s_vlvl = jnp.sort(v_lvl_m)
        v_below = jnp.searchsorted(s_vlvl, lvl_m, side="left").astype(
            jnp.int32
        )
        pos = pos + live * ((p - q) - v_below)
        count = count + live * (
            jnp.searchsorted(s_vlvl, lvl_m, side="right").astype(jnp.int32)
            - v_below
        )
        v_bmin, v_bmax = _extremes(v_lvl_nm, v_lab_nm)
        lv = live > 0
        bmin = jnp.minimum(bmin, jnp.where(lv, v_bmin, _POS))
        bmax = jnp.maximum(bmax, jnp.where(lv, v_bmax, _NEG))
        return (pos, count, bmin, bmax, pay), None

    init = (pos, count, bmin, bmax, (lvl_m, rk_m, lab_m, lvl_nm, lab_nm))
    steps = jnp.arange(1, max(n_shards - 1, 1) + 1, dtype=jnp.int32)
    (pos, count, bmin, bmax, _), _ = jax.lax.scan(step, init, steps)

    bmin = jnp.where(bmin == _POS, jnp.int64(0), bmin)
    bmax = jnp.where(bmax == _NEG, jnp.int64(0), bmax)
    if at_head:
        newlab = bmin - LABEL_GAP * (count - pos).astype(jnp.int64)
    else:
        newlab = bmax + LABEL_GAP * (pos + 1).astype(jnp.int64)
    return jnp.where(moving, newlab, label)


def renumber_ring(core: Array, label: Array, axis: str, n_shards: int,
                  note=None) -> Array:
    """``renumber`` on owned slices: global (core, label)-order ranks via
    the same ring merge-count as ``place_block_ring`` (keys are globally
    unique), then fresh LABEL_GAP-spaced labels."""
    n_owned = core.shape[0]
    q = _local_ranks(label, core)
    rank = q.astype(jnp.int64)

    def step(carry, t):
        rank, pay = carry
        pay = _ring_visiting(pay, axis, n_shards, note=note)
        v_core, v_lab = pay
        live = (t < n_shards).astype(jnp.int64)
        p = _local_ranks(
            jnp.concatenate([label, v_lab]),
            jnp.concatenate([core, v_core]),
        )[:n_owned]
        rank = rank + live * (p - q).astype(jnp.int64)
        return (rank, pay), None

    steps = jnp.arange(1, max(n_shards - 1, 1) + 1, dtype=jnp.int32)
    (rank, _), _ = jax.lax.scan(step, (rank, (core, label)), steps)
    return rank * LABEL_GAP


@jax.named_scope("coremaint.labels")
def maybe_renumber_ring(core: Array, label: Array, axis: str,
                        n_shards: int, note=None,
                        force: Array | None = None) -> Tuple[Array, Array]:
    """``maybe_renumber`` over owned slices: the local headroom verdict
    completes with one int32 pmax over the owner axis (replicated, so
    every device takes the same cond arm; the TPU compiler reduces 64-bit
    values by sum only); the relabel itself is the ring renumber, traced
    inside the cond. ``force`` (a replicated bool) ORs into the verdict —
    the weighted engine relabels whenever cores moved, since its
    fixpoints freeze labels instead of placing blocks."""
    if note is not None:
        note("pmax_scalar", 4)
    need = jax.lax.pmax(needs_renumber(label).astype(jnp.int32), axis) > 0
    if force is not None:
        need = need | force
    new_label = jax.lax.cond(
        need,
        lambda c, l: renumber_ring(c, l, axis, n_shards, note=note),
        lambda c, l: l,
        core, label,
    )
    return new_label, need


@partial(jax.jit, static_argnames=())
def renumber(core: Array, label: Array) -> Array:
    """Global relabel: fresh LABEL_GAP-spaced labels in (core, label) order.
    The vectorized analogue of the OM rebalance+split relabel."""
    n = core.shape[0]
    perm = jnp.lexsort((label, core))
    ranks = jnp.zeros(n, dtype=jnp.int64).at[perm].set(
        jnp.arange(n, dtype=jnp.int64)
    )
    return ranks * LABEL_GAP


def needs_renumber(label: Array) -> Array:
    """True when the label space is running out of headroom."""
    lim = jnp.int64(1) << 61
    return (jnp.min(label) < -lim) | (jnp.max(label) > lim)


@jax.named_scope("coremaint.labels")
def maybe_renumber(core: Array, label: Array,
                   force: Array | None = None) -> Tuple[Array, Array]:
    """Device-side renumber gate: relabel iff the label space is out of
    headroom. Returns ``(label, did_renumber)``.

    Folding the gate into the edit program means the per-batch
    ``needs_renumber`` check costs nothing on the host — no dedicated
    device->host sync, and the relabel itself runs in the same compiled
    program when (rarely) triggered. ``force`` ORs into the verdict (the
    weighted engine's label-freezing fixpoints relabel whenever any core
    moved); ``force=None`` leaves the traced program byte-identical to
    the pre-weighted gate."""
    need = needs_renumber(label)
    if force is not None:
        need = need | force
    new_label = jax.lax.cond(
        need, lambda c, l: renumber(c, l), lambda c, l: l, core, label
    )
    return new_label, need
