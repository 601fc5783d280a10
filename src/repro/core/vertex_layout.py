"""Pluggable vertex-state layout — who holds each per-vertex statistic.

Every maintenance round is "edge pass -> per-vertex decision -> commit".
The edge pass produces PARTIAL per-vertex statistics (each device scatters
only its own edge shard); the layout decides how those partials are
completed and where the per-vertex decision runs:

* ``ReplicatedVertices`` — every device keeps the full ``[n]`` vertex
  state and partial stats complete with one ``psum`` over the edge axis
  (``axis=None`` degenerates to the single-device identity). This is the
  original sharded-engine layout: per-round cross-device vertex traffic
  is O(n * n_devices) words delivered (every device receives every
  completed statistic).

* ``HaloShardedVertices`` — device at owner-axis coordinate ``i`` OWNS
  the contiguous vertex range ``[i * n_owned, (i+1) * n_owned)`` and
  keeps beyond it only a HALO working set: the vertices its local edge
  window and batch lanes actually reference, in a static pow2-capped
  buffer (the paper's Fig. 5 locality — the per-shard referenced set —
  is what bounds it). No device ever materializes an ``[n]`` vertex
  array: per-device memory is O(n / d_v + halo_cap), and the per-batch
  entry state gather of the PR-7 range engine (and the waiver
  that excused it) is gone. Per round the traffic is ONE
  bounded all_gather of halo-domain partial stats (O(d_v * halo_cap)
  words, completed by a local owner scatter-add plus — on a 2-axis
  mesh — one psum over the pure-edge axes), and halo refreshes
  restricted to the round's CHANGED owners: sparse compacted-index
  exchanges of O(frontier_cap * d_v) words (docs/DESIGN.md §4.3) with
  a per-round ``lax.cond`` falling back to a dense halo regather (a
  reduce_scatter of O(halo_cap) words — never a bitmask, never an
  ``[n]`` buffer) whenever any shard's frontier overflows the cap —
  results stay BIT-identical in every regime; the cap is a bandwidth
  knob, never a correctness knob. Decisions run on owned slices;
  labels place via the ring ``order.place_block_ring`` (O(n_owned)
  buffers, same labels). The ``vertex_sharding="range"`` engines are
  the ``edge_axes=()`` degenerate of the same machinery.

All arithmetic is integer, reduce_scatter is an exact sum, and the
refreshed halos are exact images of the owned state — which is why the
halo-sharded engines stay BIT-identical (cores AND k-order labels) to
the replicated ones (``tests/test_churn_streams.py``).

The 2-axis factorization (edge shards x vertex ranges on distinct mesh
axes, ``launch/mesh.py::make_edge_vertex_mesh``) plugs in via
``edge_axes``: stats gain one psum over the pure-edge axes after the
owner scatter (the d_e term of the §4.4 cost model); every other
collective runs over the owner axis only.

Traffic accounting
------------------
``record_traffic()`` captures, at TRACE time, one record per collective
a layout method issues, with the payload each device RECEIVES (computed
from static shapes). ``lax.while_loop`` bodies trace exactly once, so a
recorded fixpoint yields the PER-ROUND collective budget — the object
the acceptance tests assert O(n + frontier-bits * d) on, without running
a single batch. Both arms of the sparse exchange's ``lax.cond`` trace,
so their records carry a ``branch`` tag ("overflow" marks collectives
that only move on the fallback arm); filtering it out yields the
non-overflow round budget the tests pin at O(cap * d) words.
"""
from __future__ import annotations

import dataclasses
import threading
from contextlib import contextmanager
from typing import Iterator, List, Optional

import jax
import jax.numpy as jnp

Array = jax.Array


@dataclasses.dataclass
class Traffic:
    """One collective issued by a layout method (trace-time record)."""

    op: str          # "psum" | "reduce_scatter" | "gather_mask" | ...
    recv_bytes: int  # payload each participating device receives
    branch: str = ""  # "" = unconditional; "overflow" = only moves on
    #                   the sparse exchange's lax.cond fallback arm


# ``Traffic.branch`` value for each arm of the sparse exchange's
# ``lax.cond(overflow, from_bitmask, from_indices, _)``, indexed by the
# traced branch position: JAX stores cond branches as (false, true), so
# branches[0] is the non-overflow index path ("") and branches[1] the
# bitmask fallback ("overflow"). The static auditor (repro.analysis)
# uses this to line jaxpr cond-branch attribution up with the
# trace-time records below.
SPARSE_COND_BRANCHES = ("", "overflow")

_LOG: Optional[List[Traffic]] = None
_OWNER: Optional[int] = None  # thread that opened the active session
# the lock makes session entry/exit and appends atomic, so a second
# session — nested OR from another thread — fails loudly instead of
# silently stealing/corrupting the active log; the owner-thread filter
# in _note keeps a stray trace on another thread out of the session's
# records, and the branch tag is thread-local for the same reason
_LOCK = threading.Lock()
_TLS = threading.local()


@contextmanager
def record_traffic() -> Iterator[List[Traffic]]:
    """Capture the collectives issued while tracing under this context.

    Only one session may be active at a time: a nested (or concurrent)
    entry raises ``RuntimeError`` — a silently-accepted inner context
    would steal the outer one's records (every collective of the inner
    trace would land in the wrong list). Trace one program per context.
    The active context's log survives a rejected entry intact, and only
    the opening thread's traces are recorded into it.
    """
    global _LOG, _OWNER
    with _LOCK:
        if _LOG is not None:
            raise RuntimeError(
                "record_traffic() does not nest (and allows one session "
                "at a time): the second context would steal the active "
                "one's records — trace one program per context"
            )
        _LOG = log = []
        _OWNER = threading.get_ident()
    try:
        yield log
    finally:
        with _LOCK:
            _LOG = None
            _OWNER = None


@contextmanager
def _cond_branch(name: str) -> Iterator[None]:
    """Tag the records noted while tracing one arm of a ``lax.cond``
    (both arms trace exactly once, at cond-construction time).
    Thread-local, so another thread's trace cannot mislabel records."""
    prev = getattr(_TLS, "branch", "")
    _TLS.branch = name
    try:
        yield
    finally:
        _TLS.branch = prev


def _note(op: str, recv_bytes: int) -> None:
    with _LOCK:
        if _LOG is not None and _OWNER == threading.get_ident():
            _LOG.append(
                Traffic(op, int(recv_bytes), getattr(_TLS, "branch", ""))
            )


def _nbytes(x: Array) -> int:
    return int(x.size) * x.dtype.itemsize


@dataclasses.dataclass(frozen=True)
class ReplicatedVertices:
    """Full ``[n]`` vertex state on every device; stats complete by psum
    over the edge axis (identity when ``axis`` is None)."""

    n: int
    axis: Optional[str] = None
    kind: str = dataclasses.field(default="replicated", init=False)

    @property
    def n_owned(self) -> int:
        return self.n

    def complete(self, stats: Array) -> Array:
        """Partial per-vertex stats -> completed stats, full ``[n, ...]``."""
        if self.axis is None:
            return stats
        _note("psum", _nbytes(stats))
        return jax.lax.psum(stats, self.axis)

    def own(self, full: Array) -> Array:
        return full

    def gather_state(self, owned: Array) -> Array:
        return owned

    def gather_mask(self, owned_mask: Array) -> Array:
        return owned_mask

    def any_owned(self, owned_mask: Array) -> Array:
        return jnp.any(owned_mask)

    def frontier_peak(self, full_mask: Array) -> Array:
        """Frontier size of one exchanged mask — with one (replicated)
        shard that is simply the popcount. Local compute, no collective;
        the engines carry the running max through their fixpoints so
        ``stats.max_frontier`` can tune the sparse-cap planner from
        observed data (docs/DESIGN.md §4.3)."""
        return jnp.sum(full_mask, dtype=jnp.int32)

    def zeros(self, dtype=jnp.int32) -> Array:
        return jnp.zeros(self.n, dtype=dtype)

    def add_at(self, owned: Array, idx: Array, vals: Array) -> Array:
        return owned.at[idx].add(vals)


@dataclasses.dataclass(frozen=True)
class HaloShardedVertices:
    """Device at owner-axis coordinate ``i`` owns vertices
    ``[i * n_owned, (i+1) * n_owned)`` and keeps, beyond that owned
    slice, only a HALO: the ``halo_ids`` its local edge window and
    batch lanes actually reference, bucketed into a static pow2 cap
    sized at trace time so overflow is structurally impossible. No
    device ever materializes an [n] vertex buffer — per-device memory
    is O(n / n_shards + halo_cap) (docs/DESIGN.md §4.4); the PR-7 entry
    state gather (and the waiver that excused it) no longer exists.

    ``axis`` is the owner (vertex-range) mesh axis; ``edge_axes`` names
    the PURE-edge mesh axes of a 2-axis factorization
    (``launch/mesh.py::make_edge_vertex_mesh``). With ``edge_axes=()``
    the layout runs on the classic shared single axis — this is what
    ``vertex_sharding="range"`` now builds, so the 1-axis range engines
    share every line of the halo machinery. With ``edge_axes=("edge",)``
    statistics gain one psum over the pure-edge axis (the ``d_e`` term
    of the §4.4 traffic model) after the owner scatter.

    ``n`` pads up to ``n_pad = n_owned * n_shards``; phantom vertices
    past ``n`` hold zeros and are never referenced by an edge or a
    batch lane, so they can never enter a halo or a mask.

    ``frontier_cap`` (static, ``None`` = dense) switches the per-round
    halo refreshes to the sparse compacted-index exchange of
    docs/DESIGN.md §4.3 — O(cap * d_v) words — with a per-round
    ``lax.cond`` falling back to the DENSE halo regather (a
    reduce_scatter of O(halo_cap) words, never a bitmask or an [n]
    buffer) whenever any shard's frontier overflows the cap.
    Bit-identical either way: the cap is a bandwidth knob only.

    The frozen dataclass is the static configuration; ``bind(halo_ids)``
    opens the per-batch :class:`HaloSession` holding the traced halo
    arrays every fixpoint talks to.
    """

    n: int
    axis: str
    n_shards: int
    frontier_cap: Optional[int] = None
    edge_axes: tuple = ()
    kind: str = dataclasses.field(default="halo", init=False)

    @property
    def n_owned(self) -> int:
        return -(-self.n // self.n_shards)

    @property
    def n_pad(self) -> int:
        return self.n_owned * self.n_shards

    def _offset(self) -> Array:
        return jax.lax.axis_index(self.axis) * self.n_owned

    def zeros(self, dtype=jnp.int32) -> Array:
        return jnp.zeros(self.n_owned, dtype=dtype)

    def add_at(self, owned: Array, idx: Array, vals: Array) -> Array:
        """Scatter-add replicated batch contributions into the owned
        slice; rows owned by other devices fall off the end and drop
        (the same OOB trick as the sharded table writes)."""
        loc = idx - self._offset()
        safe = jnp.where((loc >= 0) & (loc < self.n_owned), loc,
                         self.n_owned)
        return owned.at[safe].add(vals, mode="drop")

    def bind(self, halo_ids: Array) -> "HaloSession":
        """Open the per-batch session over ``halo_ids`` (sorted unique
        global ids, ``n_pad``-sentinel padded to the static halo cap).
        ONE all_gather publishes every shard's halo membership for the
        batch — the table the owner-side scatter/regather collectives
        are driven by all rounds long."""
        _note("gather_halo",
              self.n_shards * int(halo_ids.shape[0])
              * halo_ids.dtype.itemsize)
        ids_all = jax.lax.all_gather(halo_ids, self.axis)
        return HaloSession(self, halo_ids, ids_all)


class HaloSession:
    """One batch's halo working set: the traced companion of
    :class:`HaloShardedVertices`.

    ``halo_ids`` is this device's sorted-unique halo membership
    ``[halo_cap]`` (global ids, ``n_pad`` sentinels past the live
    prefix); ``ids_all`` is the ``[n_shards, halo_cap]`` gathered
    membership of the whole owner axis, cached once per batch. Every
    method speaks one of two domains: OWNED ``[n_owned]`` slices (where
    decisions run) and HALO ``[halo_cap]`` arrays (what edge passes
    index). Nothing here is O(n).
    """

    def __init__(self, layout: HaloShardedVertices, halo_ids: Array,
                 ids_all: Array) -> None:
        self.layout = layout
        self.halo_ids = halo_ids
        self.ids_all = ids_all
        self.halo_cap = int(halo_ids.shape[0])

    # -- delegated owned-range geometry --------------------------------
    @property
    def n_owned(self) -> int:
        return self.layout.n_owned

    @property
    def n_pad(self) -> int:
        return self.layout.n_pad

    @property
    def axis(self) -> str:
        return self.layout.axis

    @property
    def frontier_cap(self) -> Optional[int]:
        return self.layout.frontier_cap

    def zeros(self, dtype=jnp.int32) -> Array:
        return self.layout.zeros(dtype)

    def add_at(self, owned: Array, idx: Array, vals: Array) -> Array:
        return self.layout.add_at(owned, idx, vals)

    # -- id <-> halo-position mapping ----------------------------------
    def locate(self, ids: Array) -> Array:
        """Halo position of each global id. Exact for every id the
        batch can reference (window endpoints and batch lanes are in
        the halo by construction); clamped garbage positions for
        anything else, which is safe because every statistic predicate
        is gated by the edge ``valid`` mask."""
        pos = jnp.searchsorted(self.halo_ids, ids.astype(jnp.int32))
        return jnp.clip(pos, 0, self.halo_cap - 1).astype(jnp.int32)

    def _owner_rows(self):
        """(safe_local_row, mine) over ``ids_all``: which gathered halo
        slots fall in MY owned range, and where."""
        loc = self.ids_all - self.layout._offset()
        mine = (loc >= 0) & (loc < self.n_owned)
        return jnp.where(mine, loc, 0), mine

    # -- owner values -> halo (the bounded entry/fallback regather) ----
    def gather_values(self, owned: Array) -> Array:
        """Owned values -> this device's halo values ``[halo_cap]`` via
        ONE reduce_scatter over the owner axis: each shard contributes
        the rows of ``ids_all`` it owns (every id has exactly one
        owner), and the scatter hands each device its own halo row —
        O(halo_cap) received, independent of n. This replaces the
        deleted O(n) entry state gather."""
        safe, mine = self._owner_rows()
        contrib = jnp.where(mine, owned[safe], jnp.zeros((), owned.dtype))
        _note("regather", self.halo_cap * owned.dtype.itemsize)
        # the TPU compiler has no 64-bit reduce_scatter: ship 32-bit words
        # (a no-op for int32, two words per int64) — each row has one
        # nonzero contributor, so each word's sum is that word, exactly
        out = jax.lax.psum_scatter(
            jax.lax.bitcast_convert_type(contrib, jnp.int32),
            self.axis, scatter_dimension=0, tiled=False,
        )
        return jax.lax.bitcast_convert_type(out, owned.dtype)

    # -- halo stat partials -> owned completed stats -------------------
    def complete(self, stats: Array) -> Array:
        """Halo-domain partial stats ``[halo_cap, ...]`` -> exact OWNED
        stats ``[n_owned, ...]``: one all_gather over the owner axis
        (bounded: O(d_v * halo_cap) words), a local owner scatter-add,
        then — on a 2-axis mesh — one psum over the pure-edge axes (the
        ``d_e`` term of the §4.4 cost model)."""
        _note("gather_stats", self.layout.n_shards * _nbytes(stats))
        g = jax.lax.all_gather(stats, self.axis)  # [d_v, halo_cap, ...]
        safe, mine = self._owner_rows()
        tgt = jnp.where(mine, safe, self.n_owned).reshape(-1)
        own = jnp.zeros((self.n_owned,) + stats.shape[1:], stats.dtype)
        own = own.at[tgt].add(
            g.reshape((-1,) + stats.shape[1:]), mode="drop"
        )
        if self.layout.edge_axes:
            _note("psum_edge", _nbytes(own))
            own = jax.lax.psum(own, self.layout.edge_axes)
        return own

    # -- per-round halo refreshes --------------------------------------
    def _sparse_payload(self, owned_mask: Array):
        """Count-prefixed compacted global indices of the owned changed
        set (the §4.3 wire format) plus the compaction positions."""
        cap = self.frontier_cap
        count = jnp.sum(owned_mask, dtype=jnp.int32)
        pos = jnp.cumsum(owned_mask.astype(jnp.int32)) - 1
        gidx = (self.layout._offset()
                + jnp.arange(self.n_owned, dtype=jnp.int32)).astype(
                    jnp.int32)
        safe = jnp.where(owned_mask & (pos < cap), pos, cap)
        buf = jnp.full((cap,), self.n_pad, dtype=jnp.int32)
        buf = buf.at[safe].set(gidx, mode="drop")
        return jnp.concatenate([count[None], buf]), safe

    def _halo_targets(self, flat_gidx: Array) -> Array:
        """Halo positions of gathered global indices; sentinels (and
        ids outside my halo) park one past the end and drop."""
        pos = self.locate(flat_gidx)
        hit = (self.halo_ids[pos] == flat_gidx) & (flat_gidx < self.n_pad)
        return jnp.where(hit, pos, self.halo_cap)

    def refresh_mask(self, owned_mask: Array):
        """Owned bool mask -> (halo mask ``[halo_cap]``, overflow flag).

        Dense (``frontier_cap`` unset): ONE reduce_scatter of the mask
        values over the owner axis — O(halo_cap) received, no [n] or
        bitmask buffer anywhere. Sparse: the §4.3 compacted-index
        all_gather (O(cap * d_v) words) with a per-round ``lax.cond``
        falling back to the dense regather (branch="overflow") when any
        shard's frontier overflows — bit-identical either way. The
        overflow flag is replicated (it comes off the gathered count
        column), feeding the ``BatchStats.n_overflow`` counter the
        observed-cap planner is tuned from."""
        if self.frontier_cap is None:
            return self._mask_dense(owned_mask), jnp.bool_(False)
        payload, _ = self._sparse_payload(owned_mask)
        cap = self.frontier_cap
        _note("gather_frontier", self.layout.n_shards * (cap + 1) * 4)
        g = jax.lax.all_gather(payload, self.axis)  # [d_v, cap + 1]
        overflow = jnp.max(g[:, 0]) > cap

        def from_indices(_):
            tgt = self._halo_targets(g[:, 1:].reshape(-1))
            mask = jnp.zeros(self.halo_cap, dtype=jnp.bool_)
            return mask.at[tgt].max(True, mode="drop")

        def from_dense(_):
            with _cond_branch("overflow"):
                return self._mask_dense(owned_mask)

        return jax.lax.cond(overflow, from_dense, from_indices,
                            None), overflow

    def _mask_dense(self, owned_mask: Array) -> Array:
        return self.gather_values(owned_mask.astype(jnp.int32)) > 0

    def refresh_values(self, core_own: Array, label_own: Array,
                       changed_own: Array, core_h: Array, label_h: Array):
        """Post-commit halo refresh of (core, label) values, restricted
        to the round's changed owners: sparse mode ships compacted
        (index, core, label) columns (three bounded all_gathers), dense
        mode — and the sparse overflow fallback — regathers the full
        halo values with two reduce_scatters (O(halo_cap), exact).
        Returns ``(core_h, label_h, overflow)``."""
        if self.frontier_cap is None:
            return (self.gather_values(core_own),
                    self.gather_values(label_own), jnp.bool_(False))
        payload, safe = self._sparse_payload(changed_own)
        cap = self.frontier_cap
        cbuf = jnp.zeros((cap,), jnp.int32).at[safe].set(
            core_own, mode="drop")
        lbuf = jnp.zeros((cap,), jnp.int64).at[safe].set(
            label_own, mode="drop")
        d_v = self.layout.n_shards
        _note("gather_frontier", d_v * (cap + 1) * 4)
        g_i = jax.lax.all_gather(payload, self.axis)  # [d_v, cap + 1]
        _note("gather_frontier", d_v * cap * 4)
        g_c = jax.lax.all_gather(cbuf, self.axis)     # [d_v, cap]
        _note("gather_frontier", d_v * cap * 8)
        g_l = jax.lax.all_gather(lbuf, self.axis)     # [d_v, cap]
        overflow = jnp.max(g_i[:, 0]) > cap

        def from_indices(args):
            ch, lh = args
            tgt = self._halo_targets(g_i[:, 1:].reshape(-1))
            ch = ch.at[tgt].set(g_c.reshape(-1), mode="drop")
            lh = lh.at[tgt].set(g_l.reshape(-1), mode="drop")
            return ch, lh

        def from_dense(args):
            with _cond_branch("overflow"):
                return (self.gather_values(core_own),
                        self.gather_values(label_own))

        core_h, label_h = jax.lax.cond(
            overflow, from_dense, from_indices, (core_h, label_h)
        )
        return core_h, label_h, overflow

    # -- scalar completions --------------------------------------------
    def any_owned(self, owned_mask: Array) -> Array:
        """Replicated ``any`` over the disjoint owned slices (scalar
        collective over the owner axis; owned values are replicated
        over any pure-edge axes, so the verdict is mesh-global)."""
        _note("psum_scalar", 4)
        return jax.lax.psum(
            jnp.any(owned_mask).astype(jnp.int32), self.axis
        ) > 0

    def frontier_peak(self, owned_mask: Array) -> Array:
        """LOCAL owned popcount of one refreshed mask — no collective;
        the engines carry the running max through their fixpoints and
        complete it with ONE ``pmax_scalar`` at batch end."""
        return jnp.sum(owned_mask, dtype=jnp.int32)

    def pmax_scalar(self, x: Array) -> Array:
        _note("pmax_scalar", 4)
        return jax.lax.pmax(x, self.axis)


VertexLayout = ReplicatedVertices | HaloShardedVertices


def make_layout(kind: str, n: int, axis: Optional[str],
                n_shards: int = 1,
                frontier_cap: Optional[int] = None,
                edge_axes: tuple = ()) -> VertexLayout:
    """Factory keyed by the public ``vertex_sharding`` name.

    ``"range"`` and ``"halo"`` both build :class:`HaloShardedVertices`
    — the 1-axis range engines are the ``edge_axes=()`` degenerate of
    the 2-axis halo engine, so every engine shares one halo code path
    and none materializes an [n] working copy. ``"halo"`` requires the
    pure-edge axes of a 2-axis mesh. Misconfiguration raises HERE, at
    construction — not as an opaque trace-time error three layers
    down."""
    if kind == "replicated":
        if n_shards != 1:
            raise ValueError(
                f"n_shards={n_shards} is meaningless for the replicated "
                "vertex layout (every device keeps the full state; only "
                "kind='range'/'halo' owns per-shard ranges) — pass "
                "n_shards=1 or use a range-sharded kind"
            )
        if frontier_cap is not None:
            raise ValueError(
                f"frontier_cap={frontier_cap} applies only to "
                "kind='range'/'halo' (the replicated layout exchanges "
                "no frontier masks)"
            )
        if edge_axes:
            raise ValueError(
                "edge_axes apply only to kind='halo' (the replicated "
                "layout completes over the one shared axis)"
            )
        return ReplicatedVertices(n, axis)
    if kind in ("range", "halo"):
        if axis is None:
            raise ValueError("range-sharded vertex state needs a mesh axis")
        if frontier_cap is not None and frontier_cap < 1:
            raise ValueError(
                f"frontier_cap must be >= 1 (or None for the dense halo "
                f"regather), got {frontier_cap}"
            )
        if kind == "range" and edge_axes:
            raise ValueError(
                "vertex_sharding='range' is the shared-axis layout; a "
                "2-axis mesh with pure-edge axes needs "
                "vertex_sharding='halo'"
            )
        if kind == "halo" and not edge_axes:
            raise ValueError(
                "vertex_sharding='halo' needs the 2-axis mesh's "
                "pure-edge axes (make_edge_vertex_mesh); for the "
                "shared-axis layout use vertex_sharding='range'"
            )
        return HaloShardedVertices(n, axis, n_shards, frontier_cap,
                                   tuple(edge_axes))
    raise ValueError(f"unknown vertex layout {kind!r}")
