"""CoreMaintainer — the public interface to parallel order-based core
maintenance.

The default ``unified`` engine runs every batch (mixed insertions +
removals) as ONE jitted device program (`engine.apply_batch`): dedup,
slot lookup/allocation, both fixpoints, and the label-renumber gate all
happen on device with donated buffers — the host stays off the critical
path entirely (see docs/DESIGN.md §3 for the host-sync audit).

The host keeps only
  * a lazily-rebuilt ``edge -> slot`` mirror for queries (invalidated per
    batch, materialized on first access), and
  * two sync-free monotone bounds used for capacity planning:
    ``hwm_ub`` (upper bound on the per-shard slot high-water mark
    reported exactly by ``stats.high_water``) and ``live_ub`` (upper
    bound on the live edge count ``n_edges``). The device program
    recycles tombstoned slots through an in-program free-list
    (``insert.freelist_alloc``), so under balanced churn the high-water
    mark — and with it the active window, the per-batch device work, and
    the capacity — stays flat; the bounds are re-synced from the device
    only when they cross the capacity threshold, and ``_compact`` is a
    rare defrag instead of the only reclaim path.

The seed two-program path (host-dict dedup + `insert.insert_batch` /
`remove.remove_batch`) is preserved under ``engine="host"`` as the
benchmark baseline and fallback.

``engine="sharded"`` runs the SAME one-program-per-batch semantics with
the edge-slot table sharded across the mesh (core/sharded.py,
docs/DESIGN.md §4): per-device work is bounded by the densest shard's
high-water window (not full capacity / n_devices — docs/DESIGN.md
§4.1). ``vertex_sharding`` picks where the per-vertex state lives
(core/vertex_layout.py): ``"replicated"`` (the default — each statistic
costs one psum, O(n) received per device per round), ``"range"``
(core/label owner-sharded over the same single axis: each edge shard
keeps only a bounded HALO of the vertices its active slot window
references — no [n] working copy, no entry state gather; statistics
complete with one bounded halo-stats gather + owner scatter, and only
changed-vertex halo refreshes cross the mesh per round — docs/DESIGN.md
§4.2), or ``"halo"`` (the same halo machinery on a genuine 2-axis
``mesh_shape=(d_e, d_v)`` edge x vertex mesh: edge slots shard over
both axes, vertex ranges over the owner axis only, completed statistics
gain exactly one psum over the pure-edge axis, and per-device vertex
memory drops to O(n / d_v + halo) — docs/DESIGN.md §4.4).
``frontier_exchange="sparse"`` shrinks the per-round refresh traffic
further for the paper's tiny affected sets (its Fig. 5): compacted
frontier INDICES in a static ``frontier_cap`` bucket (planned per batch
like ``active_cap`` — seeded from the running quantile of observed
``stats.max_frontier`` once the stream has produced any — or pinned
explicitly), with an in-program per-round fallback to the dense halo
regather on overflow — bit-identical results in every regime
(docs/DESIGN.md §4.3). ``freelist`` picks the slot-allocator ranking
(``"interleaved"`` | ``"hierarchical"`` — `insert.freelist_alloc`).
All engine configurations are bit-identical in cores AND k-order labels
on the same streams (tests/test_churn_streams.py).

Batches are padded to power-of-two sizes so the jit cache stays small.

Edge endpoints are validated on every edit path: out-of-range vertices
raise ``ValueError`` by default, or are masked out (dropped) under
``validate=False`` — an invalid edge can never reach the slot table or
the per-vertex stat scatters (which would clamp it onto vertex n-1).
"""
from __future__ import annotations

import collections
import dataclasses
import warnings
from typing import Any, Callable, Deque, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..graph.csr import CSRGraph, build_csr
from .decomposition import peel_decomposition, rank_to_labels
from .engine import BatchStats, apply_batch, apply_batch_weighted
from .insert import InsertStats, insert_batch
from .oracle import bz_core_decomposition
from .order import needs_renumber, renumber
from .remove import (RemoveStats, remove_batch,
                     weighted_core_fixpoint_pass)
from .sharded import make_sharded_apply

EDGE_AXIS = "data"  # mesh axis the sharded engine shards edge slots over

_ENGINES = ("unified", "host", "sharded")

# the largest weighted degree the weighted engine holds: every core, the
# promotion's start ``core + batch weight`` and every support sum stay at
# or below a weighted degree, and the bisection's ``lo + hi + 1`` (with
# lo <= hi) must fit int32
STRENGTH_MAX = (2**31 - 2) // 2


class BatchCall(NamedTuple):
    """One ``CoreMaintainer.apply_batch`` call as a profiler reads it
    after a run: its ``BatchStats`` (the round and wave counters) and
    the device program it dispatched, by its arguments' shapes."""

    stats: BatchStats
    program: Optional[Callable] = None  # None: host engine, empty batch
    args: tuple = ()  # ShapeDtypeStructs, then the static arguments

    def compiled_text(self) -> Optional[str]:
        """The optimized HLO text of the program this call ran; each
        instruction's ``op_name`` carries the ``coremaint.*`` scope of
        its phase. Lowers and compiles the program again, or finds it
        in JAX's cache: a step for after a run, not for the hot path."""
        if self.program is None:
            return None
        return self.program.lower(*self.args).compile().as_text()


# the last calls of apply_batch in this process, oldest first
RECENT_CALLS: Deque[BatchCall] = collections.deque(maxlen=64)


def _shape_of(x):
    # as jit keys its cache: an uncommitted array's placement is not
    # part of the key, so a later lowering finds the compiled program
    sharding = x.sharding if getattr(x, "committed", True) else None
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _pow2_roundup(need: int) -> int:
    """Smallest power of two >= need — the one bucketing idiom behind
    batch padding, the active window, and the frontier cap."""
    p = 1
    while p < need:
        p *= 2
    return p


def plan_window(hwm_ub: int, b_ins: int, local_cap: int) -> int:
    """Pow2 bucket of the per-shard active window covering the high-water
    bound plus a ``b_ins``-insert batch, clamped to the shard size.

    Pure in its arguments — no maintainer state, no device sync — which
    is what lets the recompile-surface audit rule (repro.analysis)
    enumerate every window the planner can ever pick."""
    return min(_pow2_roundup(max(16, hwm_ub + b_ins + 1)), local_cap)


def plan_frontier_cap(frontier_exchange: str, pinned_cap: int,
                      b_pad: int, n_owned: int, observed: int = 0) -> int:
    """Static pow2 capacity of the sparse frontier index buffer for a
    batch padded to ``b_pad`` lanes (0 when the exchange is off,
    ``pinned_cap`` verbatim when the caller pinned one).

    Deterministic in the batch BUCKET — which already keys a trace — so
    a stream with stable batch sizes never recompiles mid-stream for the
    frontier cap, exactly like the active-window bucket planning. The
    blind heuristic covers a few cascade multiples of the batch (the
    paper's Fig. 5: the affected set per edit is tiny, so per-round
    frontiers rarely outrun the batch size). ``observed`` feeds the
    stream back in: the maintainer passes a running quantile of the
    per-batch ``stats.max_frontier`` it has already harvested
    (sync-free — only device values that are ALREADY ready are read),
    and the cap grows monotonically to cover twice that quantile — a
    stream whose cascades genuinely outrun the batch multiple stops
    paying the overflow fallback after the first few batches, at the
    cost of at most log2(n_owned) extra compiles (the caps stay pow2
    buckets, so the recompile lattice stays the enumerable pow2 ladder).
    A miss-sized cap costs only the in-program dense-regather fallback
    round — never correctness — so no sync or exact bound is needed
    here. Clamped to the pow2 roof of the owned range, past which the
    sparse buffer cannot beat the dense exchange anyway (docs/DESIGN.md
    §4.3 crossover)."""
    if frontier_exchange != "sparse":
        return 0
    if pinned_cap > 0:
        return pinned_cap
    cap = _pow2_roundup(max(32, 4 * b_pad, 2 * observed))
    while cap // 2 >= n_owned:
        cap //= 2
    return cap


def bucket_lattice(local_cap: int, max_batch_lanes: int,
                   frontier_exchange: str = "bitmask",
                   pinned_cap: int = 0, n_owned: int = 1) -> list:
    """Every (window, frontier_cap) static bucket pair the planners above
    can reach for batches up to ``max_batch_lanes`` padded lanes.

    Each pair keys exactly one jitted program variant
    (``CoreMaintainer._get_sharded_fn``; the unified engine uses the
    window alone), so the lattice size IS the worst-case compile count
    over an entire stream — the quantity the recompile-surface audit
    rule bounds. Enumerated exhaustively: ``plan_window`` is monotone in
    ``hwm_ub + b_ins`` with image {pow2 p : 16 <= p < local_cap} plus
    the ``local_cap`` clamp, and ``plan_frontier_cap`` depends on the
    pow2 batch bucket plus the pow2 bucket of the observed-frontier
    quantile — whose image is the pow2 ladder from the smallest blind
    cap up to the owned-range roof (every rung reachable when the
    stream's cascades grow past it), so the sparse cap set is that full
    ladder rather than the blind batch-multiple subset."""
    windows = set()
    p = 16
    while p < local_cap:
        windows.add(p)
        p *= 2
    windows.add(min(p, local_cap))
    caps = set()
    if frontier_exchange != "sparse":
        caps.add(0)
    else:
        b = 1
        while b <= max(1, max_batch_lanes):
            caps.add(plan_frontier_cap(frontier_exchange, pinned_cap,
                                       b, n_owned))
            b *= 2
        if pinned_cap <= 0:
            # observed-quantile seeding can push any planned cap up the
            # pow2 ladder as far as the owned-range roof
            c = min(caps)
            roof = plan_frontier_cap(frontier_exchange, pinned_cap, 1,
                                     n_owned, observed=max(1, n_owned))
            while c < roof:
                caps.add(c)
                c *= 2
            caps.add(roof)
    return sorted((w, c) for w in windows for c in caps)


def _pad_pow2(x: np.ndarray, fill: int) -> np.ndarray:
    p = _pow2_roundup(max(1, len(x)))
    out = np.full(p, fill, dtype=np.int32)
    out[: len(x)] = x
    return out


def _as_edge_array(edges) -> np.ndarray:
    if edges is None:
        return np.zeros((0, 2), dtype=np.int64)
    return np.asarray(edges, dtype=np.int64).reshape(-1, 2)


def _require_x64() -> None:
    """The k-order labels are int64 and the engines pack edge keys against
    an int64 sentinel (1 << 62): with x64 disabled both silently truncate
    to int32 and corrupt state. ``import repro`` enables x64; fail loudly
    if a user (or another library) turned it off afterwards."""
    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            "CoreMaintainer needs jax_enable_x64 (int64 k-order labels and "
            "1<<62 edge-key sentinels corrupt silently under x32). "
            "Re-enable it with jax.config.update('jax_enable_x64', True) "
            "— `import repro` does this at import time."
        )


def _default_edge_mesh(vertex_sharding: str = "replicated",
                       mesh_shape: Optional[Tuple[int, int]] = None):
    from ..launch.mesh import make_edge_mesh, make_edge_vertex_mesh

    if vertex_sharding == "halo":
        # genuine 2-axis edge x vertex factorization; default (1, d) is
        # the pure owner-axis column of the §4.4 traffic model
        return make_edge_vertex_mesh(
            mesh_shape=mesh_shape or (1, len(jax.devices()))
        )
    if vertex_sharding == "range":
        # same 1-D mesh, named for its double duty: the single axis
        # carries the edge shards AND the vertex ranges
        return make_edge_vertex_mesh(axis=EDGE_AXIS)
    return make_edge_mesh(axis=EDGE_AXIS)


@dataclasses.dataclass
class CoreMaintainer:
    """Dynamic-graph core maintenance with k-order labels (JAX)."""

    n: int
    capacity: int
    src: jax.Array
    dst: jax.Array
    valid: jax.Array
    n_edges: jax.Array
    core: jax.Array
    label: jax.Array
    n_levels: int
    engine: str = "unified"     # "unified" | "host" | "sharded"
    mesh: Optional[Any] = None  # sharded engine only; needs a "data" axis
    vertex_sharding: str = "replicated"  # "replicated" | "range" | "halo"
    mesh_shape: Optional[Tuple[int, int]] = None  # (d_e, d_v) 2-axis
    #                             factorization; vertex_sharding="halo"
    #                             only, builds the default mesh
    freelist: str = "interleaved"        # "interleaved" | "hierarchical"
    frontier_exchange: str = "bitmask"   # "bitmask" (dense halo regather)
    #                                      | "sparse" (range/halo only)
    frontier_cap: int = 0       # sparse index-buffer capacity; 0 = planned
    #                             per batch as a static pow2 bucket
    weighted: bool = False      # weight-generalized engine: the slot table
    #                             carries a per-edge integer weight column
    #                             and both maintenance phases run the
    #                             weighted h-index fixpoint (one sort a
    #                             round on one device, a bisection under
    #                             a mesh axis; docs/DESIGN.md §4.5);
    #                             device engines only
    w: Optional[jax.Array] = None  # [capacity] per-slot edge weights
    #                                (weighted=True only; None -> all-ones)
    validate: bool = True       # raise on out-of-range endpoints (else mask)
    last_insert_stats: Optional[InsertStats] = None
    last_remove_stats: Optional[RemoveStats] = None
    last_batch_stats: Optional[BatchStats] = None
    slot_cache: Optional[Dict[Tuple[int, int], int]] = None
    live_ub: int = -1           # upper bound on live edges (-1: from valid)
    hwm_ub: int = -1            # upper bound on the per-shard slot
    #                             high-water mark (-1: compute from valid)
    strength_ub: int = -1       # weighted only: upper bound on every
    #                             vertex's weighted degree, so on every
    #                             weighted core (-1: compute from the table)
    _last_window: int = dataclasses.field(default=0, repr=False)
    host_renumbered: bool = False  # last host-path call triggered a renumber
    _sharded_fns: Dict[Tuple[int, int], Callable] = dataclasses.field(
        default_factory=dict, repr=False
    )
    # sparse frontier-cap observation feedback (sync-free): device
    # max_frontier scalars awaiting readiness, and the harvested host ints
    _frontier_obs: list = dataclasses.field(default_factory=list,
                                            repr=False)
    _frontier_hist: list = dataclasses.field(default_factory=list,
                                             repr=False)
    # sequence number of the next apply_batch call: the ``batch=`` tag
    # every host span of one call carries in a profile
    _batch_seq: int = dataclasses.field(default=0, repr=False)

    def __post_init__(self) -> None:
        # the FULL engine-configuration matrix is validated here, at
        # construction, each message naming the offending field —
        # a bad combination must never survive to surface as an opaque
        # trace-time error inside make_sharded_apply / the layout layer
        if self.engine not in _ENGINES:
            raise ValueError(f"unknown engine {self.engine!r}")
        if self.vertex_sharding not in ("replicated", "range", "halo"):
            raise ValueError(
                f"unknown vertex_sharding {self.vertex_sharding!r}"
            )
        if self.freelist not in ("interleaved", "hierarchical"):
            raise ValueError(f"unknown freelist {self.freelist!r}")
        if self.frontier_exchange not in ("bitmask", "sparse"):
            raise ValueError(
                f"unknown frontier_exchange {self.frontier_exchange!r}"
            )
        if self.mesh is not None and self.engine != "sharded":
            raise ValueError(
                f"mesh= is only consumed by engine='sharded' (got "
                f"engine={self.engine!r}) — a silently ignored mesh "
                "would hide a misconfigured deployment"
            )
        if (self.vertex_sharding in ("range", "halo")
                and self.engine != "sharded"):
            raise ValueError(
                f"vertex_sharding={self.vertex_sharding!r} needs "
                "engine='sharded' (the other engines keep full vertex "
                "state on one device)"
            )
        if self.mesh_shape is not None:
            if self.vertex_sharding != "halo":
                raise ValueError(
                    f"mesh_shape={self.mesh_shape} is only consumed by "
                    "vertex_sharding='halo' (the single-axis layouts "
                    "would silently ignore the factorization)"
                )
            if self.mesh is not None:
                raise ValueError(
                    "pass mesh= OR mesh_shape=, not both — mesh_shape "
                    "builds the default 2-axis mesh; a user mesh carries "
                    "its own factorization"
                )
            de, dv = self.mesh_shape
            if de < 1 or dv < 1:
                raise ValueError(
                    f"mesh_shape must be positive, got {self.mesh_shape}"
                )
        if self.freelist == "hierarchical" and self.engine != "sharded":
            raise ValueError(
                "freelist='hierarchical' needs engine='sharded' — the "
                "ranking only differs across shards (host never uses the "
                "free-list; on one shard it degenerates to interleaved), "
                "so accepting it elsewhere would silently do nothing"
            )
        if (self.frontier_exchange == "sparse"
                and self.vertex_sharding not in ("range", "halo")):
            raise ValueError(
                "frontier_exchange='sparse' needs vertex_sharding="
                "'range' or 'halo' (only the halo layouts exchange "
                "frontier refreshes; the replicated layout would "
                "silently ignore it)"
            )
        if self.frontier_cap < 0:
            raise ValueError(
                f"frontier_cap must be >= 0 (0 = plan automatically), "
                f"got {self.frontier_cap}"
            )
        if self.frontier_cap > 0 and self.frontier_exchange != "sparse":
            raise ValueError(
                f"frontier_cap={self.frontier_cap} is only consumed by "
                "frontier_exchange='sparse' — the bitmask exchange "
                "would silently ignore it"
            )
        if self.weighted:
            if self.engine == "host":
                raise ValueError(
                    "weighted=True needs a device engine ('unified' | "
                    "'sharded') — the seed host path runs the unit-count "
                    "order-maintenance kernels and has no weight column"
                )
            if self.w is None:
                # all-ones weight column: the weighted engine on unit
                # weights computes exactly the classic coreness
                self.w = jnp.ones(self.capacity, dtype=jnp.int32)
            else:
                self.w = jnp.asarray(self.w, dtype=jnp.int32)
                if self.w.shape != (self.capacity,):
                    raise ValueError(
                        f"w has shape {self.w.shape}, expected the slot "
                        f"table shape ({self.capacity},)"
                    )
        elif self.w is not None:
            raise ValueError(
                "w= (per-slot edge weights) needs weighted=True — the "
                "unweighted engines would silently ignore the column"
            )
        _require_x64()
        if self.live_ub < 0 or self.hwm_ub < 0:
            # exact initial bounds from the slot table (construction is
            # the one host-side moment where a sync is free): the global
            # high-water mark upper-bounds every shard's local one
            val = np.asarray(self.valid)
            idx = np.nonzero(val)[0]
            self.live_ub = int(idx.shape[0])
            self.hwm_ub = int(idx[-1]) + 1 if idx.size else 0
        if self.weighted and self.strength_ub < 0:
            self.strength_ub = self._max_strength()
        if self.engine == "host":
            # the host path bump-allocates from n_edges: it must cover the
            # high-water mark (device-engine saves store the live count)
            ne = int(self.n_edges)
            if ne < self.hwm_ub:
                self.n_edges = jnp.asarray(self.hwm_ub, dtype=jnp.int32)
        if self.engine == "sharded":
            if self.mesh is None:
                self.mesh = _default_edge_mesh(self.vertex_sharding,
                                               self.mesh_shape)
            if EDGE_AXIS not in dict(self.mesh.shape):
                raise ValueError(
                    f"sharded engine needs a {EDGE_AXIS!r} mesh axis; got "
                    f"axes {tuple(self.mesh.axis_names)}"
                )
            n_axes = len(tuple(self.mesh.axis_names))
            if self.vertex_sharding == "halo" and n_axes < 2:
                raise ValueError(
                    "vertex_sharding='halo' needs a 2-axis (edge x "
                    "vertex) mesh — launch.mesh.make_edge_vertex_mesh("
                    "mesh_shape=(d_e, d_v)) or mesh_shape=; a single "
                    "shared axis is vertex_sharding='range'"
                )
            if self.vertex_sharding != "halo" and n_axes > 1:
                raise ValueError(
                    f"a multi-axis mesh (axes "
                    f"{tuple(self.mesh.axis_names)}) needs "
                    "vertex_sharding='halo' — the single-axis layouts "
                    "would silently drop the pure-edge-axis partials"
                )
            if self._n_shards > 1:
                # one re-layout: pad capacity to an even shard split AND
                # stride the live slots across the shards so the densest
                # shard's high-water mark (the per-shard window bound)
                # starts near live / n_shards; save()d states keep
                # working on any device count
                self._defrag_to(self.capacity)
            else:
                self._place_sharded()

    # -- sharded placement ---------------------------------------------------
    @property
    def _n_vertex_pad(self) -> int:
        """Vertex-state length under the halo layouts: ``n`` rounded up
        to an owner-shard multiple (phantom tail vertices hold zeros and
        are never referenced by an edge or returned by ``cores()``)."""
        nd = self._d_v
        return -(-self.n // nd) * nd

    def _pad_vertex_state(self) -> None:
        core = jnp.asarray(self.core)
        label = jnp.asarray(self.label)
        pad = self._n_vertex_pad - core.shape[0]
        if pad > 0:
            self.core = jnp.concatenate(
                [core, jnp.zeros((pad,), dtype=core.dtype)]
            )
            self.label = jnp.concatenate(
                [label, jnp.zeros((pad,), dtype=label.dtype)]
            )

    def _place_sharded(self) -> None:
        """Commit the slot table sharded over every mesh axis and the
        vertex state replicated — or owner-sharded over the owner
        (``data``) axis only under the halo layouts, edge-axis
        replicated — so the jitted shard_map program never reshards its
        inputs."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        all_axes = tuple(self.mesh.axis_names)
        esh = NamedSharding(
            self.mesh, P(all_axes if len(all_axes) > 1 else EDGE_AXIS)
        )
        rep = NamedSharding(self.mesh, P())
        vsh = rep
        if self.vertex_sharding in ("range", "halo"):
            self._pad_vertex_state()
            vsh = NamedSharding(self.mesh, P(EDGE_AXIS))
        self.src = jax.device_put(jnp.asarray(self.src), esh)
        self.dst = jax.device_put(jnp.asarray(self.dst), esh)
        self.valid = jax.device_put(jnp.asarray(self.valid), esh)
        if self.weighted:
            self.w = jax.device_put(jnp.asarray(self.w), esh)
        self.core = jax.device_put(jnp.asarray(self.core), vsh)
        self.label = jax.device_put(jnp.asarray(self.label), vsh)
        self.n_edges = jax.device_put(
            jnp.asarray(self.n_edges, dtype=jnp.int32), rep
        )

    def _get_sharded_fn(self, local_active: int,
                        frontier_cap: int = 0) -> Callable:
        """Jitted sharded program for one (per-shard window, frontier
        cap) bucket pair. Both are powers of two (one cache entry per
        pair, same jit hygiene as the unified engine's ``active_cap``)."""
        key = (local_active, frontier_cap)
        fn = self._sharded_fns.get(key)
        if fn is None:
            fn = make_sharded_apply(
                self.mesh, self.n, self.n_levels, axis=EDGE_AXIS,
                local_active=local_active,
                vertex_sharding=self.vertex_sharding,
                freelist=self.freelist,
                frontier_exchange=self.frontier_exchange,
                frontier_cap=frontier_cap,
                weighted=self.weighted,
            )
            self._sharded_fns[key] = fn
        return fn

    # -- capacity planning ---------------------------------------------------
    # both buckets delegate to the module-level pure planners above, so
    # the recompile-surface audit (repro.analysis) enumerates the exact
    # lattice the live maintainer draws from
    def _window(self, b_ins: int) -> int:
        return plan_window(self.hwm_ub, b_ins, self._local_cap)

    def _frontier_bucket(self, b_pad: int) -> int:
        return plan_frontier_cap(
            self.frontier_exchange, self.frontier_cap, b_pad,
            -(-self._n_vertex_pad // self._d_v),
            observed=self._observed_frontier(),
        )

    def _observed_frontier(self) -> int:
        """Running quantile (p95) of the harvested per-batch
        ``stats.max_frontier`` observations — the datum the sparse
        frontier-cap planner is seeded from. Sync-free: only device
        scalars whose computation has ALREADY finished are read; the
        rest stay queued for a later batch."""
        if self.frontier_exchange != "sparse" or self.frontier_cap > 0:
            return 0
        pending = []
        for x in self._frontier_obs:
            if hasattr(x, "is_ready") and not x.is_ready():
                pending.append(x)
                continue
            self._frontier_hist.append(int(x))  # sync: ok (value is ready)
        self._frontier_obs = pending
        hist = self._frontier_hist[-256:]
        self._frontier_hist = hist
        if not hist:
            return 0
        return sorted(hist)[int(0.95 * (len(hist) - 1))]

    @property
    def _n_shards(self) -> int:
        """Edge-slot shard count: the FULL mesh size (edge slots shard
        over every axis; on the 2-axis halo mesh that is d_e * d_v)."""
        if self.engine != "sharded":
            return 1
        return int(np.prod([s for _, s in self.mesh.shape.items()]))

    @property
    def _d_v(self) -> int:
        """Vertex owner-shard count: the size of the owner axis alone."""
        if self.engine != "sharded":
            return 1
        return dict(self.mesh.shape)[EDGE_AXIS]

    @property
    def _local_cap(self) -> int:
        """Slots per shard (== capacity off the sharded engine)."""
        return self.capacity // self._n_shards

    # -- construction -------------------------------------------------------
    @classmethod
    def from_graph(
        cls,
        g: CSRGraph,
        capacity: Optional[int] = None,
        init: str = "host-bz",
        engine: str = "unified",
        mesh: Optional[Any] = None,
        vertex_sharding: str = "replicated",
        mesh_shape: Optional[Tuple[int, int]] = None,
        freelist: str = "interleaved",
        frontier_exchange: str = "bitmask",
        frontier_cap: int = 0,
        kernel_backend: str = "lax",
        weighted: bool = False,
        weights=None,
        validate: bool = True,
    ) -> "CoreMaintainer":
        """Build a maintainer from a static graph.

        ``weighted=True`` seeds the weight-generalized engine:
        ``weights`` aligns row-for-row with ``g.edge_array()`` (omitted
        = all ones), and the initial cores are the exact weighted
        coreness — computed on device by the same decrease-only
        weighted h-index fixpoint the engines run, started from the
        weighted-degree upper bound (``init`` is bypassed; the
        unweighted decompositions do not apply). Initial k-order labels
        are the ``(core, vertex id)`` lexicographic ranks — weighted
        maintenance freezes labels through the fixpoints and renumbers
        once per batch, so any deterministic unique assignment agrees
        across every engine configuration.

        ``kernel_backend`` names the round statistics' implementation,
        of which there is one: ``"lax"`` is the only value accepted, and
        the maintainer does not keep it (benchmark configurations still
        pass it)."""
        if kernel_backend != "lax":
            raise ValueError(
                f"kernel_backend={kernel_backend!r}: the round statistics "
                "have one implementation, 'lax'"
            )
        _require_x64()  # before any label math that would truncate quietly
        edges = g.edge_array()
        m = edges.shape[0]
        capacity = capacity or max(16, 2 * m)
        if capacity <= m:
            raise ValueError("capacity must exceed edge count")
        if weights is not None and not weighted:
            raise ValueError("weights= needs weighted=True")
        src = np.zeros(capacity, dtype=np.int32)
        dst = np.zeros(capacity, dtype=np.int32)
        val = np.zeros(capacity, dtype=bool)
        src[:m] = edges[:, 0]
        dst[:m] = edges[:, 1]
        val[:m] = True
        n_levels = g.n + 2
        if weighted:
            if weights is None:
                wv = np.ones(m, dtype=np.int64)
            else:
                wv = np.asarray(weights, dtype=np.int64).reshape(-1)
                if wv.shape[0] != m:
                    raise ValueError(
                        f"weights have length {wv.shape[0]} but the "
                        f"graph has {m} edges"
                    )
                if wv.size and (wv < 1).any():
                    raise ValueError(
                        "edge weights must be positive integers"
                    )
            wcol = np.zeros(capacity, dtype=np.int32)
            wcol[:m] = wv.astype(np.int32)
            # weighted-degree upper bound -> exact weighted cores via
            # the engines' own decrease-only fixpoint
            deg_w = np.zeros(g.n, dtype=np.int64)
            np.add.at(deg_w, edges[:, 0], wv)
            np.add.at(deg_w, edges[:, 1], wv)
            strength = int(deg_w.max()) if g.n else 0
            if strength > STRENGTH_MAX:
                raise ValueError(
                    f"a vertex's weighted degree is {strength}; the "
                    f"weighted engine holds at most {STRENGTH_MAX} "
                    f"(int32 sums)"
                )
            core, _, _, _, _ = weighted_core_fixpoint_pass(
                jnp.asarray(src), jnp.asarray(dst), jnp.asarray(val),
                jnp.asarray(wcol), jnp.asarray(deg_w.astype(np.int32)),
                g.n,
            )
            core_np = np.asarray(core)
            order = np.lexsort((np.arange(g.n), core_np))
            rank = np.zeros(g.n, dtype=np.int32)
            rank[order] = np.arange(g.n, dtype=np.int32)
            label = rank_to_labels(jnp.asarray(rank))
            return cls(
                n=g.n,
                capacity=capacity,
                src=jnp.asarray(src),
                dst=jnp.asarray(dst),
                valid=jnp.asarray(val),
                n_edges=jnp.asarray(m, dtype=jnp.int32),
                core=core,
                label=label,
                n_levels=n_levels,
                engine=engine,
                mesh=mesh,
                vertex_sharding=vertex_sharding,
                mesh_shape=mesh_shape,
                freelist=freelist,
                frontier_exchange=frontier_exchange,
                frontier_cap=frontier_cap,
                weighted=True,
                w=jnp.asarray(wcol),
                validate=validate,
                live_ub=m,
                hwm_ub=m,
                strength_ub=strength,
            )
        if init == "host-bz":
            adj = [set(g.neighbors(v).tolist()) for v in range(g.n)]
            core_np, order = bz_core_decomposition(g.n, adj)
            rank = np.zeros(g.n, dtype=np.int32)
            rank[np.asarray(order, dtype=np.int64)] = np.arange(
                g.n, dtype=np.int32
            )
            core = jnp.asarray(core_np.astype(np.int32))
            label = rank_to_labels(jnp.asarray(rank))
        elif init == "jax-peel":
            core, rank = peel_decomposition(
                jnp.asarray(src), jnp.asarray(dst), jnp.asarray(val), g.n
            )
            label = rank_to_labels(rank)
        else:
            raise ValueError(init)
        return cls(
            n=g.n,
            capacity=capacity,
            src=jnp.asarray(src),
            dst=jnp.asarray(dst),
            valid=jnp.asarray(val),
            n_edges=jnp.asarray(m, dtype=jnp.int32),
            core=core,
            label=label,
            n_levels=n_levels,
            engine=engine,
            mesh=mesh,
            vertex_sharding=vertex_sharding,
            mesh_shape=mesh_shape,
            freelist=freelist,
            frontier_exchange=frontier_exchange,
            frontier_cap=frontier_cap,
            validate=validate,
            live_ub=m,
            hwm_ub=m,
        )

    # -- queries -------------------------------------------------------------
    @property
    def edge_slot(self) -> Dict[Tuple[int, int], int]:
        """Host mirror of the live edge -> slot table.

        The unified engine allocates slots on device and only invalidates
        this dict; it is rebuilt here on first access (queries tolerate
        the sync — the per-batch edit path never touches it).
        """
        if self.slot_cache is None:
            src = np.asarray(self.src)
            dst = np.asarray(self.dst)
            live = np.nonzero(np.asarray(self.valid))[0]
            self.slot_cache = {
                (int(min(a, b)), int(max(a, b))): int(i)
                for i, a, b in zip(live, src[live], dst[live])
            }
        return self.slot_cache

    def cores(self) -> np.ndarray:
        # [: n] drops the phantom pad of range-sharded vertex state (a
        # no-op everywhere else)
        return np.asarray(self.core)[: self.n]

    def labels(self) -> np.ndarray:
        return np.asarray(self.label)[: self.n]

    def order_lt(self, u: int, v: int) -> bool:
        cu, cv = int(self.core[u]), int(self.core[v])
        if cu != cv:
            return cu < cv
        return int(self.label[u]) < int(self.label[v])

    @property
    def live_edges(self) -> int:
        return len(self.edge_slot)

    # -- validation ----------------------------------------------------------
    def _validated(self, edges, what: str, weights=None):
        """Normalize an edge batch and enforce endpoint bounds.

        With ``validate`` (the default) an out-of-range endpoint raises;
        otherwise the offending rows are masked out before they can reach
        the slot table or the stat scatters (whose index clamping would
        silently alias them onto vertex n-1). When ``weights`` is given
        it must align row-for-row with ``edges``; weights always
        validate strictly (positive integers) and masked rows drop
        their weight in lockstep. Returns ``edges`` alone, or
        ``(edges, weights)`` when weights were passed."""
        edges = _as_edge_array(edges)
        if weights is not None:
            weights = np.asarray(weights, dtype=np.int64).reshape(-1)
            if weights.shape[0] != edges.shape[0]:
                raise ValueError(
                    f"{what} weights have length {weights.shape[0]} but "
                    f"the edge batch has {edges.shape[0]} rows"
                )
            if weights.size and (weights < 1).any():
                bad_w = weights[weights < 1][0]
                raise ValueError(
                    f"{what} edge weights must be positive integers, "
                    f"got {int(bad_w)}"
                )
        if edges.size:
            bad = ((edges < 0) | (edges >= self.n)).any(axis=1)
            if bad.any():
                if self.validate:
                    row = edges[bad][0]
                    raise ValueError(
                        f"{what} edge {row.tolist()} out of range for "
                        f"n={self.n} (pass validate=False to mask instead)"
                    )
                edges = edges[~bad]
                if weights is not None:
                    weights = weights[~bad]
        if weights is not None:
            return edges, weights
        return edges

    # -- edits ----------------------------------------------------------------
    def apply_batch(
        self,
        insert_edges=None,
        remove_edges=None,
        insert_weights=None,
    ) -> BatchStats:
        """Apply one mixed batch (removals first, then insertions) in a
        single compiled device program — no host dedup, no per-batch
        device->host syncs. Under ``engine="host"`` the batch is served by
        the seed two-call path instead (stats composed from both calls);
        ``engine="sharded"`` runs the same program with the slot table
        sharded across the mesh.

        ``insert_weights`` (weighted maintainers only) aligns
        row-for-row with ``insert_edges``; omitted means weight 1 per
        edge. Duplicate rows keep the FIRST occurrence's weight, and
        inserting an already-live edge is a no-op that keeps the stored
        weight — remove + insert updates a weight.

        Each call is one ``coremaint.apply_batch`` profiler span with
        children ``validate``, ``pad``, ``plan_window``, ``transfer``
        and ``dispatch``, all tagged ``batch=<call number>``; they cost
        a flag check while no profile is being taken."""
        _require_x64()
        seq = self._batch_seq
        self._batch_seq += 1

        def span(name):
            return TraceAnnotation(f"coremaint.{name}", batch=seq)

        with span("apply_batch"):
            with span("validate"):
                if insert_weights is not None and not self.weighted:
                    raise ValueError(
                        "insert_weights= needs weighted=True — the "
                        "unweighted engines would silently drop the weights"
                    )
                # validate BOTH lists before any engine touches state, so
                # a rejected batch is rejected atomically (the host path
                # applies removals first and must not commit them before
                # the insert list has passed validation)
                if self.weighted:
                    ins_np = _as_edge_array(insert_edges)
                    if insert_weights is None:
                        insert_weights = np.ones(ins_np.shape[0],
                                                 dtype=np.int64)
                    ins, ins_wts = self._validated(insert_edges, "insert",
                                                   weights=insert_weights)
                    batch_w = int(ins_wts.sum())
                    self._check_strength(batch_w)
                else:
                    ins = self._validated(insert_edges, "insert")
                rm = self._validated(remove_edges, "remove")
            if self.engine == "host":
                with span("dispatch"):
                    n_live0 = self.live_edges
                    rm_st = self._remove_edges_host(rm)
                    n_live1 = self.live_edges
                    renumbered = self.host_renumbered
                    in_st = self._insert_edges_host(ins)
                    renumbered = renumbered or self.host_renumbered
                stats = BatchStats(
                    n_inserted=jnp.int32(self.live_edges - n_live1),
                    n_removed=jnp.int32(n_live0 - n_live1),
                    insert_rounds=in_st.rounds,
                    n_promoted=in_st.n_promoted,
                    v_plus=in_st.v_plus,
                    remove_rounds=rm_st.rounds,
                    n_dropped=rm_st.n_dropped,
                    renumbered=jnp.bool_(renumbered),
                    # the host path reclaims via _compact and has no
                    # halo exchange
                    n_recycled=jnp.int32(0),
                    high_water=self.n_edges,  # == the host bump pointer
                    max_frontier=jnp.maximum(in_st.max_frontier,
                                             rm_st.max_frontier),
                    n_overflow=jnp.int32(0),
                    forward_waves=in_st.forward_waves,
                    evict_waves=in_st.evict_waves,
                    remove_bisect_steps=jnp.int32(0),
                    insert_bisect_steps=jnp.int32(0),
                )
                self.last_batch_stats = stats
                RECENT_CALLS.append(BatchCall(stats))
                return stats
            b_ins = ins.shape[0]
            if b_ins == 0 and rm.shape[0] == 0:
                z = jnp.int32(0)
                stats = BatchStats(
                    n_inserted=z, n_removed=z, insert_rounds=z,
                    n_promoted=z, v_plus=z, remove_rounds=z, n_dropped=z,
                    renumbered=jnp.bool_(False), n_recycled=z,
                    high_water=jnp.int32(self.hwm_ub), max_frontier=z,
                    n_overflow=z, forward_waves=z, evict_waves=z,
                    remove_bisect_steps=z, insert_bisect_steps=z,
                    hindex_sorts=z if self.weighted else None,
                )
                self.last_batch_stats = stats
                RECENT_CALLS.append(BatchCall(stats))
                return stats
            with span("pad"):
                iu = _pad_pow2(ins[:, 0], 0)
                iv = _pad_pow2(ins[:, 1], 0)
                iok = np.zeros(len(iu), dtype=bool)
                iok[:b_ins] = True
                ru = _pad_pow2(rm[:, 0], 0)
                rv = _pad_pow2(rm[:, 1], 0)
                rok = np.zeros(len(ru), dtype=bool)
                rok[: rm.shape[0]] = True
                # padded lanes carry weight 1, but iok=False keeps them
                # out of the slot writes and the total-weight promotion
                # bound
                iw = (_pad_pow2(ins_wts.astype(np.int32), 1)
                      if self.weighted else None)
            with span("plan_window"):
                # may re-lay the table (defrag): before the state is read
                self._ensure_capacity(b_ins)
                # static pow2 bound on the per-shard slot high-water mark
                # incl. this batch: every edge pass runs over this
                # per-shard slot prefix only, and (because the free-list
                # allocator fills the lowest holes first) the window
                # always contains >= b_ins free slots per shard — so the
                # in-program recycler can never run dry
                window = self._window(b_ins)
                if 0 < self._last_window < window:
                    # the bucket would grow — but hwm_ub is the
                    # conservative march, not the truth. Refresh the exact
                    # device bounds (one amortized sync) before paying a
                    # recompile + wider passes: under balanced churn the
                    # true high-water mark is flat and the bucket never
                    # actually grows
                    self._refresh_bounds()
                    window = self._window(b_ins)
                self._last_window = window
            with span("transfer"):
                lanes = (iu, iv, iw, iok, ru, rv, rok) if self.weighted \
                    else (iu, iv, iok, ru, rv, rok)
                state = (self.src, self.dst, self.valid) + (
                    (self.w,) if self.weighted else ()
                ) + (self.core, self.label, self.n_edges)
                args = state + tuple(jnp.asarray(x) for x in lanes)
            with span("dispatch"), warnings.catch_warnings():
                # donation is declared for accelerator backends; backends
                # without buffer aliasing (CPU) warn and copy instead
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable"
                )
                if self.engine == "sharded":
                    # the per-shard window is sliced INSIDE the shard_map
                    # kernel (slicing the sharded buffer here would
                    # reshard); the sparse frontier cap is a second static
                    # bucket keyed off the padded batch size (0 = exchange
                    # off)
                    fcap = self._frontier_bucket(max(len(iu), len(ru)))
                    program = self._get_sharded_fn(window, fcap)
                    static = ()
                else:
                    program = (apply_batch_weighted if self.weighted
                               else apply_batch)
                    static = (self.n, self.n_levels, window)
                # the shapes before the call: it donates the state
                shapes = tuple(_shape_of(x) for x in args) + static
                out = program(*args, *static)
            if self.weighted:
                (
                    self.src,
                    self.dst,
                    self.valid,
                    self.w,
                    self.core,
                    self.label,
                    self.n_edges,
                    stats,
                ) = out
            else:
                (
                    self.src,
                    self.dst,
                    self.valid,
                    self.core,
                    self.label,
                    self.n_edges,
                    stats,
                ) = out
            # monotone sync-free bounds: each insert can raise the
            # densest shard's high-water mark by at most one (holes fill
            # first), and the live count by at most one; removals only
            # help. The exact values (stats.high_water / n_edges) are
            # re-read only when planning crosses the capacity threshold
            # (_refresh_bounds).
            self.hwm_ub = min(self.hwm_ub + b_ins, self._local_cap)
            self.live_ub = min(self.live_ub + b_ins, self.capacity)
            if self.weighted:
                # an insert raises a weighted degree by at most its weight
                self.strength_ub += batch_w
            self.slot_cache = None
            self.last_batch_stats = stats
            RECENT_CALLS.append(BatchCall(stats, program, shapes))
            if (self.frontier_exchange == "sparse"
                    and self.frontier_cap == 0):
                # queue the device scalar for the sync-free
                # observed-quantile harvest (_observed_frontier) that
                # seeds future cap buckets
                self._frontier_obs.append(stats.max_frontier)
            return stats

    def insert_edges(self, edges: np.ndarray,
                     weights: Optional[np.ndarray] = None) -> InsertStats:
        if self.engine == "host":
            if weights is not None:
                raise ValueError(
                    "weights= needs weighted=True (a device engine)"
                )
            return self._insert_edges_host(edges)
        st = self.apply_batch(insert_edges=edges, insert_weights=weights)
        self.last_insert_stats = InsertStats(
            rounds=st.insert_rounds,
            n_promoted=st.n_promoted,
            v_plus=st.v_plus,
            max_frontier=st.max_frontier,
            forward_waves=st.forward_waves,
            evict_waves=st.evict_waves,
        )
        return self.last_insert_stats

    def remove_edges(self, edges: np.ndarray) -> RemoveStats:
        if self.engine == "host":
            return self._remove_edges_host(edges)
        st = self.apply_batch(remove_edges=edges)
        self.last_remove_stats = RemoveStats(
            rounds=st.remove_rounds, n_dropped=st.n_dropped,
            max_frontier=st.max_frontier,
        )
        return self.last_remove_stats

    # -- seed two-program path (benchmark baseline; engine="host") -----------
    def _insert_edges_host(self, edges: np.ndarray) -> InsertStats:
        _require_x64()
        self.host_renumbered = False
        edges = self._validated(edges, "insert")
        lo = np.minimum(edges[:, 0], edges[:, 1])
        hi = np.maximum(edges[:, 0], edges[:, 1])
        keep, seen = [], set()
        slot_table = self.edge_slot
        for a, b in zip(lo.tolist(), hi.tolist()):
            key = (a, b)
            if a == b or key in seen or key in slot_table:
                continue
            seen.add(key)
            keep.append(key)
        if not keep:
            self.last_insert_stats = None
            z = jnp.int32(0)
            return InsertStats(rounds=z, n_promoted=z, v_plus=z,
                               max_frontier=z, forward_waves=z,
                               evict_waves=z)
        arr = np.asarray(keep, dtype=np.int32)
        if int(self.n_edges) + arr.shape[0] + 1 >= self.capacity:
            self._compact()  # replaces slot_cache — re-read below
            if int(self.n_edges) + arr.shape[0] + 1 >= self.capacity:
                self._grow(arr.shape[0])
        base = int(self.n_edges)
        slot_table = self.edge_slot
        for i, key in enumerate(keep):
            slot_table[key] = base + i
        new_src = _pad_pow2(arr[:, 0], 0)
        new_dst = _pad_pow2(arr[:, 1], 0)
        new_ok = np.zeros(len(new_src), dtype=bool)
        new_ok[: arr.shape[0]] = True
        (
            self.src,
            self.dst,
            self.valid,
            self.n_edges,
            self.core,
            self.label,
            stats,
        ) = insert_batch(
            self.src,
            self.dst,
            self.valid,
            self.core,
            self.label,
            jnp.asarray(new_src),
            jnp.asarray(new_dst),
            jnp.asarray(new_ok),
            self.n_edges,
            self.n,
            self.n_levels,
        )
        # on the host path n_edges IS the bump pointer (slot high-water)
        self.hwm_ub = int(self.n_edges)
        self.live_ub = self.hwm_ub
        self.host_renumbered = self._maybe_renumber()
        self.last_insert_stats = stats
        return stats

    def _remove_edges_host(self, edges: np.ndarray) -> RemoveStats:
        _require_x64()
        self.host_renumbered = False
        edges = self._validated(edges, "remove")
        slots = []
        slot_table = self.edge_slot
        for a, b in edges:
            key = (int(min(a, b)), int(max(a, b)))
            slot = slot_table.pop(key, None)
            if slot is not None:
                slots.append(slot)
        if not slots:
            self.last_remove_stats = None
            return RemoveStats(jnp.int32(0), jnp.int32(0), jnp.int32(0))
        padded = _pad_pow2(np.asarray(slots, dtype=np.int32), -1)
        self.valid, self.core, self.label, stats = remove_batch(
            self.src,
            self.dst,
            self.valid,
            self.core,
            self.label,
            jnp.asarray(padded),
            self.n,
            self.n_levels,
        )
        self.host_renumbered = self._maybe_renumber()
        self.last_remove_stats = stats
        return stats

    # -- maintenance -----------------------------------------------------------
    def _maybe_renumber(self) -> bool:
        if bool(needs_renumber(self.label)):
            self.label = renumber(self.core, self.label)
            return True
        return False

    def _refresh_bounds(self) -> None:
        """Amortized sync point: replace the monotone worst-case planning
        bounds with the exact values the device already computed —
        ``stats.high_water`` (per-shard high-water mark) and ``n_edges``
        (live count). Called only when the conservative bounds cross the
        capacity threshold; the per-batch edit path stays sync-free.
        Under balanced churn the exact high-water mark is flat (the
        free-list recycles every tombstone), so this usually reveals
        plenty of headroom and no defrag or growth happens at all."""
        # called from apply_batch only: the span joins that call's tag
        with TraceAnnotation("coremaint.refresh_bounds",
                             batch=self._batch_seq - 1):
            if self.last_batch_stats is not None:
                self.hwm_ub = int(self.last_batch_stats.high_water)
            self.live_ub = int(self.n_edges)

    def _max_strength(self) -> int:
        """The largest weighted degree of the live slot table (a sync)."""
        val = np.asarray(self.valid)
        w = np.asarray(self.w)[val]
        deg = sum(np.bincount(np.asarray(end)[val], w, minlength=self.n)
                  for end in (self.src, self.dst))
        return int(deg.max()) if self.n else 0

    def _check_strength(self, batch_w: int) -> None:
        """Refuse a weighted batch whose inserted weight ``batch_w`` could
        lift a weighted degree, or the promotion's start ``core +
        batch weight``, past ``STRENGTH_MAX``, before any state changes.
        The sync-free bound ``strength_ub`` only grows; where it would
        cross the limit it is first replaced by the table's exact
        largest weighted degree (one sync), so removals are counted."""
        if self.strength_ub + batch_w <= STRENGTH_MAX:
            return
        self.strength_ub = self._max_strength()
        if self.strength_ub + batch_w > STRENGTH_MAX:
            raise ValueError(
                f"inserting total weight {batch_w} onto a largest "
                f"weighted degree of {self.strength_ub} could exceed "
                f"{STRENGTH_MAX}, the most the weighted engine's int32 "
                f"sums hold"
            )

    def _ensure_capacity(self, b_ins: int) -> None:
        """Make the per-shard window able to hold the live slots plus this
        batch. Escalates: sync-free bound check -> exact-bound refresh
        (one amortized sync) -> defrag, growing in the same re-layout if
        even a perfectly packed table would leave no window headroom —
        so the sharded buffers are placed at most ONCE per call (the old
        compact-then-grow path placed them twice)."""
        if self.hwm_ub + b_ins + 1 < self._local_cap:
            return
        self._refresh_bounds()
        if self.hwm_ub + b_ins + 1 < self._local_cap:
            return
        nd = self._n_shards
        new_cap = self.capacity
        # after a balanced defrag the densest shard holds ceil(live / nd)
        while -(-self.live_ub // nd) + b_ins + 1 >= new_cap // nd:
            new_cap = max(new_cap * 2, new_cap + nd * (2 * b_ins + 16))
        with TraceAnnotation("coremaint.defrag", batch=self._batch_seq - 1):
            self._defrag_to(new_cap)

    def _defrag_to(self, new_cap: int) -> None:
        """Repack live slots into a balanced layout at ``new_cap`` total
        capacity (compact + grow fused: one buffer re-layout, one sharded
        placement). Live edges are strided across the shards — edge j
        lands on shard ``j % n_shards`` — so every shard's high-water
        mark starts at ~``live / n_shards``. Preserves core/label state.
        Rare: the in-program free-list reclaims tombstones batch-by-batch,
        so this only runs when the exact bounds genuinely leave no window
        headroom (large net growth or a lopsided loaded layout)."""
        nd = self._n_shards
        new_cap += (-new_cap) % nd
        src = np.asarray(self.src)
        dst = np.asarray(self.dst)
        val = np.asarray(self.valid)
        live = np.nonzero(val)[0]
        m = live.shape[0]
        if new_cap <= m:
            raise ValueError(
                f"defrag target {new_cap} cannot hold {m} live edges"
            )
        local_cap = new_cap // nd
        j = np.arange(m, dtype=np.int64)
        tgt = (j % nd) * local_cap + j // nd
        new_src = np.zeros(new_cap, dtype=np.int32)
        new_dst = np.zeros(new_cap, dtype=np.int32)
        new_val = np.zeros(new_cap, dtype=bool)
        new_src[tgt] = src[live]
        new_dst[tgt] = dst[live]
        new_val[tgt] = True
        self.src = jnp.asarray(new_src)
        self.dst = jnp.asarray(new_dst)
        self.valid = jnp.asarray(new_val)
        if self.weighted:
            wcol = np.asarray(self.w)
            new_w = np.zeros(new_cap, dtype=np.int32)
            new_w[tgt] = wcol[live]
            self.w = jnp.asarray(new_w)
        self.n_edges = jnp.asarray(m, dtype=jnp.int32)
        self.capacity = new_cap
        self.live_ub = m
        self.hwm_ub = -(-m // nd) if m else 0
        self._last_window = 0  # fresh layout: let the next batch re-bucket
        # the mirror is stale either way; let the edge_slot property
        # rebuild it lazily (the unified engine never reads it)
        self.slot_cache = None
        if self.engine == "sharded":
            self._place_sharded()

    def _compact(self) -> None:
        """Drop tombstoned slots (host-path reclaim; a defrag elsewhere).
        The one edit-path step that syncs — amortized over many batches."""
        self._defrag_to(self.capacity)

    def _grow(self, need: int) -> None:
        self._grow_to(max(self.capacity * 2, self.capacity + 2 * need + 16))

    def _grow_to(self, new_cap: int) -> None:
        """Extend the slot table with dead headroom — the host-path
        growth step. The device engines grow through ``_defrag_to``
        (which also re-strides across shards); delegate so a sharded
        caller can never produce an unbalanced un-restrided layout."""
        if self.engine == "sharded":
            self._defrag_to(new_cap)
            return
        pad = new_cap - self.capacity
        if pad <= 0:
            return

        def ext(x, fill):
            x = jnp.asarray(x)
            return jnp.concatenate(
                [x, jnp.full((pad,), fill, dtype=x.dtype)]
            )

        self.src = ext(self.src, 0)
        self.dst = ext(self.dst, 0)
        self.valid = ext(self.valid, False)
        if self.weighted:
            self.w = ext(self.w, 0)
        self.capacity = new_cap

    # -- persistence -------------------------------------------------------------
    def save(self, path: str) -> None:
        """Checkpoint the maintainer. The free-list is implicit — a dead
        slot is exactly a ``valid=False`` entry — so tombstones, the
        recycler's state, and the per-shard high-water marks all
        round-trip through the ``valid`` mask (load() recomputes the
        planning bounds from it, shard-count independent). Range-sharded
        vertex state is saved UNPADDED (``[:n]``), so the checkpoint is
        also vertex-shard-count independent: a state saved range-sharded
        over 8 devices reloads replicated on 1 and vice versa.
        Weighted maintainers add the per-slot weight column ``w``
        (aligned with ``src``/``dst``/``valid``)."""
        payload = dict(
            n=self.n,
            capacity=self.capacity,
            src=np.asarray(self.src),
            dst=np.asarray(self.dst),
            valid=np.asarray(self.valid),
            n_edges=np.asarray(self.n_edges),
            core=self.cores(),
            label=self.labels(),
        )
        if self.weighted:
            payload["w"] = np.asarray(self.w)
        np.savez_compressed(path, **payload)

    @classmethod
    def load(
        cls,
        path: str,
        engine: str = "unified",
        mesh: Optional[Any] = None,
        vertex_sharding: str = "replicated",
        mesh_shape: Optional[Tuple[int, int]] = None,
        freelist: str = "interleaved",
        frontier_exchange: str = "bitmask",
        frontier_cap: int = 0,
        weighted: bool = False,
        validate: bool = True,
    ) -> "CoreMaintainer":
        z = np.load(path)
        w = None
        if weighted:
            # checkpoints from an unweighted maintainer carry no weight
            # column; loading one weighted adopts unit weights (exactly
            # the classic-coreness specialization)
            w = jnp.asarray(z["w"]) if "w" in z.files else None
        return cls(
            n=int(z["n"]),
            capacity=int(z["capacity"]),
            src=jnp.asarray(z["src"]),
            dst=jnp.asarray(z["dst"]),
            valid=jnp.asarray(z["valid"]),
            n_edges=jnp.asarray(z["n_edges"]),
            core=jnp.asarray(z["core"]),
            label=jnp.asarray(z["label"]),
            n_levels=int(z["n"]) + 2,
            engine=engine,
            mesh=mesh,
            vertex_sharding=vertex_sharding,
            mesh_shape=mesh_shape,
            freelist=freelist,
            frontier_exchange=frontier_exchange,
            frontier_cap=frontier_cap,
            weighted=weighted,
            w=w,
            validate=validate,
            slot_cache=None,  # lazily rebuilt from the live table
            # live_ub / hwm_ub default to -1: __post_init__ recomputes
            # both exactly from the saved valid mask, which makes the
            # high-water bookkeeping portable across device counts (a
            # state saved on 1 device reloads sharded over 8 and vice
            # versa; the sharded path re-strides the layout on entry)
        )


def maintainer_from_edges(n: int, edges: np.ndarray, **kw) -> CoreMaintainer:
    return CoreMaintainer.from_graph(build_csr(n, edges), **kw)
