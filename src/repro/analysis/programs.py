"""Trace the engine matrix's programs for static auditing.

The repo's performance claims are STRUCTURAL properties of traced
programs (one reduce_scatter per round, no vertex-sized psum under the
sparse exchange, donated batch buffers, a bounded jit-variant lattice).
This module produces the artifacts the audit rules inspect, without
executing a single batch:

* ``ENGINE_CONFIGS`` — the nine engine configurations
  (host / unified / sharded / vertex_range / frontier_sparse /
  vertex_halo / pallas, all bit-identical on unweighted streams, plus
  the weight-generalized ``weighted`` / ``weighted_sharded`` pair —
  bit-identical to each other and to ``weighted_core_oracle`` on
  weighted streams), exactly the matrix
  ``tests/test_churn_streams.py`` proves equivalent. The ``pallas``
  config is the sharded engine with the fused COO stat kernels
  (kernels/coremaint.py): the fusion swaps only LOCAL partials, so its
  collective histogram and memory budgets must EQUAL the lax sharded
  config's — an equality the audit enforces, not assumes. The
  ``vertex_halo`` config runs the halo working set on a genuine 2-axis
  edge x vertex mesh (``mesh_shape=(d_e, d_v)``,
  ``launch/mesh.py::make_edge_vertex_mesh``) — its manifest carries the
  §4.4 two-axis traffic/memory formulas in d_e/d_v/hcap, and the audit
  re-traces it under BOTH 8-device factorizations (4x2 and 2x4) against
  the one committed manifest;
* ``trace_removal_round`` / ``trace_promotion_round`` — shard_map-trace
  ONE fixpoint under a vertex layout, returning both the trace-time
  traffic log (``record_traffic``) and the closed jaxpr: a
  ``lax.while_loop`` body traces exactly once, so either view IS the
  per-round collective budget (and ``rules.cross_check_round`` verifies
  they agree);
* ``trace_engine`` — the full picture for one config: batch-program
  jaxprs, lowered computations (for donation/aliasing checks), round
  traces, the planned (window, frontier-cap) buckets, and the size
  environment budget formulas evaluate in.

Audit parameters are fixed and small (n=192, capacity=384, 8 batch
lanes): collective COUNTS are device-count independent (shard_map
traces one program regardless of mesh size) and every SIZE is checked
against a closed-form formula in (n, d, d_e, d_v, cap, hcap, ...), so
the same committed manifest gates 1-device and 8-device CI runs in
every mesh factorization. ``n`` is deliberately NOT a power of two:
the static halo capacity is (n=192, window=16, lanes=8 -> hcap=64),
and a pow2 ``n`` could collide with it, letting a halo buffer
dimension masquerade as a vertex-sized one in the solved formulas.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..core.api import plan_frontier_cap, plan_window
from ..core.engine import (
    DONATED_STATE_ARGS,
    WEIGHTED_DONATED_STATE_ARGS,
    apply_batch,
    apply_batch_weighted,
    build_halo_ids,
    halo_cap_for,
)
from ..core.insert import insert_batch, promotion_fixpoint, \
    promotion_fixpoint_halo
from ..core.remove import remove_batch, removal_fixpoint, \
    removal_fixpoint_halo, weighted_core_fixpoint_pass
from ..core.sharded import make_sharded_apply
from ..core.vertex_layout import Traffic, make_layout, record_traffic
from ..launch.mesh import EDGE_SHARD_AXIS, make_edge_vertex_mesh

EDGE_AXIS = "data"


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """One point of the engine matrix, keyed by its audit name."""

    name: str
    engine: str                       # "host" | "unified" | "sharded"
    vertex_sharding: str = "replicated"
    frontier_exchange: str = "bitmask"
    frontier_cap: int = 0             # pinned sparse cap (sparse only)
    freelist: str = "interleaved"
    kernel_backend: str = "lax"       # "lax" | "pallas" stat kernels
    weighted: bool = False            # weight-generalized engine (both
    #                                   phases run the weighted h-index
    #                                   bisection fixpoint; the slot
    #                                   table carries a weight column)
    # canonical (d_e, d_v) factorization for vertex_sharding="halo";
    # the audit CLI's --mesh-shape re-traces the same config (and the
    # same committed manifest) under other factorizations
    mesh_shape: Optional[Tuple[int, int]] = None

    @property
    def is_sharded(self) -> bool:
        return self.engine == "sharded"


ENGINE_CONFIGS: Dict[str, EngineConfig] = {
    c.name: c
    for c in (
        EngineConfig("host", "host"),
        EngineConfig("unified", "unified"),
        EngineConfig("sharded", "sharded"),
        EngineConfig("vertex_range", "sharded", vertex_sharding="range"),
        EngineConfig(
            "frontier_sparse", "sharded", vertex_sharding="range",
            frontier_exchange="sparse", frontier_cap=16,
        ),
        EngineConfig(
            "vertex_halo", "sharded", vertex_sharding="halo",
            frontier_exchange="sparse", frontier_cap=16,
            mesh_shape=(4, 2),
        ),
        EngineConfig("pallas", "sharded", kernel_backend="pallas"),
        EngineConfig("weighted", "unified", weighted=True),
        EngineConfig("weighted_sharded", "sharded", weighted=True),
    )
}


@dataclasses.dataclass(frozen=True)
class AuditParams:
    """Fixed trace-time sizes. ``n`` and ``capacity`` must be divisible
    by every audited device count (1 and 8 in CI, in every mesh
    factorization) so the range/halo layouts pad nothing and the
    formulas stay exact. ``n`` is NOT a power of two on purpose — the
    pow2 halo capacity (hcap=64 at these parameters) must never equal
    ``n`` or ``n_owned`` in either paired trace environment, or the
    memory formula solver could mislabel a halo buffer as vertex-sized
    (see the module docstring)."""

    n: int = 192
    capacity: int = 384
    lanes: int = 8  # padded batch lanes (both insert and remove lists)

    @property
    def n_levels(self) -> int:
        return self.n + 2


def resolve_mesh(cfg: EngineConfig, d: int,
                 mesh_shape: Optional[Tuple[int, int]] = None):
    """The mesh one engine config is traced on at ``d`` devices.

    Non-halo sharded configs get the classic 1-D edge mesh. Halo
    configs get the 2-axis ``make_edge_vertex_mesh``: an explicit
    ``mesh_shape`` (the audit CLI's --mesh-shape) wins, else the
    config's canonical factorization, else ``(1, d)``; a 1-device trace
    (the paired memory trace) degenerates to ``(1, 1)``."""
    if cfg.vertex_sharding != "halo":
        if mesh_shape is not None:
            raise ValueError(
                f"mesh_shape={mesh_shape} applies only to "
                "vertex_sharding='halo' configs (the 1-axis engines "
                "trace on the shared edge/owner axis)"
            )
        return jax.make_mesh((d,), (EDGE_AXIS,))
    shape = mesh_shape or cfg.mesh_shape or (1, d)
    if shape[0] * shape[1] != d and mesh_shape is None:
        # the canonical factorization targets the CI device count; any
        # other count (the paired 1-device memory trace, a local run)
        # falls back to a pure owner-axis column of the same size
        shape = (1, d)
    return make_edge_vertex_mesh(d, tuple(shape), axis=EDGE_AXIS,
                                 edge_axis=EDGE_SHARD_AXIS)


def trace_removal_round(
    vertex_sharding: str, n: int, cap: int, mesh,
    frontier_cap: Optional[int] = None,
    window: Optional[int] = None, lanes: int = 8,
    kernel_backend: str = "lax",
) -> Tuple[List[Traffic], Any]:
    """Trace (not run) the removal fixpoint under shard_map.

    Returns ``(log, closed_jaxpr)``: the layout collectives recorded for
    ONE loop round plus the traced program (walk it with
    ``walker.primitive_names`` / ``walker.collectives``). This is the
    one source of truth behind the traffic assertions in
    ``tests/test_vertex_layout.py`` and the audit's round budgets.

    ``window`` mirrors the engine's per-shard active window: the engine
    slices slots to the planned window BEFORE binding the halo session,
    so the traced halo capacity (and with it every halo-sized recv)
    matches the committed budget only if the round trace windows the
    same way. ``None`` keeps the whole local shard (replicated traces,
    standalone use).
    """
    axis = EDGE_AXIS
    all_axes = tuple(mesh.axis_names)
    edge_axes = tuple(a for a in all_axes if a != axis)
    n_shards = dict(mesh.shape)[axis]
    espec = P(all_axes if len(all_axes) > 1 else axis)
    if vertex_sharding in ("range", "halo"):
        layout = make_layout(vertex_sharding, n, axis, n_shards,
                             frontier_cap, edge_axes)
        n_pad = layout.n_pad

        def kernel(src, dst, valid, core, label, ru, rv):
            w = src.shape[0] if window is None else window
            src_w, dst_w, valid_w = src[:w], dst[:w], valid[:w]
            # lane ids fed twice (insert + remove lists) so the traced
            # halo capacity equals the engine's 2*lanes lanes_total
            halo_ids = build_halo_ids(layout, src_w, dst_w,
                                      ru, rv, ru, rv, n)
            session = layout.bind(halo_ids)
            core_h = session.gather_values(core)
            label_h = session.gather_values(label)
            src_h = session.locate(src_w)
            dst_h = session.locate(dst_w)
            return removal_fixpoint_halo(
                src_h, dst_h, valid_w, core, label, core_h, label_h,
                session, n + 2, kernel_backend=kernel_backend,
            )

        sm = shard_map(
            kernel, mesh=mesh,
            in_specs=(espec, espec, espec, P(axis), P(axis), P(), P()),
            out_specs=(P(axis), P(axis), P(), P(), P(),
                       P(axis), P(axis), P(), P()),
            check_vma=False,
        )
        src = jnp.zeros(cap, jnp.int32)
        dst = jnp.ones(cap, jnp.int32)
        valid = jnp.zeros(cap, bool)
        core = jnp.zeros(n_pad, jnp.int32)
        label = jnp.zeros(n_pad, jnp.int64)
        ru = jnp.zeros(lanes, jnp.int32)
        rv = jnp.ones(lanes, jnp.int32)
        with record_traffic() as log:
            jaxpr = jax.make_jaxpr(sm)(src, dst, valid, core, label,
                                       ru, rv)
        return log, jaxpr

    layout = make_layout("replicated", n, axis)

    def kernel(src, dst, valid, core, label):
        return removal_fixpoint(src, dst, valid, core, label, n, n + 2,
                                layout=layout,
                                kernel_backend=kernel_backend)

    sm = shard_map(
        kernel, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(), P()),
        out_specs=(P(), P(), P(), P(), P(), P()),
        check_vma=False,
    )
    src = jnp.zeros(cap, jnp.int32)
    dst = jnp.ones(cap, jnp.int32)
    valid = jnp.zeros(cap, bool)
    core = jnp.zeros(n, jnp.int32)
    label = jnp.zeros(n, jnp.int64)
    with record_traffic() as log:
        jaxpr = jax.make_jaxpr(sm)(src, dst, valid, core, label)
    return log, jaxpr


def trace_promotion_round(
    vertex_sharding: str, n: int, cap: int, mesh,
    frontier_cap: Optional[int] = None, lanes: int = 8,
    window: Optional[int] = None,
    kernel_backend: str = "lax",
) -> Tuple[List[Traffic], Any]:
    """Trace the promotion fixpoint under shard_map — the insertion-side
    counterpart of ``trace_removal_round``. Returns ``(log, jaxpr)``;
    records cover one outer round (seed + forward waves + evictions +
    the next-round statistics pass)."""
    axis = EDGE_AXIS
    all_axes = tuple(mesh.axis_names)
    edge_axes = tuple(a for a in all_axes if a != axis)
    n_shards = dict(mesh.shape)[axis]
    espec = P(all_axes if len(all_axes) > 1 else axis)
    if vertex_sharding in ("range", "halo"):
        layout = make_layout(vertex_sharding, n, axis, n_shards,
                             frontier_cap, edge_axes)
        n_pad = layout.n_pad

        def kernel(src, dst, valid, core, label, nu, nv, nok, hi, dout):
            w = src.shape[0] if window is None else window
            src_w, dst_w, valid_w = src[:w], dst[:w], valid[:w]
            halo_ids = build_halo_ids(layout, src_w, dst_w,
                                      nu, nv, nu, nv, n)
            session = layout.bind(halo_ids)
            core_h = session.gather_values(core)
            label_h = session.gather_values(label)
            src_h = session.locate(src_w)
            dst_h = session.locate(dst_w)
            u_pos = session.locate(nu)
            v_pos = session.locate(nv)
            return promotion_fixpoint_halo(
                src_h, dst_h, valid_w, core, label, core_h, label_h,
                nu, nv, u_pos, v_pos, nok, hi, dout, session, n + 2,
                kernel_backend=kernel_backend,
            )

        sm = shard_map(
            kernel, mesh=mesh,
            in_specs=(espec, espec, espec, P(axis), P(axis),
                      P(), P(), P(), P(axis), P(axis)),
            out_specs=(P(axis), P(axis), P(), P(), P(),
                       P(axis), P(), P(), P(), P()),
            check_vma=False,
        )
        src = jnp.zeros(cap, jnp.int32)
        dst = jnp.ones(cap, jnp.int32)
        valid = jnp.zeros(cap, bool)
        core = jnp.zeros(n_pad, jnp.int32)
        label = jnp.zeros(n_pad, jnp.int64)
        nu = jnp.zeros(lanes, jnp.int32)
        nv = jnp.ones(lanes, jnp.int32)
        nok = jnp.zeros(lanes, bool)
        hi = jnp.zeros(n_pad, jnp.int32)
        dout = jnp.zeros(n_pad, jnp.int32)
        with record_traffic() as log:
            jaxpr = jax.make_jaxpr(sm)(src, dst, valid, core, label,
                                       nu, nv, nok, hi, dout)
        return log, jaxpr

    layout = make_layout("replicated", n, axis)

    def kernel(src, dst, valid, core, label, nu, nv, nok, hi, dout):
        return promotion_fixpoint(src, dst, valid, core, label,
                                  nu, nv, nok, hi, dout, n, n + 2,
                                  layout=layout,
                                  kernel_backend=kernel_backend)

    sm = shard_map(
        kernel, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(), P(),
                  P(), P(), P(), P(), P()),
        out_specs=(P(), P(), P(), P(), P(), P(), P()),
        check_vma=False,
    )
    src = jnp.zeros(cap, jnp.int32)
    dst = jnp.ones(cap, jnp.int32)
    valid = jnp.zeros(cap, bool)
    core = jnp.zeros(n, jnp.int32)
    label = jnp.zeros(n, jnp.int64)
    nu = jnp.zeros(lanes, jnp.int32)
    nv = jnp.ones(lanes, jnp.int32)
    nok = jnp.zeros(lanes, bool)
    hi = jnp.zeros(n, jnp.int32)
    dout = jnp.zeros(n, jnp.int32)
    with record_traffic() as log:
        jaxpr = jax.make_jaxpr(sm)(src, dst, valid, core, label,
                                   nu, nv, nok, hi, dout)
    return log, jaxpr


def trace_weighted_round(
    n: int, cap: int, mesh,
    kernel_backend: str = "lax",
) -> Tuple[List[Traffic], Any]:
    """Trace the weighted h-index fixpoint under shard_map — the one
    round shape of BOTH weighted maintenance phases (removal runs it
    from the current cores, promotion from ``core + W``; the traced
    collective structure is identical, so one budget entry covers
    both). The in-round histogram counts one layout completion per
    bisection probe: the inner bisection ``while`` nests inside the
    outer fixpoint ``while``, and both bodies trace exactly once."""
    axis = EDGE_AXIS
    layout = make_layout("replicated", n, axis)

    def kernel(src, dst, valid, ew, core):
        return weighted_core_fixpoint_pass(
            src, dst, valid, ew, core, n, layout=layout,
            kernel_backend=kernel_backend,
        )

    sm = shard_map(
        kernel, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P()),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=False,
    )
    src = jnp.zeros(cap, jnp.int32)
    dst = jnp.ones(cap, jnp.int32)
    valid = jnp.zeros(cap, bool)
    ew = jnp.ones(cap, jnp.int32)
    core = jnp.zeros(n, jnp.int32)
    with record_traffic() as log:
        jaxpr = jax.make_jaxpr(sm)(src, dst, valid, ew, core)
    return log, jaxpr


@dataclasses.dataclass
class TracedEngine:
    """Everything the audit rules inspect for one engine config."""

    config: EngineConfig
    params: AuditParams
    n_devices: int
    window: int           # planned per-shard active-window bucket
    frontier_cap: int     # planned sparse-cap bucket (0 = exchange off)
    programs: Dict[str, Any]        # name -> ClosedJaxpr (full program)
    lowered: Dict[str, Any]         # name -> jax.stages.Lowered
    donated: Dict[str, Tuple[int, ...]]  # name -> declared donated args
    rounds: Dict[str, Tuple[List[Traffic], Any]]  # name -> (log, jaxpr)
    sizes: Dict[str, int]           # env for budget recv_bytes formulas


def _batch_args(params: AuditParams, n_state: int,
                weighted: bool = False):
    b = jnp.zeros(params.lanes, jnp.int32)
    ok = jnp.zeros(params.lanes, bool)
    state = (
        jnp.zeros(params.capacity, jnp.int32),
        jnp.zeros(params.capacity, jnp.int32),
        jnp.zeros(params.capacity, bool),
    )
    if weighted:
        # the weighted engines add the per-slot weight column to the
        # donated state and a replicated per-lane weight to the batch
        state += (jnp.ones(params.capacity, jnp.int32),)
    state += (
        jnp.zeros(n_state, jnp.int32),
        jnp.zeros(n_state, jnp.int64),
        jnp.int32(0),
    )
    if weighted:
        return state + (b, b, jnp.ones(params.lanes, jnp.int32), ok,
                        b, b, ok)
    return state + (b, b, ok, b, b, ok)


def trace_engine(name: str,
                 params: Optional[AuditParams] = None,
                 devices: Optional[int] = None,
                 mesh_shape: Optional[Tuple[int, int]] = None,
                 ) -> TracedEngine:
    """Trace + lower every auditable program of one engine config on the
    current device count.

    ``devices`` forces a mesh size smaller than the process's device
    count (sharded configs only — host/unified always trace at d=1).
    The memory auditor uses this to trace each sharded program at TWO
    mesh sizes in one process: shard_map traces one program regardless
    of mesh size, so the paired jaxprs are structurally identical and a
    lockstep walk can solve each buffer dimension against two distinct
    size environments (repro.analysis.memory).

    ``mesh_shape`` overrides a halo config's canonical (d_e, d_v)
    factorization — CI re-traces ``vertex_halo`` at 8 devices under
    BOTH 4x2 and 2x4 against the one committed manifest, which is what
    makes the budget formulas genuinely two-axis rather than fitted to
    a single device split."""
    if name not in ENGINE_CONFIGS:
        raise ValueError(
            f"unknown engine config {name!r} "
            f"(expected one of {sorted(ENGINE_CONFIGS)})"
        )
    cfg = ENGINE_CONFIGS[name]
    params = params or AuditParams()
    if not cfg.is_sharded:
        d = 1
    elif devices is not None:
        if devices > len(jax.devices()):
            raise ValueError(
                f"devices={devices} exceeds the process's "
                f"{len(jax.devices())} devices"
            )
        d = devices
    else:
        d = len(jax.devices())
    if mesh_shape is not None and mesh_shape[0] * mesh_shape[1] != d:
        raise ValueError(
            f"mesh_shape {mesh_shape[0]}x{mesh_shape[1]} needs "
            f"{mesh_shape[0] * mesh_shape[1]} devices, tracing {d}"
        )
    if cfg.is_sharded:
        mesh = resolve_mesh(cfg, d, mesh_shape)
        if cfg.vertex_sharding == "halo":
            d_e, d_v = dict(mesh.shape)[EDGE_SHARD_AXIS], \
                dict(mesh.shape)[EDGE_AXIS]
        else:
            d_e, d_v = 1, d
    else:
        mesh = None
        d_e, d_v = 1, 1
    n, cap, lanes = params.n, params.capacity, params.lanes
    if cfg.is_sharded and (n % d_v or cap % d):
        raise ValueError(
            f"audit sizes n={n}, capacity={cap} must divide the device "
            f"counts d={d}, d_v={d_v} (pad-free range/halo layouts keep "
            "formulas exact)"
        )
    local_cap = cap // d
    n_owned = -(-n // d_v)
    window = plan_window(0, lanes, local_cap)
    fcap = plan_frontier_cap(cfg.frontier_exchange, cfg.frontier_cap,
                             lanes, n_owned)

    programs: Dict[str, Any] = {}
    lowered: Dict[str, Any] = {}
    donated: Dict[str, Tuple[int, ...]] = {}
    rounds: Dict[str, Tuple[List[Traffic], Any]] = {}

    if cfg.engine == "host":
        # the seed two-program path: one jit per edit kind, no donation
        # (the baseline copies per call — its manifest says so)
        src, dst, valid, core, label, n_edges, iu, iv, iok, ru, rv, rok = (
            _batch_args(params, n)
        )
        ins_args = (src, dst, valid, core, label, iu, iv, iok, n_edges)
        programs["insert_batch"] = jax.make_jaxpr(
            lambda *a: insert_batch(*a, n, params.n_levels)
        )(*ins_args)
        lowered["insert_batch"] = insert_batch.lower(
            *ins_args, n=n, n_levels=params.n_levels
        )
        donated["insert_batch"] = ()
        slots = jnp.full(lanes, -1, jnp.int32)
        rm_args = (src, dst, valid, core, label, slots)
        programs["remove_batch"] = jax.make_jaxpr(
            lambda *a: remove_batch(*a, n, params.n_levels)
        )(*rm_args)
        lowered["remove_batch"] = remove_batch.lower(
            *rm_args, n=n, n_levels=params.n_levels
        )
        donated["remove_batch"] = ()
    elif cfg.engine == "unified":
        args = _batch_args(params, n, weighted=cfg.weighted)
        if cfg.weighted:
            programs["apply_batch"] = jax.make_jaxpr(
                lambda *a: apply_batch_weighted(*a, n, params.n_levels,
                                                window)
            )(*args)
            lowered["apply_batch"] = apply_batch_weighted.lower(
                *args, n=n, n_levels=params.n_levels, active_cap=window
            )
            donated["apply_batch"] = WEIGHTED_DONATED_STATE_ARGS
        else:
            programs["apply_batch"] = jax.make_jaxpr(
                lambda *a: apply_batch(*a, n, params.n_levels, window)
            )(*args)
            lowered["apply_batch"] = apply_batch.lower(
                *args, n=n, n_levels=params.n_levels, active_cap=window
            )
            donated["apply_batch"] = DONATED_STATE_ARGS
    else:
        fn = make_sharded_apply(
            mesh, n, params.n_levels, axis=EDGE_AXIS,
            local_active=window,
            vertex_sharding=cfg.vertex_sharding,
            freelist=cfg.freelist,
            frontier_exchange=cfg.frontier_exchange,
            frontier_cap=fcap,
            kernel_backend=cfg.kernel_backend,
            weighted=cfg.weighted,
        )
        n_state = (n_owned * d_v
                   if cfg.vertex_sharding in ("range", "halo") else n)
        args = _batch_args(params, n_state, weighted=cfg.weighted)
        programs["apply_batch"] = jax.make_jaxpr(fn)(*args)
        lowered["apply_batch"] = fn.lower(*args)
        donated["apply_batch"] = (WEIGHTED_DONATED_STATE_ARGS
                                  if cfg.weighted else DONATED_STATE_ARGS)
        if cfg.weighted:
            # one round shape serves both weighted phases (the
            # promotion fixpoint is the same program from core + W)
            rounds["weighted_round"] = trace_weighted_round(
                n, cap, mesh, kernel_backend=cfg.kernel_backend,
            )
        else:
            round_fcap = (fcap if cfg.frontier_exchange == "sparse"
                          else None)
            rounds["removal_round"] = trace_removal_round(
                cfg.vertex_sharding, n, cap, mesh, round_fcap,
                window=window, lanes=lanes,
                kernel_backend=cfg.kernel_backend,
            )
            rounds["promotion_round"] = trace_promotion_round(
                cfg.vertex_sharding, n, cap, mesh, round_fcap, lanes,
                window=window,
                kernel_backend=cfg.kernel_backend,
            )

    n_pad = (n_owned * d_v
             if cfg.vertex_sharding in ("range", "halo") else n)
    hcap = (halo_cap_for(window, 2 * lanes, n_pad)
            if cfg.vertex_sharding in ("range", "halo") else 0)
    sizes = dict(
        n=n, d=d, d_e=d_e, d_v=d_v, cap=fcap, n_owned=n_owned,
        n_pad=n_pad, hcap=hcap,
        lanes=lanes, window=window, local_cap=local_cap,
    )
    return TracedEngine(
        config=cfg, params=params, n_devices=d, window=window,
        frontier_cap=fcap, programs=programs, lowered=lowered,
        donated=donated, rounds=rounds, sizes=sizes,
    )
