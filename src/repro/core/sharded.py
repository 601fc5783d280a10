"""Multi-device core maintenance via shard_map (beyond-paper scaling).

The paper targets one shared-memory node; here the edge slots are sharded
across the mesh's ``data`` axis, and the VERTEX state's home is a
pluggable layout (core/vertex_layout.py): replicated by default (the
small side: n << m for the paper's graphs and batches — every
neighborhood statistic becomes a local segment_sum over the device's
edge shard + one ``psum``) or range-sharded for wide meshes
(``vertex_sharding="range"``: one ``reduce_scatter`` per statistic +
bit-packed frontier masks, docs/DESIGN.md §4.2). The fixpoint loops are
unchanged — bulk-synchronous rounds are mesh-agnostic, which is exactly
why the reformulation scales to pods.

``make_sharded_apply`` is the full order-based maintenance engine behind
``CoreMaintainer(engine="sharded")``: the exact ``engine.apply_batch``
program (dedup, slot lookup, free-list slot recycling, removal fixpoint,
promotion rounds, place_block label assignment, renumber gate) with the
slot table sharded across the mesh and every per-vertex statistic
completed by one psum (docs/DESIGN.md §4). It wraps
``engine.batch_program`` — the unified engine's program body, not a copy
— in a ``shard_map``, with the body's ``axis`` parameter (threaded down
into the remove.py / insert.py fixpoints) supplying the psums, so
unified and sharded engines cannot drift algorithmically. Per-batch
work is bounded by the per-shard high-water window (``local_active``),
sliced locally inside the kernel so the sharded placement never moves.

The older core-only kernels (``make_sharded_remove`` /
``make_sharded_insert_round``) are kept as minimal building blocks for
experiments that maintain core numbers without k-order labels.

For 1000+-node deployments the replicated-vertex assumption breaks; that
is what the halo-sharded layouts are for (core/vertex_layout.py —
``HaloShardedVertices``): the vertex state itself is range-sharded over
the owner axis, each edge shard keeps only a bounded HALO of the
vertices its windowed slot prefix references (no [n] working copy on
any device — per-device memory is O(n / d_v + halo)), every fixpoint
statistic completes with one bounded halo-stats gather + owner scatter
(+ one pure-edge-axis psum on a 2-axis mesh), and only changed-vertex
halo refreshes cross the mesh per round — compacted frontier INDICES in
a fixed ``frontier_cap`` bucket under ``frontier_exchange="sparse"``
(§4.3), with a per-round dense O(halo) regather fallback on overflow.
``vertex_sharding="range"`` is the 1-axis (shared-axis) degenerate;
``vertex_sharding="halo"`` runs on a genuine 2-axis edge x vertex mesh
(``launch/mesh.py::make_edge_vertex_mesh``, docs/DESIGN.md §4.4).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .engine import (DONATED_STATE_ARGS, WEIGHTED_DONATED_STATE_ARGS,
                     batch_program, batch_program_halo)
from .vertex_layout import make_layout

Array = jax.Array


def make_sharded_apply(mesh: Mesh, n: int, n_levels: int,
                       axis: str = "data",
                       local_active: int | None = None,
                       vertex_sharding: str = "replicated",
                       freelist: str = "interleaved",
                       frontier_exchange: str = "bitmask",
                       frontier_cap: int = 0,
                       weighted: bool = False):
    """Build the jitted sharded mixed-batch engine over ``mesh``.

    The returned function has the same signature and semantics as
    ``engine.apply_batch`` minus the ``n``/``n_levels``/``active_cap``
    statics: ``(src, dst, valid, core, label, n_edges, ins_u, ins_v,
    ins_ok, rm_u, rm_v, rm_ok) -> (src, dst, valid, core, label, n_edges,
    stats)``. ``src``/``dst``/``valid`` must be sharded along ``axis``
    (capacity divisible by the axis size); everything else is replicated —
    except ``core``/``label`` under ``vertex_sharding="range"``, which
    are range-sharded along the same axis (padded to a shard multiple,
    api.py owns the padding).

    ``vertex_sharding`` selects the vertex layout (vertex_layout.py):

    * ``"replicated"`` — every device keeps full [n] vertex state; each
      statistic costs one psum (O(n) received per device per round);
    * ``"range"`` — device ``i`` OWNS vertex range ``i`` on the SHARED
      single mesh axis, and beyond its owned slice keeps only a bounded
      HALO of the vertices its windowed slot prefix references
      (``engine.build_halo_ids`` — no [n] working copy anywhere, no
      entry state gather): statistics complete with one bounded
      halo-stats gather + local owner scatter, decisions run on owned
      slices, labels place via the ring ``order.place_block_ring``, and
      per-round traffic is changed-restricted halo refreshes. Integer
      arithmetic end to end, so the result is BIT-identical to every
      other engine.
    * ``"halo"`` — the same halo machinery on a genuine 2-axis mesh
      (``mesh`` must carry one pure-edge axis plus the owner ``axis``;
      ``launch/mesh.py::make_edge_vertex_mesh(mesh_shape=(d_e, d_v))``):
      edge slots shard over BOTH axes, vertex ranges over the owner
      axis only, and completed statistics gain one psum over the
      pure-edge axis (the d_e term of docs/DESIGN.md §4.4). Per-device
      vertex memory is O(n / d_v + halo).

    ``freelist`` picks the slot-allocator ranking (``"interleaved"`` |
    ``"hierarchical"`` — `insert.freelist_alloc`).

    ``frontier_exchange`` picks how the per-round changed-vertex halo
    refreshes cross the owner axis under range/halo sharding:
    ``"bitmask"`` (historical name — now the DENSE halo regather, one
    O(halo_cap) reduce_scatter per refresh) or ``"sparse"`` (the §4.3
    compacted-index exchange: ``frontier_cap`` global indices per
    shard, count-prefixed and sentinel-padded, O(cap * d_v) words per
    round with a per-round lax.cond falling back to the dense regather
    when any shard's frontier overflows the cap — bit-identical either
    way). ``frontier_cap`` is STATIC: one jitted engine per cap bucket,
    like ``local_active`` (api.py plans the pow2 bucket).

    ``weighted`` builds the weight-generalized engine instead: the slot
    table carries a fourth sharded column ``w`` (per-slot edge weight,
    riding the same espec/donation treatment as ``src``/``dst``/
    ``valid``), the batch gains a replicated ``ins_w`` lane array, and
    both maintenance phases run the weighted h-index bisection fixpoint
    (core/remove.py::weighted_core_fixpoint_pass and its halo twin) —
    the weighted partial sums complete through the SAME layout
    collectives as the unit-count statistics, so no new collective
    primitives appear. The returned function's signature becomes
    ``(src, dst, valid, w, core, label, n_edges, ins_u, ins_v, ins_w,
    ins_ok, rm_u, rm_v, rm_ok) -> (src, dst, valid, w, core, label,
    n_edges, stats)``. With ``weighted=False`` (the default) no weight
    array is threaded anywhere, so the traced program — and the
    committed collective/budget manifests — stay byte-identical to the
    pre-weighted engine.

    ``local_active`` is the per-shard high-water window — the sharded
    analogue of the unified engine's ``active_cap``. Slicing a SHARDED
    buffer would force a reshard, so the slice happens INSIDE the
    shard_map kernel on each device's local (already materialized) shard:
    every edge pass runs over ``local_active`` slots per device instead
    of ``capacity / n_devices``, bounding per-batch work by the densest
    shard's live prefix. The host sizes it from the pow2 bucket of
    ``stats.high_water`` (api.py), so live slots — and the free slots the
    allocator needs — always sit inside the window, and the local tail
    past it stays all-invalid. ``None`` runs the full shard (no slicing).

    Division of labor inside the kernel (docs/DESIGN.md §4):

    * slot lookup — each device searches its LOCAL sorted shard; an edge
      lives in exactly one shard, so one psum of the found flags yields
      the global membership/removal verdict without materializing a
      global sort;
    * tombstoning — each device masks only its own slots (no cross-device
      slot indices ever exist);
    * slot allocation — ``insert.freelist_alloc``: dead slots are ranked
      globally (interleaved across shards from one all_gather of the
      windowed dead masks, or shard-by-shard from per-shard scalar free
      counts under ``freelist="hierarchical"``); each device writes the
      batch-cumsum ranks that land in its own shard and drops the rest
      via out-of-bounds scatter semantics;
    * fixpoints — the shared removal/promotion loops with ``layout=…``:
      local scatter-adds completed by the vertex layout each round (one
      psum when replicated; one reduce_scatter + bit-packed mask
      gathers when range-sharded), so every device runs the loop in
      lockstep on identical replicated working core/label values;
    * labels/renumber — pure vertex-state computation on those
      replicated working values — no collective.
    """
    all_axes = tuple(mesh.axis_names)
    if axis not in all_axes:
        raise ValueError(
            f"mesh has axes {all_axes}, no vertex/owner axis {axis!r}"
        )
    edge_axes = tuple(a for a in all_axes if a != axis)
    n_shards = dict(mesh.shape)[axis]
    if frontier_exchange not in ("bitmask", "sparse"):
        raise ValueError(
            f"unknown frontier_exchange {frontier_exchange!r} "
            "(expected 'bitmask' or 'sparse')"
        )
    if frontier_exchange == "sparse" and vertex_sharding not in (
            "range", "halo"):
        raise ValueError(
            "frontier_exchange='sparse' needs vertex_sharding='range' "
            "or 'halo' (the replicated layout exchanges no frontier "
            "masks)"
        )
    if frontier_exchange == "sparse" and frontier_cap < 1:
        raise ValueError(
            f"frontier_exchange='sparse' needs frontier_cap >= 1, got "
            f"{frontier_cap}"
        )
    if frontier_exchange != "sparse" and frontier_cap != 0:
        raise ValueError(
            f"frontier_cap={frontier_cap} is only consumed by "
            "frontier_exchange='sparse' — the dense halo exchange would "
            "silently ignore it"
        )
    if vertex_sharding != "halo" and edge_axes:
        raise ValueError(
            f"a multi-axis mesh (axes {all_axes}) needs "
            "vertex_sharding='halo' — the replicated and shared-axis "
            "range layouts complete statistics over ONE axis and would "
            "silently drop the pure-edge partials"
        )
    # None = replicated: batch_program builds its own ReplicatedVertices
    # over the edge axis, and the kernel skips the owned-state plumbing.
    # Anything else resolves (and validates) through the layout factory.
    layout = (
        None if vertex_sharding == "replicated"
        else make_layout(
            vertex_sharding, n, axis, n_shards,
            frontier_cap if frontier_exchange == "sparse" else None,
            edge_axes,
        )
    )
    # table collectives (lookup/membership psums, free-list ranking,
    # high-water pmax) complete over EVERY axis the slots are sharded on
    table_axis = all_axes if len(all_axes) > 1 else axis

    def _check_window(shard_len):
        if local_active is not None and local_active > shard_len:
            # an oversized window (e.g. sized from the GLOBAL high-water
            # mark instead of the per-shard one) would slice past the
            # shard and silently splice a SHORT table back together —
            # refuse loudly instead of corrupting the slot table
            raise ValueError(
                f"local_active={local_active} exceeds the per-shard "
                f"capacity {shard_len} — the window must be sized "
                "from the PER-SHARD high-water mark (capacity / "
                "n_shards at most), not the global slot count"
            )

    def _kernel(src, dst, valid, core, label, n_edges,
                ins_u, ins_v, ins_ok, rm_u, rm_v, rm_ok):
        # the UNIFIED engine's program body, verbatim, over this device's
        # local shard: its axis parameter turns every table reduction and
        # fixpoint statistic into local-scatter + layout completion
        # (engine.py). The per-shard window is a LOCAL slice (cf.
        # engine.apply_batch's active_cap prefix): the all-invalid tail
        # is spliced back on.
        _check_window(src.shape[0])
        w = src.shape[0] if local_active is None else local_active
        full_src, full_dst, full_valid = src, dst, valid
        if layout is None:
            src, dst, valid, core, label, n_edges, stats = batch_program(
                src[:w], dst[:w], valid[:w], core, label, n_edges,
                ins_u, ins_v, ins_ok, rm_u, rm_v, rm_ok,
                n, n_levels, axis=axis, layout=None, freelist=freelist,
            )
        else:
            # halo program: core/label stay OWNED [n_owned] slices end
            # to end — the edge passes index a bounded halo working set
            # (engine.build_halo_ids) instead of a gathered [n] copy
            src, dst, valid, core, label, n_edges, stats = (
                batch_program_halo(
                    src[:w], dst[:w], valid[:w], core, label, n_edges,
                    ins_u, ins_v, ins_ok, rm_u, rm_v, rm_ok,
                    n, n_levels, table_axis=table_axis, layout=layout,
                    freelist=freelist,
                )
            )
        with jax.named_scope("coremaint.table"):
            src = jnp.concatenate([src, full_src[w:]])
            dst = jnp.concatenate([dst, full_dst[w:]])
            valid = jnp.concatenate([valid, full_valid[w:]])
        return src, dst, valid, core, label, n_edges, stats

    def _kernel_weighted(src, dst, valid, ew, core, label, n_edges,
                         ins_u, ins_v, ins_w, ins_ok, rm_u, rm_v, rm_ok):
        # weighted twin: the weight column ``ew`` is sliced/spliced in
        # lockstep with the other slot columns and threaded into the
        # shared program body as its ``w=`` argument
        _check_window(src.shape[0])
        win = src.shape[0] if local_active is None else local_active
        full_src, full_dst, full_valid, full_ew = src, dst, valid, ew
        if layout is None:
            src, dst, valid, ew, core, label, n_edges, stats = (
                batch_program(
                    src[:win], dst[:win], valid[:win], core, label,
                    n_edges, ins_u, ins_v, ins_ok, rm_u, rm_v, rm_ok,
                    n, n_levels, axis=axis, layout=None,
                    freelist=freelist, w=ew[:win], ins_w=ins_w,
                )
            )
        else:
            src, dst, valid, ew, core, label, n_edges, stats = (
                batch_program_halo(
                    src[:win], dst[:win], valid[:win], core, label,
                    n_edges, ins_u, ins_v, ins_ok, rm_u, rm_v, rm_ok,
                    n, n_levels, table_axis=table_axis, layout=layout,
                    freelist=freelist, w=ew[:win], ins_w=ins_w,
                )
            )
        with jax.named_scope("coremaint.table"):
            src = jnp.concatenate([src, full_src[win:]])
            dst = jnp.concatenate([dst, full_dst[win:]])
            valid = jnp.concatenate([valid, full_valid[win:]])
            ew = jnp.concatenate([ew, full_ew[win:]])
        return src, dst, valid, ew, core, label, n_edges, stats

    espec = P(all_axes if len(all_axes) > 1 else axis)
    vspec = P() if layout is None else P(axis)
    if weighted:
        shardmapped = shard_map(
            _kernel_weighted,
            mesh=mesh,
            in_specs=(
                espec, espec, espec, espec,       # src, dst, valid, w
                vspec, vspec, P(),                # core, label, n_edges
                P(), P(), P(), P(), P(), P(), P(),  # batch (replicated)
            ),
            out_specs=(espec, espec, espec, espec, vspec, vspec, P(), P()),
            check_vma=False,
        )
        return jax.jit(shardmapped,
                       donate_argnums=WEIGHTED_DONATED_STATE_ARGS)
    shardmapped = shard_map(
        _kernel,
        mesh=mesh,
        in_specs=(
            espec, espec, espec,                # src, dst, valid
            vspec, vspec, P(),                  # core, label, n_edges
            P(), P(), P(), P(), P(), P(),       # batch (replicated)
        ),
        out_specs=(espec, espec, espec, vspec, vspec, P(), P()),
        check_vma=False,
    )
    return jax.jit(shardmapped, donate_argnums=DONATED_STATE_ARGS)


def _seg_psum(data: Array, ids: Array, n: int, axis: str) -> Array:
    out = jax.ops.segment_sum(data, ids, num_segments=n)
    return jax.lax.psum(out, axis)


def _count_ge_sharded(src, dst, valid, vals, n, axis):
    to_src = (valid & (vals[dst] >= vals[src])).astype(jnp.int32)
    to_dst = (valid & (vals[src] >= vals[dst])).astype(jnp.int32)
    return _seg_psum(to_src, src, n, axis) + _seg_psum(to_dst, dst, n, axis)


def make_sharded_remove(mesh: Mesh, n: int, axis: str = "data"):
    """Build a jitted sharded removal fixpoint over ``mesh``.

    Edge arrays must be sharded along ``axis``; core is replicated.
    Removal slots are pre-applied by the caller (valid already updated).
    """

    def _kernel(src, dst, valid, core):
        def cond(state):
            return state[1]

        def body(state):
            core, _ = state
            mcd = _count_ge_sharded(src, dst, valid, core, n, axis)
            drop = (mcd < core) & (core > 0)
            return core - drop.astype(jnp.int32), jnp.any(drop)

        core, _ = jax.lax.while_loop(cond, body, (core, jnp.bool_(True)))
        return core

    shardmapped = shard_map(
        _kernel,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(shardmapped)


def make_sharded_insert_round(mesh: Mesh, n: int, axis: str = "data"):
    """One promotion round (seed -> forward -> evict) as a sharded kernel.

    The caller loops rounds until ``n_promoted == 0`` (host loop keeps the
    per-round HLO small; each round is fully collective-parallel).
    Returns (new_core, promoted_mask).
    """

    def _kernel(src, dst, valid, core, label, seed):
        def count_gt(vals):
            a = (valid & (vals[dst] > vals[src])).astype(jnp.int32)
            b = (valid & (vals[src] > vals[dst])).astype(jnp.int32)
            return _seg_psum(a, src, n, axis) + _seg_psum(b, dst, n, axis)

        same = valid & (core[src] == core[dst])
        hi = count_gt(core)
        a = (same & (label[dst] > label[src])).astype(jnp.int32)
        b = (same & (label[src] > label[dst])).astype(jnp.int32)
        dout_same = _seg_psum(a, src, n, axis) + _seg_psum(b, dst, n, axis)

        def fwd_cond(state):
            return state[2]

        def fwd_body(state):
            reach, passing, _ = state
            rp = reach & passing
            a = (same & (label[dst] < label[src]) & rp[dst]).astype(jnp.int32)
            b = (same & (label[src] < label[dst]) & rp[src]).astype(jnp.int32)
            din = _seg_psum(a, src, n, axis) + _seg_psum(b, dst, n, axis)
            new_passing = (hi + dout_same + din) > core
            gd = (same & rp[src] & (label[src] < label[dst])).astype(jnp.int32)
            gs = (same & rp[dst] & (label[dst] < label[src])).astype(jnp.int32)
            grow = (_seg_psum(gd, dst, n, axis) + _seg_psum(gs, src, n, axis)) > 0
            new_reach = reach | grow
            changed = jnp.any(new_reach != reach) | jnp.any(
                new_passing != passing
            )
            return new_reach, new_passing, changed

        init_pass = (hi + dout_same) > core
        reach, passing, _ = jax.lax.while_loop(
            fwd_cond, fwd_body, (seed, init_pass, jnp.bool_(True))
        )

        def ev_cond(state):
            return state[1]

        def ev_body(state):
            cand, _ = state
            a = (same & cand[dst]).astype(jnp.int32)
            b = (same & cand[src]).astype(jnp.int32)
            sup = hi + _seg_psum(a, src, n, axis) + _seg_psum(b, dst, n, axis)
            new_cand = cand & (sup > core)
            return new_cand, jnp.any(new_cand != cand)

        cand, _ = jax.lax.while_loop(
            ev_cond, ev_body, (reach & passing, jnp.bool_(True))
        )
        return core + cand.astype(jnp.int32), cand

    shardmapped = shard_map(
        _kernel,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(shardmapped)


def shard_edges(mesh: Mesh, axis, *arrays) -> Tuple[Array, ...]:
    """Place COO slot arrays with the edge dimension sharded on ``axis``
    (one mesh axis name, or a tuple of axis names on a 2-axis mesh)."""
    sharding = NamedSharding(mesh, P(axis))
    return tuple(jax.device_put(a, sharding) for a in arrays)
