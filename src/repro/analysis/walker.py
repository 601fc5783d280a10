"""Recursive jaxpr walking — the substrate every audit rule stands on.

A traced JAX program is a tree of jaxprs: the top-level jaxpr plus the
sub-jaxprs carried in equation params (``pjit``'s ``jaxpr``, ``while``'s
``cond_jaxpr``/``body_jaxpr``, ``cond``'s ``branches``, ``shard_map``'s
``jaxpr``, ``scan``, ``custom_jvp_call``, …). The walker visits every
equation of every nested jaxpr exactly once and records WHERE it sits as
a context path of ``"primitive:param[i]"`` tags, so rules can attribute
a primitive to

* a fixpoint ROUND — any path element ``"while:body_jaxpr"``: a
  ``lax.while_loop`` body traces exactly once, so equations inside it
  ARE the per-round program (the same fact the trace-time traffic
  accounting of ``core/vertex_layout.py`` stands on);
* a ``lax.cond`` ARM — path elements ``"cond:branches[i]"``; for the
  sparse frontier exchange the branch index maps to the
  ``Traffic.branch`` tag through
  ``vertex_layout.SPARSE_COND_BRANCHES`` (branch 1 is the overflow
  fallback), which is how jaxpr-derived budgets line up with the
  trace-time records.

Nothing here executes a program: all facts come from equation params and
the static shapes/dtypes of their ``aval``s.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterator, List, Optional, Sequence, Set, Tuple

# Collective primitives that move bytes across a mesh axis. ``psum``
# covers pmin/pmax-free reductions (the engines only psum); ``pmax`` is
# listed because slot_high_water completes with one. ``reduce_scatter``
# is what lax.psum_scatter traces to; ``all_gather`` covers both the
# bit-packed mask and the sparse index exchange.
COLLECTIVE_PRIMS = frozenset(
    {
        "psum",
        "pmax",
        "pmin",
        "all_gather",
        "reduce_scatter",
        "ppermute",
        "pshuffle",
        "all_to_all",
    }
)

# Primitives that round-trip through the host (or an arbitrary Python
# callback) at RUN time — none may appear in a batch program: a single
# one serializes the device stream on every batch.
HOST_CALLBACK_PRIMS = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "python_callback",
        "callback",
        "infeed",
        "outfeed",
        "host_local_array_to_global_array",
        "global_array_to_host_local_array",
    }
)

ROUND_TAG = "while:body_jaxpr"  # path element marking a fixpoint round

# Launch-class primitives: each one the XLA runtime dispatches as (at
# least) its own kernel on an accelerator backend. The gather/scatter
# family is what ``jax.ops.segment_sum`` and the endpoint-state reads
# lower to, and what ``count_round_launches`` measures.
LAUNCH_PRIMS = frozenset(
    {
        "gather",
        "scatter",
        "scatter-add",
        "scatter-max",
        "scatter-min",
        "scatter-mul",
        "sort",
    }
)


def _as_jaxpr(v: Any):
    """Unwrap a param value to a raw Jaxpr, or None."""
    inner = getattr(v, "jaxpr", None)  # ClosedJaxpr -> Jaxpr
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    if hasattr(v, "eqns"):
        return v
    return None


def sub_jaxprs(eqn) -> Iterator[Tuple[str, Any]]:
    """Yield ``(tag, jaxpr)`` for every sub-jaxpr an equation carries.

    ``tag`` is ``"primitive:param"`` (plus ``"[i]"`` for params holding a
    sequence of jaxprs, e.g. ``cond``'s ``branches``). Purely generic:
    any param value that quacks like a (Closed)Jaxpr is descended into,
    so new primitives with nested programs are walked without changes
    here.
    """
    prim = eqn.primitive.name
    for name, val in eqn.params.items():
        if isinstance(val, (list, tuple)):
            for i, v in enumerate(val):
                j = _as_jaxpr(v)
                if j is not None:
                    yield f"{prim}:{name}[{i}]", j
        else:
            j = _as_jaxpr(val)
            if j is not None:
                yield f"{prim}:{name}", j


@dataclasses.dataclass(frozen=True)
class Site:
    """One equation plus the context path it was found under."""

    eqn: Any
    path: Tuple[str, ...]

    @property
    def prim(self) -> str:
        return self.eqn.primitive.name

    @property
    def in_round(self) -> bool:
        """True iff the equation sits inside a ``lax.while_loop`` body —
        i.e. it executes once per fixpoint round."""
        return ROUND_TAG in self.path

    @property
    def cond_branches(self) -> Tuple[int, ...]:
        """Branch indices of every enclosing ``lax.cond``, outermost
        first (``lax.cond(pred, true_fn, false_fn)`` traces branches as
        ``(false_fn, true_fn)`` — index 1 is the true arm)."""
        out = []
        for tag in self.path:
            if tag.startswith("cond:branches["):
                out.append(int(tag[len("cond:branches[") : -1]))
        return tuple(out)


def iter_sites(closed) -> Iterator[Site]:
    """Depth-first walk over every equation of a (closed) jaxpr, nested
    sub-jaxprs included."""
    jaxpr = getattr(closed, "jaxpr", closed)

    def walk(jx, path: Tuple[str, ...]) -> Iterator[Site]:
        for eqn in jx.eqns:
            yield Site(eqn, path)
            for tag, sub in sub_jaxprs(eqn):
                yield from walk(sub, path + (tag,))

    yield from walk(jaxpr, ())


def primitive_names(closed) -> Set[str]:
    """All primitive names in a (closed) jaxpr, nested jaxprs included.

    Drop-in replacement for the ad-hoc walkers formerly local to
    ``tests/test_vertex_layout.py``.
    """
    return {s.prim for s in iter_sites(closed)}


def _aval_bytes(aval) -> int:
    try:
        size = 1
        for d in aval.shape:
            size *= int(d)
        return size * aval.dtype.itemsize
    except (AttributeError, TypeError):
        return 0


@dataclasses.dataclass(frozen=True)
class CollectiveSite:
    """One collective equation with its statically-known payload.

    ``out_bytes`` is the payload each participating device RECEIVES,
    read off the output avals: an ``all_gather`` output is the full
    gathered array, a ``reduce_scatter`` output is the per-device
    scattered slice, a ``psum`` output is the full reduced array — in
    every case exactly the quantity ``vertex_layout.record_traffic``
    notes at trace time, which is what makes the §4.2/§4.3 traffic
    model mechanically cross-checkable against the jaxpr
    (``rules.cross_check_round``).
    """

    op: str
    out_bytes: int
    out_elems: int
    path: Tuple[str, ...]
    in_round: bool
    cond_branches: Tuple[int, ...]
    # mesh axis names the collective completes over (psum's "axes"
    # param / ppermute's "axis_name"), normalized to strings — lets the
    # round-psum rule distinguish a pure-edge-axis partial reduction
    # (bounded by the 2-axis traffic model) from a forbidden
    # vertex-axis one
    axes: Tuple[str, ...] = ()


def _eqn_axes(eqn) -> Tuple[str, ...]:
    raw = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if raw is None:
        return ()
    if isinstance(raw, (str, int)):
        raw = (raw,)
    return tuple(str(a) for a in raw)


def collectives(closed) -> List[CollectiveSite]:
    """Every collective primitive in the program, with payload sizes."""
    out: List[CollectiveSite] = []
    for s in iter_sites(closed):
        if s.prim not in COLLECTIVE_PRIMS:
            continue
        nbytes = 0
        nelems = 0
        for ov in s.eqn.outvars:
            nbytes += _aval_bytes(ov.aval)
            try:
                sz = 1
                for d in ov.aval.shape:
                    sz *= int(d)
                nelems += sz
            except (AttributeError, TypeError):
                pass
        out.append(
            CollectiveSite(
                op=s.prim,
                out_bytes=nbytes,
                out_elems=nelems,
                path=s.path,
                in_round=s.in_round,
                cond_branches=s.cond_branches,
                axes=_eqn_axes(s.eqn),
            )
        )
    return out


def count_round_launches(closed) -> dict:
    """Histogram of launch-class primitives that execute once per
    FIXPOINT ROUND (``Site.in_round`` only). Counts are per traced
    round body: a ``lax.while_loop`` body traces exactly once, so the
    histogram IS the per-round launch budget."""
    hist: dict = {}
    for s in iter_sites(closed):
        if not s.in_round or s.prim not in LAUNCH_PRIMS:
            continue
        hist[s.prim] = hist.get(s.prim, 0) + 1
    return hist


def count_collectives(closed, prims: Optional[Sequence[str]] = None) -> dict:
    """Histogram of collective primitive names over the whole program
    (counts are device-count independent: shard_map traces one program
    regardless of the mesh size, only shapes change)."""
    names = COLLECTIVE_PRIMS if prims is None else frozenset(prims)
    hist: dict = {}
    for s in iter_sites(closed):
        if s.prim in names:
            hist[s.prim] = hist.get(s.prim, 0) + 1
    return hist
