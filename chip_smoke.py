#!/usr/bin/env python3
"""Chip smoke: core maintenance end to end on a TPU, in one process.

What runs is what a user calls. A Graph500-specification R-MAT graph
(``graph.generators.rmat``: initiator 0.57/0.19/0.19/0.05, edge factor
16) at SCALE 16 has 65,536 vertices and 910,247 edges after dedup
(seed 0). ``CoreMaintainer.from_graph(g, engine="unified",
kernel_backend="lax", init="jax-peel")`` loads it with the default
capacity of 2m slots and peels it on the device. Then four mixed
``apply_batch`` calls each remove 25,000 sampled live edges and insert
25,000 edges: batch 1 fresh absent edges, batches 2-4 the previous
batch's removals (the paper's remove-then-reinsert protocol). Every
batch pads to the same lanes and the same window bucket, so one batch
program is compiled.

After the stream, each of these is fatal when it fails:
  * the cores equal ``core.oracle.bz_from_csr`` of the final edge set,
    which the host tracks on its own;
  * the labels satisfy the k-order certificate ``dout(v) <= core(v)``;
  * the device's slot table holds exactly the host's edge set, and
    ``n_edges`` equals its size;
  * each batch removed and inserted exactly the edges it was given;
  * one batch program was compiled for the whole stream.

What this size cuts from ``configs/coremaint.py::full()`` (4,847,571
vertices, 140M slots): 74x fewer vertices and 77x fewer slots. Each cut
and what forced it:
  * SCALE 20 -> 16. At SCALE 20 (1,048,576 vertices, 31.4M slots) one
    TPU v5e took 1,273 s for the device peel (628 waves) and 331 s for
    the first batch, compile included, and did not end within 30
    minutes: the lax path runs every peel wave and every fixpoint round
    as gathers and scatter-adds over the whole slot window, at tens of
    nanoseconds per slot and pass. SCALE 17-19 have not been run on a
    chip; scaling the SCALE 16 and 20 runs (peel ~ slots^1.4, a round ~
    window slots) puts a cold SCALE 18 run near 10 minutes, so a
    20-minute budget for the whole smoke does not by itself force
    SCALE 16. The full size
    compiles for one chip at ~10.7 GB of device memory; its host-side
    setup (generator, oracle) is ROADMAP B1.
  * 50,000 -> 25,000 edges removed and inserted per batch (2^15 lanes
    instead of 2^16). Graph500 defines no update stream; 50,000 is 0.3%
    of a SCALE 20 graph's edges, while 25,000 is already 2.7% of this
    one's, and a second lane bucket would be a second cold compile.
The oracle, a Python heap peel, runs in a thread alongside the device
work; it is never skipped.

``--chips 4`` runs only the multi-chip path, on the same graph and
stream: ``engine="sharded", vertex_sharding="halo", mesh_shape=(2, 2)``
against the unified engine on one of the four chips and the oracle.
Cores AND labels of the two engines must be bit-identical. It prints
each device's bytes in use, so that state piled onto one device shows.

The smoke needs a TPU: with none it exits non-zero before any work, and
it never falls back to the CPU. It starts no child process. The last
line of stdout is ``{"ok": true, "device": {"platform": "tpu", "kind":
..., "count": ...}}``; any failure exits non-zero without that line.

Usage: python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
from collections import Counter
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
PLATFORM = "tpu"  # the only platform the smoke accepts
SCALE = 16        # Graph500 SCALE: 2**SCALE vertices
EDGE_FACTOR = 16  # Graph500 specification
N_BATCHES = 4
BATCH = 25_000    # edges removed and inserted per batch
SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    sys.exit(f"chip_smoke: FAILED: {msg}")


def require_chips(n_chips: int):
    """The devices, or exit non-zero: no TPU means no smoke."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"chip_smoke: no TPU found ({e})")
    if devices[0].platform != PLATFORM:
        sys.exit(
            f"chip_smoke: no TPU found: JAX reports platform "
            f"{devices[0].platform!r}; this smoke never runs elsewhere"
        )
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} TPU "
                 f"chips, JAX reports {len(devices)}")
    return devices


class CompileLog:
    """Backend compiles per program, counted and timed (a persistent-cache
    hit is timed as its retrieval), and the cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax

        self.seconds: Counter = Counter()
        self.count: Counter = Counter()
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            name = kw.get("fun_name", "?")
            self.seconds[name] += duration
            self.count[name] += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def summary(self) -> str:
        top = {k: f"{self.count[k]}x {v:.3f}s"
               for k, v in self.seconds.most_common(4)}
        return (f"compile_s={sum(self.seconds.values()):.3f} over "
                f"{sum(self.count.values())} compiles, "
                f"persistent_cache_hits={self.hits}, slowest={top}")


# -- host side: graph, stream, oracle ---------------------------------------
def _keys(edges: np.ndarray, n: int) -> np.ndarray:
    lo = np.minimum(edges[:, 0], edges[:, 1]).astype(np.int64)
    hi = np.maximum(edges[:, 0], edges[:, 1]).astype(np.int64)
    return lo * n + hi


def _edges(keys: np.ndarray, n: int) -> np.ndarray:
    return np.stack([keys // n, keys % n], axis=1)


def _absent(live: np.ndarray, n: int, k: int, rng) -> np.ndarray:
    """k distinct uniform random keys of edges absent from ``live``."""
    out = np.zeros(0, dtype=np.int64)
    while out.size < k:
        uv = rng.integers(0, n, size=(2 * k, 2), dtype=np.int64)
        cand = np.unique(_keys(uv[uv[:, 0] != uv[:, 1]], n))
        pos = np.minimum(np.searchsorted(live, cand), live.size - 1)
        cand = np.setdiff1d(cand[live[pos] != cand], out)
        out = np.concatenate([out, rng.permutation(cand)])
    return out[:k]


def make_stream(g, batch: int, rng):
    """The four mixed batches as ``(insert_edges, remove_edges)`` and the
    sorted keys of the final edge set, tracked on the host."""
    n = g.n
    live = np.sort(_keys(g.edge_array(), n))
    batches = []
    prev_rm = None
    for _ in range(N_BATCHES):
        rm = rng.choice(live, size=batch, replace=False)
        ins = _absent(live, n, batch, rng) if prev_rm is None else prev_rm
        live = np.delete(live, np.searchsorted(live, np.sort(rm)))
        ins_sorted = np.sort(ins)
        live = np.insert(live, np.searchsorted(live, ins_sorted), ins_sorted)
        batches.append((_edges(ins, n), _edges(rm, n)))
        prev_rm = rm
    return batches, live


def oracle_cores(n: int, live: np.ndarray):
    from repro.core.oracle import bz_from_csr
    from repro.graph.csr import build_csr

    t0 = time.perf_counter()
    core = bz_from_csr(build_csr(n, _edges(live, n)))
    return core, time.perf_counter() - t0


# -- device side ------------------------------------------------------------
def run_stream(mt, batches, name: str) -> None:
    import jax

    for i, (ins, rm) in enumerate(batches, 1):
        t0 = time.perf_counter()
        st = mt.apply_batch(insert_edges=ins, remove_edges=rm)
        jax.block_until_ready((mt.src, mt.dst, mt.valid, mt.core,
                               mt.label, mt.n_edges, st))
        dt = time.perf_counter() - t0
        s = jax.device_get(st)
        log(f"{name} batch {i}: wall_s={dt:.6f} "
            f"removed={int(s.n_removed)} inserted={int(s.n_inserted)} "
            f"remove_rounds={int(s.remove_rounds)} "
            f"insert_rounds={int(s.insert_rounds)} "
            f"V*_removal={int(s.n_dropped)} V*_insertion={int(s.n_promoted)} "
            f"V+={int(s.v_plus)} renumbered={bool(s.renumbered)} "
            f"high_water={int(s.high_water)}")
        if int(s.n_removed) != rm.shape[0] or int(s.n_inserted) != ins.shape[0]:
            fail(f"{name} batch {i} removed {int(s.n_removed)} and inserted "
                 f"{int(s.n_inserted)} edges, expected {rm.shape[0]} and "
                 f"{ins.shape[0]}")


def check_state(mt, live: np.ndarray, name: str) -> None:
    """Slot table == the host's edge set; labels satisfy the k-order
    certificate ``dout(v) <= core(v)``."""
    n = mt.n
    core, label = mt.cores(), mt.labels()
    val = np.asarray(mt.valid)
    s = np.asarray(mt.src)[val].astype(np.int64)
    d = np.asarray(mt.dst)[val].astype(np.int64)
    keys = np.sort(_keys(np.stack([s, d], axis=1), n))
    if not np.array_equal(keys, live):
        fail(f"{name}: the slot table holds {keys.size} edges that are not "
             f"the host's {live.size}")
    if int(mt.n_edges) != live.size:
        fail(f"{name}: n_edges={int(mt.n_edges)}, host count {live.size}")
    d_after = (core[d] > core[s]) | ((core[d] == core[s]) & (label[d] > label[s]))
    dout = np.bincount(np.where(d_after, s, d), minlength=n)
    bad = np.nonzero(dout > core)[0]
    if bad.size:
        fail(f"{name}: k-order certificate dout <= core violated at "
             f"{bad.size} vertices (first {bad[:5].tolist()})")
    log(f"{name}: slot table == host edge set ({live.size} edges), "
        f"n_edges ok, certificate dout <= core holds at all {n} vertices")


def check_cores(got: np.ndarray, want: np.ndarray, what: str) -> None:
    diff = np.nonzero(got != want)[0]
    if diff.size:
        fail(f"{what}: differ at {diff.size} vertices "
             f"(first {diff[:5].tolist()})")
    log(f"{what}: equal at all {want.size} vertices")


def batch_program_memory(mt) -> str:
    """The compiler's memory figures for the batch program the stream
    ran: lowered again with the same arguments and static sizes, JAX's
    in-process caches hand back that executable instead of compiling
    (the line prints how long that took)."""
    import jax.numpy as jnp

    from repro.core import engine

    lanes = 1 << (BATCH - 1).bit_length()
    idx, ok = jnp.zeros(lanes, jnp.int32), jnp.zeros(lanes, jnp.bool_)
    t0 = time.perf_counter()
    m = engine.apply_batch.lower(
        mt.src, mt.dst, mt.valid, mt.core, mt.label, mt.n_edges,
        idx, idx, ok, idx, idx, ok, mt.n, mt.n_levels, mt._last_window,
        kernel_backend=mt.kernel_backend,
    ).compile().memory_analysis()
    return (f"batch program memory_analysis "
            f"({time.perf_counter() - t0:.3f} s to lower and fetch): "
            f"argument={m.argument_size_in_bytes} "
            f"output={m.output_size_in_bytes} "
            f"alias={m.alias_size_in_bytes} temp={m.temp_size_in_bytes} "
            f"generated_code={m.generated_code_size_in_bytes}")


def live_array_bytes() -> Counter:
    """Bytes of the live jax arrays held on each device (by device id):
    what of ``bytes_in_use`` is array state rather than anything else."""
    import jax

    held: Counter = Counter()
    for a in jax.live_arrays():
        shard = math.prod(a.sharding.shard_shape(a.shape)) * a.dtype.itemsize
        for dev in a.sharding.device_set:
            held[dev.id] += shard
    return held


def build(g, **engine) -> object:
    from repro.core.api import CoreMaintainer

    t0 = time.perf_counter()
    mt = CoreMaintainer.from_graph(g, kernel_backend="lax", init="jax-peel",
                                   **engine)
    mt.core.block_until_ready()
    log(f"from_graph {engine}: {time.perf_counter() - t0:.3f} s "
        f"capacity={mt.capacity}")
    return mt


def one_chip(g, batches, live, oracle, clog) -> None:
    import jax

    from repro.core import engine

    before = engine.apply_batch._cache_size()
    mt = build(g, engine="unified")
    run_stream(mt, batches, "unified")
    compiles = engine.apply_batch._cache_size() - before
    log(f"batch programs compiled: {compiles}; {clog.summary()}")
    if compiles != 1:
        fail(f"{compiles} batch programs compiled for one bucket")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"device 0 peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_in_use={stats.get('bytes_in_use')}")
    log(batch_program_memory(mt))
    check_state(mt, live, "unified")
    core, secs = oracle.result()
    log(f"oracle bz_from_csr: {secs:.3f} s, max core {int(core.max())}")
    check_cores(mt.cores(), core, "unified cores vs oracle")


def four_chips(g, batches, live, oracle, clog) -> None:
    import jax

    mt = build(g, engine="unified")
    run_stream(mt, batches, "unified")
    check_state(mt, live, "unified")
    ref_core, ref_label = mt.cores(), mt.labels()
    del mt
    gc.collect()  # free chip 0 before the mesh run reports bytes in use
    mt = build(g, engine="sharded", vertex_sharding="halo", mesh_shape=(2, 2))
    run_stream(mt, batches, "halo(2,2)")
    log(clog.summary())
    arrays = live_array_bytes()
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        log(f"device {dev.id} bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"live_array_bytes={arrays[dev.id]}")
    check_state(mt, live, "halo(2,2)")
    check_cores(mt.cores(), ref_core, "halo(2,2) cores vs unified")
    check_cores(mt.labels(), ref_label, "halo(2,2) labels vs unified")
    core, secs = oracle.result()
    log(f"oracle bz_from_csr: {secs:.3f} s, max core {int(core.max())}")
    check_cores(ref_core, core, "unified cores vs oracle")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = require_chips(args.chips)
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.compile_cache import enable_compile_cache
    from repro.graph.generators import rmat

    log(f"devices: {len(devices)} x {devices[0].device_kind}; "
        f"compile cache: {enable_compile_cache()}")
    clog = CompileLog()
    t0 = time.perf_counter()
    g = rmat(SCALE, EDGE_FACTOR << SCALE, seed=SEED)
    log(f"graph: R-MAT SCALE {SCALE} n={g.n} m={g.m} "
        f"({time.perf_counter() - t0:.3f} s)")
    t0 = time.perf_counter()
    batches, live = make_stream(g, BATCH, np.random.default_rng(SEED))
    log(f"stream: {N_BATCHES} batches of {BATCH} removals + "
        f"{BATCH} insertions ({time.perf_counter() - t0:.3f} s)")
    with ThreadPoolExecutor(max_workers=1) as pool:
        # the host oracle overlaps the device work; result() re-raises
        oracle = pool.submit(oracle_cores, g.n, live)
        run = four_chips if args.chips == 4 else one_chip
        run(g, batches, live, oracle, clog)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
