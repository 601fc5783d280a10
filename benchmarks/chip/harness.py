"""One run of one cell: build the graph and stream from the seed, load
them into ``CoreMaintainer``, warm up, measure a closed-loop window of
bursts, check the result against the plain reference, and print the
result line.

Everything that belongs to one configuration, traffic mix or metric is
a file found by its name: ``configs/<config>.json`` (through
``BENCHMARK.json``), ``generators/<generator>.py``,
``traffic/<traffic>.json``, the stream generator that the traffic file
names, ``streams/<stream>.py``, and ``metrics/<metric>.py``. A metric
file defines ``read(run) -> float | None`` over a ``RunData``; ``None``
leaves the metric out of the line.

A generator file defines ``generate(params, rng)``, which returns ``(n,
edges)``: the unique undirected edges ``lo < hi`` as an ``[m, 2]`` int
array sorted by ``lo * n + hi``. A configuration whose ``maintainer``
has ``"weighted": true`` names a generator that returns ``(n, edges,
weights)``, ``weights`` positive integers row for row with ``edges``,
and also defines ``draw_weights(params, rng, k)``: ``k`` weights for the
fresh pairs that its stream inserts. The configuration's generator fixes
the weight law; the harness carries the weights with their edges
through the relabelling, into ``CoreMaintainer`` and into the checks.

A configuration's ``checks`` names the numbers that decide ``correct``,
those its guarantees imply, from ``reference.CHECKS``; the result line
carries exactly those.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

import numpy as np

from . import reference, roofline
from . import trace as tr

ROOT = Path(__file__).resolve().parent
REPO = ROOT.parents[1]
PLATFORM = "tpu"
CONTROLS = ("stale",)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Bench:
    """``BENCHMARK.json`` and the files it names, found by name under
    ``root``."""

    def __init__(self, spec: dict, root: Path = ROOT, repo: Path = REPO):
        self.spec, self.root, self.repo = spec, Path(root), Path(repo)

    @classmethod
    def load(cls, repo: Path = REPO) -> "Bench":
        return cls(json.loads((Path(repo) / "BENCHMARK.json").read_text()),
                   repo=repo)

    def _entry(self, key: str, name: str) -> dict:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"BENCHMARK.json has no {key} entry {name!r}")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        path = self.repo / self._entry("configs", name)["file"]
        return json.loads(path.read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.root / "traffic" / f"{name}.json").read_text())

    def _module(self, kind: str, name: str):
        path = self.root / kind / f"{name}.py"
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{kind}_{name}".replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod  # dataclasses look their module up
        spec.loader.exec_module(mod)
        return mod

    def generator(self, name: str):
        return self._module("generators", name)

    def reader(self, name: str):
        return self._module("metrics", name)

    def stream(self, name: str):
        return self._module("streams", name)

    def metrics(self, cell: str, traced: bool) -> list:
        """The cell's end-to-end metrics, or with ``traced`` its
        per-layer ones."""
        key = "per_layer" if traced else "end_to_end"
        return [m for m in self.spec[key]
                if cell in m.get("workloads", [cell])]


@dataclass
class Burst:
    plan_s: float      # apply_batch call: host planning and dispatch
    latency_s: float   # call until the burst's BatchStats are ready
    sent_removed: int   # edges sent for removal
    sent_inserted: int  # edges sent for insertion
    removed: int = 0
    inserted: int = 0
    remove_rounds: int = 0
    insert_rounds: int = 0
    v_plus: int = 0


@dataclass
class RunData:
    """What the metric readers read."""

    setup_s: float
    window_s: float
    bursts: list
    compiles_in_window: int
    trace: Optional[tr.Trace] = None  # --trace 1 runs only


class CompileCounter:
    """Backend compiles (persistent-cache loads included) seen through
    ``jax.monitoring`` while the counter is entered."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1


def require_chips(n_chips: int):
    """The devices, or exit non-zero: the benchmark runs on a TPU only."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"chipbench: no accelerator found ({e})")
    if devices[0].platform != PLATFORM:
        sys.exit(f"chipbench: JAX reports platform {devices[0].platform!r}; "
                 f"this benchmark runs on a TPU only")
    if len(devices) < n_chips:
        sys.exit(f"chipbench: the cell needs {n_chips} chips, JAX reports "
                 f"{len(devices)}")
    return devices


def seeded(seed: int, k: int) -> list:
    """``k`` independent generators from one seed of any size or sign."""
    ss = np.random.SeedSequence(seed % (1 << 64))
    return [np.random.default_rng(s) for s in ss.spawn(k)]


def canonical(n: int, edges: np.ndarray, weights=None):
    """Unique undirected edges ``lo < hi`` sorted by ``lo * n + hi``;
    with ``weights``, ``(edges, weights)``, each edge's weight (its first
    row's) moved with it."""
    lo, hi = edges.min(axis=1), edges.max(axis=1)
    if weights is None:
        key = np.unique(lo * n + hi)
        return np.stack([key // n, key % n], axis=1)
    key, first = np.unique(lo * n + hi, return_index=True)
    return np.stack([key // n, key % n], axis=1), np.asarray(weights)[first]


def checks_of(cfg: dict) -> tuple:
    """The checks a configuration names, refused where one is unknown."""
    checks = tuple(cfg["checks"])
    unknown = sorted(set(checks) - set(reference.CHECKS))
    if unknown or not checks:
        raise ValueError(f"configuration {cfg['name']!r} names checks "
                         f"{unknown or 'none'}; the harness knows "
                         f"{reference.CHECKS}")
    return checks


def _block(mt, st) -> None:
    import jax

    jax.block_until_ready((mt.src, mt.dst, mt.valid, mt.w, mt.core,
                           mt.label, mt.n_edges, st))


def run_cell(bench: Bench, cell_name: str, seed: int, seconds: float,
             traced: bool, t_start: float, devices,
             control: Optional[str] = None,
             trace_dir: Optional[Path] = None) -> dict:
    """One run; returns the result line as a dict."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileOptions, TraceAnnotation

    from repro.core.api import CoreMaintainer
    from repro.graph.csr import build_csr

    cell = bench.cell(cell_name)
    cfg = bench.config(cell["config"])
    checks = checks_of(cfg)
    weighted = bool(cfg["maintainer"].get("weighted", False))
    traffic = bench.traffic(cell["traffic"])
    # the graph and the bursts are the cell's own, fixed by the seeds in
    # its files; the run's seed draws the vertex labels and the probe
    g_rng = np.random.default_rng(cfg["graph_seed"])
    s_rng = np.random.default_rng(traffic["stream_seed"])
    l_rng, p_rng = seeded(seed, 2)

    t0 = time.perf_counter()
    gen = bench.generator(cfg["generator"])
    n, edges, *weights = gen.generate(cfg, g_rng)
    weights = weights[0] if weights else None
    if weighted != (weights is not None):
        raise ValueError(f"configuration {cfg['name']!r}: a weighted "
                         f"maintainer needs a generator that returns "
                         f"weights, and only it takes them")
    perm = l_rng.permutation(n)
    stream = bench.stream(traffic["stream"])
    if weights is None:
        st = stream.build(n, edges, traffic, s_rng, perm)
        edges = canonical(n, perm[edges])
    else:
        # fresh pairs' weights from a generator spawned apart from s_rng
        w_rng = seeded(traffic["stream_seed"], 1)[0]
        st = stream.build(n, edges, traffic, s_rng, perm, weights=weights,
                          draw_weights=lambda k: gen.draw_weights(
                              cfg, w_rng, k))
        edges, weights = canonical(n, perm[edges], weights)
    if st.max_live >= cfg["capacity"]:
        raise ValueError(f"the stream holds up to {st.max_live} edges, the "
                         f"capacity is {cfg['capacity']}")
    log(f"graph {cfg['name']}: n={n} m={edges.shape[0]}; stream "
        f"{cell['traffic']}: {traffic['stream']} "
        f"{ {k: v for k, v in traffic.items() if k != 'about'} } "
        f"({time.perf_counter() - t0:.3f} s)")

    counts_off = 0
    with CompileCounter() as compiles:
        t0 = time.perf_counter()
        mt = CoreMaintainer.from_graph(build_csr(n, edges),
                                       capacity=cfg["capacity"],
                                       weights=weights,
                                       **cfg["maintainer"])
        mt.core.block_until_ready()
        log(f"from_graph: {time.perf_counter() - t0:.3f} s, "
            f"capacity={mt.capacity}")
        t0 = time.perf_counter()
        ins, rm, ins_w = st.warmup
        s = mt.apply_batch(insert_edges=ins, remove_edges=rm,
                           insert_weights=ins_w)
        _block(mt, s)
        s = jax.device_get(s)
        counts_off += abs(int(s.n_removed) - len(rm)) + abs(
            int(s.n_inserted) - len(ins))
        log(f"warm-up burst: {time.perf_counter() - t0:.3f} s, rounds "
            f"{int(s.remove_rounds)}+{int(s.insert_rounds)}, "
            f"compiles so far {compiles.count}")

        # a copy of the cores and labels after each burst, for the probe
        snap = jax.jit(lambda c, lab: (jnp.copy(c), jnp.copy(lab)))
        jax.block_until_ready(snap(mt.core, mt.label))

        tmp = None
        if traced:
            tmp = Path(trace_dir) if trace_dir else Path(
                tempfile.mkdtemp(prefix="chipbench-trace-"))
            opts = ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(tmp), profiler_options=opts)
        bursts, stats, snaps = [], [], []
        compiles_before = compiles.count
        setup_s = time.perf_counter() - t_start
        win = TraceAnnotation("bench.window")
        win.__enter__()
        w0 = time.perf_counter()
        i = 0
        while True:
            if i == st.n_bursts:
                log(f"the stream ran out after {i} bursts: the window ends "
                    f"at {time.perf_counter() - w0:.3f} s")
                break
            ins, rm, ins_w = st.burst_edges(i)
            b0 = time.perf_counter()
            with TraceAnnotation("bench.plan"):
                s = mt.apply_batch(insert_edges=ins, remove_edges=rm,
                                   insert_weights=ins_w)
            b1 = time.perf_counter()
            with TraceAnnotation("bench.wait"):
                _block(mt, s)
            b2 = time.perf_counter()
            bursts.append(Burst(b1 - b0, b2 - b0, len(rm), len(ins)))
            stats.append(s)
            snaps.append(snap(mt.core, mt.label))
            i += 1
            if b2 - w0 >= seconds:
                break
        window_s = b2 - w0
        win.__exit__(None, None, None)
        in_window = compiles.count - compiles_before
        if traced:
            jax.profiler.stop_trace()

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    for b, s in zip(bursts, jax.device_get(stats)):
        b.removed, b.inserted = int(s.n_removed), int(s.n_inserted)
        b.remove_rounds, b.insert_rounds = (int(s.remove_rounds),
                                            int(s.insert_rounds))
        b.v_plus = int(s.v_plus)
    failed = sum(b.removed != b.sent_removed or b.inserted != b.sent_inserted
                 for b in bursts)
    counts_off += sum(abs(b.removed - b.sent_removed)
                      + abs(b.inserted - b.sent_inserted) for b in bursts)
    state = {k: np.asarray(getattr(mt, k)) for k in
             ("src", "dst", "valid", "core", "label", "n_edges")
             + (("w",) if weighted else ())}
    last = len(bursts) - 1
    k = int(p_rng.integers(0, last)) if last > 0 else None
    probe = jax.device_get(snaps[k]) if k is not None else None
    del mt, stats, s, snaps
    gc.collect()
    log(f"window: {len(bursts)} bursts in {window_s:.6f} s, "
        f"{in_window} compiles in it, peak_bytes_in_use={peak}")
    for j, b in enumerate(bursts):
        log(f"burst {j}: {b.latency_s:.6f} s, rounds {b.remove_rounds}+"
            f"{b.insert_rounds}, |V+| {b.v_plus}, applied {b.removed}+"
            f"{b.inserted} of {b.sent_removed}+{b.sent_inserted}")

    def edge_set(i):
        """The host's edge keys after burst ``i``, with their weights
        (None unweighted), and the reference's cores of them."""
        if not weighted:
            keys = st.live_after(i)
            return keys, None, reference.core_numbers(n, keys)
        keys, w = st.live_after(i, weights=True)
        return keys, w, reference.weighted_core_numbers(n, keys, w)

    t0 = time.perf_counter()
    live, live_w, want = edge_set(last)
    if probe is not None:
        then, _, want_then = edge_set(k)
        probe = (*probe, then, want_then)
        log(f"probe: burst {k} of {last + 1}")

    def readings():
        return reference.readings(n, state, live, want, counts_off, probe,
                                  checks=checks, live_w=live_w)

    read = readings()
    if control == "stale":
        # the reference in the program's place, one burst behind; the
        # program's own verdict is logged beside it
        log(f"control {control}: the program's own readings {read}, "
            f"correct={reference.verdict(read)}")
        state["core"] = edge_set(last - 1)[2]
        read = readings()
    elif control is not None:
        raise ValueError(f"unknown control {control!r}; know {CONTROLS}")
    correct = reference.verdict(read)
    log(f"reference: {time.perf_counter() - t0:.3f} s")

    run = RunData(setup_s=setup_s, window_s=window_s, bursts=bursts,
                  compiles_in_window=in_window)
    dev = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(peak),
    }
    line = {"correct": correct, "attempted": len(bursts), "failed": failed}
    if traced:
        try:
            run.trace = tr.read_xspace(tr.find_xspace(tmp))
            dev["busy_s"] = tr.busy_s(run.trace)
            dev["window_s"] = tr.window_s(run.trace)
            line["breakdown"] = tr.breakdown(run.trace)
            if trace_dir:
                per = tr.op_seconds(run.trace)
                (Path(trace_dir) / "ops.json").write_text(json.dumps(
                    sorted(per.items(), key=lambda kv: -kv[1]), indent=0))
        finally:
            if not trace_dir:
                shutil.rmtree(tmp, ignore_errors=True)
    metrics = {}
    for m in bench.metrics(cell_name, traced):
        v = bench.reader(m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = dev
    line["checks"] = {name: {"value": v, "limit": reference.LIMITS[name]}
                      for name, v in read.items()}
    for name, v in read.items():
        log(f"check {name}: {v} (limit {reference.LIMITS[name]})")
    return line


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=CONTROLS, default=None,
                    help="break one guarantee (the control run of the "
                         "correctness check; never part of a measurement)")
    ap.add_argument("--trace-dir", type=Path, default=None,
                    help="keep the --trace 1 profile and its op table here")
    args = ap.parse_args(argv)

    bench = Bench.load()
    cell = bench.cell(args.workload)
    if not (REPO / "src" / "repro").is_dir():
        sys.exit("chipbench: the program (src/repro) is not in this checkout")
    devices = require_chips(cell["chips"])[: cell["chips"]]
    roofline.peaks(devices[0].device_kind)  # an unknown chip is an error
    sys.path.insert(0, str(REPO / "src"))
    from repro.compile_cache import enable_compile_cache

    log(f"devices: {len(devices)} x {devices[0].device_kind}; compile "
        f"cache: {enable_compile_cache()}")
    line = run_cell(bench, args.workload, args.seed, args.seconds,
                    bool(args.trace), t_start, devices,
                    control=args.control, trace_dir=args.trace_dir)
    print(json.dumps(line), flush=True)
    return 0
