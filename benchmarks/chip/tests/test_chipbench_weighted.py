"""The weighted deployment ``rmat16w8`` on the CPU: its generator, its
bursts against ``rmat16.burst25k``'s, the committed configuration run
whole at SCALE 8, and the two readers of the bisection-step counters on
hand-made runs."""
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmarks.chip import harness  # noqa: E402
from test_chipbench_phases import (HAND_MAP, _burst, _Call,  # noqa: E402
                                   _hand_made_run, _stats)

BENCH = harness.Bench.load(REPO)
SEED = 2**31 + 12345  # past 32 signed bits, as the benchmark's seeds are
W8CELL = "tinyw8.burst"  # the committed rmat16w8 configuration at SCALE 8
UCELL = "tiny8.burst"  # rmat16 at SCALE 8: the same bursts, unweighted
WEIGHTED_CHECKS = ["core_mismatch", "slot_table_diff", "n_edges_diff",
                   "burst_count_diff", "probe_core_mismatch"]
BISECT = ("program.bisect_steps_per_burst", "program.ms_per_bisect_step")


def _cfg(name, **over):
    cfg = BENCH.config(name)
    cfg.update(over)
    return cfg


def _gen(cfg, seed):
    return BENCH.generator(cfg["generator"]).generate(
        cfg, np.random.default_rng(seed))


def test_weighted_rmat_is_rmat_with_quantized_spec_weights():
    cfg = _cfg("rmat16w8", scale=10)
    levels = cfg["weight_levels"]
    n, e, w = _gen(cfg, 11)
    rng = np.random.default_rng(11)
    n0, e0 = BENCH.generator("rmat").generate(cfg, rng)
    assert n == n0 and np.array_equal(e, e0)  # rmat's graph, edge for edge
    # row for row with the edges: the spec's u ~ U[0, 1), drawn from the
    # same generator after the graph, quantized to 1 + floor(levels * u)
    assert w.shape == (e.shape[0],) and w.dtype.kind == "i"
    assert np.array_equal(w, 1 + np.floor(levels * rng.random(e.shape[0])))
    assert w.min() == 1 and w.max() == levels
    assert np.array_equal(w, _gen(cfg, 11)[2])
    assert not np.array_equal(w, _gen(cfg, 12)[2])
    # each level's share within sampling error (5 sigma) at SCALE 10
    p = 1 / levels
    share = np.bincount(w, minlength=levels + 1)[1:] / w.size
    assert np.all(np.abs(share - p) < 5 * np.sqrt(p * (1 - p) / w.size))
    # fresh pairs draw from the same law
    d = BENCH.generator(cfg["generator"]).draw_weights(
        cfg, np.random.default_rng(3), 20000)
    assert set(np.unique(d)) == set(range(1, levels + 1))


def test_weighted_cell_sends_rmat16s_bursts():
    """``rmat16w8.burst25k`` sends ``rmat16.burst25k``'s edges, each
    re-inserted with the weight it was drawn with."""
    cells = {c: BENCH.cell(c) for c in ("rmat16.burst25k",
                                        "rmat16w8.burst25k")}
    perm = harness.seeded(SEED, 2)[0].permutation(1 << 16)
    got = {}
    for name, c in cells.items():
        cfg = BENCH.config(c["config"])
        traffic = BENCH.traffic(c["traffic"])
        n, e, *w = _gen(cfg, cfg["graph_seed"])
        kw = {}
        if w:
            gen = BENCH.generator(cfg["generator"])
            w_rng = harness.seeded(traffic["stream_seed"], 1)[0]
            kw = dict(weights=w[0], draw_weights=lambda k: gen.draw_weights(
                cfg, w_rng, k))
        st = BENCH.stream(traffic["stream"]).build(
            n, e, traffic, np.random.default_rng(traffic["stream_seed"]),
            perm, **kw)
        got[name] = (e, w, st.warmup, [st.burst_edges(i) for i in range(3)])
    (e0, _, wu0, b0), (e1, (w1,), wu1, b1) = got.values()
    assert np.array_equal(e0, e1)
    for a, b in zip([wu0, *b0], [wu1, *b1]):
        assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
        assert a[2] is None and b[2].shape == (len(b[0]),)

    # a re-inserted edge keeps its weight: the graph's under the labels,
    # or the one the warm-up drew for a fresh pair
    def key(u, v):
        return int(min(u, v)), int(max(u, v))

    weight_of = {key(perm[u], perm[v]): x for (u, v), x in zip(e1, w1)}
    weight_of.update((key(*k), x) for k, x in zip(wu1[0], wu1[2]))
    ins, _, ins_w = b1[1]
    assert [weight_of[key(*k)] for k in ins] == ins_w.tolist()


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The committed benchmark plus the committed ``rmat16w8`` and
    ``rmat16`` configurations at SCALE 8 and a short burst mix, added as
    new files in a copy; the bisection metrics also read in the tiny
    weighted cell."""
    root = tmp_path_factory.mktemp("bench") / "chip"
    shutil.copytree(harness.ROOT, root,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for src, name in (("rmat16w8", "tinyw8"), ("rmat16", "tiny8")):
        cfg = json.loads((root / "configs" / f"{src}.json").read_text())
        cfg.update(name=name, scale=8, capacity=1 << 14)
        (root / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    (root / "traffic" / "tiny.json").write_text(json.dumps(
        {"stream": "bursts", "stream_seed": 5, "remove": 48, "insert": 48,
         "insert_from": "removed", "cycle": 6}))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for cell in (W8CELL, UCELL):
        name = cell.split(".")[0]
        spec["configs"].append({"name": name, "file": str(
            root / "configs" / f"{name}.json")})
        spec["workloads"].append({"name": cell, "config": name,
                                  "traffic": "tiny", "chips": 1})
    for m in spec["per_layer"]:
        if "rmat16w8.burst25k" in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + [W8CELL]
    return harness.Bench(spec, root=root)


def _run(bench, cell, traced=False, control=None):
    import jax

    return harness.run_cell(bench, cell, SEED, 0.3, traced,
                            time.perf_counter(), jax.devices()[:1],
                            control=control)


def test_committed_weighted_configuration_runs_correct(bench):
    line = _run(bench, W8CELL)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"edges_per_s", "setup_s"}
    # exactly the five checks the configuration names, each at 0
    assert list(line["checks"]) == WEIGHTED_CHECKS
    assert all(c["value"] == 0 == c["limit"]
               for c in line["checks"].values())


def test_committed_weighted_configuration_control_is_not_correct(bench):
    line = _run(bench, W8CELL, control="stale")
    assert line["correct"] is False
    assert line["checks"]["core_mismatch"]["value"] > 0


def test_traced_weighted_line_reads_the_bisection_steps(bench):
    line = _run(bench, W8CELL, traced=True)
    assert line["correct"] is True
    assert line["metrics"]["program.bisect_steps_per_burst"]["value"] > 0
    # only the cells the metrics list report them
    assert not set(BISECT) & set(_run(bench, UCELL, traced=True)["metrics"])


def _read_bisect(run):
    return {name: BENCH.reader(name).read(run) for name in BISECT}


def _hand_hlo():
    return "ENTRY %main () -> s32[8] {\n" + "".join(
        f'  %{k} = {s} {o}(), metadata={{op_name="coremaint.{p}/x"}}\n'
        for k, (s, o, p) in HAND_MAP.items()) + "}\n"


def test_bisection_readers_on_a_hand_made_run(monkeypatch):
    hlo = _hand_hlo()
    calls = [_Call(hlo, **_stats(2, 1, 0, 0), remove_bisect_steps=30,
                   insert_bisect_steps=20),
             _Call(hlo, **_stats(1, 1, 0, 0), remove_bisect_steps=10,
                   insert_bisect_steps=12)]
    run = _hand_made_run(monkeypatch, calls, [_burst(2, 1), _burst(1, 1)])
    got = _read_bisect(run)
    assert got["program.bisect_steps_per_burst"] == (50 + 22) / 2
    # the hand-made trace holds 140 ns of remove.stats, no promote.stats
    assert got["program.ms_per_bisect_step"] == \
        pytest.approx(1e3 * 140e-9 / 72)


def test_bisection_readers_fall_silent_without_the_counters(monkeypatch):
    import repro.core.api as api

    hlo = _hand_hlo()
    # a program that counts rounds and waves but not bisection steps, as
    # the parent commit's does
    run = _hand_made_run(monkeypatch, [_Call(hlo, **_stats(2, 1, 0, 0))],
                         [_burst(2, 1)])
    assert _read_bisect(run) == dict.fromkeys(BISECT)
    # a program without the record
    monkeypatch.delattr(api, "RECENT_CALLS")
    assert _read_bisect(run) == dict.fromkeys(BISECT)
    # counted, but the run untraced: no time per step (the count is the
    # program's own and needs no trace)
    run = _hand_made_run(monkeypatch, [_Call(
        hlo, **_stats(2, 1, 0, 0), remove_bisect_steps=3,
        insert_bisect_steps=4)], [_burst(2, 1)])
    run.trace = None
    assert _read_bisect(run) == {"program.bisect_steps_per_burst": 7.0,
                                 "program.ms_per_bisect_step": None}
    # an unweighted window counts no steps: no time per step
    run = _hand_made_run(monkeypatch, [_Call(
        hlo, **_stats(2, 1, 3, 4), remove_bisect_steps=0,
        insert_bisect_steps=0)], [_burst(2, 1)])
    assert _read_bisect(run)["program.ms_per_bisect_step"] is None
