"""Whole runs of the harness on the CPU at a tiny size: the result line,
cells added as new files only, the control and the planted faults that
``correct`` must catch, and the refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(REPO))

from benchmarks.chip import harness  # noqa: E402

SEED = 2**31 + 12345  # past 32 signed bits, as the benchmark's seeds are
CELL = "tiny.burst"
WCELL = "tinyw.burst"  # the same graph and bursts with edge weights
DATA = Path(__file__).resolve().parent / "data"
WEIGHTED_CHECKS = ["core_mismatch", "slot_table_diff", "n_edges_diff",
                   "burst_count_diff", "probe_core_mismatch"]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The committed benchmark plus one configuration, traffic mix, cell
    and per-layer metric added as new files in a copy: nothing that
    exists is edited."""
    root = tmp_path_factory.mktemp("bench") / "chip"
    shutil.copytree(harness.ROOT, root,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = json.loads((root / "configs" / "rmat16.json").read_text())
    cfg.update(name="tiny", scale=9, capacity=1 << 14)
    (root / "configs" / "tiny.json").write_text(json.dumps(cfg))
    # a weighted configuration: its generator draws the weights, and it
    # names the checks the weighted engine's guarantees imply
    shutil.copy(DATA / "rmat_uniform_weights.py", root / "generators")
    weighted = dict(cfg, name="tinyw", generator="rmat_uniform_weights",
                    max_weight=8, checks=WEIGHTED_CHECKS,
                    maintainer=dict(cfg["maintainer"], weighted=True))
    # refused: a check the harness does not know (before the generator,
    # which would raise, is called), and a weighted maintainer fed by a
    # generator that returns no weights
    (root / "generators" / "boom.py").write_text(
        "def generate(params, rng):\n    raise RuntimeError('called')\n")
    unknown = dict(cfg, name="tinyu", generator="boom",
                   checks=cfg["checks"] + ["no_such_check"])
    unweighed = dict(weighted, name="tinyv", generator="rmat")
    for c in (weighted, unknown, unweighed):
        (root / "configs" / f"{c['name']}.json").write_text(json.dumps(c))
    (root / "traffic" / "tiny.json").write_text(json.dumps(
        {"stream": "bursts", "stream_seed": 5, "remove": 48, "insert": 48,
         "insert_from": "removed", "cycle": 6}))
    # a mix of another shape, as data only: insert-only bursts
    (root / "traffic" / "tiny_insert.json").write_text(json.dumps(
        {"stream": "bursts", "stream_seed": 5, "remove": 0, "insert": 48,
         "insert_from": "absent", "bursts": 100}))
    # and one whose stream generator is a new file, found by its name
    (root / "streams" / "removals.py").write_text(
        "from benchmarks.chip.streams import bursts\n\n\n"
        "def build(n, edges, traffic, rng, perm):\n"
        "    return bursts.build(n, edges, dict(traffic, insert=0), rng,\n"
        "                        perm)\n")
    (root / "traffic" / "tiny_remove.json").write_text(json.dumps(
        {"stream": "removals", "stream_seed": 5, "remove": 16,
         "bursts": 3}))
    (root / "metrics" / "extra.bursts.py").write_text(
        "def read(run):\n    return len(run.bursts)\n")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for name in ("tiny", "tinyw", "tinyu", "tinyv"):
        spec["configs"].append({"name": name, "file": str(
            root / "configs" / f"{name}.json")})
    for cell, traffic in ((CELL, "tiny"), ("tiny.insert", "tiny_insert"),
                          ("tiny.remove", "tiny_remove"), (WCELL, "tiny"),
                          ("tinyw.insert", "tiny_insert"),
                          ("tinyu.burst", "tiny"), ("tinyv.burst", "tiny")):
        spec["workloads"].append({"name": cell, "config": cell.split(".")[0],
                                  "traffic": traffic, "chips": 1})
    # an end-to-end metric that only the new cell reports
    (root / "metrics" / "burst_max_s.py").write_text(
        "def read(run):\n"
        "    return max(b.latency_s for b in run.bursts)\n")
    spec["end_to_end"].append({"name": "burst_max_s", "unit": "s",
                               "better": "lower", "bound": 0.25,
                               "source": "host_clock", "workloads": [CELL]})
    spec["per_layer"].append({"name": "extra.bursts", "unit": "bursts",
                              "better": "higher", "source": "host_clock",
                              "layer": "host planning",
                              "moves": "edges_per_s"})
    return harness.Bench(spec, root=root)


def _run(bench, traced=False, control=None, seconds=0.3, seed=SEED,
         cell=CELL):
    import jax

    return harness.run_cell(bench, cell, seed, seconds, traced,
                            time.perf_counter(), jax.devices()[:1],
                            control=control)


@pytest.mark.parametrize("cell,metrics,checks", [
    (CELL, {"edges_per_s", "burst_max_s", "setup_s"}, harness.reference.CHECKS),
    (WCELL, {"edges_per_s", "setup_s"}, WEIGHTED_CHECKS),
], ids=["unweighted", "weighted"])
def test_result_line_has_the_contract_keys(bench, cell, metrics, checks):
    line = _run(bench, cell=cell)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == metrics
    # exactly the checks the configuration names, probe's among them
    assert list(line["checks"]) == list(checks)
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert all(c["value"] == 0 == c["limit"]
               for c in line["checks"].values())
    assert "probe_core_mismatch" in line["checks"]  # an earlier burst
    json.dumps(line)


@pytest.mark.parametrize("cell,seconds", [("tiny.insert", 0.3),
                                          ("tiny.remove", 60.0),
                                          ("tinyw.insert", 0.3)])
def test_mixes_of_other_shapes_run_from_new_files(bench, cell, seconds):
    line = _run(bench, cell=cell, seconds=seconds)
    assert line["correct"] is True and line["failed"] == 0
    if cell == "tiny.remove":  # the window ends where the stream runs out
        assert line["attempted"] == 3
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert "burst_max_s" not in line["metrics"]  # listed for CELL only


def test_the_seed_relabels_and_keeps_the_work(bench):
    a = _run(bench, traced=True, cell="tiny.remove", seconds=60.0)
    b = _run(bench, traced=True, cell="tiny.remove", seconds=60.0,
             seed=SEED + 1)
    assert a["correct"] is True and b["correct"] is True
    assert a["attempted"] == b["attempted"] == 3
    assert (a["metrics"]["program.rounds_per_burst"]
            == b["metrics"]["program.rounds_per_burst"])


def test_traced_line_reads_the_per_layer_metrics(bench):
    line = _run(bench, traced=True)
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    got = line["metrics"]
    # the metric added as a new file is read without any edit
    assert got["extra.bursts"]["value"] == line["attempted"]
    assert got["compile.in_window"]["value"] == 0
    assert got["program.rounds_per_burst"]["value"] > 0
    assert got["host.plan_ms"]["value"] > 0
    # the CPU has no TPU plane: the device readers find nothing to read
    assert "device.idle_pct" not in got and "program.ms_per_round" not in got
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell", [CELL, WCELL], ids=["unweighted",
                                                    "weighted"])
def test_control_is_not_correct(bench, cell):
    line = _run(bench, control="stale", cell=cell)
    assert line["correct"] is False
    assert line["checks"]["core_mismatch"]["value"] > 0


@pytest.mark.parametrize("cell", ["tinyu.burst", "tinyv.burst"],
                         ids=["unknown_check", "weighted_without_weights"])
def test_configuration_is_refused(bench, cell):
    with pytest.raises(ValueError, match="checks|weights"):
        _run(bench, cell=cell)


# where each program keeps its state, lanes and outputs: (src, dst, valid,
# [w,] core, label, n_edges, lanes...) in, the state and the stats out
AT = {
    "apply_batch": {"state": 6, "core": 3, "ok": (8, 11)},
    "apply_batch_weighted": {"state": 7, "core": 4, "ok": (10, 13), "w": 3,
                             "ins_w": 9},
}


def _unchanged(real, at, *a, **kw):
    keep = [a[i].copy() for i in range(at["state"])]
    out = real(*a, **kw)
    return (*keep, out[at["state"]])


def _half_batch(real, at, *a, **kw):
    a = list(a)
    for i in at["ok"]:  # ins_ok, rm_ok: the second half of the lanes off
        ok = np.asarray(a[i]).copy()
        ok[len(ok) // 2:] = False
        a[i] = ok
    return real(*a, **kw)


def _altered_core(real, at, *a, **kw):
    out = list(real(*a, **kw))
    out[at["core"]] = out[at["core"]].at[7].add(1)
    return tuple(out)


def _unit_weights(real, at, *a, **kw):
    a = list(a)
    a[at["ins_w"]] = np.ones_like(np.asarray(a[at["ins_w"]]))
    return real(*a, **kw)


def _altered_weight(real, at, *a, **kw):
    out = list(real(*a, **kw))
    slot = int(np.argmax(np.asarray(out[2])))  # the first valid slot
    out[at["w"]] = out[at["w"]].at[slot].add(1)
    return tuple(out)


@pytest.mark.parametrize("program,fault,caught", [
    ("apply_batch", _unchanged, ("slot_table_diff",)),
    ("apply_batch", _half_batch, ("burst_count_diff",)),
    ("apply_batch", _altered_core, ("core_mismatch",)),
    ("apply_batch", _altered_core, ("probe_core_mismatch",)),
    ("apply_batch_weighted", _unchanged, ("slot_table_diff",)),
    ("apply_batch_weighted", _half_batch, ("burst_count_diff",)),
    ("apply_batch_weighted", _unit_weights,
     ("core_mismatch", "slot_table_diff")),
    ("apply_batch_weighted", _altered_weight, ("slot_table_diff",)),
    ("apply_batch_weighted", _altered_core,
     ("core_mismatch", "probe_core_mismatch")),
], ids=["state_unchanged", "half_batch", "answer_altered",
        "answer_altered_earlier", "weighted_state_unchanged",
        "weighted_half_batch", "weighted_unit_weights",
        "weighted_weight_altered", "weighted_answer_altered"])
def test_planted_fault_is_not_correct(bench, monkeypatch, program, fault,
                                      caught):
    """Each fault a one-chip cell can have, planted under the timed path
    (the unified engine's batch program, unweighted or weighted, as
    ``CoreMaintainer`` calls it). There is no exchange between chips to
    leave out on one chip. A weighted cell can also lose the insertions'
    weights or alter a slot's weight where it is produced."""
    from repro.core import api

    real = getattr(api, program)
    monkeypatch.setattr(api, program,
                        lambda *a, **kw: fault(real, AT[program], *a, **kw))
    line = _run(bench, cell=WCELL if program.endswith("weighted") else CELL)
    assert line["correct"] is False
    # the weighted cell names no certificate check: the faults must show
    # in the checks it does name
    assert any(line["checks"][c]["value"] > 0 for c in caught)
    if fault is _altered_core:  # both the end and the earlier burst
        for c in caught:
            assert line["checks"][c]["value"] > 0


def test_benchmark_refuses_to_run_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "benchmarks/chip/run.py", "--workload",
           "rmat16.burst25k", "--seed", str(SEED), "--seconds", "1",
           "--trace", "0"]
    t0 = time.perf_counter()
    p = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr
    assert time.perf_counter() - t0 < 60  # refused before any work
    # a checkout that holds only BENCHMARK.json and the benchmark's files
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.ROOT, tmp_path / "benchmarks" / "chip")
    p = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "src/repro" in p.stderr
