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
    spec["configs"].append({"name": "tiny",
                            "file": str(root / "configs" / "tiny.json")})
    for cell, traffic in ((CELL, "tiny"), ("tiny.insert", "tiny_insert"),
                          ("tiny.remove", "tiny_remove")):
        spec["workloads"].append({"name": cell, "config": "tiny",
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


def test_result_line_has_the_contract_keys(bench):
    line = _run(bench)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"edges_per_s", "burst_max_s", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert all(c["value"] == 0 == c["limit"]
               for c in line["checks"].values())
    assert "probe_core_mismatch" in line["checks"]  # an earlier burst
    json.dumps(line)


@pytest.mark.parametrize("cell,seconds", [("tiny.insert", 0.3),
                                          ("tiny.remove", 60.0)])
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


def test_control_is_not_correct(bench):
    line = _run(bench, control="stale")
    assert line["correct"] is False
    assert line["checks"]["core_mismatch"]["value"] > 0


def _unchanged(real, *a, **kw):
    keep = [a[i].copy() for i in range(6)]
    out = real(*a, **kw)
    return (*keep, out[6])


def _half_batch(real, *a, **kw):
    a = list(a)
    for i in (8, 11):  # ins_ok, rm_ok: the second half of the lanes off
        ok = np.asarray(a[i]).copy()
        ok[len(ok) // 2:] = False
        a[i] = ok
    return real(*a, **kw)


def _altered_core(real, *a, **kw):
    out = list(real(*a, **kw))
    out[3] = out[3].at[7].add(1)
    return tuple(out)


@pytest.mark.parametrize("fault,caught", [
    (_unchanged, "slot_table_diff"),
    (_half_batch, "burst_count_diff"),
    (_altered_core, "core_mismatch"),
    (_altered_core, "probe_core_mismatch"),
], ids=["state_unchanged", "half_batch", "answer_altered",
        "answer_altered_earlier"])
def test_planted_fault_is_not_correct(bench, monkeypatch, fault, caught):
    """Each fault a one-chip cell can have, planted under the timed path
    (the unified engine's batch program as ``CoreMaintainer`` calls it).
    There is no exchange between chips to leave out on one chip."""
    from repro.core import api

    real = api.apply_batch
    monkeypatch.setattr(api, "apply_batch",
                        lambda *a, **kw: fault(real, *a, **kw))
    line = _run(bench)
    assert line["correct"] is False
    assert line["checks"][caught]["value"] > 0


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
