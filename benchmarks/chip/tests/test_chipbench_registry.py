"""``BENCHMARK.json`` against the benchmark's contract, and every name in
it found as a file of its own."""
import json
import re
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(REPO))

from benchmarks.chip import harness, roofline  # noqa: E402

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
BENCH = harness.Bench(SPEC)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmarks/chip"]
    assert SPEC["command"][0] == "python3"
    for word in SPEC["command"][1:]:
        assert word.startswith("benchmarks/chip/")
        assert (REPO / word).is_file()
    assert 1 <= SPEC["run_seconds"] <= 51


def test_a_full_check_of_24_cells_fits_the_time_limit():
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_text_fields():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[key]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_finds_its_files_and_reports_enough(cell):
    w = BENCH.cell(cell)
    cfg = BENCH.config(w["config"])
    assert cfg["name"] == w["config"]
    for key in next(c for c in SPEC["configs"]
                    if c["name"] == w["config"])["reduced"]:
        assert key in cfg and key in cfg["reduced"]
    assert hasattr(BENCH.generator(cfg["generator"]), "generate")
    traffic = BENCH.traffic(w["traffic"])
    assert callable(BENCH.stream(traffic["stream"]).build)
    e2e = [m["name"] for m in BENCH.metrics(cell, traced=False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = BENCH.metrics(cell, traced=True)
    assert layer
    for m in BENCH.metrics(cell, False) + layer:
        assert callable(BENCH.reader(m["name"]).read)
        if m in layer:  # what it moves is reported in this cell
            assert m["moves"] in e2e


def test_config_files_lie_under_paths_and_are_distinct():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    for f in files:
        assert f.startswith(SPEC["paths"][0] + "/") and (REPO / f).is_file()


def test_peaks_know_the_v5e_and_refuse_an_unknown_chip():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["source"]
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
