"""The weighted cell once its program takes each round's h-index from one
sort (one device, no mesh axis): the committed ``rmat16w8``
configuration at SCALE 8 reads no bisection step and a sorted share of
1.0, and the reader of ``program.sorted_hindex_share`` on hand-made
runs."""
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmarks.chip import harness  # noqa: E402
from test_chipbench_phases import (_burst, _Call,  # noqa: E402
                                   _hand_made_run, _stats)
from test_chipbench_weighted import (UCELL, W8CELL, _hand_hlo,  # noqa: E402
                                     _run, bench)

SORTED = "program.sorted_hindex_share"


def test_traced_weighted_line_reads_the_sorted_share(bench):
    line = _run(bench, W8CELL, traced=True)
    assert line["correct"] is True
    assert line["metrics"][SORTED]["value"] == 1.0
    # no bisection step, so no time per step
    assert line["metrics"]["program.bisect_steps_per_burst"]["value"] == 0
    assert "program.ms_per_bisect_step" not in line["metrics"]
    # only the cells the metric lists report it
    assert SORTED not in _run(bench, UCELL, traced=True)["metrics"]


def test_sorted_share_reader_on_hand_made_runs(monkeypatch):
    read = harness.Bench.load(REPO).reader(SORTED).read
    hlo = _hand_hlo()
    calls = [_Call(hlo, **_stats(2, 1, 0, 0), hindex_sorts=3),
             _Call(hlo, **_stats(1, 1, 0, 0), hindex_sorts=0)]
    run = _hand_made_run(monkeypatch, calls, [_burst(2, 1), _burst(1, 1)])
    assert read(run) == 3 / 5
    # a program that does not count them, as the parent commit's does
    run = _hand_made_run(monkeypatch, [_Call(hlo, **_stats(2, 1, 0, 0))],
                         [_burst(2, 1)])
    assert read(run) is None
    # an unweighted program, whose stats leave the counter at None
    run = _hand_made_run(monkeypatch, [_Call(
        hlo, **_stats(2, 1, 3, 4), hindex_sorts=None)], [_burst(2, 1)])
    assert read(run) is None
    # no rounds: no share
    run = _hand_made_run(monkeypatch, [_Call(
        hlo, **_stats(0, 0, 0, 0), hindex_sorts=0)], [_burst(0, 0)])
    assert read(run) is None
