"""chip_smoke.py off the chip: it refuses a host without a TPU, and its
one-chip and four-chip paths run end to end at a tiny SCALE when the
test steers its platform check to the CPU and its sizes down."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture
def smoke(monkeypatch, tmp_path):
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # a placed cache dir keeps the smoke from turning on the persistent
    # cache for the rest of this test process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(mod, "SCALE", 9)
    monkeypatch.setattr(mod, "BATCH", 64)
    monkeypatch.setattr(mod, "SEED", 3)
    return mod


def test_smoke_refuses_a_host_without_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert "no TPU found" in str(e.value.code)
    assert capsys.readouterr().out == ""  # no result line, no work done


def test_smoke_one_chip_path_runs_on_cpu(smoke, monkeypatch, capsys):
    monkeypatch.setattr(smoke, "PLATFORM", "cpu")
    assert smoke.main([]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}
    }
    text = "\n".join(out)
    assert "unified batch 4:" in text
    assert "unified cores vs oracle: equal" in text
    assert "certificate dout <= core holds" in text
    assert "batch programs compiled: 1" in text
    assert "batch program memory_analysis (" in text


def test_smoke_stream_protocol(smoke):
    """Batch 1 inserts absent edges; batches 2-4 re-insert the previous
    batch's removals; the tracked final edge set matches a replay."""
    import numpy as np

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.graph.generators import rmat

    g = rmat(8, 16 << 8, seed=1)
    batches, live = smoke.make_stream(g, 50, np.random.default_rng(1))
    n = g.n
    cur = set(map(int, smoke._keys(g.edge_array(), n)))
    prev_rm = None
    for i, (ins, rm) in enumerate(batches):
        ins_k, rm_k = smoke._keys(ins, n), smoke._keys(rm, n)
        assert len(set(rm_k.tolist())) == 50 and set(rm_k.tolist()) <= cur
        cur -= set(rm_k.tolist())
        assert len(set(ins_k.tolist())) == 50
        assert not set(ins_k.tolist()) & cur
        if prev_rm is not None:
            assert sorted(ins_k.tolist()) == sorted(prev_rm.tolist())
        cur |= set(ins_k.tolist())
        prev_rm = rm_k
    assert sorted(cur) == live.tolist()


def test_smoke_four_chip_path_runs_on_cpu(tmp_path):
    """The --chips 4 path on four virtual CPU devices: halo (2, 2) cores
    and labels bit-identical to unified, cores equal to the oracle."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = (
        "import sys; sys.path.insert(0, sys.argv[1]); import chip_smoke; "
        "chip_smoke.PLATFORM = 'cpu'; chip_smoke.SCALE = 9; "
        "chip_smoke.BATCH = 64; chip_smoke.SEED = 5; "
        "sys.exit(chip_smoke.main(['--chips', '4']))"
    )
    out = subprocess.run([sys.executable, "-c", code, ROOT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1])["device"]["count"] == 4
    text = out.stdout
    assert "halo(2,2) cores vs unified: equal" in text
    assert "halo(2,2) labels vs unified: equal" in text
    assert "unified cores vs oracle: equal" in text
    assert text.count("bytes_in_use=") >= 4
    assert text.count("live_array_bytes=") == 4
