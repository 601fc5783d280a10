"""Dry-run machinery on a small 8-device mesh (subprocess): lower+compile
representative cells with their PartitionSpecs — the same code path the
512-device production dry-run uses (launch/dryrun.py)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    from jax.sharding import NamedSharding

    from repro.launch.steps import build_cell

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    jax.set_mesh(mesh)
    cells = [
        ("qwen2-7b", "train_4k"),
        ("deepseek-v2-lite-16b", "decode_32k"),
        ("pna", "full_graph_sm"),
        ("deepfm", "retrieval_cand"),
    ]
    for arch, shape in cells:
        prog = build_cell(arch, shape, smoke=True, multi_pod=False)
        in_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, s), prog.in_specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
        )
        with mesh:
            compiled = jax.jit(prog.fn, in_shardings=in_sh).lower(
                *prog.abstract_inputs
            ).compile()
        assert compiled.cost_analysis() is not None
        print("OK", arch, shape)
    """
)


@pytest.mark.slow
def test_dryrun_cells_compile_on_8dev(tmp_path):
    script = tmp_path / "dryrun_small.py"
    script.write_text(_SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    out = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        env=env, timeout=900,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert out.stdout.count("OK") == 4
