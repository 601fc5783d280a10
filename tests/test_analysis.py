"""Tests of the static-analysis subsystem (repro/analysis/).

Two layers:

* seeded violations — for EACH audit rule, a minimal program built to
  violate exactly that invariant, proving the rule actually fires and
  that its finding names the offending primitive / program / round
  (a rule that can't fail guards nothing);
* the real engines — the committed budget manifests must hold on the
  current device count, api.py must pass the host-sync lint clean, and
  the walker/formula plumbing must round-trip.
"""
import json
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.analysis import (
    AuditParams,
    EngineConfig,
    Finding,
    TracedEngine,
    audit_engines,
    check_bench,
    eval_formula,
    generate_budget,
    guess_formula,
    iter_sites,
    lint_file,
    make_check,
    make_report,
    primitive_names,
    run_rules,
    tainted_truncations,
    trace_engine,
)
from repro.analysis.programs import trace_removal_round


# -- fixtures ---------------------------------------------------------------

def _mini_traced(config=None, window=16, fcap=0, sizes=None, **fields):
    """A hand-built TracedEngine around seeded programs — small enough
    that each rule test states its whole world explicitly."""
    cfg = config or EngineConfig("seeded", "unified")
    params = AuditParams(n=8, capacity=32, lanes=4)
    base = dict(programs={}, lowered={}, donated={}, rounds={})
    base.update(fields)
    return TracedEngine(
        config=cfg, params=params, n_devices=1, window=window,
        frontier_cap=fcap,
        sizes=sizes or dict(n=8, d=1, cap=fcap, n_owned=8, n_pad=8,
                            lanes=4, window=window, local_cap=32),
        **base,
    )


def _budget(**over):
    b = {
        "program_collectives": {},
        "rounds": {},
        "forbid_round_vertex_psum": False,
        "donated_args": {},
        "max_callback_primitives": 0,
        "max_tainted_truncations": 0,
        "max_jit_variants": 99,
        "large_output_bytes": 1024,
        "require_large_outputs_donated": False,
    }
    b.update(over)
    return b


def _run(traced, budget, rule):
    return run_rules(traced, budget, names=[rule])[rule]


# -- seeded violations: each rule must fire, naming the offender ------------

def test_seeded_collective_budget_histogram_drift():
    """A program whose collective histogram doesn't match the manifest
    fires with both the budgeted and the observed counts."""
    mesh = jax.make_mesh((1,), ("data",))
    sm = shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                   in_specs=(P(),), out_specs=P(), check_vma=False)
    jx = jax.make_jaxpr(sm)(jnp.zeros(8, jnp.int32))
    traced = _mini_traced(programs={"apply_batch": jx})
    budget = _budget(program_collectives={"apply_batch": {"psum": 2}})
    [f] = _run(traced, budget, "collective_budget")
    assert f.program == "apply_batch"
    assert "psum" in f.message and "drifted" in f.message


def test_seeded_collective_budget_vertex_psum_in_round():
    """The forbid_round_vertex_psum guarantee: a vertex-sized psum
    inside a while-loop body is flagged, naming the primitive, its
    size, and where it sits."""
    mesh = jax.make_mesh((1,), ("data",))
    n = 8

    def kernel(x):
        def body(c):
            return jax.lax.psum(c, "data") + 1

        return jax.lax.while_loop(lambda c: c[0] < 10, body, x)

    sm = shard_map(kernel, mesh=mesh, in_specs=(P(),), out_specs=P(),
                   check_vma=False)
    jx = jax.make_jaxpr(sm)(jnp.zeros(n, jnp.int32))
    traced = _mini_traced(programs={"apply_batch": jx})
    budget = _budget(
        program_collectives={"apply_batch": {"psum": 1}},
        forbid_round_vertex_psum=True,
    )
    finds = _run(traced, budget, "collective_budget")
    assert any("vertex-sized psum" in f.message
               and "while:body_jaxpr" in f.message for f in finds)


def test_seeded_collective_budget_round_op_mismatch():
    """A round whose budget lists the wrong collective fires naming BOTH
    ops and the round."""
    mesh = jax.make_mesh((1,), ("data",))
    log, jx = trace_removal_round("range", 8, 16, mesh)
    traced = _mini_traced(rounds={"removal_round": (log, jx)})
    budget = _budget(rounds={"removal_round": {
        "main": [{"op": "psum", "recv_bytes": "n * 3 * 4"},
                 {"op": "all_gather", "recv_bytes": "d * ceil_div(n_owned, 8)"}],
        "overflow": [],
    }})
    finds = _run(traced, budget, "collective_budget")
    assert any("removal_round" in f.message and "psum" in f.message
               and "reduce_scatter" in f.message for f in finds)


def test_seeded_traffic_cross_check_catches_a_lying_note():
    """If the trace-time accounting and the jaxpr disagree — here a
    tampered byte note — the cross-check inside collective_budget
    reports the exact collective."""
    import dataclasses as dc

    mesh = jax.make_mesh((1,), ("data",))
    log, jx = trace_removal_round("range", 8, 16, mesh)
    # tamper the setup entry regather (a reduce_scatter in the jaxpr)
    assert log[1].op == "regather"
    lied = [log[0], dc.replace(log[1], recv_bytes=log[1].recv_bytes + 4)]
    lied += log[2:]
    traced = _mini_traced(rounds={"removal_round": (lied, jx)})
    finds = _run(traced, _budget(), "collective_budget")
    assert any("cross-check" in f.message and "reduce_scatter" in f.message
               for f in finds)


def test_seeded_host_sync_callback_fires():
    def f(x):
        return jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )

    jx = jax.make_jaxpr(f)(jnp.zeros(4, jnp.float32))
    traced = _mini_traced(programs={"apply_batch": jx})
    finds = _run(traced, _budget(), "host_sync")
    assert finds and all("pure_callback" in f.message for f in finds)
    assert finds[0].program == "apply_batch"


def test_seeded_host_sync_undonated_large_output_fires():
    f = jax.jit(lambda x: x * 2)  # no donate_argnums
    x = jnp.zeros(256, jnp.int32)  # 1024B: at the threshold
    traced = _mini_traced(
        programs={"apply_batch": jax.make_jaxpr(lambda a: a * 2)(x)},
        lowered={"apply_batch": f.lower(x)},
    )
    budget = _budget(require_large_outputs_donated=True)
    [f_] = _run(traced, budget, "host_sync")
    assert "does not alias" in f_.message and "1024B" in f_.message


def test_seeded_donation_drift_fires():
    f = jax.jit(lambda x: x * 2)  # declares nothing donated
    x = jnp.zeros(256, jnp.int32)
    traced = _mini_traced(lowered={"apply_batch": f.lower(x)})
    budget = _budget(donated_args={"apply_batch": [0]})
    finds = _run(traced, budget, "donation")
    assert any("donated-arg set drifted" in f.message
               and "[0]" in f.message for f in finds)


def test_seeded_donation_passes_when_lowering_donates():
    f = jax.jit(lambda x: x * 2, donate_argnums=(0,))
    x = jnp.zeros(256, jnp.int32)
    traced = _mini_traced(lowered={"apply_batch": f.lower(x)})
    budget = _budget(donated_args={"apply_batch": [0]})
    assert _run(traced, budget, "donation") == []


def test_seeded_dtype_policy_sentinel_truncation_fires():
    """The exact corruption _require_x64 guards against: an int64
    sentinel pushed through an int32 convert."""
    def f(x):
        big = jnp.int64(1) << 62
        return (x + big).astype(jnp.int32)

    jx = jax.make_jaxpr(f)(jnp.zeros(4, jnp.int64))
    traced = _mini_traced(programs={"apply_batch": jx})
    finds = _run(traced, _budget(), "dtype_policy")
    assert finds and "convert_element_type" in finds[0].message
    assert "2**31" in finds[0].message


def test_taint_is_cut_at_booleans_and_sort_permutations():
    """The two precision cuts that keep the rule quiet on the real
    programs: comparing against a sentinel yields an untainted flag,
    and an argsort permutation never inherits its keys' taint — but
    the sorted KEYS themselves stay tainted."""
    big = jnp.int64(1) << 62

    def clean(x):
        flag = x == big                       # bool: taint dies here
        perm = jnp.argsort(x + big)           # keys tainted, perm not
        return (jnp.where(flag, 1, 0).astype(jnp.int32),
                perm.astype(jnp.int32))

    assert tainted_truncations(
        jax.make_jaxpr(clean)(jnp.zeros(4, jnp.int64))) == []

    def dirty(x):
        return jnp.sort(x + big).astype(jnp.int32)  # the keys column

    assert tainted_truncations(
        jax.make_jaxpr(dirty)(jnp.zeros(4, jnp.int64))) != []


def test_taint_propagates_through_while_carry():
    def f(x):
        big = jnp.int64(1) << 62

        def body(c):
            return c + big

        y = jax.lax.while_loop(lambda c: c[0] < 5, body, x)
        return y.astype(jnp.int32)

    assert tainted_truncations(
        jax.make_jaxpr(f)(jnp.zeros(4, jnp.int64))) != []


def test_seeded_recompile_surface_fires():
    """A manifest pinning fewer jit variants than the planner lattice
    reaches fires and prints the lattice."""
    traced = _mini_traced(
        config=EngineConfig("seeded", "sharded"),
        sizes=dict(n=64, d=1, cap=0, n_owned=64, n_pad=64, lanes=8,
                   window=16, local_cap=256),
    )
    finds = _run(traced, _budget(max_jit_variants=1), "recompile_surface")
    assert any("max_jit_variants=1" in f.message for f in finds)
    # a traced bucket outside the planner lattice is its own finding
    traced_off = _mini_traced(
        config=EngineConfig("seeded", "sharded"), window=7,
        sizes=dict(n=64, d=1, cap=0, n_owned=64, n_pad=64, lanes=8,
                   window=7, local_cap=256),
    )
    finds = _run(traced_off, _budget(max_jit_variants=99),
                 "recompile_surface")
    assert any("unplanned variant" in f.message for f in finds)


# -- walker / formula plumbing ---------------------------------------------

def test_walker_attributes_cond_branches():
    def f(p, x):
        return jax.lax.cond(p, lambda v: v + 1, lambda v: v - 1, x)

    jx = jax.make_jaxpr(f)(True, jnp.int32(1))
    branch_sites = [s for s in iter_sites(jx) if s.cond_branches]
    assert branch_sites, "no sites attributed to a cond branch"
    assert {s.cond_branches[0] for s in branch_sites} == {0, 1}
    assert "cond" in primitive_names(jx)


def test_eval_formula_restricted():
    env = dict(n=64, d=8, n_owned=8, cap=16)
    assert eval_formula("n_owned * 3 * 4", env) == 96
    assert eval_formula("d * (cap + 1) * 4", env) == 544
    assert eval_formula("d * ceil_div(n_owned, 8)", env) == 8
    assert eval_formula(42, env) == 42
    with pytest.raises(ValueError, match="unknown size name"):
        eval_formula("bogus + 1", env)
    with pytest.raises(ValueError):
        eval_formula("__import__('os')", env)


def test_guess_formula_prefers_structural_over_literal():
    env = dict(n=64, d=8, n_owned=8, n_pad=64, cap=16, lanes=8,
               window=16, local_cap=32)
    assert guess_formula(8 * 3 * 4, env) == "n_owned * 3 * 4"
    assert guess_formula(8 * 17 * 4, env) == "d * (cap + 1) * 4"
    assert guess_formula(1234567, env) == 1234567  # falls back literal


# -- hostlint ---------------------------------------------------------------

_LINT_FIXTURE = textwrap.dedent(
    """
    import numpy as np

    class M:
        def apply_batch(self):
            a = int(self.n_edges)
            b = self.core.block_until_ready()
            c = float(self.label[0])
            d = np.asarray(self.valid)
            e = self.n_edges.item()
            f = int(self.n_edges)  # sync: ok
            g = int(self.capacity)
            return a

        def _refresh_bounds(self):
            return int(self.n_edges)
    """
)


def test_hostlint_seeded_violations_fire(tmp_path):
    p = tmp_path / "fixture.py"
    p.write_text(_LINT_FIXTURE)
    finds = lint_file(str(p))
    msgs = [f.message for f in finds]
    assert len(finds) == 5, msgs
    assert all(f.func == "apply_batch" for f in finds)
    assert any("int(...)" in m for m in msgs)
    assert any("block_until_ready" in m for m in msgs)
    assert any("float(...)" in m for m in msgs)
    assert any("np.asarray" in m for m in msgs)
    assert any(".item()" in m for m in msgs)
    # the allowlisted line, the host-int call, and the amortized sync
    # point outside the sync-free set are all untouched
    allowed_lines = [i + 1 for i, line in
                     enumerate(_LINT_FIXTURE.splitlines())
                     if "# sync: ok" in line or "capacity" in line
                     or "_refresh_bounds" in line]
    assert not any(f.lineno in allowed_lines for f in finds)


def test_hostlint_real_api_is_clean():
    """The shipped planning path keeps its sync-free promise."""
    assert lint_file() == []


def test_hostlint_all_targets_clean():
    """The full lint surface — api.py plus the engine-level builders in
    core/engine.py, core/sharded.py, and the fixpoint builders in
    core/remove.py / core/insert.py — is sync-free."""
    from repro.analysis.hostlint import LINT_TARGETS

    for path in LINT_TARGETS:
        assert lint_file(path) == [], path


def test_hostlint_covers_fixpoint_builders():
    """Regression: the remove/insert fixpoint builders (including the
    weighted h-index passes and the halo twins) stay in the lint
    surface, and a host coercion of a weighted device parameter (the
    weight column, the halo working set) fires by bare name."""
    import os

    from repro.analysis.hostlint import (
        DEVICE_PARAMS,
        INSERT_PATH,
        LINT_TARGETS,
        REMOVE_PATH,
    )

    assert {"removal_fixpoint", "weighted_core_fixpoint_pass",
            "weighted_core_fixpoint_pass_halo"} \
        <= LINT_TARGETS[os.path.normpath(REMOVE_PATH)]
    assert {"promotion_fixpoint", "weighted_promotion_fixpoint",
            "weighted_promotion_fixpoint_halo", "freelist_alloc"} \
        <= LINT_TARGETS[os.path.normpath(INSERT_PATH)]
    assert {"w", "total_w", "src_h", "core_h"} <= DEVICE_PARAMS


def test_hostlint_weighted_param_coercion_fires(tmp_path):
    p = tmp_path / "remove_fixture.py"
    p.write_text(textwrap.dedent(
        """
        import numpy as np

        def weighted_core_fixpoint_pass(src, dst, valid, w, core, n):
            maxw = int(w)                 # device column: sync
            cap = int(w.shape[0])         # static aval metadata: fine
            tw = np.asarray(total_w)      # sync: ok  (reviewed)
            return core
        """
    ))
    finds = lint_file(
        str(p), funcs=frozenset({"weighted_core_fixpoint_pass"})
    )
    [f] = finds
    assert "int(...)" in f.message


def test_hostlint_bare_device_param_fires(tmp_path):
    """Engine-level helpers are free functions: device state is a bare
    parameter name, not self.<field> — the lint must still catch a host
    coercion of it (and leave static python ints alone)."""
    p = tmp_path / "engine_fixture.py"
    p.write_text(textwrap.dedent(
        """
        def batch_program(src, dst, valid, core, label, n_edges, n):
            rounds = int(n)           # static python int: fine
            width = bool(n_edges)     # device scalar: sync
            return core

        def helper_outside_set(core):
            return int(core)
        """
    ))
    finds = lint_file(str(p), funcs=frozenset({"batch_program"}))
    [f] = finds
    assert f.func == "batch_program"
    assert "bool(...)" in f.message


# -- benchcheck -------------------------------------------------------------

def test_benchcheck_flags_incoherent_artifact(tmp_path):
    from repro.analysis.benchcheck import BENCH_SCHEMA

    p = tmp_path / "bench.json"
    p.write_text(json.dumps({
        "schema": BENCH_SCHEMA,
        "engines_agree": False,
        "churn": {"engines_agree": True},
        "frontier_scaling": [{"frontier_exchange": "bitmask"}],
    }))
    check = check_bench(str(p))
    assert check["rule"] == "bench_coherence" and not check["ok"]
    msgs = [f["message"] for f in check["findings"]]
    assert any("engines diverged" in m for m in msgs)
    assert any("lacks 'vertex_sharded'" in m for m in msgs)
    assert any("n_devices" in m for m in msgs)
    assert any("not a sparse-frontier row" in m for m in msgs)


def test_benchcheck_launch_section(tmp_path):
    """The v3 launch checks: a pallas round with no pallas_call (silent
    fallback to the unfused path) and a pallas round that does not beat
    lax's launch count are both incoherent; a genuinely-fused strictly
    smaller section passes those checks."""
    from repro.analysis.benchcheck import BENCH_SCHEMA

    base = {
        "schema": BENCH_SCHEMA,
        "engines_agree": True,
        "churn": {"engines_agree": True},
        "pallas": {"batches_per_s": 3.0},
    }
    p = tmp_path / "bench.json"
    p.write_text(json.dumps({**base, "launches_per_round": {
        "lax": {"removal": {"gather": 2}, "promotion": {"gather": 4}},
        "pallas": {"removal": {"gather": 2},          # no pallas_call
                   "promotion": {"pallas_call": 1, "gather": 4}},
    }}))
    msgs = [f["message"] for f in check_bench(str(p))["findings"]]
    assert any("traces no pallas_call" in m for m in msgs)
    assert any("not strictly fewer" in m for m in msgs)

    p.write_text(json.dumps({**base, "launches_per_round": {
        "lax": {"removal": {"gather": 9}, "promotion": {"gather": 9}},
        "pallas": {"removal": {"pallas_call": 1, "scatter": 2},
                   "promotion": {"pallas_call": 3, "scatter": 2}},
    }}))
    msgs = [f["message"] for f in check_bench(str(p))["findings"]]
    assert not any("pallas" in m and "launch" in m for m in msgs)

    p.write_text(json.dumps(base))  # section absent entirely
    msgs = [f["message"] for f in check_bench(str(p))["findings"]]
    assert any("launches_per_round" in m for m in msgs)


def test_benchcheck_v4_sections(tmp_path):
    """The v4 coherence rules: interpret-mode pallas rows are excluded
    from speedup coherence (the launch-count claim stays), mesh_scaling
    rows must be halo rows whose [d_e, d_v] shape factorizes their
    device count, and the frontier autoplan must show the overflow
    fallback receding."""
    from repro.analysis.benchcheck import BENCH_SCHEMA

    base = {
        "schema": BENCH_SCHEMA,
        "engines_agree": True,
        "churn": {"engines_agree": True},
    }
    p = tmp_path / "bench.json"
    # interpret-mode pallas at a sub-1 speedup: NOT a finding; the same
    # row without the stamp demands a coherent speedup and flags both
    p.write_text(json.dumps({
        **base,
        "pallas": {"batches_per_s": 3.0, "interpret_mode": True},
        "speedup_pallas_vs_host": 0.4,
    }))
    msgs = [f["message"] for f in check_bench(str(p))["findings"]]
    assert not any("speedup_pallas" in m for m in msgs)
    p.write_text(json.dumps({
        **base,
        "pallas": {"batches_per_s": 3.0},
        "speedup_pallas_vs_host": 0.4,
    }))
    msgs = [f["message"] for f in check_bench(str(p))["findings"]]
    assert any("interpret_mode stamp" in m for m in msgs)
    assert any("speedup_pallas_vs_host is 0.40x" in m for m in msgs)
    # a timed non-interpret engine row below the host baseline
    p.write_text(json.dumps({
        **base,
        "vertex_halo": {"batches_per_s": 5.0},
        "speedup_vertex_halo_vs_host": 0.9,
    }))
    msgs = [f["message"] for f in check_bench(str(p))["findings"]]
    assert any("speedup_vertex_halo_vs_host is 0.90x" in m for m in msgs)
    # mesh_scaling rows: the shape must factorize the device count, and
    # only halo rows belong in the sweep
    p.write_text(json.dumps({
        **base,
        "mesh_scaling": [
            {"n_devices": 8, "mesh_shape": [4, 2],
             "vertex_sharding": "halo"},
            {"n_devices": 8, "mesh_shape": [4, 4],
             "vertex_sharding": "halo"},
            {"n_devices": 8, "mesh_shape": [2, 4],
             "vertex_sharding": "range"},
        ],
    }))
    msgs = [f["message"] for f in check_bench(str(p))["findings"]]
    assert not any("mesh_scaling[0]" in m for m in msgs)
    assert any("mesh_scaling[1]" in m and "factorizing" in m for m in msgs)
    assert any("mesh_scaling[2]" in m and "not a halo row" in m
               for m in msgs)
    # the autoplan section must show fewer overflow fallbacks after
    p.write_text(json.dumps({
        **base,
        "frontier_autoplan": {"overflow_rounds_before": 2,
                              "overflow_rounds_after": 5,
                              "blind_cap": 256, "tuned_cap": 512},
    }))
    msgs = [f["message"] for f in check_bench(str(p))["findings"]]
    assert any("did not reduce overflow" in m for m in msgs)
    p.write_text(json.dumps({
        **base,
        "frontier_autoplan": {"overflow_rounds_before": 9,
                              "overflow_rounds_after": 0,
                              "blind_cap": 256, "tuned_cap": 512},
    }))
    msgs = [f["message"] for f in check_bench(str(p))["findings"]]
    assert not any("overflow" in m for m in msgs)


def test_benchcheck_v5_sections(tmp_path):
    """The v5 coherence rules: the weighted row must have been timed,
    and the temporal sliding-window section must drain (insertions ==
    removals, all-zero final cores), agree across engines, carry a sane
    window/stride pair, and time every temporal engine."""
    from repro.analysis.benchcheck import BENCH_SCHEMA

    base = {
        "schema": BENCH_SCHEMA,
        "engines_agree": True,
        "churn": {"engines_agree": True},
    }
    p = tmp_path / "bench.json"
    p.write_text(json.dumps({
        **base,
        "weighted": {"batches_per_s": 0.0},
        "temporal": {
            "window": 6, "stride": 9,  # stride > window: gap, flagged
            "engines_agree": False,
            "total_insertions": 500, "total_removals": 480,
            "final_cores_zero": False,
            "host": {"batches_per_s": 2.0},
            # unified row missing entirely; sharded present but untimed
            "sharded": {"batches_per_s": 0.0},
            "weighted": {"batches_per_s": 1.0},
        },
    }))
    msgs = [f["message"] for f in check_bench(str(p))["findings"]]
    assert any("weighted.batches_per_s is not > 0" in m for m in msgs)
    assert any("temporal engines diverged" in m for m in msgs)
    assert any("did not drain" in m for m in msgs)
    assert any("final_cores_zero" in m for m in msgs)
    assert any("window/stride malformed" in m for m in msgs)
    assert any("lacks the 'unified' engine row" in m for m in msgs)
    assert any("temporal.sharded.batches_per_s is not > 0" in m
               for m in msgs)
    # a coherent v5 artifact raises none of the new findings
    p.write_text(json.dumps({
        **base,
        "weighted": {"batches_per_s": 4.0},
        "temporal": {
            "window": 6, "stride": 3,
            "engines_agree": True,
            "total_insertions": 500, "total_removals": 500,
            "final_cores_zero": True,
            "host": {"batches_per_s": 2.0},
            "unified": {"batches_per_s": 3.0},
            "sharded": {"batches_per_s": 1.0},
            "weighted": {"batches_per_s": 1.5},
        },
    }))
    msgs = [f["message"] for f in check_bench(str(p))["findings"]]
    assert not any("temporal" in m or "weighted" in m for m in msgs)


def test_benchcheck_missing_artifact_one_actionable_finding(tmp_path):
    """A missing BENCH_stream.json must produce ONE finding telling the
    user how to regenerate it — not a traceback, not a cascade of
    lacks-key noise."""
    check = check_bench(str(tmp_path / "nope.json"))
    assert not check["ok"]
    [f] = check["findings"]
    assert "no bench artifact" in f["message"]
    assert "benchmarks.run" in f["message"]


def test_benchcheck_stale_schema_one_actionable_finding(tmp_path):
    """An artifact predating the current schema stamp (e.g. recorded
    before max_frontier observability) is rejected with a single
    regenerate hint, even if its other fields look coherent."""
    p = tmp_path / "bench.json"
    p.write_text(json.dumps({
        "schema": "repro.analysis/bench/v1",
        "engines_agree": True,
        "churn": {"engines_agree": True},
    }))
    check = check_bench(str(p))
    assert not check["ok"]
    [f] = check["findings"]
    assert "predates the current artifact schema" in f["message"]
    assert "repro.analysis/bench/v1" in f["message"]
    assert "benchmarks.run" in f["message"]


def test_benchcheck_accepts_committed_artifact():
    import os

    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_stream.json")
    check = check_bench(path)
    assert check["ok"], check["findings"]


# -- report schema ----------------------------------------------------------

def test_report_schema_roundtrip():
    bad = Finding("collective_budget", "unified", "boom", "apply_batch")
    checks = [make_check("collective_budget", "unified", [bad]),
              make_check("donation", "unified", [])]
    report = make_report(checks, n_devices=1)
    assert report["schema"] == "repro.analysis/report/v1"
    assert report["ok"] is False
    assert report["checks"][0]["findings"][0]["message"] == "boom"
    assert json.loads(json.dumps(report)) == report  # JSON-serializable


# -- the real engines against the committed manifests ----------------------

def test_audit_passes_on_committed_budgets_fast_engines():
    """host + unified on the current device count — the full five-config
    matrix (including the sharded traces at 1 AND 8 devices) is gated by
    the CI analysis job via the CLI."""
    report = audit_engines(["host", "unified"])
    failing = [c for c in report["checks"] if not c["ok"]]
    assert report["ok"], failing


@pytest.mark.slow
def test_audit_passes_on_committed_budgets_all_engines():
    report = audit_engines(sorted(
        __import__("repro.analysis.programs",
                   fromlist=["ENGINE_CONFIGS"]).ENGINE_CONFIGS))
    failing = [c for c in report["checks"] if not c["ok"]]
    assert report["ok"], failing


@pytest.mark.slow
def test_generated_budget_matches_committed_manifest():
    """--write-budgets is reproducible: regenerating the unified
    manifest on this device count reproduces the committed one
    byte-for-byte (guards against drift between the generator and the
    checked-in files)."""
    from repro.analysis import load_budget

    traced = trace_engine("unified")
    fresh = generate_budget(traced)
    committed = load_budget("unified")
    fresh["generated_with"].pop("devices")
    committed["generated_with"].pop("devices")
    assert fresh == committed
