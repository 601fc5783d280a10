"""Memory auditor (repro.analysis.memory): seeded violations + runtime
cross-checks.

Each rule the memory auditor adds is proven to FIRE on a hand-seeded
violation (naming the offending primitive/path), and the d=1 symbolic
formulas are validated against real buffer sizes and the compiled
program's ``memory_analysis()`` — the liveness model is static, so this
is the one place its numbers meet actual allocations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.analysis import (
    AuditParams,
    EngineConfig,
    TracedEngine,
    generate_memory_section,
    load_budget,
    profile_program,
    replicated_vertex_sites,
    trace_engine,
)
from repro.analysis.memory import STATE_ARGS, body_arg_map
from repro.analysis.rules import eval_formula, run_rules


def _mini_traced(config=None, programs=None, donated=None, sizes=None):
    cfg = config or EngineConfig("seeded", "unified")
    return TracedEngine(
        config=cfg, params=AuditParams(n=8, capacity=32, lanes=4),
        n_devices=1, window=16, frontier_cap=0,
        programs=programs or {}, lowered={}, donated=donated or {},
        rounds={},
        sizes=sizes or dict(n=8, d=1, cap=0, n_owned=8, n_pad=8,
                            lanes=4, window=16, local_cap=32),
    )


def _memory_findings(traced, section):
    return run_rules(traced, {"memory": section},
                     names=["memory_budget"])["memory_budget"]


# -- the liveness pass itself ----------------------------------------------

def test_profile_donation_frees_inputs_early():
    """A donated input dies at its last use; a retained one is pinned to
    the end — the difference is exactly the input's bytes."""
    x = jnp.zeros(1024, jnp.float32)
    jx = jax.make_jaxpr(lambda x: (x + 1.0) * 2.0)(x)
    pinned = profile_program(jx, donated=())
    freed = profile_program(jx, donated=(0,))
    assert pinned.point_bytes[-1] - freed.point_bytes[-1] == x.nbytes
    assert freed.peak < pinned.peak or freed.peak == pinned.peak


def test_profile_while_round_points_tagged():
    """Points inside a lax.while_loop body are the per-round working
    set; round_peak must come from them and only them."""
    def f(x):
        return jax.lax.while_loop(
            lambda c: c[1] < 4,
            lambda c: (c[0] * 2, c[1] + 1),
            (x, jnp.int32(0)),
        )

    jx = jax.make_jaxpr(f)(jnp.zeros(256, jnp.float32))
    prof = profile_program(jx)
    assert any(prof.in_round)
    assert not all(prof.in_round)
    assert prof.round_peak <= prof.peak
    assert prof.round_peak == max(
        b for b, r in zip(prof.point_bytes, prof.in_round) if r
    )


def test_while_carry_not_double_counted():
    """The while body's returned carry aliases the loop's output — a
    body that only rescales a big carry must not cost two copies of it
    at the loop boundary."""
    big = 1 << 20

    def f(x):
        return jax.lax.while_loop(
            lambda c: c[1] < 4, lambda c: (c[0] * 2, c[1] + 1),
            (x, jnp.int32(0)),
        )

    jx = jax.make_jaxpr(f)(jnp.zeros(big, jnp.float32))
    prof = profile_program(jx, donated=(0,))
    # x + one temp inside the body = 2 copies; 3 would mean the carry
    # out-alias was dropped
    assert prof.peak < 3 * big * 4


# -- seeded violations: each rule must fire, naming the offender ------------

def test_seeded_undonated_vertex_sized_output_fires():
    """require_state_donated: a vertex-sized output that aliases no
    donated input is a hidden per-batch copy — the rule names it."""
    jx = jax.make_jaxpr(lambda x: x * 2)(jnp.zeros(8, jnp.int32))
    traced = _mini_traced(programs={"apply_batch": jx},
                          donated={"apply_batch": ()})
    section = generate_memory_section(traced)
    assert section["require_state_donated"] is True
    finds = _memory_findings(traced, section)
    [f] = [f for f in finds if "aliases no donated input" in f.message]
    assert f.program == "apply_batch"
    assert "int32[8]" in f.message
    # donating the input clears it
    traced_ok = _mini_traced(programs={"apply_batch": jx},
                             donated={"apply_batch": (0,)})
    section_ok = generate_memory_section(traced_ok)
    assert not [f for f in _memory_findings(traced_ok, section_ok)
                if "aliases no donated" in f.message]


def test_seeded_replicated_vertex_buffer_fires():
    """forbid_replicated_vertex_buffers: a 1-D all_gather that
    materializes >= n elements inside the shard_map body is refused at
    generation time (the halo refactor deleted the waiver mechanism —
    there is nothing left to excuse it) and flagged by the check rule
    when such a program is audited against a clean committed section."""
    mesh = jax.make_mesh((1,), ("data",))
    sm = shard_map(lambda x: jax.lax.all_gather(x, "data", tiled=True),
                   mesh=mesh, in_specs=(P("data"),), out_specs=P(),
                   check_vma=False)
    jx = jax.make_jaxpr(sm)(jnp.zeros(8, jnp.int32))
    cfg = EngineConfig("seeded_range", "sharded", vertex_sharding="range")
    traced = _mini_traced(config=cfg, programs={"apply_batch": jx},
                          donated={"apply_batch": (0,)})
    assert [elems for _, elems in
            replicated_vertex_sites(jx, 8)] == [8]
    with pytest.raises(RuntimeError, match="replicated"):
        generate_memory_section(traced)
    # the check rule fires too: audit the offending trace against the
    # section a CLEAN program commits (same shapes, no gather)
    clean_sm = shard_map(lambda x: x + 1, mesh=mesh,
                         in_specs=(P("data"),), out_specs=P("data"),
                         check_vma=False)
    clean = jax.make_jaxpr(clean_sm)(jnp.zeros(8, jnp.int32))
    clean_traced = _mini_traced(config=cfg,
                                programs={"apply_batch": clean},
                                donated={"apply_batch": (0,)})
    section = generate_memory_section(clean_traced)
    assert section["forbid_replicated_vertex_buffers"] is True
    assert section["waivers"] == []
    finds = _memory_findings(traced, section)
    [f] = [f for f in finds if "O(n)-replicated" in f.message]
    assert "all_gather" in f.message and "no committed waiver" in f.message


def test_seeded_stale_waiver_fires():
    """A waiver whose site no longer traces is stale — silently keeping
    it would let a future regression hide behind a dead exemption."""
    jx = jax.make_jaxpr(lambda x: x + 1)(jnp.zeros(8, jnp.int32))
    cfg = EngineConfig("seeded_range", "sharded", vertex_sharding="range")
    traced = _mini_traced(config=cfg, programs={"apply_batch": jx},
                          donated={"apply_batch": (0,)})
    section = generate_memory_section(traced)
    section["waivers"] = [{"program": "apply_batch", "op": "all_gather",
                           "in_round": False, "count": 2,
                           "reason": "gone"}]
    finds = _memory_findings(traced, section)
    assert any("stale waiver" in f.message for f in finds)


def test_seeded_wrong_peak_formula_fires():
    jx = jax.make_jaxpr(lambda x: x + 1)(jnp.zeros(8, jnp.int32))
    traced = _mini_traced(programs={"apply_batch": jx},
                          donated={"apply_batch": (0,)})
    section = generate_memory_section(traced)
    section["programs"]["apply_batch"]["peak"] = "1"
    finds = _memory_findings(traced, section)
    assert any("peak live bytes drifted" in f.message for f in finds)


def test_missing_memory_section_fires_with_regenerate_hint():
    traced = _mini_traced(programs={}, donated={})
    finds = run_rules(traced, {}, names=["memory_budget"])["memory_budget"]
    [f] = finds
    assert "no memory section" in f.message
    assert "--write-budgets" in f.message


# -- the committed manifests ------------------------------------------------

@pytest.mark.parametrize(
    "engine", ["vertex_range", "frontier_sparse", "vertex_halo"])
def test_committed_range_engines_pass_unwaived(engine):
    """The halo refactor deleted the entry core/label gather — every
    range/halo manifest now enforces the replicated-buffer rule with an
    EMPTY waiver list (a reappearing gather fails generation outright,
    so no silent re-waiving is possible)."""
    mem = load_budget(engine)["memory"]
    assert mem["forbid_replicated_vertex_buffers"] is True
    assert mem["waivers"] == []


def test_committed_replicated_engines_have_no_waivers():
    for engine in ("host", "unified", "sharded"):
        mem = load_budget(engine)["memory"]
        assert mem["forbid_replicated_vertex_buffers"] is False
        assert mem["waivers"] == []


# -- d=1 formulas vs actual buffers -----------------------------------------

def test_at_rest_formulas_match_real_buffer_sizes_exactly():
    """Every at_rest formula in the committed unified manifest equals —
    to the byte — the nbytes of the concrete state array the engine
    actually carries at that argument position."""
    traced = trace_engine("unified")
    mem = load_budget("unified")["memory"]["programs"]["apply_batch"]
    env = traced.sizes
    state = {
        "src": jnp.zeros(traced.params.capacity, jnp.int32),
        "dst": jnp.zeros(traced.params.capacity, jnp.int32),
        "valid": jnp.zeros(traced.params.capacity, bool),
        "core": jnp.zeros(traced.params.n, jnp.int32),
        "label": jnp.zeros(traced.params.n, jnp.int64),
        "n_edges": jnp.int32(0),
    }
    at_rest = dict(mem["at_rest"])
    assert set(at_rest) == set(state)
    for name, arr in state.items():
        assert eval_formula(at_rest[name], env) == arr.nbytes, name


def test_donated_formula_matches_compiled_alias_bytes_exactly():
    """XLA's own donation accounting agrees with the symbolic credit:
    the compiled unified batch program aliases exactly the bytes the
    manifest's ``donated`` formula predicts."""
    traced = trace_engine("unified")
    mem = load_budget("unified")["memory"]["programs"]["apply_batch"]
    ma = traced.lowered["apply_batch"].compile().memory_analysis()
    assert (eval_formula(mem["donated"], traced.sizes)
            == ma.alias_size_in_bytes)


def test_peak_formula_bounds_compiled_memory_analysis():
    """The symbolic peak is an UN-FUSED upper bound: it must cover the
    compiled program's actual residency (args + outputs + temps -
    aliased), and stay within 8x of it — XLA's fusion collapses
    elementwise chains the jaxpr-level model counts individually, and a
    looser ratio would mean the model stopped tracking real buffers."""
    traced = trace_engine("unified")
    mem = load_budget("unified")["memory"]["programs"]["apply_batch"]
    model = eval_formula(mem["peak"], traced.sizes)
    ma = traced.lowered["apply_batch"].compile().memory_analysis()
    measured = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert measured <= model <= 8 * measured


def test_sharded_state_args_resolve_through_body_arg_map():
    """shard_map prepends hoisted constants to its body invars; the
    outer->body argument map must still land every STATE_ARGS position
    on the owned per-device shard of the right array."""
    traced = trace_engine("vertex_range")
    closed = traced.programs["apply_batch"]
    amap = body_arg_map(closed)
    from repro.analysis.memory import program_body

    body = program_body(closed)
    env = traced.sizes
    expect = {
        "src": ("int32", env["local_cap"]),
        "dst": ("int32", env["local_cap"]),
        "valid": ("bool", env["local_cap"]),
        "core": ("int32", env["n_owned"]),
        "label": ("int64", env["n_owned"]),
        "n_edges": ("int32", None),
    }
    for name, pos in STATE_ARGS["apply_batch"]:
        aval = body.invars[amap[pos]].aval
        dtype, dim = expect[name]
        assert str(aval.dtype) == dtype, name
        assert (aval.shape == () if dim is None
                else aval.shape == (dim,)), name


@pytest.mark.slow
def test_memory_audit_passes_for_all_committed_engines():
    from repro.analysis import audit_engines
    from repro.analysis.programs import ENGINE_CONFIGS

    report = audit_engines(sorted(ENGINE_CONFIGS),
                           rules=["memory_budget"])
    failing = [c for c in report["checks"] if not c["ok"]]
    assert report["ok"], failing
