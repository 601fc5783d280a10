"""The batch program's own measurement: every compiled kernel of the
unified program runs under a ``coremaint.*`` phase scope (what the
benchmark's per-phase device-time split reads), and the FORWARD / EVICT
wave counters in ``BatchStats`` count the passes the promotion rounds
really make."""
import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import CoreMaintainer
from repro.core.engine import apply_batch
from repro.graph.generators import erdos_renyi
from repro.graph.stream import churn_stream

# the opcodes that carry device time in this program's traces
TIMED = ("fusion", "sort", "scatter", "gather", "while")
_COMP = re.compile(r"^(ENTRY )?%(\S+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*? ([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# computations an instruction runs as a unit of its own (loop bodies and
# conditions, branches, calls); fused computations, comparators and
# reducers run inside their caller
_RUNS = re.compile(r"(?:body|condition|true_computation|"
                   r"false_computation)=%([\w.-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_CALL = re.compile(r"to_apply=%([\w.-]+)")


def _executed_instructions(hlo_text: str):
    """``(name, opcode, op_name or None)`` of every instruction in a
    computation that runs as a unit: the entry and, transitively, the
    bodies, conditions, branches and call targets it names."""
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(2), [])
            if m.group(1):
                entry = m.group(2)
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            cur.append((m.group(1), m.group(2), line))
    out, todo, seen = [], [entry], set()
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for ins, opcode, line in comps[name]:
            op_name = _OP_NAME.search(line)
            out.append((ins, opcode, op_name.group(1) if op_name else None))
            todo += _RUNS.findall(line)
            for group in _BRANCHES.findall(line):
                todo += [b.strip().lstrip("%") for b in group.split(",")]
            if opcode == "call":
                todo += _CALL.findall(line)
    return out


@pytest.mark.parametrize("backend", ["lax", "pallas"])
def test_every_kernel_of_the_unified_program_has_a_phase_scope(backend):
    n, cap, lanes, window = 48, 256, 8, 128
    z = jnp.zeros
    args = (z(cap, jnp.int32), jnp.ones(cap, jnp.int32), z(cap, bool),
            z(n, jnp.int32), z(n, jnp.int64), jnp.int32(0),
            z(lanes, jnp.int32), jnp.ones(lanes, jnp.int32), z(lanes, bool),
            z(lanes, jnp.int32), jnp.ones(lanes, jnp.int32), z(lanes, bool))
    text = apply_batch.lower(*args, n, n + 2, window,
                             kernel_backend=backend).compile().as_text()
    rows = _executed_instructions(text)
    timed = [r for r in rows if r[1] in TIMED]
    assert sum(r[1] == "while" for r in timed) >= 5  # every fixpoint loop
    # an instruction with no op_name at all is the compiler's own (a
    # broadcast of a constant, a copy); every one that JAX traced carries
    # the scope of its phase
    unscoped = [r for r in timed if r[2] is not None
                and "/coremaint." not in r[2]]
    assert not unscoped, unscoped[:5]
    phases = {r[2].rsplit("/coremaint.", 1)[1].split("/")[0]
              for r in timed if r[2]}
    assert phases == {"table", "remove.stats", "promote.seed",
                      "promote.forward", "promote.evict", "promote.stats",
                      "labels"}


def _maintainers(**configs):
    g = erdos_renyi(30, 110, seed=4)
    return g, {name: CoreMaintainer.from_graph(g, capacity=4 * g.m + 64,
                                               **kw)
               for name, kw in configs.items()}


def test_wave_counters_on_a_removal_only_batch():
    g, ms = _maintainers(unified={}, host=dict(engine="host"),
                         weighted=dict(weighted=True))
    rm = g.edge_array()[:6]
    st = {name: m.apply_batch(remove_edges=rm) for name, m in ms.items()}
    for name in ("host", "weighted"):
        assert int(st[name].forward_waves) == 0, name
        assert int(st[name].evict_waves) == 0, name
    # the unified program still runs its one promotion round with no
    # seeds: one forward wave and one evict wave, each a real pass
    u = st["unified"]
    assert (int(u.insert_rounds), int(u.forward_waves),
            int(u.evict_waves)) == (1, 1, 1)
    # and an empty batch runs nothing at all
    e = ms["unified"].apply_batch()
    assert int(e.forward_waves) == int(e.evict_waves) == 0


@pytest.mark.parametrize("config", [{}, dict(kernel_backend="pallas"),
                                    dict(engine="sharded"),
                                    dict(engine="host")])
def test_wave_counters_cover_every_promotion_round(config):
    g, ms = _maintainers(m=config)
    m = ms["m"]
    ran = 0
    for ev in churn_stream(g, 4, 12, seed=6, p_reinsert=0.5):
        st = m.apply_batch(insert_edges=ev.edges, remove_edges=ev.removals)
        rounds = int(st.insert_rounds)
        # every promotion round runs at least one wave of each loop
        assert int(st.forward_waves) >= rounds
        assert int(st.evict_waves) >= rounds
        ran += rounds
    assert ran > 0


def test_weighted_engine_counts_no_waves():
    g, ms = _maintainers(w=dict(weighted=True))
    rng = np.random.default_rng(0)
    for ev in churn_stream(g, 3, 10, seed=2, p_reinsert=0.5):
        st = ms["w"].apply_batch(
            insert_edges=ev.edges, remove_edges=ev.removals,
            insert_weights=rng.integers(1, 4, len(ev.edges)))
        assert int(st.insert_rounds) > 0
        assert int(st.forward_waves) == int(st.evict_waves) == 0


def test_recent_calls_record_each_batch_and_its_program():
    import jax

    from repro.core import api

    g, ms = _maintainers(unified={}, host=dict(engine="host"))
    m, rm = ms["unified"], g.edge_array()[:6]
    st = m.apply_batch(remove_edges=rm, insert_edges=rm[:2])
    call = api.RECENT_CALLS[-1]
    assert call.stats is st
    compiles = []

    def on(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        text = call.compiled_text()
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    # the program the call ran, found in JAX's cache: no second compile
    assert not compiles
    assert "coremaint.promote.forward" in text
    # a call that ran no device program has no text
    m.apply_batch()
    ms["host"].apply_batch(remove_edges=rm)
    assert [c.compiled_text() for c in list(api.RECENT_CALLS)[-2:]] == \
        [None, None]
    assert api.RECENT_CALLS.maxlen == 64
