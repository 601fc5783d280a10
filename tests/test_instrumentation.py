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


def test_every_kernel_of_the_unified_program_has_a_phase_scope():
    n, cap, lanes, window = 48, 256, 8, 128
    z = jnp.zeros
    args = (z(cap, jnp.int32), jnp.ones(cap, jnp.int32), z(cap, bool),
            z(n, jnp.int32), z(n, jnp.int64), jnp.int32(0),
            z(lanes, jnp.int32), jnp.ones(lanes, jnp.int32), z(lanes, bool),
            z(lanes, jnp.int32), jnp.ones(lanes, jnp.int32), z(lanes, bool))
    text = apply_batch.lower(*args, n, n + 2, window).compile().as_text()
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
    # bisection steps exist on a weighted path with a mesh axis only:
    # the one-device weighted program takes each round's h-index from
    # one sort, and counts those rounds
    for name in ("host", "unified", "weighted"):
        assert int(st[name].remove_bisect_steps) == 0, name
        assert int(st[name].insert_bisect_steps) == 0, name
    for name in ("host", "unified"):
        assert st[name].hindex_sorts is None, name
    wt = st["weighted"]
    assert int(wt.hindex_sorts) == \
        int(wt.remove_rounds) + int(wt.insert_rounds) > 0
    # the unified program still runs its one promotion round with no
    # seeds: one forward wave and one evict wave, each a real pass
    u = st["unified"]
    assert (int(u.insert_rounds), int(u.forward_waves),
            int(u.evict_waves)) == (1, 1, 1)
    # and an empty batch runs nothing at all
    e = ms["unified"].apply_batch()
    assert int(e.forward_waves) == int(e.evict_waves) == 0


@pytest.mark.parametrize("config", [{}, dict(engine="sharded"),
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


def _replay_weighted_fixpoint(n, live, start):
    """A plain NumPy replay of the weighted fixpoint on the ``(lo, hi)
    -> weight`` edge set ``live``, from the upper bound ``start``: each
    round bisects every vertex's weighted h-index in lockstep, ``mid =
    (lo + hi + 1) // 2`` kept where the weight to neighbours of core >=
    mid reaches mid, until no lane moves; rounds run until no core
    drops. Returns ``(cores, steps, rounds)``, the steps summed over the
    rounds (the last, unchanging round's included)."""
    e = np.asarray(sorted(live), dtype=np.int64).reshape(-1, 2)
    w = np.asarray([live[k] for k in sorted(live)], dtype=np.int64)
    u, v = e[:, 0], e[:, 1]
    core, steps, rounds = np.asarray(start, dtype=np.int64), 0, 0
    while True:
        rounds += 1
        lo, hi = np.zeros(n, np.int64), np.maximum(core, 0)
        while (lo < hi).any():
            mid = (lo + hi + 1) // 2
            s = (np.bincount(u, w * (core[v] >= mid[u]), minlength=n)
                 + np.bincount(v, w * (core[u] >= mid[v]), minlength=n))
            ok = s >= mid
            lo, hi = np.where(ok, mid, lo), np.where(ok, hi, mid - 1)
            steps += 1
        new = np.minimum(core, lo)
        if (new == core).all():
            return core, steps, rounds
        core = new


@pytest.mark.parametrize("config", [
    {}, dict(engine="sharded"),
    # the halo program on a degenerate (1, 1) mesh: its own loops
    dict(engine="sharded", vertex_sharding="halo"),
], ids=["unified", "sharded", "halo"])
def test_bisection_steps_match_a_numpy_replay(config):
    g = erdos_renyi(30, 110, seed=4)
    rng = np.random.default_rng(11)
    w0 = rng.integers(1, 9, g.m)
    m = CoreMaintainer.from_graph(g, capacity=4 * g.m + 64, weighted=True,
                                  weights=w0, **config)
    norm = [tuple(sorted(map(int, e))) for e in g.edge_array()]
    live = dict(zip(norm, map(int, w0)))
    steps = []
    for ev in churn_stream(g, 3, 10, seed=2, p_reinsert=0.5):
        iw = rng.integers(1, 9, len(ev.edges))
        core0 = m.cores()
        st = m.apply_batch(insert_edges=ev.edges, remove_edges=ev.removals,
                           insert_weights=iw)
        for e in ev.removals:
            live.pop(tuple(sorted(map(int, e))), None)
        core1, rm_steps, rm_rounds = _replay_weighted_fixpoint(
            g.n, live, core0)
        total_w = 0  # the weight the batch really inserted
        for e, wt in zip(ev.edges, iw):
            k = tuple(sorted(map(int, e)))
            if k[0] != k[1] and k not in live:
                live[k] = int(wt)
                total_w += int(wt)
        core2, ins_steps, ins_rounds = _replay_weighted_fixpoint(
            g.n, live, core1 + total_w)
        np.testing.assert_array_equal(m.cores(), core2)
        assert int(st.forward_waves) == int(st.evict_waves) == 0
        assert (int(st.remove_rounds), int(st.insert_rounds)) == \
            (rm_rounds, ins_rounds)
        if config:
            assert (int(st.remove_bisect_steps),
                    int(st.insert_bisect_steps)) == (rm_steps, ins_steps)
            assert int(st.hindex_sorts) == 0
        else:
            # one device: every round's h-index comes from one sort,
            # the replay's rounds with no bisection step
            assert (int(st.remove_bisect_steps),
                    int(st.insert_bisect_steps)) == (0, 0)
            assert int(st.hindex_sorts) == rm_rounds + ins_rounds
        steps.append(rm_steps + ins_steps)
    # what the replay counts on these seeded batches, and the sharded
    # and halo programs with it
    assert steps == [62, 107, 77]


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
