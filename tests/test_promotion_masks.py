"""The promotion rounds' FORWARD and EVICT waves read per-edge masks built
once per round (``graph_ops.WaveMasks``) instead of gathering core and
label on every wave.

Two pins: the masked rounds are bit-identical to the per-wave formulas
(kept here as the reference) on the single-device program and on the
edge-sharded replicated layout; and the lowered batch program's FORWARD
and EVICT loop bodies gather from boolean vertex vectors only, so the
core and label gathers cannot drift back into the loops."""
from __future__ import annotations

import os
import re
import subprocess
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import sample_absent

from repro.core import graph_ops as G
from repro.core.api import CoreMaintainer
from repro.core.engine import apply_batch
from repro.core.insert import promotion_fixpoint
from repro.core.oracle import bz_from_csr
from repro.core.order import place_block
from repro.graph.csr import add_edges_csr, remove_edges_csr
from repro.graph.generators import barabasi_albert, rmat

# one shape for every case, so each program compiles once
N, CAP, LANES = 256, 4096, 64
N_DEVICES = 4
OUTPUTS = ("core", "label", "rounds", "v_plus", "forward_waves",
           "evict_waves")


@partial(jax.jit, static_argnames=("n", "n_levels"))
def _reference_promotion(src, dst, valid, core, label, new_src, new_dst,
                         new_ok, hi, dout_same, n, n_levels):
    """The promotion rounds with every FORWARD and EVICT wave gathering
    core and label per edge, as the waves did before the masks."""
    i32 = jnp.int32

    def seg2(to_src, to_dst):
        return (jax.ops.segment_sum(to_src.astype(i32), src, num_segments=n)
                + jax.ops.segment_sum(to_dst.astype(i32), dst,
                                      num_segments=n))

    def forward(core, label, seed, hi, dout_same):
        def body(state):
            reach, passing, _, waves = state
            rp = reach & passing
            same = valid & (core[src] == core[dst])
            din = seg2(same & (label[dst] < label[src]) & rp[dst],
                       same & (label[src] < label[dst]) & rp[src])
            new_passing = (hi + dout_same + din) > core
            new_reach = reach | (din > 0)
            changed = (jnp.any(new_reach != reach)
                       | jnp.any(new_passing != passing))
            return new_reach, new_passing, changed, waves + 1

        init = (hi + dout_same) > core
        reach, passing, _, waves = jax.lax.while_loop(
            lambda s: s[2], body, (seed, init, jnp.bool_(True), i32(0)))
        return reach, passing, waves

    def evict(core, cand, hi):
        def body(state):
            cand, evict_round, rnd, _ = state
            same = valid & (core[src] == core[dst])
            support = hi + seg2(same & cand[dst], same & cand[src])
            new_cand = cand & (support > core)
            evict_round = jnp.where(cand & ~new_cand, rnd, evict_round)
            return new_cand, evict_round, rnd + 1, jnp.any(new_cand != cand)

        cand, evict_round, rnd, _ = jax.lax.while_loop(
            lambda s: s[3], body,
            (cand, jnp.zeros(n, i32), i32(1), jnp.bool_(True)))
        return cand, evict_round, rnd - 1

    def stats(core, label):
        same = valid & (core[src] == core[dst])
        hi = seg2(valid & (core[dst] > core[src]),
                  valid & (core[src] > core[dst]))
        dout = seg2(same & (label[dst] > label[src]),
                    same & (label[src] > label[dst]))
        return hi, dout

    def round_body(state):
        core, label, _, prev, rounds, v_plus, hi, dout, fwd, ev = state
        e_src_lt = (core[new_src] < core[new_dst]) | (
            (core[new_src] == core[new_dst])
            & (label[new_src] < label[new_dst]))
        root = jnp.where(e_src_lt, new_src, new_dst)
        seed = jnp.zeros(n, i32).at[root].add(new_ok.astype(i32)) > 0
        seed = seed | ((hi + dout) > core) | prev
        reach, passing, f = forward(core, label, seed, hi, dout)
        cand0 = reach & passing
        cand, evict_round, e = evict(core, cand0, hi)
        new_core = core + cand.astype(i32)
        label = place_block(new_core, label, cand, at_head=True,
                            n_levels=n_levels)
        label = place_block(new_core, label, cand0 & ~cand, at_head=False,
                            n_levels=n_levels, round_key=evict_round)
        hi, dout = stats(new_core, label)
        return (new_core, label, jnp.any((hi + dout) > new_core), cand,
                rounds + 1, v_plus | reach, hi, dout, fwd + f, ev + e)

    z = i32(0)
    none = jnp.zeros(n, bool)
    core, label, _, _, rounds, v_plus, _, _, fwd, ev = jax.lax.while_loop(
        lambda s: s[2], round_body,
        (core, label, jnp.bool_(True), none, z, none, hi, dout_same, z, z))
    return core, label, rounds, v_plus, fwd, ev


@partial(jax.jit, static_argnames=("n", "n_levels"))
def _masked_promotion(src, dst, valid, core, label, new_src, new_dst, new_ok,
                      hi, dout_same, n, n_levels):
    core, label, rounds, v_plus, _, fwd, ev = promotion_fixpoint(
        src, dst, valid, core, label, new_src, new_dst, new_ok, hi,
        dout_same, n, n_levels)
    return core, label, rounds, v_plus, fwd, ev


def _sharded_promotion(inputs, n_devices):
    """``promotion_fixpoint`` under ``shard_map`` over edge shards with
    the replicated vertex layout, as the sharded engine runs it."""
    from jax import shard_map
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from repro.core.vertex_layout import make_layout

    mesh = Mesh(np.asarray(jax.devices()[:n_devices]), ("e",))
    layout = make_layout("replicated", N, "e")

    def kernel(src, dst, valid, core, label, nu, nv, nok, hi, dout):
        core, label, rounds, v_plus, _, fwd, ev = promotion_fixpoint(
            src, dst, valid, core, label, nu, nv, nok, hi, dout, N, N + 2,
            layout=layout)
        return core, label, rounds, v_plus, fwd, ev

    run = jax.jit(shard_map(
        kernel, mesh=mesh,
        in_specs=(P("e"),) * 3 + (P(),) * 7, out_specs=(P(),) * 6,
        check_vma=False,
    ))
    return run(*(inputs[k] for k in _ARGS))


_ARGS = ("src", "dst", "valid", "core", "label", "new_src", "new_dst",
         "new_ok", "hi", "dout_same")
CASES = [(graph, batch) for graph in ("ba", "rmat")
         for batch in ("insert", "mixed")]


def _case(graph: str, batch: str):
    """A maintained state with a pending batch written into the table:
    the inputs of ``promotion_fixpoint`` and the final graph.

    ``ba``: preferential attachment, nearly every vertex at one core
    number, so ties in core are everywhere; ``rmat``: skewed degrees,
    many core levels. ``insert``: a full lane batch of fresh edges;
    ``mixed``: a removal batch first, then half of the removed edges
    re-inserted with as many fresh ones."""
    seed = CASES.index((graph, batch))
    g = (barabasi_albert(N, deg=8, seed=seed) if graph == "ba"
         else rmat(8, 2048, seed=seed))
    m = CoreMaintainer.from_graph(g, capacity=CAP)
    rng = np.random.default_rng(seed + 11)
    cur = g
    if batch == "mixed":
        edges = g.edge_array()
        rm = edges[rng.choice(edges.shape[0], size=LANES // 2,
                              replace=False)]
        m.apply_batch(remove_edges=rm)
        cur = remove_edges_csr(g, rm)
        ins = np.concatenate([rm[: LANES // 4],
                              sample_absent(cur, rng, LANES // 4)])
    else:
        ins = sample_absent(cur, rng, LANES)
    cur = add_edges_csr(cur, ins)

    src, dst, valid = (np.asarray(a).copy() for a in (m.src, m.dst, m.valid))
    assert src.shape[0] == CAP
    slots = np.flatnonzero(~valid)[: ins.shape[0]]
    src[slots], dst[slots], valid[slots] = ins[:, 0], ins[:, 1], True
    pad = LANES - ins.shape[0]
    new_src = np.concatenate([ins[:, 0], np.zeros(pad, np.int64)])
    new_dst = np.concatenate([ins[:, 1], np.ones(pad, np.int64)])
    new_ok = np.arange(LANES) < ins.shape[0]
    core = np.asarray(m.core)
    label = np.asarray(m.label)
    assert core.shape == (N,)
    hi, dout_same = G.hi_and_dout_same(
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(valid),
        jnp.asarray(core), jnp.asarray(label), N)
    inputs = dict(
        src=src.astype(np.int32), dst=dst.astype(np.int32), valid=valid,
        core=core, label=label, new_src=new_src.astype(np.int32),
        new_dst=new_dst.astype(np.int32), new_ok=new_ok,
        hi=np.asarray(hi), dout_same=np.asarray(dout_same),
    )
    return inputs, cur


@pytest.fixture(scope="module")
def cases():
    return {c: _case(*c) for c in CASES}


def _run_sharded_child(inputs_path: str, out_path: str) -> None:
    """Entry point of the forced-device child process."""
    assert len(jax.devices()) >= N_DEVICES, jax.devices()
    z = np.load(inputs_path)
    out = {}
    for graph, batch in CASES:
        inputs = {k: z[f"{graph}-{batch}/{k}"] for k in _ARGS}
        for name, val in zip(OUTPUTS, _sharded_promotion(inputs, N_DEVICES)):
            out[f"{graph}-{batch}/{name}"] = np.asarray(val)
    np.savez(out_path, **out)


@pytest.fixture(scope="module")
def sharded_outputs(cases, tmp_path_factory):
    """One child process on ``N_DEVICES`` forced CPU devices runs every
    case under the sharded replicated layout."""
    d = tmp_path_factory.mktemp("promotion_masks")
    inputs_path, out_path = str(d / "inputs.npz"), str(d / "out.npz")
    np.savez(inputs_path, **{f"{g}-{b}/{k}": v
                             for (g, b), (inputs, _) in cases.items()
                             for k, v in inputs.items()})
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={N_DEVICES}")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "..", "src"), here])
    code = ("import sys, test_promotion_masks as t; "
            "t._run_sharded_child(sys.argv[1], sys.argv[2])")
    proc = subprocess.run(
        [sys.executable, "-c", code, inputs_path, out_path],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    z = np.load(out_path)
    return {c: [z[f"{c[0]}-{c[1]}/{name}"] for name in OUTPUTS]
            for c in CASES}


@pytest.mark.parametrize("engine", ["lax", "sharded"])
@pytest.mark.parametrize("graph,batch", CASES)
def test_masked_waves_match_the_per_wave_reference(graph, batch, engine,
                                                    cases, request):
    inputs, final_graph = cases[(graph, batch)]
    args = [jnp.asarray(inputs[k]) for k in _ARGS]
    want = [np.asarray(x) for x in _reference_promotion(*args, N, N + 2)]
    if engine == "lax":
        got = [np.asarray(x) for x in _masked_promotion(*args, N, N + 2)]
    else:
        got = request.getfixturevalue("sharded_outputs")[(graph, batch)]
    for name, w, g in zip(OUTPUTS, want, got):
        np.testing.assert_array_equal(g, w, err_msg=name)
    core, rounds, fwd, ev = got[0], int(got[2]), int(got[4]), int(got[5])
    np.testing.assert_array_equal(core, bz_from_csr(final_graph))
    # the batch promotes, and some round runs more than one wave of a loop
    assert (core > inputs["core"]).any()
    assert fwd + ev > 2 * rounds


# -- the hoist, pinned in the lowered program --------------------------------

_COMP = re.compile(r"^(?:ENTRY )?([\w.\-]+) \{$")
_INSTR = re.compile(r"^\s*(?:ROOT )?([\w.\-]+) = (.*?) ([a-z][a-z0-9-]*)"
                    r"\(([^)]*)\)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_RUNS = re.compile(r"(?:body|condition|true_computation|"
                   r"false_computation)=([\w.\-]+)")
_CALL = re.compile(r"to_apply=([\w.\-]+)")


def _computations(hlo_text: str):
    """``{computation: [(name, result type, opcode, operands, line)]}``."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            operands = [o.strip() for o in m.group(4).split(",")]
            cur.append((m.group(1), m.group(2), m.group(3), operands, line))
    return comps


def _loop_gathers(comps, body: str):
    """The operand types of every gather the loop body runs, through the
    calls and nested loops it makes."""
    out, todo, seen = [], [body], set()
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        types = {ins: rtype for ins, rtype, _, _, _ in comps[name]}
        for _, _, opcode, operands, line in comps[name]:
            if opcode == "gather":
                out.append(types[operands[0]])
            todo += _RUNS.findall(line)
            if opcode == "call":
                todo += _CALL.findall(line)
    return out


def test_wave_loops_gather_only_boolean_vertex_vectors():
    n, cap, lanes, window = 48, 256, 8, 128
    z = jnp.zeros
    args = (z(cap, jnp.int32), jnp.ones(cap, jnp.int32), z(cap, bool),
            z(n, jnp.int32), z(n, jnp.int64), jnp.int32(0),
            z(lanes, jnp.int32), jnp.ones(lanes, jnp.int32), z(lanes, bool),
            z(lanes, jnp.int32), jnp.ones(lanes, jnp.int32), z(lanes, bool))
    text = apply_batch.lower(*args, n, n + 2, window,
                             kernel_backend="lax").as_text(
        dialect="hlo", debug_info=True)
    comps = _computations(text)
    loops = {}
    for instrs in comps.values():
        for _, _, opcode, _, line in instrs:
            op_name = _OP_NAME.search(line)
            if opcode != "while" or not op_name:
                continue
            for phase in ("forward", "evict"):
                if op_name.group(1).endswith(
                        f"coremaint.promote.{phase}/while"):
                    loops.setdefault(phase, []).append(
                        re.search(r"body=([\w.\-]+)", line).group(1))
    assert set(loops) == {"forward", "evict"}, loops
    for phase, bodies in loops.items():
        for body in bodies:
            gathers = _loop_gathers(comps, body)
            # rp[src], rp[dst] (forward) or cand[dst], cand[src] (evict):
            # boolean vertex vectors of this wave, nothing of core or label
            assert len(gathers) == 2, (phase, gathers)
            assert all(t == f"pred[{n}]{{0}}" for t in gathers), (
                phase, gathers)
