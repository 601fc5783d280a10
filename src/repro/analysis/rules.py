"""The audit rules — each one pins a structural invariant the engine
matrix's performance claims stand on, against a committed per-engine
budget manifest (``analysis/budgets/<engine>.json``).

Registry (``RULES``, decorated with ``@rule``):

* ``collective_budget`` — exact per-program collective histograms plus
  ordered per-round collective lists with closed-form payload sizes
  (``recv_bytes`` formulas in n/d/cap/…), and the no-vertex-sized-psum
  guarantee of the range layouts; also cross-checks the trace-time
  traffic accounting against the jaxpr (``cross_check_round``) so the
  §4.2/§4.3 traffic model can never silently drift from the program.
* ``host_sync`` — no host-callback primitive in any batch program, and
  every large output aliases a donated input in the lowered computation
  (a non-donated large output is a hidden per-batch copy).
* ``donation`` — the buffers the engines declare donated
  (``engine.DONATED_STATE_ARGS``) really are donated in the lowering
  AND carry a donation marker in the StableHLO (``tf.aliasing_output``
  pins, or ``jax.buffer_donor`` on multi-device lowerings).
* ``dtype_policy`` — int64 sentinel values (the ``1 << 62`` edge-key /
  tombstone sentinel) are never truncated through an int32 convert:
  value-taint analysis from big integer literals, cut at boolean
  outputs and paired through ``sort`` operands (so argsort index
  columns never inherit their keys' taint).
* ``launch_budget`` — the per-round kernel-launch histogram
  (gather/scatter/sort) stays pinned to the manifest.
* ``recompile_surface`` — the (window, frontier-cap) static bucket
  lattice the planners can reach stays within the manifest's jit
  variant bound (the class of mid-stream recompile that halved unified
  throughput before the pow2 bucketing).

Budget ``recv_bytes`` entries are FORMULA STRINGS (e.g.
``"n_owned * 3 * 4"``, ``"d * (cap + 1) * 4"``) evaluated in the traced
engine's size environment, so one committed manifest gates every device
count.
"""
from __future__ import annotations

import ast
import dataclasses
import operator
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.api import bucket_lattice
from ..core.vertex_layout import SPARSE_COND_BRANCHES, Traffic
from .walker import (
    COLLECTIVE_PRIMS,
    HOST_CALLBACK_PRIMS,
    CollectiveSite,
    collectives,
    count_collectives,
    count_round_launches,
    iter_sites,
)


@dataclasses.dataclass
class Finding:
    """One actionable violation: which rule, which engine config, which
    program/round, and a message naming the offending primitive."""

    rule: str
    engine: str
    message: str
    program: str = ""

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:  # pragma: no cover - cosmetic
        where = f" [{self.program}]" if self.program else ""
        return f"{self.rule}/{self.engine}{where}: {self.message}"


RULES: Dict[str, Callable] = {}


def rule(name: str):
    def deco(fn):
        RULES[name] = fn
        return fn
    return deco


def run_rules(traced, budget: dict,
              names: Optional[Sequence[str]] = None) -> Dict[str, List[Finding]]:
    """Run (a subset of) the registry against one traced engine; returns
    ``{rule_name: findings}`` (empty lists mean the rule passed)."""
    out: Dict[str, List[Finding]] = {}
    for name in (names or sorted(RULES)):
        out[name] = RULES[name](traced, budget)
    return out


# -- recv_bytes formula evaluation ----------------------------------------

def _ceil_div(a: int, b: int) -> int:
    return -(-int(a) // int(b))


_FORMULA_FUNCS = {"ceil_div": _ceil_div, "min": min, "max": max}
_BIN_OPS = {
    ast.Add: operator.add, ast.Sub: operator.sub, ast.Mult: operator.mul,
    ast.FloorDiv: operator.floordiv, ast.Mod: operator.mod,
}


def eval_formula(expr, env: Dict[str, int]) -> int:
    """Evaluate a budget size formula — integer arithmetic over the
    traced engine's size names (n, d, cap, n_owned, n_pad, window,
    lanes, local_cap) plus ceil_div/min/max. Anything else is a
    manifest error and raises."""
    if isinstance(expr, (int, np.integer)):
        return int(expr)

    def ev(node):
        if isinstance(node, ast.Expression):
            return ev(node.body)
        if isinstance(node, ast.Constant) and isinstance(node.value, int):
            return node.value
        if isinstance(node, ast.Name):
            if node.id in env:
                return int(env[node.id])
            raise ValueError(f"unknown size name {node.id!r} in formula")
        if isinstance(node, ast.BinOp) and type(node.op) in _BIN_OPS:
            return _BIN_OPS[type(node.op)](ev(node.left), ev(node.right))
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            return -ev(node.operand)
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id in _FORMULA_FUNCS and not node.keywords):
            return _FORMULA_FUNCS[node.func.id](*[ev(a) for a in node.args])
        raise ValueError(f"unsupported formula syntax: {ast.dump(node)}")

    return int(ev(ast.parse(str(expr), mode="eval")))


# candidate formulas --write-budgets matches observed payloads against,
# most-specific first; an unmatched payload is committed as its literal
# byte count (still valid, just device-count specific)
FORMULA_CANDIDATES = (
    "4",
    "8",
    "n_owned * 3 * 4",
    "n_owned * 2 * 4",
    "n_owned * 4",
    "n_owned * 8",
    "n * 3 * 4",
    "n * 2 * 4",
    "n * 4",
    "n_pad * 4",
    "n_pad * 8",
    "d_v * hcap * 3 * 4",
    "d_v * hcap * 2 * 4",
    "d_v * hcap * 4",
    "hcap * 4",
    "hcap * 8",
    "d_v * (cap + 1) * 4",
    "d_v * cap * 4",
    "d_v * cap * 8",
    "d * (cap + 1) * 4",
    "d * ceil_div(n_owned, 8)",
    "d * window",
    "d * 4",
)


def guess_formula(nbytes: int, env: Dict[str, int],
                  nbytes_b: Optional[int] = None,
                  env_b: Optional[Dict[str, int]] = None) -> Any:
    """Match an observed payload against the candidate formulas.

    When a paired observation (the same collective site traced in a
    second size environment) is supplied, a candidate must reproduce
    BOTH byte counts. The single-env form is ambiguous on the 2-axis
    audit point — e.g. ``hcap * 4`` and ``d_v * cap * 8`` both evaluate
    to 256 at (d_e, d_v) = (4, 2) — and a wrongly committed formula
    would fail the moment CI re-traces the manifest under the other
    factorization. Pairing against the 1-device trace (where those two
    diverge: 256 vs 128) makes the choice unique."""
    for cand in FORMULA_CANDIDATES:
        try:
            if eval_formula(cand, env) != int(nbytes):
                continue
            if (env_b is not None
                    and eval_formula(cand, env_b) != int(nbytes_b)):
                continue
        except ValueError:
            continue  # candidate names a size this env does not carry
        return cand
    return int(nbytes)


# -- round attribution ----------------------------------------------------

def split_round_collectives(
    closed,
) -> Tuple[List[CollectiveSite], List[CollectiveSite],
           List[CollectiveSite], List[CollectiveSite]]:
    """Partition a round trace's collectives into (setup, main,
    overflow, stray): unconditional collectives BEFORE the fixpoint
    loop (the halo layouts' one-time bind + state gather — paid per
    batch, not per round), unconditional in-round collectives,
    collectives on the sparse exchange's overflow cond arm
    (``branches[1]`` — the tag mapping is
    ``vertex_layout.SPARSE_COND_BRANCHES``), and anything
    unattributable (on a cond arm no budget names)."""
    setup, main, overflow, stray = [], [], [], []
    for c in collectives(closed):
        if not c.in_round:
            (setup if not c.cond_branches else stray).append(c)
        elif not c.cond_branches:
            main.append(c)
        elif (len(c.cond_branches) == 1
              and SPARSE_COND_BRANCHES[c.cond_branches[0]] == "overflow"):
            overflow.append(c)
        else:
            stray.append(c)
    return setup, main, overflow, stray


# trace-time Traffic.op -> the jaxpr primitive it must lower to
TRAFFIC_TO_PRIM = {
    "psum": "psum",
    "psum_scalar": "psum",
    "psum_edge": "psum",
    "pmin_scalar": "pmin",
    "pmax_scalar": "pmax",
    "ppermute": "ppermute",
    "gather_frontier": "all_gather",
    "gather_halo": "all_gather",
    "gather_stats": "all_gather",
    "regather": "reduce_scatter",
}


def cross_check_round(log: List[Traffic], closed) -> List[str]:
    """Verify the trace-time traffic accounting against the jaxpr.

    The §4.2/§4.3/§4.4 traffic model is asserted from
    ``record_traffic`` payload notes; this check proves those notes
    describe the REAL program: collective-by-collective (same order,
    branch attribution via ``SPARSE_COND_BRANCHES``), the noted
    ``recv_bytes`` must equal the lowered collective's output payload
    and the noted op must map to the traced primitive. Unbranched log
    entries split positionally between the setup prefix and the
    in-round remainder — both execute in trace order, so the first
    ``len(setup)`` notes ARE the pre-loop collectives. Returns
    human-readable mismatch strings (empty = the model is honest).
    Either side lying — an unnoted collective, a wrong byte count, a
    mislabeled branch — shows up here.
    """
    mismatches: List[str] = []
    jsetup, jmain, jover, stray = split_round_collectives(closed)
    for c in stray:
        mismatches.append(
            f"jaxpr has an unattributable collective {c.op} "
            f"({c.out_bytes}B) at {'/'.join(c.path) or '<top>'} — "
            "not covered by the traffic accounting"
        )
    plain = [t for t in log if t.branch == ""]
    lsetup, lmain = plain[:len(jsetup)], plain[len(jsetup):]
    lover = [t for t in log if t.branch == "overflow"]
    for tag, lside, jside in (("setup", lsetup, jsetup),
                              ("main", lmain, jmain),
                              ("overflow", lover, jover)):
        if len(lside) != len(jside):
            mismatches.append(
                f"{tag}: traffic log notes {len(lside)} collectives "
                f"({[t.op for t in lside]}) but the jaxpr contains "
                f"{len(jside)} ({[c.op for c in jside]})"
            )
            continue
        for i, (t, c) in enumerate(zip(lside, jside)):
            want_prim = TRAFFIC_TO_PRIM.get(t.op)
            if want_prim is None:
                mismatches.append(
                    f"{tag}[{i}]: unknown traffic op {t.op!r} (no "
                    "primitive mapping)"
                )
            elif c.op != want_prim:
                mismatches.append(
                    f"{tag}[{i}]: traffic notes {t.op} (-> {want_prim}) "
                    f"but the jaxpr primitive is {c.op}"
                )
            if t.recv_bytes != c.out_bytes:
                mismatches.append(
                    f"{tag}[{i}]: traffic notes {t.recv_bytes}B for "
                    f"{t.op} but the {c.op} output carries "
                    f"{c.out_bytes}B"
                )
    return mismatches


# -- rule 1: collective budget --------------------------------------------

@rule("collective_budget")
def check_collective_budget(traced, budget: dict) -> List[Finding]:
    cfg = traced.config
    env = traced.sizes
    findings: List[Finding] = []

    def bad(msg: str, program: str = "") -> None:
        findings.append(Finding("collective_budget", cfg.name, msg, program))

    want_progs = budget.get("program_collectives", {})
    for prog, closed in traced.programs.items():
        want = want_progs.get(prog)
        got = count_collectives(closed)
        if want is None:
            bad(
                f"no program_collectives budget for {prog!r} "
                f"(observed {got or '{}'}) — regenerate with "
                "`audit --write-budgets`",
                prog,
            )
        elif {k: int(v) for k, v in want.items()} != got:
            bad(
                f"collective histogram drifted: budget {want} vs "
                f"traced {got or '{}'}",
                prog,
            )

    want_rounds = budget.get("rounds", {})
    for rname, (log, closed) in traced.rounds.items():
        jsetup, jmain, jover, stray = split_round_collectives(closed)
        for c in stray:
            bad(
                f"unattributable collective {c.op} ({c.out_bytes}B) at "
                f"{'/'.join(c.path) or '<top>'} in {rname}",
                rname,
            )
        rb = want_rounds.get(rname)
        if rb is None:
            bad(
                f"no round budget for {rname!r} (observed setup="
                f"{[c.op for c in jsetup]}, main="
                f"{[c.op for c in jmain]}, overflow="
                f"{[c.op for c in jover]})",
                rname,
            )
        else:
            for key, jside in (("setup", jsetup), ("main", jmain),
                               ("overflow", jover)):
                spec = rb.get(key, [])
                if len(spec) != len(jside):
                    bad(
                        f"{rname}/{key}: budget lists "
                        f"{[s['op'] for s in spec]} but the round "
                        f"contains {[c.op for c in jside]}",
                        rname,
                    )
                    continue
                for i, (s, c) in enumerate(zip(spec, jside)):
                    if s["op"] != c.op:
                        bad(
                            f"{rname}/{key}[{i}]: budget op "
                            f"{s['op']!r} but traced {c.op!r} at "
                            f"{'/'.join(c.path)}",
                            rname,
                        )
                    wb = eval_formula(s["recv_bytes"], env)
                    if wb != c.out_bytes:
                        bad(
                            f"{rname}/{key}[{i}]: {c.op} moves "
                            f"{c.out_bytes}B but the budget formula "
                            f"{s['recv_bytes']!r} = {wb}B",
                            rname,
                        )
        # the traffic model must agree with the program it describes
        for m in cross_check_round(log, closed):
            bad(f"traffic-model cross-check in {rname}: {m}", rname)

    if budget.get("forbid_round_vertex_psum"):
        n = env["n"]
        # pure-edge-axis psums are the 2-axis layouts' statistic
        # completion: their payload is the owned slice, which at
        # d_v = 1 IS n-sized — size alone cannot distinguish it from
        # the forbidden vertex-axis reduction, but the axis set can
        exempt = set(budget.get("round_psum_axes_exempt", ()))
        scopes = [(p, c) for p, c in traced.programs.items()]
        scopes += [(r, jx) for r, (_, jx) in traced.rounds.items()]
        for prog, closed in scopes:
            for c in collectives(closed):
                if c.op == "psum" and c.in_round and c.out_elems >= n:
                    if exempt and c.axes and set(c.axes) <= exempt:
                        continue
                    bad(
                        f"vertex-sized psum inside a fixpoint round: "
                        f"{c.out_elems} elems (>= n={n}) over axes "
                        f"{c.axes} at {'/'.join(c.path)} — the halo "
                        "layouts must move owned slices "
                        "(reduce_scatter) + bounded frontier/halo "
                        "buffers only",
                        prog,
                    )
    return findings


# -- rule 2: host-sync detector -------------------------------------------

def _donation_markers(lowered) -> Tuple[set, int]:
    """Donation evidence read off the StableHLO (both forms survive CPU
    lowering even though the CPU backend copies instead of aliasing at
    run time): ``tf.aliasing_output = K`` pins an input to output K
    (single-device jit), while multi-device shard_map lowerings mark the
    input ``jax.buffer_donor = true`` and leave the output pairing to
    the compiler. Returns (aliased output indices, donor-marked input
    count)."""
    text = lowered.as_text()
    aliased = {int(m)
               for m in re.findall(r"tf\.aliasing_output\s*=\s*(\d+)", text)}
    donors = len(re.findall(r"jax\.buffer_donor\s*=\s*true", text))
    return aliased, donors


def _donated_arg_avals(lowered) -> list:
    import jax

    return [getattr(a, "aval", None) or a._aval
            for a in jax.tree_util.tree_leaves(lowered.args_info)
            if getattr(a, "donated", False)]


@rule("host_sync")
def check_host_sync(traced, budget: dict) -> List[Finding]:
    cfg = traced.config
    findings: List[Finding] = []
    allowed = int(budget.get("max_callback_primitives", 0))
    for prog, closed in traced.programs.items():
        sites = [s for s in iter_sites(closed)
                 if s.prim in HOST_CALLBACK_PRIMS]
        if len(sites) > allowed:
            for s in sites:
                findings.append(Finding(
                    "host_sync", cfg.name,
                    f"host-callback primitive {s.prim!r} at "
                    f"{'/'.join(s.path) or '<top>'} — a device->host "
                    "round-trip on every batch serializes the stream",
                    prog,
                ))
    if budget.get("require_large_outputs_donated"):
        thresh = int(budget.get("large_output_bytes", 1024))
        for prog, lowered in traced.lowered.items():
            aliased, _ = _donation_markers(lowered)
            # donor-marked inputs without a pinned output (the shard_map
            # form): a large output is covered if a donated input of the
            # SAME byte size is still unclaimed
            donor_bytes = [
                int(np.prod(a.shape or (1,))) * a.dtype.itemsize
                for a in _donated_arg_avals(lowered)
            ]
            closed = traced.programs[prog]
            for i, aval in enumerate(closed.out_avals):
                nbytes = int(np.prod(aval.shape or (1,))) * aval.dtype.itemsize
                if nbytes < thresh or i in aliased:
                    continue
                if nbytes in donor_bytes:
                    donor_bytes.remove(nbytes)
                    continue
                findings.append(Finding(
                    "host_sync", cfg.name,
                    f"output {i} ({aval.dtype}{list(aval.shape)}, "
                    f"{nbytes}B >= {thresh}B) does not alias a "
                    "donated input — an undonated large output is a "
                    "hidden per-batch copy",
                    prog,
                ))
    return findings


# -- rule 3: donation verifier --------------------------------------------

@rule("donation")
def check_donation(traced, budget: dict) -> List[Finding]:
    import jax

    cfg = traced.config
    findings: List[Finding] = []
    declared = budget.get("donated_args", {})
    for prog, lowered in traced.lowered.items():
        want = set(declared.get(prog, ()))
        infos = jax.tree_util.tree_leaves(lowered.args_info)
        got = {i for i, a in enumerate(infos) if getattr(a, "donated", False)}
        if got != want:
            findings.append(Finding(
                "donation", cfg.name,
                f"donated-arg set drifted: budget declares "
                f"{sorted(want)} but the lowering donates "
                f"{sorted(got)}",
                prog,
            ))
        aliased, donors = _donation_markers(lowered)
        marked = len(aliased) + donors
        if marked < len(want):
            findings.append(Finding(
                "donation", cfg.name,
                f"only {marked} donation markers (tf.aliasing_output / "
                f"jax.buffer_donor) in the StableHLO but {len(want)} "
                "buffers are declared donated — a declared donation "
                "the lowering drops is a silent copy",
                prog,
            ))
    return findings


# -- rule 4: dtype policy (sentinel taint) --------------------------------

TAINT_THRESHOLD = 1 << 31  # any value needing more than int32


def _value_tainted(val) -> bool:
    try:
        arr = np.asarray(val)
    except Exception:
        return False
    if arr.dtype.kind not in "iu" or arr.size == 0:
        return False
    return int(np.abs(arr.astype(np.int64, copy=False)).max()) >= TAINT_THRESHOLD


def _is_literal(v) -> bool:
    return hasattr(v, "val") and not hasattr(v, "count")


# scalar constant folding for taint SOURCES: the engines build the
# sentinel as ``jnp.int64(1) << 62``, which traces as a shift_left
# equation over small literals — without folding, no big literal ever
# appears in the jaxpr and the rule would pass vacuously
_FOLD_OPS: Dict[str, Callable] = {
    "shift_left": lambda a, b: a << b,
    "add": operator.add,
    "sub": operator.sub,
    "mul": operator.mul,
    "neg": operator.neg,
    "convert_element_type": lambda a: a,
    "broadcast_in_dim": lambda a: a,
}


def tainted_truncations(closed) -> List[str]:
    """Find int64->int32 converts applied to sentinel-tainted values.

    Taint SOURCES are integer literals/consts >= 2**31 (the engines'
    ``1 << 62`` edge-key / tombstone sentinel). Taint propagates through
    every equation's outputs, with two cuts that keep the rule exact on
    the real programs: boolean outputs drop taint (a comparison against
    a sentinel yields an ordinary flag), and ``sort`` pairs operand i
    with output i (so an argsort permutation never inherits its keys'
    taint). Control flow recurses structurally: while loops iterate the
    body to a taint fixpoint over the carry, cond unions its branches,
    scan fixpoints the carry, pjit/shard_map/custom_jvp map inputs
    one-to-one. A flagged site means a >=2**31 value CAN reach an int32
    truncation — exactly the silent corruption ``_require_x64`` guards
    against at the API boundary, caught here inside the programs.
    """
    findings: List[str] = []
    seen = set()

    def sub_closed(v):
        if hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
            return v.jaxpr, list(getattr(v, "consts", ()))
        if hasattr(v, "eqns"):
            return v, []
        return None, None

    def run(jx, in_taint: List[bool], const_taint: List[bool],
            path: Tuple[str, ...]) -> List[bool]:
        taint: Dict[Any, bool] = {}
        known: Dict[Any, int] = {}  # folded scalar int constants
        for var, t in zip(jx.invars, in_taint):
            taint[var] = bool(t)
        for var, t in zip(jx.constvars, const_taint):
            taint[var] = bool(t)

        def tin(v) -> bool:
            if _is_literal(v):
                return _value_tainted(v.val)
            return taint.get(v, False)

        def kval(v) -> Optional[int]:
            if _is_literal(v):
                arr = np.asarray(v.val)
                if arr.dtype.kind in "iu" and arr.size == 1:
                    return int(arr)
                return None
            return known.get(v)

        for eqn in jx.eqns:
            prim = eqn.primitive.name
            ins = [tin(v) for v in eqn.invars]
            any_in = any(ins)
            outs = [any_in] * len(eqn.outvars)

            if prim == "convert_element_type" and ins[0]:
                src_dt = eqn.invars[0].aval.dtype
                dst_dt = eqn.outvars[0].aval.dtype
                if (src_dt.kind in "iu" and src_dt.itemsize == 8
                        and dst_dt.kind in "iu" and dst_dt.itemsize < 8):
                    key = (path, id(eqn))
                    if key not in seen:
                        seen.add(key)
                        findings.append(
                            f"tainted {src_dt}->{dst_dt} "
                            "convert_element_type at "
                            f"{'/'.join(path) or '<top>'} — a >=2**31 "
                            "sentinel reaches an int32 truncation"
                        )
            elif prim == "sort":
                # operand i sorts into output i: keys' taint stays on
                # the key column, never on the permutation column
                outs = list(ins)
            elif prim == "while":
                cn = eqn.params["cond_nconsts"]
                bn = eqn.params["body_nconsts"]
                cjx, cconsts = sub_closed(eqn.params["cond_jaxpr"])
                bjx, bconsts = sub_closed(eqn.params["body_jaxpr"])
                cc = ins[:cn]
                bc = ins[cn:cn + bn]
                carry = list(ins[cn + bn:])
                for _ in range(len(carry) + 1):
                    out = run(bjx, bc + carry,
                              [_value_tainted(c) for c in bconsts],
                              path + ("while:body_jaxpr",))
                    new = [a or b for a, b in zip(carry, out)]
                    if new == carry:
                        break
                    carry = new
                run(cjx, cc + carry, [_value_tainted(c) for c in cconsts],
                    path + ("while:cond_jaxpr",))
                outs = carry
            elif prim == "cond":
                ops = list(ins[1:])
                branch_outs = None
                for i, br in enumerate(eqn.params["branches"]):
                    bjx, bconsts = sub_closed(br)
                    out = run(bjx, ops,
                              [_value_tainted(c) for c in bconsts],
                              path + (f"cond:branches[{i}]",))
                    branch_outs = (out if branch_outs is None else
                                   [a or b for a, b in zip(branch_outs, out)])
                outs = branch_outs or []
            elif prim == "scan":
                nc = eqn.params["num_consts"]
                ncar = eqn.params["num_carry"]
                bjx, bconsts = sub_closed(eqn.params["jaxpr"])
                consts_t = ins[:nc]
                carry = list(ins[nc:nc + ncar])
                xs = ins[nc + ncar:]
                ys: List[bool] = []
                for _ in range(len(carry) + 1):
                    out = run(bjx, consts_t + carry + xs,
                              [_value_tainted(c) for c in bconsts],
                              path + ("scan:jaxpr",))
                    new = [a or b for a, b in zip(carry, out[:ncar])]
                    ys = out[ncar:]
                    if new == carry:
                        break
                    carry = new
                outs = carry + ys
            elif any(True for _ in _sub_jaxpr_params(eqn)):
                # one-to-one input mapping covers pjit / shard_map /
                # custom_jvp_call / remat; anything unrecognized falls
                # back to broadcasting the joint input taint (sound,
                # possibly conservative)
                outs = None
                for tag, (sjx, sconsts) in _sub_jaxpr_params(eqn):
                    sub_in = (ins if len(sjx.invars) == len(ins)
                              else [any_in] * len(sjx.invars))
                    out = run(sjx, sub_in,
                              [_value_tainted(c) for c in sconsts],
                              path + (tag,))
                    if len(out) == len(eqn.outvars):
                        outs = (out if outs is None else
                                [a or b for a, b in zip(outs, out)])
                if outs is None:
                    outs = [any_in] * len(eqn.outvars)

            if prim in _FOLD_OPS and len(eqn.outvars) == 1:
                kins = [kval(v) for v in eqn.invars]
                if all(k is not None for k in kins):
                    try:
                        val = int(_FOLD_OPS[prim](*kins))
                    except Exception:
                        val = None
                    if val is not None:
                        known[eqn.outvars[0]] = val
                        if abs(val) >= TAINT_THRESHOLD:
                            outs = [True]  # a computed sentinel: source

            for var, t in zip(eqn.outvars, outs):
                # taint cannot survive a boolean: comparisons against
                # sentinels are ordinary flags
                aval = getattr(var, "aval", None)
                if aval is not None and getattr(aval, "dtype", None) is not None \
                        and aval.dtype.kind == "b":
                    t = False
                taint[var] = bool(t)
        return [tin(v) for v in jx.outvars]

    def _sub_jaxpr_params(eqn):
        prim = eqn.primitive.name
        for pname, val in eqn.params.items():
            vals = val if isinstance(val, (list, tuple)) else [val]
            for i, v in enumerate(vals):
                sjx, sconsts = sub_closed(v)
                if sjx is not None:
                    tag = (f"{prim}:{pname}[{i}]"
                           if isinstance(val, (list, tuple))
                           else f"{prim}:{pname}")
                    yield tag, (sjx, sconsts)

    jaxpr = getattr(closed, "jaxpr", closed)
    consts = list(getattr(closed, "consts", ()))
    run(jaxpr, [False] * len(jaxpr.invars),
        [_value_tainted(c) for c in consts], ())
    return findings


@rule("dtype_policy")
def check_dtype_policy(traced, budget: dict) -> List[Finding]:
    cfg = traced.config
    findings: List[Finding] = []
    allowed = int(budget.get("max_tainted_truncations", 0))
    scopes = list(traced.programs.items())
    scopes += [(r, jx) for r, (_, jx) in traced.rounds.items()]
    total = []
    for prog, closed in scopes:
        for msg in tainted_truncations(closed):
            total.append(Finding("dtype_policy", cfg.name, msg, prog))
    if len(total) > allowed:
        findings.extend(total)
    return findings


# -- rule 5: per-round launch budget --------------------------------------

@rule("launch_budget")
def check_launch_budget(traced, budget: dict) -> List[Finding]:
    """Pin the per-round kernel-launch histogram: the launch-class
    primitives per fixpoint round (``LAUNCH_PRIMS``) must equal the
    committed ``round_launches`` section — a drifted count is a silently
    re-grown gather/scatter train."""
    cfg = traced.config
    findings: List[Finding] = []

    def bad(msg: str, program: str = "") -> None:
        findings.append(Finding("launch_budget", cfg.name, msg, program))

    want_rounds = budget.get("round_launches", {})
    for rname, (_, closed) in traced.rounds.items():
        got = count_round_launches(closed)
        want = want_rounds.get(rname)
        if want is None:
            bad(
                f"no round_launches budget for {rname!r} (observed "
                f"{got or '{}'}) — regenerate with "
                "`audit --write-budgets`",
                rname,
            )
        elif {k: int(v) for k, v in want.items()} != got:
            bad(
                f"launch histogram drifted: budget {want} vs traced "
                f"{got or '{}'}",
                rname,
            )
    return findings


# -- rule 6: recompile-surface auditor ------------------------------------

@rule("recompile_surface")
def check_recompile_surface(traced, budget: dict) -> List[Finding]:
    cfg = traced.config
    findings: List[Finding] = []
    max_variants = int(budget.get("max_jit_variants", 0))
    if cfg.engine == "host":
        # the host path jits per pow2 batch bucket — no window/cap lattice
        variants = max(1, traced.params.lanes).bit_length()
        if variants > max_variants:
            findings.append(Finding(
                "recompile_surface", cfg.name,
                f"{variants} pow2 batch buckets (lanes <= "
                f"{traced.params.lanes}) exceed max_jit_variants="
                f"{max_variants}",
            ))
        return findings
    lattice = bucket_lattice(
        traced.sizes["local_cap"], traced.params.lanes,
        cfg.frontier_exchange, cfg.frontier_cap,
        traced.sizes["n_owned"],
    )
    if len(lattice) > max_variants:
        findings.append(Finding(
            "recompile_surface", cfg.name,
            f"the planner can reach {len(lattice)} (window, cap) "
            f"buckets {lattice} but max_jit_variants="
            f"{max_variants} — every extra bucket is a mid-stream "
            "recompile",
        ))
    if (traced.window, traced.frontier_cap) not in lattice:
        findings.append(Finding(
            "recompile_surface", cfg.name,
            f"traced bucket (window={traced.window}, "
            f"cap={traced.frontier_cap}) is not in the planner "
            f"lattice {lattice} — the trace used an unplanned variant",
        ))
    return findings


# registers the memory_budget rule (import-cycle-safe: memory imports
# Finding/eval_formula/rule from this module at its top, which is fully
# defined by the time this line runs in either import order)
from . import memory as _memory  # noqa: E402,F401
