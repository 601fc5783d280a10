"""Audit CLI + report schema — ``python -m repro.analysis.audit``.

Runs every registered rule (repro.analysis.rules) over the traced
programs of each engine configuration and diffs against the committed
budget manifests (``budgets/<engine>.json``), emitting one
machine-readable report (schema ``repro.analysis/report/v1`` — the same
shape ``benchcheck`` uses for the BENCH_stream.json coherence gate, so
CI consumes exactly one report format).

Usage:
    python -m repro.analysis.audit --engine all            # gate
    python -m repro.analysis.audit --engine all --devices 8
    python -m repro.analysis.audit --engine all --memory   # memory only
    python -m repro.analysis.audit --write-budgets --devices 8
    python -m repro.analysis.audit --check-bench BENCH_stream.json

``--devices N`` re-execs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` when the current
process already initialized JAX with a different count (importing this
package imports jax, so the flag cannot be set in-process).

``--write-budgets`` regenerates the manifests from the traced programs.
Run it at ``--devices 8``: payload formulas are matched against the
observed byte counts, and several candidates coincide numerically on 1
device (``n_owned == n``) — a multi-device trace disambiguates them so
the committed formula holds on EVERY device count. The memory section
goes further: each sharded engine is traced a SECOND time on an
explicit 1-device mesh and every buffer dimension is solved against
both size environments at once (see ``memory.generate_memory_section``).
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
from typing import Dict, List, Optional, Sequence

SCHEMA = "repro.analysis/report/v1"
BUDGET_SCHEMA = "repro.analysis/budget/v4"
BUDGET_DIR = os.path.join(os.path.dirname(__file__), "budgets")
_CHILD_GUARD = "_REPRO_AUDIT_REEXEC"


def make_check(rule: str, engine: str, findings: Sequence) -> dict:
    """One report entry: a rule applied to one engine config."""
    return {
        "rule": rule,
        "engine": engine,
        "ok": not findings,
        "findings": [
            f.as_dict() if hasattr(f, "as_dict") else dict(f)
            for f in findings
        ],
    }


def make_report(checks: List[dict], **meta) -> dict:
    return {
        "schema": SCHEMA,
        "ok": all(c["ok"] for c in checks),
        "checks": checks,
        **meta,
    }


def budget_path(engine: str, budget_dir: Optional[str] = None) -> str:
    return os.path.join(budget_dir or BUDGET_DIR, f"{engine}.json")


def load_budget(engine: str, budget_dir: Optional[str] = None) -> dict:
    path = budget_path(engine, budget_dir)
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no budget manifest for engine {engine!r} at {path} — "
            "generate one with `python -m repro.analysis.audit "
            "--write-budgets --devices 8` and commit it"
        )
    with open(path) as fh:
        budget = json.load(fh)
    got = budget.get("schema")
    if got != BUDGET_SCHEMA:
        raise ValueError(
            f"budget manifest {path} has schema {got!r} but this "
            f"auditor expects {BUDGET_SCHEMA!r} — regenerate with "
            "`python -m repro.analysis.audit --write-budgets "
            "--devices 8` and commit the result"
        )
    return budget


def generate_budget(traced, paired=None) -> dict:
    """Build a budget manifest from a traced engine: exact collective
    histograms, ordered per-round op lists with payload formulas
    (``rules.guess_formula``), the donated-arg sets, the jit-variant
    bound computed at its 1-device maximum (the window lattice is
    largest when one shard holds the whole table), and the symbolic
    per-device memory section (``memory.generate_memory_section``;
    ``paired`` is the same engine traced at a different mesh size).

    The paired trace disambiguates BOTH kinds of formulas: buffer
    dimensions (memory section) and round payload sizes — the round
    jaxprs are structurally identical at every mesh size (ring steps
    are live-masked, not unrolled), so the collective sites zip
    one-to-one and ``guess_formula`` can demand a candidate reproduce
    both environments' byte counts (several candidates coincide at a
    single audit point, e.g. ``hcap * 4 == d_v * cap * 8`` at
    (d_e, d_v) = (4, 2))."""
    from ..core.api import bucket_lattice
    from ..launch.mesh import EDGE_SHARD_AXIS
    from .memory import generate_memory_section
    from .rules import guess_formula, split_round_collectives
    from .walker import count_collectives, count_round_launches

    cfg = traced.config
    env = traced.sizes
    if paired is None:
        paired = []
    elif not isinstance(paired, (list, tuple)):
        paired = [paired]
    # payload formulas pair against the most size-divergent point (the
    # 1-device trace comes first from write_budgets)
    pair0 = paired[0] if paired else None
    rounds = {}
    for rname, (_, closed) in traced.rounds.items():
        sides = dict(zip(
            ("setup", "main", "overflow", "stray"),
            split_round_collectives(closed),
        ))
        if sides["stray"]:
            raise RuntimeError(
                f"{cfg.name}/{rname}: cannot budget unattributable "
                f"collectives {[c.op for c in sides['stray']]}"
            )
        psides: dict = {}
        if pair0 is not None and rname in pair0.rounds:
            pt = dict(zip(
                ("setup", "main", "overflow", "stray"),
                split_round_collectives(pair0.rounds[rname][1]),
            ))
            if not pt["stray"] and all(
                len(pt[k]) == len(sides[k])
                for k in ("setup", "main", "overflow")
            ):
                psides = pt
        rounds[rname] = {
            side: [
                {"op": c.op, "recv_bytes": guess_formula(
                    c.out_bytes, env,
                    psides[side][i].out_bytes if psides else None,
                    pair0.sizes if psides else None,
                )}
                for i, c in enumerate(cols)
            ]
            for side, cols in ((k, sides[k])
                               for k in ("setup", "main", "overflow"))
        }
    if cfg.engine == "host":
        max_variants = max(1, traced.params.lanes).bit_length()
    else:
        # d=1 maximizes the window lattice; committing that bound keeps
        # one manifest valid on every audited device count
        max_variants = len(bucket_lattice(
            traced.params.capacity, traced.params.lanes,
            cfg.frontier_exchange, cfg.frontier_cap, traced.params.n,
        ))
    return {
        "schema": BUDGET_SCHEMA,
        "engine": cfg.name,
        "generated_with": {
            "n": traced.params.n,
            "capacity": traced.params.capacity,
            "lanes": traced.params.lanes,
            "devices": traced.n_devices,
            "mesh_shape": (
                [env["d_e"], env["d_v"]]
                if cfg.vertex_sharding == "halo" else None
            ),
        },
        "program_collectives": {
            p: count_collectives(jx) for p, jx in traced.programs.items()
        },
        "rounds": rounds,
        # launch-class primitives per fixpoint round (a fused pallas_call
        # counts as ONE; rules.check_launch_budget pins these and, for
        # pallas configs, proves the count strictly beats the lax twin)
        "round_launches": {
            rname: count_round_launches(closed)
            for rname, (_, closed) in traced.rounds.items()
        },
        "forbid_round_vertex_psum": cfg.vertex_sharding in ("range", "halo"),
        # pure-edge-axis statistic psums are budgeted traffic, not the
        # forbidden vertex-axis reduction (their payload is the owned
        # slice, n-sized only in the degenerate d_v=1 factorization)
        "round_psum_axes_exempt": (
            [EDGE_SHARD_AXIS] if cfg.vertex_sharding == "halo" else []
        ),
        "donated_args": {
            p: list(traced.donated.get(p, ())) for p in traced.lowered
        },
        "max_callback_primitives": 0,
        "max_tainted_truncations": 0,
        "max_jit_variants": max_variants,
        "large_output_bytes": 1024,
        "require_large_outputs_donated": cfg.engine != "host",
        "memory": generate_memory_section(traced, paired),
    }


def audit_engines(engines: Sequence[str],
                  budget_dir: Optional[str] = None,
                  params=None,
                  rules: Optional[Sequence[str]] = None,
                  mesh_shape=None) -> dict:
    """Pytest-importable entry: trace + audit the given engine configs
    against their committed budgets, returning one report dict.
    ``rules`` restricts the run to a subset of the registry (the CLI's
    ``--memory`` flag passes ``["memory_budget"]``). ``mesh_shape``
    overrides the (d_e, d_v) factorization of halo configs only — CI
    audits ``vertex_halo`` under both 4x2 and 2x4 against the one
    committed manifest; other configs in the same run ignore it."""
    import jax

    from .programs import ENGINE_CONFIGS, AuditParams, trace_engine
    from .rules import run_rules

    params = params or AuditParams()
    checks: List[dict] = []
    for name in engines:
        shape = (mesh_shape
                 if ENGINE_CONFIGS[name].vertex_sharding == "halo"
                 else None)
        traced = trace_engine(name, params, mesh_shape=shape)
        budget = load_budget(name, budget_dir)
        for rname, findings in run_rules(traced, budget, rules).items():
            checks.append(make_check(rname, name, findings))
    return make_report(
        checks,
        n_devices=len(jax.devices()),
        engines=list(engines),
        mesh_shape=list(mesh_shape) if mesh_shape else None,
        params={"n": params.n, "capacity": params.capacity,
                "lanes": params.lanes},
    )


def write_budgets(engines: Sequence[str],
                  budget_dir: Optional[str] = None,
                  params=None) -> List[str]:
    from .programs import ENGINE_CONFIGS, AuditParams, trace_engine

    params = params or AuditParams()
    out_dir = budget_dir or BUDGET_DIR
    os.makedirs(out_dir, exist_ok=True)
    written = []
    for name in engines:
        traced = trace_engine(name, params)
        # second trace on an explicit 1-device mesh: shard_map traces
        # one program regardless of mesh size, so the paired point
        # sequences line up and buffer-size formulas get solved against
        # two size environments at once (memory.generate_memory_section)
        paired = []
        if ENGINE_CONFIGS[name].is_sharded and traced.n_devices > 1:
            paired.append(trace_engine(name, params, devices=1))
            # halo configs add every other (d_e, d_v) factorization CI
            # can audit at this device count: the 1-device pair can't
            # separate d_v-only dependences (d_v == 1 collapses them),
            # and the PEAK program point itself moves between
            # factorizations — the committed max() must cover each
            # point that is the peak somewhere
            if ENGINE_CONFIGS[name].vertex_sharding == "halo":
                d = traced.n_devices
                canon = (traced.sizes["d_e"], traced.sizes["d_v"])
                others = [(canon[1], canon[0]), (1, d), (d, 1)]
                for shape in dict.fromkeys(others):
                    if shape != canon and shape[0] * shape[1] == d:
                        paired.append(trace_engine(name, params,
                                                   mesh_shape=shape))
        path = budget_path(name, out_dir)
        with open(path, "w") as fh:
            json.dump(generate_budget(traced, paired), fh, indent=2,
                      sort_keys=True)
            fh.write("\n")
        written.append(path)
    return written


def _reexec_with_devices(n_devices: int, argv: Sequence[str]) -> int:
    """Re-run this CLI in a subprocess with N forced host devices.
    Needed because importing repro.analysis already initialized jax —
    XLA_FLAGS must be set before that import, not after. The child is
    pinned to the CPU: it audits traced programs on forced host devices
    and must never contend for an accelerator this process holds."""
    if os.environ.get(_CHILD_GUARD):
        print(
            f"audit: failed to force {n_devices} host devices via "
            "XLA_FLAGS (still seeing a different count after re-exec)",
            file=sys.stderr,
        )
        return 2
    env = dict(os.environ)
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", ""),
    ).strip()
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env[_CHILD_GUARD] = "1"
    src_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env["PYTHONPATH"] = os.pathsep.join(
        [src_root] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    cmd = [sys.executable, "-m", "repro.analysis.audit", *argv]
    return subprocess.call(cmd, env=env)


def _print_summary(report: dict) -> None:
    for c in report["checks"]:
        mark = "ok  " if c["ok"] else "FAIL"
        print(f"[{mark}] {c['engine']:16s} {c['rule']}")
        for f in c["findings"]:
            print(f"       - {f['message']}")
    verdict = "PASS" if report["ok"] else "FAIL"
    extra = (f" on {report['n_devices']} device(s)"
             if "n_devices" in report else "")
    print(f"audit {verdict}{extra}")


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m repro.analysis.audit",
        description="Static audit of the engine matrix's traced programs",
    )
    p.add_argument("--engine", default="all",
                   help="comma-separated engine configs, or 'all'")
    p.add_argument("--devices", type=int, default=None,
                   help="force this many host devices (re-execs with "
                        "XLA_FLAGS when needed)")
    p.add_argument("--out", default=None,
                   help="write the JSON report here")
    p.add_argument("--budget-dir", default=None,
                   help="manifest directory (default: the committed "
                        "package budgets/)")
    p.add_argument("--memory", action="store_true",
                   help="run only the memory_budget rule (symbolic "
                        "per-device peak / at-rest / donation audit)")
    p.add_argument("--mesh-shape", default=None, metavar="DExDV",
                   help="re-trace halo configs under this (d_e, d_v) "
                        "factorization, e.g. 2x4 (non-halo configs "
                        "ignore it; product must equal --devices)")
    p.add_argument("--write-budgets", action="store_true",
                   help="regenerate the budget manifests instead of "
                        "checking (run with --devices 8)")
    p.add_argument("--check-bench", default=None, metavar="PATH",
                   help="check a BENCH_stream.json artifact for "
                        "coherence instead of auditing engines")
    args = p.parse_args(argv)

    if args.check_bench:
        from .benchcheck import check_bench

        report = make_report([check_bench(args.check_bench)],
                             artifact=args.check_bench)
        if args.out:
            with open(args.out, "w") as fh:
                json.dump(report, fh, indent=2)
        _print_summary(report)
        return 0 if report["ok"] else 1

    import jax  # after arg parsing: --help must not initialize a backend

    if args.devices is not None and len(jax.devices()) != args.devices:
        child_argv = [a for a in (argv if argv is not None else sys.argv[1:])]
        return _reexec_with_devices(args.devices, child_argv)

    from .programs import ENGINE_CONFIGS

    engines = (sorted(ENGINE_CONFIGS) if args.engine == "all"
               else args.engine.split(","))
    for e in engines:
        if e not in ENGINE_CONFIGS:
            p.error(f"unknown engine {e!r} "
                    f"(expected one of {sorted(ENGINE_CONFIGS)})")

    if args.write_budgets:
        if len(jax.devices()) == 1:
            print(
                "audit: writing budgets from a 1-device trace — size "
                "formulas may not disambiguate (n_owned == n); prefer "
                "--write-budgets --devices 8",
                file=sys.stderr,
            )
        for path in write_budgets(engines, args.budget_dir):
            print(f"wrote {path}")
        return 0

    mesh_shape = None
    if args.mesh_shape:
        m = re.fullmatch(r"(\d+)x(\d+)", args.mesh_shape)
        if not m:
            p.error(f"--mesh-shape must look like 4x2, got "
                    f"{args.mesh_shape!r}")
        mesh_shape = (int(m.group(1)), int(m.group(2)))

    report = audit_engines(
        engines, args.budget_dir,
        rules=["memory_budget"] if args.memory else None,
        mesh_shape=mesh_shape,
    )
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(report, fh, indent=2)
    _print_summary(report)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
