import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, print memory/cost analysis, collect roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only-check]
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results.json

The single-pod (16x16) compile feeds the roofline table; the multi-pod
(2x16x16) compile proves the "pod" axis shards.
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.launch.mesh import HW, make_production_mesh  # noqa: E402
from repro.launch.steps import build_cell, cell_names  # noqa: E402
from repro.configs import arch_names, get_arch  # noqa: E402

_COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^\n]*"
)
_SHAPE_RE = re.compile(r"\b((?:f|bf|s|u|pred)\d*)\[([\d,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
}


_OP_RE = re.compile(
    r"=\s*(?P<result>\([^)]*\)|[a-z0-9]+\[[\d,]*\](?:\{[^}]*\})?)\s*"
    r"(?P<kind>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\("
)


_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _group_size(line: str, default: int = 16) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def collective_bytes(hlo_text: str) -> dict:
    """Per-device WIRE bytes of every collective, ring-algorithm model:

      all-gather        result * (p-1)/p
      reduce-scatter    result * (p-1)        (result is the shard)
      all-reduce        2 * result * (p-1)/p  (RS + AG phases)
      all-to-all        result * (p-1)/p
      collective-permute result

    p is parsed from replica_groups on each op line.
    """
    out = {
        "all-gather": 0.0, "all-reduce": 0.0, "reduce-scatter": 0.0,
        "all-to-all": 0.0, "collective-permute": 0.0,
    }
    counts = {k: 0 for k in out}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        total = 0
        for dt, dims in _SHAPE_RE.findall(m.group("result")):
            nbytes = _DTYPE_BYTES.get(dt, 4)
            size = 1
            if dims:
                for d in dims.split(","):
                    if d:
                        size *= int(d)
            total += size * nbytes
        kind = m.group("kind")
        p = _group_size(line)
        if kind == "all-reduce":
            wire = 2.0 * total * (p - 1) / p
        elif kind == "reduce-scatter":
            wire = 1.0 * total * (p - 1)
        elif kind == "collective-permute":
            wire = float(total)
        else:  # all-gather, all-to-all
            wire = 1.0 * total * (p - 1) / p
        out[kind] += wire
        counts[kind] += 1
    out["op_counts"] = counts
    return out


def _layer_probe(arch: str, shape: str, mesh, multi_pod: bool):
    """Per-layer HLO cost probe for LM cells.

    XLA's cost_analysis counts a rolled ``scan`` body ONCE (calibrated in
    EXPERIMENTS.md §Dry-run), so the full-program numbers undercount the
    layer stack by (L-1)x. This probe lowers ONE layer with the same
    shardings; run_cell reports corrected = rolled + (L-1) * probe.
    """
    import functools

    from jax.sharding import PartitionSpec as P
    from repro.models import transformer as tf_mod
    from repro.parallel import sharding as shard_rules

    import dataclasses as _dc

    mod = get_arch(arch)
    if mod.FAMILY != "lm":
        return None
    cfg = _dc.replace(
        mod.full(),
        batch_axes=("pod", "data") if multi_pod else "data",
        tp_axis="model",
        attn_chunk=2048,
    )
    cell = next(c for c in mod.SHAPES if c.name == shape)
    L = cfg.n_layers
    p_abs = jax.eval_shape(
        functools.partial(tf_mod.init_params, cfg), jax.random.PRNGKey(0)
    )
    lay_abs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
        p_abs["layers"],
    )
    lay_spec = jax.tree.map(
        lambda s: jax.sharding.PartitionSpec(*s[1:]),
        shard_rules.lm_param_specs(cfg)["layers"],
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )
    batch_ax = ("pod", "data") if multi_pod else "data"
    pods = 2 if multi_pod else 1

    if cell.kind in ("train", "prefill"):
        b, s = cell.params["batch"], cell.params["seq"]
        x_abs = jax.ShapeDtypeStruct((b, s, cfg.d_model), cfg.dtype)
        x_spec = P(batch_ax, None, None)
        positions = None

        if cell.kind == "train":
            def probe(lp, x):
                pos = jnp.arange(x.shape[1])[None, :]

                def f(args):
                    lp_, x_ = args
                    y, aux = tf_mod._layer_fwd(cfg, lp_, x_, pos)
                    return jnp.sum(y.astype(jnp.float32)) + aux

                g = jax.grad(f)((lp, x))
                return g
        else:
            def probe(lp, x):
                pos = jnp.arange(x.shape[1])[None, :]
                y, _ = tf_mod._layer_fwd(cfg, lp, x, pos)
                return y

        abstract = (lay_abs, x_abs)
        specs = (lay_spec, x_spec)
    else:  # decode
        b, t = cell.params["batch"], cell.params["cache"]
        cache_abs = jax.eval_shape(
            functools.partial(tf_mod.init_cache, cfg, b, t)
        )
        lc_abs = {
            k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
            for k, v in cache_abs.items() if k != "length"
        }
        full_cache_spec = shard_rules.lm_cache_specs(
            cfg, multi_pod, batch=b
        )
        lc_spec = {
            k: jax.sharding.PartitionSpec(*v[1:])
            for k, v in full_cache_spec.items() if k != "length"
        }
        x_abs = jax.ShapeDtypeStruct((b, 1, cfg.d_model), cfg.dtype)
        x_spec = (
            P(batch_ax, None, None)
            if b % (16 * pods) == 0 else P(None, None, None)
        )

        def probe(lp, lc, x):
            y, _ = tf_mod._decode_layer(cfg, lp, x, lc, jnp.int32(t // 2))
            return y

        abstract = (lay_abs, lc_abs, x_abs)
        specs = (lay_spec, lc_spec, x_spec)

    in_sh = jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )
    with mesh:
        compiled = jax.jit(probe, in_shardings=in_sh).lower(
            *abstract
        ).compile()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    return {
        "n_layers": L,
        "flops": float(ca.get("flops", 0.0)),
        "dot_flops": dot_flops(hlo),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
    }


def model_flops(arch: str, shape: str) -> float:
    """Analytic MODEL_FLOPS: 6*N*D train (N_active for MoE), 2*N*D for
    prefill, 2*N_active per decoded token + attention KV term."""
    mod = get_arch(arch)
    cell = next(c for c in mod.SHAPES if c.name == shape)
    if mod.FAMILY == "lm":
        cfg = mod.full()
        n_act = cfg.n_active_params
        if cell.kind == "train":
            d = cell.params["batch"] * cell.params["seq"]
            return 6.0 * n_act * d
        if cell.kind == "prefill":
            d = cell.params["batch"] * cell.params["seq"]
            return 2.0 * n_act * d
        # decode: matmul flops + attention against the cache
        b, t = cell.params["batch"], cell.params["cache"]
        if cfg.attention == "mla":
            m = cfg.mla
            attn = 2.0 * b * t * cfg.n_heads * (
                m.kv_lora + m.rope_head_dim + m.kv_lora
            )
        else:
            attn = 4.0 * b * t * cfg.n_heads * cfg.d_head
        return 2.0 * n_act * b + attn * cfg.n_layers
    if mod.FAMILY == "recsys":
        cfg = mod.full()
        b = cell.params.get("batch", 1)
        d_in = cfg.n_sparse * cfg.embed_dim
        dims = (d_in,) + tuple(cfg.mlp_dims) + (1,)
        mlp = sum(2 * a * c for a, c in zip(dims[:-1], dims[1:]))
        per_ex = mlp + 4 * cfg.n_sparse * cfg.embed_dim
        mult = 3.0 if cell.kind == "train" else 1.0
        if cell.kind == "retrieval":
            return 2.0 * cell.params["n_candidates"] * cfg.embed_dim
        return mult * per_ex * b
    return 0.0  # GNN: reported via HLO only (no closed form in 6ND terms)


_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\(?([a-z0-9]+)\[([\d,]*)\]")
_DOT_LINE_RE = re.compile(
    r"=\s*[a-z0-9]+\[(?P<res>[\d,]*)\][^=]*?\bdot\("
    r"\s*%?(?P<a>[\w.\-]+)\s*,\s*%?(?P<b>[\w.\-]+)\s*\)"
    r".*?lhs_contracting_dims=\{(?P<lc>[\d,]*)\}"
)


def dot_flops(hlo_text: str) -> float:
    """Matmul flops counted directly from optimized HLO dot ops
    (per-device): 2 * prod(result dims) * prod(lhs contracting sizes).
    Operand shapes come from a module-wide symbol table (HLO text omits
    operand types on the op line). Transparent alternative to XLA's
    aggregate 'flops', which also counts elementwise/convert traffic."""
    defs = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            name, _, dims = m.groups()
            defs[name] = [int(d) for d in dims.split(",") if d]
    total = 0.0
    for line in hlo_text.splitlines():
        m = _DOT_LINE_RE.search(line)
        if not m:
            continue
        res = [int(d) for d in m.group("res").split(",") if d]
        lhs = defs.get(m.group("a"), [])
        lc = [int(d) for d in m.group("lc").split(",") if d]
        k = 1
        for dim in lc:
            if dim < len(lhs):
                k *= lhs[dim]
        r = 1
        for d in res:
            r *= d
        total += 2.0 * r * k
    return total


def run_cell(arch: str, shape: str, multi_pod: bool, verbose: bool = True,
             probe_layers: bool = True, unroll: bool = False):
    mesh = make_production_mesh(multi_pod=multi_pod)
    prog = build_cell(arch, shape, smoke=False, multi_pod=multi_pod,
                      unroll=unroll)
    if unroll:
        probe_layers = False  # exact: every layer present in the HLO
    in_shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        prog.in_specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )
    out_shardings = None
    if prog.out_specs is not None:
        out_shardings = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec),
            prog.out_specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
        )
    t0 = time.time()
    jax.set_mesh(mesh)
    with mesh:
        jitted = jax.jit(
            prog.fn, in_shardings=in_shardings, out_shardings=out_shardings,
            donate_argnums=prog.donate,
        )
        lowered = jitted.lower(*prog.abstract_inputs)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    dflops = dot_flops(hlo)

    # scan-body correction: cost_analysis counts the layer scan body once;
    # add (L-1) x the per-layer probe costs (LM cells only).
    probe = (
        _layer_probe(arch, shape, mesh, multi_pod) if probe_layers else None
    )
    if probe:
        k = probe["n_layers"] - 1
        cost = dict(cost or {})
        dflops = dflops + k * probe["dot_flops"]
        cost["flops"] = float(cost.get("flops", 0.0)) + k * probe["flops"]
        cost["bytes accessed"] = (
            float(cost.get("bytes accessed", 0.0))
            + k * probe["bytes_accessed"]
        )
        for key in coll:
            if key == "op_counts":
                continue
            coll[key] += k * probe["collective_bytes"].get(key, 0)

    n_dev = mesh.devices.size
    result = {
        "arch": arch,
        "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(n_dev),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops": float(cost.get("flops", 0.0)) if cost else 0.0,
        "dot_flops": dflops,
        "bytes_accessed": float(cost.get("bytes accessed", 0.0))
        if cost else 0.0,
        "collective_bytes": coll,
        "mem": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": getattr(
                mem, "peak_memory_in_bytes",
                getattr(mem, "temp_size_in_bytes", 0),
            ),
        },
    }
    # roofline terms (single-pod table; see EXPERIMENTS.md §Roofline).
    # cost_analysis flops/bytes are PER-DEVICE program costs (calibrated);
    # collective result-bytes are per-device wire bytes.
    coll_total = sum(v for k, v in coll.items() if k != "op_counts")
    # memory term: argument+output bytes are the schedule-independent HBM
    # traffic floor (weights/opt-state/cache each touched once); XLA's
    # fusion-blind "bytes accessed" is reported as the pessimistic bound.
    mem_floor = (
        result["mem"]["argument_bytes"] + result["mem"]["output_bytes"]
    )
    result["roofline"] = {
        "t_compute_s": result["dot_flops"] / HW["peak_flops_bf16"],
        "t_memory_s": mem_floor / HW["hbm_bw"],
        "t_collective_s": coll_total
        / (HW["ici_bw_per_link"] * HW["ici_links"]),
        "t_memory_xla_upper_s": result["bytes_accessed"] / HW["hbm_bw"],
    }
    terms = {
        k: v for k, v in result["roofline"].items()
        if k in ("t_compute_s", "t_memory_s", "t_collective_s")
    }
    dom = max(terms, key=terms.get)
    result["roofline"]["dominant"] = dom
    mf = model_flops(arch, shape)
    result["model_flops_global"] = mf
    dot_global = result["dot_flops"] * n_dev
    result["model_vs_hlo"] = (mf / dot_global) if dot_global else None
    if verbose:
        print(f"== {arch} x {shape} on {result['mesh']} "
              f"({n_dev} devices) ==")
        print(f"   lower {t_lower:.1f}s  compile {t_compile:.1f}s")
        print(f"   memory_analysis: {result['mem']}")
        print(f"   cost_analysis: xla_flops={result['flops']:.3e} "
              f"dot_flops={result['dot_flops']:.3e} "
              f"bytes={result['bytes_accessed']:.3e}")
        print(f"   model_flops={mf:.3e} useful-ratio={result['model_vs_hlo']}")
        print(f"   collectives: {coll}")
        print(f"   roofline: {terms}")
        sys.stdout.flush()
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--include-coremaint", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--unroll", action="store_true",
                    help="unroll the layer scan for exact HLO accounting")
    args = ap.parse_args()

    assert len(jax.devices()) == 512, (
        f"expected 512 virtual devices, got {len(jax.devices())}"
    )
    cells = []
    if args.all:
        for a in arch_names(include_coremaint=args.include_coremaint):
            for s in cell_names(a):
                cells.append((a, s))
    else:
        archs = [args.arch] if args.arch else arch_names()
        for a in archs:
            shapes = [args.shape] if args.shape else cell_names(a)
            for s in shapes:
                cells.append((a, s))

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results, failures = [], []
    for a, s in cells:
        for mp in meshes:
            try:
                results.append(run_cell(a, s, multi_pod=mp,
                                        unroll=args.unroll))
            except Exception as e:  # noqa: BLE001
                traceback.print_exc()
                failures.append((a, s, mp, repr(e)))
    print(f"\n{len(results)} cells compiled OK, {len(failures)} failed")
    for f in failures:
        print("FAILED:", f)
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(results, fh, indent=1)
        print(f"wrote {args.out}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
