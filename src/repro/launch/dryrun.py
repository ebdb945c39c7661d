import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
).strip()

# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces a JSON artifact with:
  * ok / error
  * compile seconds
  * cost_analysis flops & bytes (per-device, SPMD-partitioned program)
  * per-collective traffic estimate parsed from the partitioned HLO
  * memory_analysis output (backend-dependent; best-effort on CPU)
  * derived roofline terms (v5e constants; see benchmarks/roofline.py)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import gc
import json
import re
import time
from pathlib import Path
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, OptimConfig, get_config, shape_applicable
from repro.distributed.sharding import (
    batch_spec,
    filter_spec_for_mesh,
    param_specs,
)
from repro.launch.mesh import dp_size, make_production_mesh
from repro.launch.steps import (
    abstract_train_state,
    build_prefill_step,
    build_serve_step,
    build_train_step,
)
from repro.models.api import ModelSpec

# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute",
)
_SHAPE_RE = re.compile(r"([a-z]\w*)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0
    if not dims:
        return b
    return b * int(np.prod([int(d) for d in dims.split(",") if d]))


def _group_size(line: str, default: int) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([0-9, ]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    return default


def parse_collectives(hlo: str, n_devices: int) -> Dict[str, Any]:
    """Per-device collective traffic estimate (ring schedules) from the
    SPMD-partitioned HLO text."""
    out: Dict[str, Dict[str, float]] = {}
    total = 0.0
    for line in hlo.splitlines():
        op = None
        for c in _COLLECTIVES:
            if f" {c}(" in line or f" {c}-start(" in line:
                op = c
                break
        if op is None:
            continue
        # operand shapes: everything after the opcode's opening paren
        idx = line.find(op)
        operands = line[idx:]
        shapes = _SHAPE_RE.findall(operands)
        op_bytes = sum(_shape_bytes(d, s) for d, s in shapes)
        n = max(_group_size(line, n_devices), 2)
        ring = (n - 1) / n
        if op == "all-reduce":
            traffic = 2.0 * op_bytes * ring
        elif op == "all-gather":
            traffic = op_bytes * (n - 1)  # operand is the local shard
        else:  # reduce-scatter / all-to-all / collective-permute
            traffic = op_bytes * ring if op != "collective-permute" else op_bytes
        rec = out.setdefault(op, {"count": 0, "bytes": 0.0, "traffic": 0.0})
        rec["count"] += 1
        rec["bytes"] += op_bytes
        rec["traffic"] += traffic
        total += traffic
    return {"ops": out, "traffic_bytes": total}


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------


def _tree_shardings(mesh, spec_tree, shape_tree=None):
    """NamedShardings from a PartitionSpec tree, filtered for the mesh."""

    def one(s, shp=None):
        return NamedSharding(mesh, filter_spec_for_mesh(s, mesh, shp))

    if shape_tree is None:
        return jax.tree_util.tree_map(one, spec_tree,
                                      is_leaf=lambda x: isinstance(x, P))
    return jax.tree_util.tree_map(
        lambda s, t: one(s, t.shape), spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def lower_cell(arch: str, shape_name: str, mesh) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    spec = ModelSpec(cfg)
    schema = spec.schema()
    # §Perf layout profiles: REPRO_LAYOUT=dp replicates parameters and
    # spreads the batch over BOTH axes — the right layout for small models
    # whose TP collectives dwarf their compute (whisper-base, smollm).
    layout = os.environ.get("REPRO_LAYOUT", "default")
    if layout == "dp":
        rules = {k: None for k in
                 ("layers", "vocab", "embed", "heads", "kv", "ffn", "inner",
                  "experts")}
        pspecs = param_specs(schema, mesh, rules)
        bspec = P(("data", "model"))
    elif layout == "tp_only":
        # serving layout: no FSDP dim (no optimizer state to shard) —
        # params TP-sharded over "model", replicated over "data"; kills
        # the per-step weight all-gathers that dominate decode cells.
        from repro.distributed.sharding import DEFAULT_RULES

        rules = dict(DEFAULT_RULES)
        rules["embed"] = None
        pspecs = param_specs(schema, mesh, rules)
        bspec = batch_spec(mesh)
    else:
        pspecs = param_specs(schema, mesh)
        bspec = batch_spec(mesh)
    p_shardings = _tree_shardings(mesh, pspecs)
    n_dev = mesh.devices.size
    inputs = spec.input_specs(shape)

    def bshard(sds):
        return NamedSharding(mesh, filter_spec_for_mesh(
            P(*([bspec[0]] + [None] * (len(sds.shape) - 1))), mesh, sds.shape))

    if shape.kind == "train":
        mb = cfg.microbatch.get(shape_name, 8)
        dp = dp_size(mesh)
        accum = max(1, shape.global_batch // max(mb * dp, 1))
        while shape.global_batch % accum or (shape.global_batch // accum) % dp:
            accum -= 1
        step = build_train_step(spec, OptimConfig(), accum_steps=accum)
        state = abstract_train_state(spec)
        opt_sh = jax.tree_util.tree_map(
            lambda _: None, state["opt"], is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)
        )
        state_sh = {
            "params": p_shardings,
            "opt": type(state["opt"])(
                NamedSharding(mesh, P()),
                p_shardings, p_shardings, p_shardings,
            ),
        }
        batch_sh = {k: bshard(v) for k, v in inputs.items()}
        jitted = jax.jit(step, in_shardings=(state_sh, batch_sh), donate_argnums=0)
        args = (state, inputs)
        extra = {"accum_steps": accum}
    elif shape.kind == "prefill":
        step = build_prefill_step(spec)
        in_sh = [p_shardings, bshard(inputs["tokens"])]
        args = [spec.abstract_params(), inputs["tokens"]]
        if "frontend" in inputs:
            in_sh.append(bshard(inputs["frontend"]))
            args.append(inputs["frontend"])
        jitted = jax.jit(step, in_shardings=tuple(in_sh))
        args = tuple(args)
        extra = {}
    else:  # decode
        step = build_serve_step(spec)
        cache_sp = spec.cache_pspec()
        cache_specs = inputs["cache"]
        cache_sh = _tree_shardings(
            mesh,
            {k: cache_sp[k] for k in cache_specs},
            cache_specs,
        )
        jitted = jax.jit(
            step,
            in_shardings=(
                p_shardings,
                cache_sh,
                bshard(inputs["tokens"]),
                NamedSharding(mesh, P()),
            ),
            donate_argnums=1,
        )
        args = (spec.abstract_params(), cache_specs, inputs["tokens"], inputs["pos"])
        extra = {}

    t0 = time.time()
    lowered = jitted.lower(*args)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": dict(zip(mesh.axis_names, [int(s) for s in mesh.devices.shape])),
        "n_devices": int(n_dev),
        "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
        **extra,
    }
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        rec["cost_analysis"] = {
            k: float(v)
            for k, v in ca.items()
            if isinstance(v, (int, float)) and (
                k in ("flops", "bytes accessed", "optimal_seconds")
                or k.startswith("bytes accessed")
            )
        }
    except Exception as e:  # pragma: no cover
        rec["cost_analysis_error"] = str(e)
    try:
        ma = compiled.memory_analysis()
        rec["memory_analysis"] = {
            a: int(getattr(ma, a))
            for a in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes",
            )
            if hasattr(ma, a)
        } or str(ma)
    except Exception as e:  # pragma: no cover
        rec["memory_analysis_error"] = str(e)
    try:
        from repro.launch import hlo_analysis

        hlo = compiled.as_text()
        rec["hlo"] = hlo_analysis.analyze(hlo)  # loop-aware per-device costs
        rec["collectives"] = parse_collectives(hlo, n_devices=int(n_dev))
        rec["hlo_bytes"] = len(hlo)
        del hlo
    except Exception as e:  # pragma: no cover
        rec["collectives_error"] = str(e)
    del compiled, lowered, jitted
    gc.collect()
    return rec


def run_cell(arch: str, shape_name: str, mesh_kind: str, outdir: Path, force=False) -> Dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    path = outdir / mesh_kind / f"{arch}__{shape_name}.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    if path.exists() and not force:
        return json.loads(path.read_text())
    applicable, why = shape_applicable(cfg, shape)
    if not applicable:
        rec = {"arch": arch, "shape": shape_name, "mesh_kind": mesh_kind,
               "skipped": True, "reason": why}
        path.write_text(json.dumps(rec, indent=2))
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    try:
        with jax.set_mesh(mesh):
            rec = lower_cell(arch, shape_name, mesh)
        rec["ok"] = True
    except Exception as e:
        rec = {"arch": arch, "shape": shape_name, "ok": False, "error": f"{type(e).__name__}: {e}"}
    rec["mesh_kind"] = mesh_kind
    path.write_text(json.dumps(rec, indent=2))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()
    outdir = Path(args.out)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape)]

    n_ok = n_fail = n_skip = 0
    for mesh_kind in meshes:
        for a, s in cells:
            t0 = time.time()
            rec = run_cell(a, s, mesh_kind, outdir, force=args.force)
            dt = time.time() - t0
            if rec.get("skipped"):
                tag, n_skip = "SKIP", n_skip + 1
            elif rec.get("ok"):
                tag, n_ok = "OK", n_ok + 1
            else:
                tag, n_fail = "FAIL", n_fail + 1
            print(
                f"[{tag}] {mesh_kind:6s} {a:24s} {s:12s} {dt:6.1f}s "
                f"{rec.get('error', '')[:120]}",
                flush=True,
            )
    print(f"done: ok={n_ok} fail={n_fail} skip={n_skip}")


if __name__ == "__main__":
    main()
