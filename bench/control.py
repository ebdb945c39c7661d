#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, in one process: one run of the cell (set-up and a window of
`--seconds`), then the widest gap of the served tokens under the plain
reference (the program's reading) and the widest gap of the tokens that the
control, the reference in float8, ranks first at the same positions (the
control's reading). Prints one JSON line per seed. The benchmark's own runs
do not run this; `bench/limits/<cell>.json` records what it read.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def readings(cell, seed: int, seconds: float, log=sys.stderr) -> dict:
    from bench import check, harness

    t = time.perf_counter()
    result, served = harness.measure(cell, seed, seconds, False, t, log)
    gc.collect()
    program = check.gaps(cell.root, cell.config, seed, served)
    control = check.gaps(cell.root, cell.config, seed, served, control=True)
    return {"seed": seed, "program": max(program.values()),
            "control": max(control.values()),
            "served_tokens": sum(len(o) for _, o in served.values()),
            "metrics": {k: v["value"] for k, v in result["metrics"].items()}}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)  # JAX writes no entry into a missing directory
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness

    cell = harness.load_cell(ROOT, args.workload)
    if jax.devices()[0].platform != "tpu":
        sys.exit("bench: no TPU")
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds)), flush=True)


if __name__ == "__main__":
    main()
