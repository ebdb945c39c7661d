#!/usr/bin/env python3
"""Run one benchmark cell on the chip JAX finds and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell is looked up in `BENCHMARK.json`.
The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics), `device`, with `--trace 1` a
`breakdown`, and last `check`, each compared number with its limit. The
run exits non-zero with no such line when JAX finds no TPU, fewer chips
than the cell asks for, or no program (`src/repro`) in the checkout. The
persistent compilation cache is `<checkout>/.jax_cache`.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="with --trace 1, write the profile under DIR and keep it")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no program at {ROOT / 'src' / 'repro'}")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)  # JAX writes no entry into a missing directory
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = harness.load_cell(ROOT, args.workload)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no device: {e}")
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < cell.chips:
        fail(f"the cell asks for {cell.chips} chips, JAX finds {len(devices)}")
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START,
                         keep_trace=args.keep_trace)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
