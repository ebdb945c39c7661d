#!/usr/bin/env python3
"""Split the host time of a traced benchmark run by the engine's phases.

    python3 bench/run.py --workload <cell> ... --trace 1 --keep-trace DIR
    python3 bench/phases.py DIR

`TieredEngine.step` marks its phases with host spans named "tiered.<phase>"
(compact, residency, promote, decode, lru), each inside the benchmark's
"bench.step" span. `trace_reduce.reduce_profile` keeps only the "bench.*"
spans, so this reads the "tiered.*" spans of the same profile itself and
applies `host_ms_per_step`'s rule to them: a span's host time is its length
minus the device-busy time inside it, summed over the spans inside the
window, over the number of "bench.step" spans. Prints one JSON object:

host_ms_per_step    `host_ms_per_step`'s number
phases              {phase: host ms per step}; phases that never ran read 0
unspanned_ms        host ms per step inside "bench.step" but in no phase
share               {phase: % of host_ms_per_step}
idle_gaps           the longest device-idle gaps, each named by the innermost
                    span of either kind that covers its middle
device_ops          the device's top operations, as the breakdown gives them
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402

PREFIX = "tiered."
PHASES = ("compact", "residency", "promote", "decode", "lru")


def program_spans(profile) -> dict:
    """{name: [[start, end], ...]} of the program's "tiered.*" host spans."""
    spans = {}
    for plane in profile.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        spans.setdefault(ev.name, []).append(
                            [int(ev.start_ns), int(ev.end_ns)])
    return {k: sorted(v) for k, v in spans.items()}


def span_host_ns(red: dict, intervals) -> int:
    """Host time of the spans that lie inside the window: each one's length
    minus the device-busy time inside it."""
    lo, hi = red["window_ns"]
    return sum((b - a) - trace_reduce.overlap_ns(red["busy"], a, b)
               for a, b in intervals if lo <= a and b <= hi)


def split(red: dict, spans: dict) -> dict | None:
    """The phase split of one reduced trace and its program spans; None
    where the window holds no "bench.step" span."""
    lo, hi = red["window_ns"]
    steps = [s for s in red["spans"].get("bench.step", []) if lo <= s[0] and s[1] <= hi]
    if not steps:
        return None
    per_step = lambda ns: ns / len(steps) / 1e6  # noqa: E731
    host = per_step(span_host_ns(red, steps))
    phases = {p: per_step(span_host_ns(red, spans.get(PREFIX + p, []))) for p in PHASES}
    return {
        "host_ms_per_step": host,
        "phases": phases,
        "unspanned_ms": host - sum(phases.values()),
        "share": {p: 100.0 * v / host if host else None for p, v in phases.items()},
        "idle_gaps": trace_reduce.idle_gaps({**red, "spans": {**red["spans"], **spans}}),
        "device_ops": trace_reduce.top_ops(red),
    }


def main(argv=None) -> None:
    import argparse

    from jax.profiler import ProfileData

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("profile", help="a profile (.xplane.pb) or a directory holding one")
    path = Path(ap.parse_args(argv).profile)
    if path.is_dir():
        path = max(path.rglob("*.xplane.pb"), key=lambda f: f.stat().st_mtime)
    profile = ProfileData.from_file(str(path))
    print(json.dumps(split(trace_reduce.reduce_profile(profile), program_spans(profile))))


if __name__ == "__main__":
    main()
