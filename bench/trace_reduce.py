"""Reduce a profiler trace (`*.xplane.pb`) to the numbers the per-layer
metrics read.

What the trace holds on a TPU v5e (read by hand from a trace of this
benchmark recorded on the chip; an excerpt is in `tests/data/`):

- The device plane is named `/device:TPU:0`. Its line "XLA Modules" has
  one event per program execution, named `<module>(<program id>)`: the
  decode step is `jit_step`, each eager operation of the engine's host
  policy is a program of its own (`jit_squeeze`, `jit_scatter`, ...). Its
  line "XLA Ops" has one event per HLO instruction that ran, named by the
  instruction's text (`%paged_decode_attention_pallas.7 = bf16[...]
  custom-call(...)`), with no stat naming its program: an op belongs to
  the module execution whose interval holds its start.
- The host plane `/host:CPU` has a line per thread; the benchmark's own
  spans are the events whose names start with "bench.".

The reduction keeps only what lies inside the benchmark's "bench.window"
span, on the host's clock, which the profiler puts device events on too.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

DEVICE = "/device:TPU:0"
SPAN_PREFIX = "bench."


def merge(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def overlap_ns(merged, lo, hi) -> int:
    """Time of the merged (sorted, disjoint) intervals inside [lo, hi)."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


# control flow around other ops (a scan's while loop): its time is its
# children's, so it is left out of the per-op table
CONTAINER = re.compile(r"(while|conditional|call)(\.\d+)?$")


def _module_name(event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", event_name)


def _op_name(event_name: str) -> str:
    """"%fusion.12 = bf16[8]{0} fusion(...)" -> "fusion.12"."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def reduce_profile(profile) -> dict:
    """`profile`: a `jax.profiler.ProfileData`. Every cell runs on one chip,
    so only `/device:TPU:0` is read. Returns a JSON-able dict:

    window_ns      [start, end] of the bench.window span
    busy_ns        union of the device's op and program intervals in the window
    spans          {name: [[start, end], ...]} of bench.* host spans
    modules        {module: {"calls": n, "ns": device time}}
    ops            {module: {op: {"calls": n, "ns": device time}}}
    busy           merged busy intervals (for gap attribution)
    """
    spans = defaultdict(list)
    device = None
    for plane in profile.planes:
        if plane.name == DEVICE:
            device = plane
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans[ev.name].append([int(ev.start_ns), int(ev.end_ns)])
    if not spans.get("bench.window"):
        raise ValueError("the trace holds no bench.window span")
    lo, hi = spans["bench.window"][0]
    lines = {line.name: line for line in device.lines} if device is not None else {}
    runs = sorted((int(ev.start_ns), int(ev.end_ns), _module_name(ev.name))
                  for ev in (lines["XLA Modules"].events if "XLA Modules" in lines else ()))
    starts = [r[0] for r in runs]
    modules = defaultdict(lambda: {"calls": 0, "ns": 0})
    for s, e, name in runs:
        if lo < e and s < hi:
            modules[name]["calls"] += 1
            modules[name]["ns"] += min(e, hi) - max(s, lo)
    ops = defaultdict(lambda: defaultdict(lambda: {"calls": 0, "ns": 0}))
    intervals = []
    for ev in lines["XLA Ops"].events if "XLA Ops" in lines else ():
        s, e = int(ev.start_ns), int(ev.end_ns)
        if e <= lo or s >= hi:
            continue
        intervals.append((s, e))  # module executions are added below
        op = _op_name(ev.name)
        if CONTAINER.match(op):
            continue
        i = bisect.bisect_right(starts, s) - 1
        module = runs[i][2] if i >= 0 and s < runs[i][1] else "?"
        rec = ops[module][op]
        rec["calls"] += 1
        rec["ns"] += min(e, hi) - max(s, lo)
    busy = merge(clip(intervals + [(s, e) for s, e, _ in runs], lo, hi))
    return {
        "window_ns": [lo, hi],
        "busy_ns": sum(e - s for s, e in busy),
        "spans": {k: sorted(v) for k, v in spans.items()},
        "modules": {k: dict(v) for k, v in modules.items()},
        "ops": {m: {k: dict(v) for k, v in d.items()} for m, d in ops.items()},
        "busy": busy,
    }


def reduce_file(path) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)))


def idle_gaps(red: dict, top: int = 10) -> list:
    """The longest device-idle gaps in the window, each named by the
    innermost bench.* host span that covers its middle."""
    lo, hi = red["window_ns"]
    edges = [lo] + [t for iv in red["busy"] for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        names = [(b - a, name) for name, ivs in red["spans"].items()
                 if name != "bench.window" for a, b in ivs if a <= mid < b]
        out.append([min(names)[1] if names else "bench.window", (e - s) / 1e9])
    return out


def top_ops(red: dict, top: int = 10) -> list:
    flat = [(rec["ns"], f"{module}/{op}") for module, d in red["ops"].items()
            for op, rec in d.items()]
    return [[name, ns / 1e9] for ns, name in sorted(flat, reverse=True)[:top]]
