"""Host time per engine step, in ms: the length of the benchmark's
"bench.step" spans (around `TieredEngine.step`) minus the device-busy time
inside them, over the number of steps. Source: the profiler trace."""
from bench.trace_reduce import overlap_ns


def read(run):
    spans = run.trace["spans"].get("bench.step", [])
    lo, hi = run.trace["window_ns"]
    spans = [(a, b) for a, b in spans if lo <= a and b <= hi]
    if not spans:
        return None
    host = sum((b - a) - overlap_ns(run.trace["busy"], a, b) for a, b in spans)
    return host / len(spans) / 1e6
