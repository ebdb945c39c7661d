"""Model FLOP utilisation of the device's busy time in the window, in % of
the chip's bf16 peak: the model FLOPs of every token decoded in the window,
at the context each attended, over (device busy time x peak). The count is
`bench/flops.py::decode_token_flops`: the configuration's reference
module's own where it defines one, else the dense count (2 per matmul
weight including the unembedding, plus q.k and p.v over the context). The
busy time is the union of the device's operations in the traced window
(trace_reduce), not the host's window, which the profiler stretches; the
host's share is `device_idle_share`, and decode_mfu x (1 - idle share) is
the share over the whole window. Padding rows of a partly filled batch do
no model work and count nothing."""
from bench.flops import decode_token_flops


def read(run):
    if not run.tokens or not run.trace["busy_ns"]:
        return None
    cfg, root = run.cell.config, run.cell.root
    flops = sum(decode_token_flops(cfg, c, root=root)
                for s in run.steps for c in s.contexts)
    return 100.0 * flops / (run.trace["busy_ns"] / 1e9 * run.peaks["bf16_flops_per_s"])
