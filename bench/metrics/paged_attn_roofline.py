"""Share of its roofline that the paged-attention Pallas kernel reaches, in %.

The least time the chip needs for the window's paged-attention calls is the
larger of their bytes over peak HBM bandwidth and their FLOPs over peak
bf16 FLOP/s. Both come from `bench/flops.py::paged_attn_work`, one decode
step at a time: the configuration's reference module's own count where it
defines one, else the dense count (bytes: K and V of each scheduled row's
valid pages, counted from below, plus q and the output). The kernel's time
is the device time of its custom call inside the decode step (module
"jit_step") on the trace's "XLA Ops" line. The compiled step names that
call after the function that wraps the `pallas_call`,
"paged_decode_attention_pallas.<n>" (read from the compiled program's HLO
for a v5e). A decode step that ran with no such op is an error, not a
missing reading.
"""
from bench.flops import paged_attn_work

MODULE, KERNEL = "jit_step", "paged_decode_attention_pallas"


def read(run):
    ops = run.trace["ops"].get(MODULE, {})
    ns = sum(rec["ns"] for name, rec in ops.items() if name.startswith(KERNEL))
    if not run.tokens:
        return None
    if not ns:
        raise ValueError(f"no {KERNEL} op inside {MODULE} in the trace: {sorted(ops)[:20]}")
    cfg, eng, root = run.cell.config, run.cell.mix["engine"], run.cell.root
    nbytes = flops = 0
    for s in run.steps:
        b, f = paged_attn_work(cfg, s.contexts, eng, root=root)
        nbytes, flops = nbytes + b, flops + f
    least = max(nbytes / run.peaks["hbm_bytes_per_s"],
                flops / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / (ns / 1e9)
