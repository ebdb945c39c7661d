"""Share of its roofline that the paged-attention Pallas kernel reaches, in %.

The least time the chip needs for the window's paged-attention calls is the
larger of their bytes over peak HBM bandwidth and their FLOPs over peak
bf16 FLOP/s (bench/flops.py; bytes: K and V of each scheduled row's valid
pages, counted from below, plus q and the output). The kernel's time is the
device time of its custom call inside the decode step (module "jit_step")
on the trace's "XLA Ops" line. The compiled step names that call after the
function that wraps the `pallas_call`, "paged_decode_attention_pallas.<n>"
(read from the compiled program's HLO for a v5e). A decode step that ran
with no such op is an error, not a missing reading.
"""
from bench.flops import paged_attn_bytes, paged_pages_lower_bound

MODULE, KERNEL = "jit_step", "paged_decode_attention_pallas"


def read(run):
    ops = run.trace["ops"].get(MODULE, {})
    ns = sum(rec["ns"] for name, rec in ops.items() if name.startswith(KERNEL))
    if not run.tokens:
        return None
    if not ns:
        raise ValueError(f"no {KERNEL} op inside {MODULE} in the trace: {sorted(ops)[:20]}")
    cfg, eng = run.cell.config, run.cell.mix["engine"]
    page = eng["page_size"]
    nbytes = flops = 0
    for s in run.steps:
        pages = [paged_pages_lower_bound(c, page, eng["log_slots"], eng["batch"])
                 for c in s.contexts]
        nbytes += paged_attn_bytes(cfg, pages, page)
        flops += cfg["n_layers"] * sum(4 * cfg["n_heads"] * cfg["head_dim"] * p * page
                                       for p in pages)
    least = max(nbytes / run.peaks["hbm_bytes_per_s"],
                flops / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / (ns / 1e9)
