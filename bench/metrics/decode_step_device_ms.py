"""Device time of one call of the compiled decode step, in ms: its
executions on the trace's "XLA Modules" line, summed over the window, over
the number of calls. The program is the jitted `step` that
`tiering.build_paged_decode_step` returns; its HLO module is "jit_step"
(read from the compiled program's HLO for a v5e). A window that decoded
tokens ran the step, so a trace that holds no call of it is an error, not
a missing reading."""

MODULE = "jit_step"


def read(run):
    rec = run.trace["modules"].get(MODULE)
    if not rec or not rec["calls"]:
        if run.tokens:
            raise ValueError(f"{run.tokens} tokens decoded but no {MODULE} in the trace: "
                             f"{sorted(run.trace['modules'])[:20]}")
        return None
    return rec["ns"] / rec["calls"] / 1e6
