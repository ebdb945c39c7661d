"""Device time of one call of the compiled log compaction, in ms. The
engine jits a local function named `compact_log` around
`tiering.compact_log`, whose HLO module is named "jit_compact_log". The
reader does not depend on that name: it finds the program as the module
that runs the compaction kernel, the Pallas
call whose HLO instruction is named "log_compact_pallas.<n>" (read from the
compiled program's HLO for a v5e). A window in which no compaction ran
reports nothing; one whose counters count compactions but whose trace holds
no such module is an error."""

KERNEL = "log_compact_pallas"


def read(run):
    mods = [m for m, ops in run.trace["ops"].items()
            if any(op.startswith(KERNEL) for op in ops)]
    recs = [run.trace["modules"][m] for m in mods if m in run.trace["modules"]]
    calls = sum(r["calls"] for r in recs)
    if not calls:
        if run.stats.get("compactions"):
            raise ValueError(f"{run.stats['compactions']} compactions counted but no "
                             f"module running {KERNEL} in the trace")
        return None
    return sum(r["ns"] for r in recs) / calls / 1e6
