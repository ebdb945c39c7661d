"""Device time of one call of the promotion copy, in ms. The engine
compiles `tiering.promote_pages` once, and launches it at most once a step
for all the pages that step promotes; its HLO module is named
"jit_promote_pages". The program counts its launches in
`ServeStats.promote_calls`. A program without that counter reports nothing,
as does a window in which nothing was promoted; one whose counter counts
launches but whose trace holds no such module is an error."""

MODULE = "jit_promote_pages"


def read(run):
    calls = run.stats.get("promote_calls")
    if not calls:
        return None
    rec = run.trace["modules"].get(MODULE)
    if not rec or not rec["calls"]:
        raise ValueError(f"{calls} promotion launches counted but no module "
                         f"{MODULE} in the trace")
    return rec["ns"] / rec["calls"] / 1e6
