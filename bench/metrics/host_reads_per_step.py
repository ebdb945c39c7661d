"""Device->host reads of the engine's host policy per step: the
`ServeStats.host_reads` delta over the window (every read `TieredEngine`
makes goes through its counted `_fetch`) over the number of window steps.
A program without the counter reports nothing. Source: the program's
counter."""


def read(run):
    if "host_reads" not in run.stats or not run.steps:
        return None
    return run.stats["host_reads"] / len(run.steps)
