"""Coordinated context switches (`ServeStats.parks`) per 1,000 tokens
decoded in the window. Source: the program's counter."""


def read(run):
    if not run.tokens:
        return None
    return run.stats["parks"] * 1000.0 / run.tokens
