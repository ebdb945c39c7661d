"""Pages promoted from the slow tier to the fast pool
(`ServeStats.promoted_pages`) per 1,000 tokens decoded in the window.
Source: the program's counter."""


def read(run):
    if not run.tokens:
        return None
    return run.stats["promoted_pages"] * 1000.0 / run.tokens
