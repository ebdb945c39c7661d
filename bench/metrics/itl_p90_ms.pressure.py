"""The 90th percentile of the window's inter-token gaps, in ms, as
`itl_p90_ms` takes it (`harness.itl_samples`), in a cell where it is not
steady enough to be held to a bound: under pool pressure most gaps are one
step and the parked requests' are many, and the percentile falls on the
edge between the two, so it moves by a whole step with one step more or
less in the window. Source: the host's clock, in the traced run."""
import numpy as np


def read(run):
    return float(np.percentile(run.itl, 90)) * 1e3 if run.itl else None
