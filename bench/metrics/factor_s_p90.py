"""``factor_s_p90``: the 90th percentile of the seconds of every
factorization in the window, each timed on the host clock from its init to
its finished factors; over all of them, with linear interpolation."""

import numpy as np


def read(run):
    if run.trace is not None or not run.job_s:
        return None
    return float(np.percentile(run.job_s, 90))
