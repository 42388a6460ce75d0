"""``cols_per_s``: columns of all factorizations completed in the window,
over the window's seconds (host clock, from the window's start to the end of
its last factorization)."""


def read(run):
    if run.trace is not None or not run.job_s:
        return None
    return run.cols_per_job * len(run.job_s) / run.window_s
