"""``factor_mfu``: the least time the chips could take for one
factorization's required work (``work/``: the larger of operations over the
peak rate and bytes over the peak bandwidth, over all chips of the cell),
times the factorizations completed per second of the traced window, in
percent."""

import work


def read(run):
    tr = run.trace
    if tr is None or not tr.chips or not run.job_s:
        return None
    least = work.least_seconds(run.work, run.peak, run.chips)
    return 100.0 * least * len(run.job_s) / tr.window_s()
