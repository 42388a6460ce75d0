"""``finalize_ms``: device busy milliseconds in the ``finalize`` stage (the
core solve: from the stage's marker to the job's end marker on the device),
per factorization; on several chips, the busiest chip."""


def read(run):
    tr = run.trace
    if tr is None or not tr.chips or not tr.has_stage("finalize") or not run.job_s:
        return None
    return max(1e3 * tr.busy_s(c, "finalize") for c in tr.chips) / len(run.job_s)
