"""``stream_ms``: device busy milliseconds in the ``stream`` stage (the
engine's scan over all panels: from the stage's marker to finalize's on the
device), per factorization; on several chips, the busiest chip."""


def read(run):
    tr = run.trace
    if tr is None or not tr.chips or not tr.has_stage("stream") or not run.job_s:
        return None
    return max(1e3 * tr.busy_s(c, "stream") for c in tr.chips) / len(run.job_s)
