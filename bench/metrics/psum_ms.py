"""``psum_ms``: milliseconds per factorization in which an all-reduce ran
or was in flight on the device (synchronous, or from its start to its done),
on the chip that spends most on them."""

PATTERN = r"all-reduce"


def read(run):
    tr = run.trace
    if tr is None or not tr.chips or not run.job_s:
        return None
    per_chip = [tr.in_flight_s(c, PATTERN) for c in tr.chips]
    if not any(per_chip):
        return None
    return 1e3 * max(per_chip) / len(run.job_s)
