"""``osnap_roofline``: the least time the OSNAP sketches of one single-pass
SVD could take on the ``countsketch`` kernel, in percent of the kernel's
summed device time: whatever implements them, the sketches must read ``A``
once (``m n`` float32 entries at the peak HBM bandwidth), times the
factorizations of the traced window, over the summed device time of the
``countsketch`` operations there. A fixed least, not a per-call count: it
stays at or under 100% whichever calls a later route makes. Nothing is read
where the kernel did not run."""

import work

# the kernel's device operation as the TPU trace names it
PATTERN = r"^%countsketch"


def read(run):
    tr = run.trace
    if tr is None or not tr.chips or not run.job_s:
        return None
    calls, seconds = 0, 0.0
    for chip in tr.chips:
        n, s = tr.op_stats(chip, PATTERN)
        calls, seconds = calls + n, seconds + s
    if not calls:
        return None
    d = run.config["data"]
    # one read of A in all, against the kernel time summed over every chip
    least = work.F32 * d["m"] * d["n"] / run.peak["hbm_bytes_per_s"]
    return 100.0 * least * len(run.job_s) / seconds
