"""``panel_update_roofline``: the ``panel_update`` Pallas kernel's share of its
roofline, in percent: its calls in the traced window times the least time
one call could take (``work/panel_update.py``: the larger of operations over
the peak rate and bytes over the peak bandwidth), over the kernel's summed
device time. Nothing is read where the kernel did not run."""

import work
from work import panel_update

# the kernel's device operation as the TPU trace names it
PATTERN = r"^%panel_update_kernel"


def read(run):
    tr = run.trace
    if tr is None or not tr.chips:
        return None
    least = work.least_seconds(panel_update.count(run.config), run.peak)
    calls, seconds = 0, 0.0
    for chip in tr.chips:
        n, s = tr.op_stats(chip, PATTERN)
        calls, seconds = calls + n, seconds + s
    if not calls:
        return None
    return 100.0 * calls * least / seconds
