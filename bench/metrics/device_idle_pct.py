"""``device_idle_pct``: 100 x (1 - device busy / window) of the traced window,
busy being the union of the device operations' intervals; on several chips,
the chip that idles most."""


def read(run):
    tr = run.trace
    if tr is None or not tr.chips:
        return None
    w = tr.window_s()
    return max(100.0 * (1.0 - tr.busy_s(c) / w) for c in tr.chips)
