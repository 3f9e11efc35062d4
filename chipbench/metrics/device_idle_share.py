"""Device: share of the window with no operation running on the chip (%),
1 - busy / window from the profiler trace, averaged over the chips."""


def read(w):
    if w.device is None or w.device.window_s <= 0:
        return None
    return 100.0 * (1.0 - w.device.busy_s / w.device.window_s)
