"""Device: 1 - (union of device-op intervals) / traced window, from the
profiler's trace."""


def read(run):
    tr = run.trace
    if tr is None or not tr.device_ops or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
