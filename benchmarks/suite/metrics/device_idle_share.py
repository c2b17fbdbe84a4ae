"""device_idle_share: 1 - (union of device op intervals / traced window),
in percent; the window spans whole warm fits (device trace)."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * run.trace.idle_share
