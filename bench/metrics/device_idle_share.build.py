"""Share of the traced build window in which the device ran nothing:
1 − (union of device op intervals) / window, averaged over the cell's
devices, in %."""


def read(run):
    s = run.trace_summary
    if s is None or s.window_s <= 0:
        return None
    return s.idle_share * 100.0
