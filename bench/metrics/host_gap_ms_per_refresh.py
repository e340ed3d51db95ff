"""Device idle milliseconds between programs, per pool refresh.

The traced window less the device time of every program run in it (the
``XLA Modules`` intervals: the stretches in which no program ran on the
device, so the host held it), over the refresh calls of the window.  The
idle time inside programs is the rest of ``device_idle_share.build``."""


def read(run):
    s, calls = run.trace_summary, run.counters.get("calls", 0)
    if s is None or not calls or not s.programs:
        return None
    return (s.window_s - sum(s.programs.values())) / calls * 1e3
