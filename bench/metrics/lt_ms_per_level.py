"""Device milliseconds of one level of the dense LT traversal.

The device time of every run of ``run_fused_lt_block`` in the traced
window, over the levels its batches ran (the ``levels`` counter of each
batch, summed by the ``build_lt`` driver after the window).  A dense
level sweeps every edge whatever the frontier, so this is the price of
one sweep.  Nothing without the program's runs or the level count."""

PROGRAM = r"run_fused_lt_block"


def read(run):
    s, levels = run.trace_summary, run.counters.get("levels")
    if s is None or not levels or levels <= 0:
        return None
    seconds, runs = s.program_seconds(PROGRAM)
    if not runs:
        return None
    return seconds / levels * 1e3
