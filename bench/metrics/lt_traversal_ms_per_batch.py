"""Device milliseconds of the dense LT traversal program per 64-color
batch.

The device time of every run of the LT block program
(``run_fused_lt_block``) in the traced window, over the batches the
window completed (the driver's count).  Nothing when the program did not
run."""

PROGRAM = r"run_fused_lt_block"


def read(run):
    s, batches = run.trace_summary, run.counters.get("batches", 0)
    if s is None or not batches:
        return None
    seconds, runs = s.program_seconds(PROGRAM)
    if not runs:
        return None
    return seconds / batches * 1e3
