"""Device time per decode group step: the traced window's executions of
the end and cloud decode-stage programs, summed, over the number of end
stage executions, in milliseconds."""

from benchlib import trace as T

END = ("jit_end_step",)
CLOUD = ("jit_cloud_step",)


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    ends = T.modules_matching(run.trace, END, lo, hi)
    if not ends:
        return None
    clouds = T.modules_matching(run.trace, CLOUD, lo, hi)
    return sum(d for _, _, d in ends + clouds) / len(ends) / 1e6
