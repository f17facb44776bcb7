"""Device time per prefill chunk: the traced window's executions of the
end and cloud prefill-chunk programs, summed, over the number of end
chunk executions, in milliseconds."""

from benchlib import trace as T

END = ("jit_end_prefill_chunk",)
CLOUD = ("jit_cloud_prefill_chunk",)


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    ends = T.modules_matching(run.trace, END, lo, hi)
    if not ends:
        return None
    clouds = T.modules_matching(run.trace, CLOUD, lo, hi)
    return sum(d for _, _, d in ends + clouds) / len(ends) / 1e6
