"""Admission wait: 95th percentile over the window's requests of the
engine's own stamps, admit time (a prefill takes the request's slot) minus
submit time, in seconds, the profiler's stall after a traced window left
out (``benchlib/stamps.py``).  Requests the engine never admitted, and
engines without the stamps, give nothing."""

from benchlib import stamps, stats


def read(run):
    waits = stamps.durations(run, "submit_time", "admit_time")
    return stats.percentile(waits, 95) if waits else None
