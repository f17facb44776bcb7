"""Prefill time: 95th percentile over the window's requests of the
engine's own stamps, prefill-done time (the prompt's last chunk back from
the cloud tier) minus admit time, in seconds, the profiler's stall after a
traced window left out (``benchlib/stamps.py``).  Requests whose prefill
did not finish, and engines without the stamps, give nothing."""

from benchlib import stamps, stats


def read(run):
    took = stamps.durations(run, "admit_time", "prefill_done_time")
    return stats.percentile(took, 95) if took else None
