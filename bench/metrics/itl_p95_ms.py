"""95th percentile of every gap between consecutive output tokens of every
window request (host clock), in milliseconds."""

from benchlib import stats


def read(run):
    gaps = stats.itls(run.window)
    return stats.percentile(gaps, 95) * 1e3 if gaps else None
