"""How late the load generator sent: 95th percentile over the window's
requests of send time minus due time (host clock), in milliseconds.  Open
loop only: a closed-loop client sends the moment it may."""

from benchlib import stats


def read(run):
    if run.loop != "open":
        return None
    return stats.percentile(stats.lags(run.window), 95) * 1e3
