"""95th percentile over all the window's requests of first-token time
minus due time (host clock)."""

from benchlib import stats


def read(run):
    return stats.percentile(stats.ttfts(run.window), 95)
