"""Output tokens delivered inside the window, by any request (a closed
loop's pre-roll requests included), over the window's seconds."""

from benchlib import stats


def read(run):
    w = run.window
    return stats.tokens_between(w, w.t0, w.t1) / (w.t1 - w.t0)
