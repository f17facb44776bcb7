"""Mean duration of the engine's ``step()`` inside the window, from the
harness's spans around each call (host clock), in milliseconds."""

import numpy as np

from benchlib import stats


def read(run):
    ticks = stats.step_ticks(run.window)
    return float(np.mean([t.end - t.start for t in ticks])) * 1e3 if ticks else None
