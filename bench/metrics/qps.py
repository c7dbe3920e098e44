"""qps: queries answered per second over the whole window (closed backlog).

Every query answered in the window, over the window's whole length on the
host clock: from the first submit to the end of the last drain.
"""
import numpy as np


def read(run):
    if run.traffic["arrivals"] != "backlog":
        return None
    w = run.window
    return int(np.sum(np.all(w.ids >= 0, 1))) / w.seconds
