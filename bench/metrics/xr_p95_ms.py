"""95th percentile, over the XR-class requests due in the window, of last
token minus due time; an unfinished request counts as window end minus
due time."""

import numpy as np


def read(w):
    lat = [(r.finished if r.finished is not None
            and r.finished <= w.window_s else w.window_s) - r.due
           for r in w.requests if r.xr and 0.0 <= r.due < w.window_s]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
