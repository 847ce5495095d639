"""90th percentile, over the requests due in the window, of first token
minus due time; a request with no token when the window closes counts as
window end minus due time."""

import numpy as np


def read(w):
    lat = [(r.tokens[0] if r.tokens and r.tokens[0] <= w.window_s
            else w.window_s) - r.due
           for r in w.requests if 0.0 <= r.due < w.window_s]
    return float(np.percentile(lat, 90)) * 1e3 if lat else None
