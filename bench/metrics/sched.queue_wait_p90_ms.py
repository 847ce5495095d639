"""90th percentile, over the requests due in the window, of the wait from
due time to the tick that gave the request a slot (read from the engine's
slots between ticks); one still waiting at the close counts to the close."""

import numpy as np


def read(w):
    wait = [(r.assigned if r.assigned is not None
             and r.assigned <= w.window_s else w.window_s) - r.due
            for r in w.requests if 0.0 <= r.due < w.window_s]
    return float(np.percentile(wait, 90)) * 1e3 if wait else None
