"""Share of the XR-class requests due in the window whose last token came
within their deadline; an unfinished request has missed it."""


def read(w):
    xr = [r for r in w.requests if r.xr and 0.0 <= r.due < w.window_s]
    if not xr:
        return None
    met = sum(1 for r in xr if r.finished is not None
              and r.finished <= w.window_s
              and (r.finished - r.due) * 1e3 <= r.deadline_ms)
    return 100.0 * met / len(xr)
