"""Output tokens delivered in the window, per second of the window."""


def read(w):
    n = sum(1 for r in w.requests for t in r.tokens if 0.0 < t <= w.window_s)
    return n / w.window_s if n else None
