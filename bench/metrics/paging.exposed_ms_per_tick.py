"""Paging wait that blocked a tick (the engine's exposed fence time), per
tick: its growth between the first and the last fence of the traced
window over the ticks in between."""


def read(w):
    f = w.fences
    if len(f) < 2 or f[-1][1] == f[0][1]:
        return None
    return 1e3 * (f[-1][0] - f[0][0]) / (len(f) - 1)
