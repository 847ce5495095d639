"""Bytes the weight pages moved over the host link per tick (MB = 1e6
bytes): the growth of the pager's wire-byte counter between the first and
the last fence of the traced window, over the ticks in between."""


def read(w):
    f = w.fences
    if len(f) < 2 or f[-1][1] == f[0][1]:
        return None
    return (f[-1][1] - f[0][1]) / 1e6 / (len(f) - 1)
