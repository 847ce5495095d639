"""Seconds from process start to the window's opening: building the
weights and the engine, compiling and warming up, and for a closed loop
the first wave of prefills that runs before the window."""


def read(w):
    return w.setup_s
