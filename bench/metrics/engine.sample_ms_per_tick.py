"""Host time spent sampling tokens per tick: the program's
``engine.sample`` spans (key split, eager sampling ops, readback of the
tokens) summed over the traced window, over its ticks."""

from bench import program_spans


def read(w):
    return program_spans.per_tick_ms(w, "engine.sample")
