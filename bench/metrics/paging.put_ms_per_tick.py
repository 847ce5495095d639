"""Page-worker time per tick spent in ``jax.device_put`` of fetched
pages: the program's ``paging.put`` spans summed over the traced window,
over its ticks."""

from bench import program_spans


def read(w):
    return program_spans.per_tick_ms(w, "paging.put")
