"""Page-worker time per tick spent checking page CRCs (``zlib.crc32`` over
every streamed byte, read in place in fixed chunks, the larger buffers'
chunks on a small shared thread pool): the program's ``paging.crc`` spans
summed over the traced window, over its ticks."""

from bench import program_spans


def read(w):
    return program_spans.per_tick_ms(w, "paging.crc")
