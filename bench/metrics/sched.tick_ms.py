"""Median host wall time of a scheduler tick, from the program's
``sched.tick`` spans in the profiler trace."""

import statistics

from bench import program_spans


def read(w):
    t = program_spans.of(w)
    if t is None or not t.ticks:
        return None
    return statistics.median(s.ms for s in t.named(program_spans.TICK))
