"""Device time per call of the decode program, from the profiler trace."""

DECODE = "jit__decode_impl"


def read(w):
    calls = (w.trace or {}).get("programs", {}).get(DECODE)
    return 1e3 * sum(calls) / len(calls) if calls else None
