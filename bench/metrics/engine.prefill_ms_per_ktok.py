"""Device time of the prefill programs per 1000 real (unpadded) prompt
tokens, over the traced window."""

PREFILL = "jit_impl"


def read(w):
    calls = (w.trace or {}).get("programs", {}).get(PREFILL)
    tokens = sum(t for t, _k in w.prefill_calls)
    if not calls or not tokens:
        return None
    return 1e3 * sum(calls) / (tokens / 1e3)
