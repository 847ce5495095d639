"""Share of the decode step's roofline: the least time of the decode
calls over their device time.  A call's least time is the larger of its
operations over peak and its bytes over HBM bandwidth, counted from
shapes (bench/counts.py): packed weights with their scales, the tied
embedding as LM head, and the keys and values of live positions only."""

from bench import counts

DECODE = "jit__decode_impl"


def least_times(w):
    peak, bw = w.peaks["bf16_flops"], w.peaks["hbm_bytes_per_s"]
    out = []
    for keys in w.decode_calls:
        flops, nbytes = counts.decode_call(w.dims, keys)
        out.append((flops / peak, nbytes / bw))
    return out


def read(w):
    calls = (w.trace or {}).get("programs", {}).get(DECODE)
    least = least_times(w)
    if not calls or not least:
        return None
    mean_least = sum(max(c, b) for c, b in least) / len(least)
    return 100.0 * mean_least / (sum(calls) / len(calls))
