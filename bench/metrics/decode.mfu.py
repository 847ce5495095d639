"""Model FLOPs of all prefill and decode work in the traced window over
what the chip's bf16 peak could do in the window (counted from shapes by
the cell's family: matmuls of the real tokens and attention over their
live positions)."""


def read(w):
    if not w.trace or not (w.decode_calls or w.prefill_calls):
        return None
    d, fam = w.dims, w.family
    flops = sum(fam.decode_call(d, keys)[0] for keys in w.decode_calls)
    flops += sum(fam.prefill_flops(d, t, k) for t, k in w.prefill_calls)
    return 100.0 * flops / (w.trace["window_s"] * w.peaks["bf16_flops"])
