"""95th percentile of the gaps between consecutive output tokens of the
requests that stream (three tokens or more), both tokens delivered in the
window.  Tokens are stamped when a tick hands them back, so the two tokens
of a request's first tick are 0 apart; two-token tracker calls, which
finish in one tick, are one-shot calls with no stream to time."""

import numpy as np


def read(w):
    gaps = [b - a for r in w.requests if r.item.new_tokens >= 3
            for a, b in zip(r.tokens, r.tokens[1:])
            if 0.0 < a and b <= w.window_s]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
