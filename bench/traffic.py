"""The one traffic generator: turns a mix file (``bench/traffic/<mix>.json``)
and a seed into requests.

Every seed gets the same set of sizes and arrival gaps, in another order:
sizes are the quantiles ``(i + 0.5) / n`` of the stream's distribution and
Poisson gaps the quantiles of the exponential, permuted by the seed.  Only
the order and the token ids change with the seed, so runs with different
seeds do the same amount of work.

Mix file keys:

- ``loop``: ``"open"`` (requests are due on a schedule, whatever the
  server does) or ``"closed"`` (``clients`` clients, each sending its next
  request when the last one completes; ``requests_per_client`` each).
- ``rate_hz``: the open loop's rate parameter r; a stream's ``arrival``
  runs at ``per_rate * r`` per second, ``periodic`` (from ``phase_s``) or
  ``poisson``.
- ``scheduler``: ``prefill_chunk``, ``token_budget``, ``preemptive`` and
  ``admission`` of the serving scheduler.
- ``trace_seconds`` (optional): a ``--trace 1`` run's window is at most
  this long (the profiler's trace of a busy chip grows by megabytes a
  second).
- ``streams``: name, priority, ``deadline_ms`` (null = best effort),
  ``xr`` (counted as an XR-class stream), and ``prompt`` / ``new_tokens``
  length distributions: ``fixed`` (value), ``uniform`` (lo..hi inclusive)
  or ``lognormal`` (median, sigma, clipped to lo..hi).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Item:
    """One request as generated: due time (open loop, seconds from the
    window's start) or client index (closed loop)."""
    uid: int
    stream: str
    prompt: np.ndarray
    new_tokens: int
    due_s: Optional[float] = None
    client: Optional[int] = None


def quantile_sizes(dist: Dict, n: int) -> List[int]:
    """``n`` integer sizes at the quantiles (i + 0.5) / n of ``dist``."""
    kind = dist["dist"]
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if kind == "fixed":
            v = dist["value"]
        elif kind == "uniform":
            lo, hi = dist["lo"], dist["hi"]
            v = lo + min(int(u * (hi - lo + 1)), hi - lo)
        elif kind == "lognormal":
            z = NormalDist().inv_cdf(u)
            v = int(round(dist["median"] * math.exp(dist["sigma"] * z)))
            v = min(max(v, dist["lo"]), dist["hi"])
        else:
            raise ValueError(f"unknown length distribution {kind!r}")
        out.append(int(v))
    return out


def _arrivals(arrival: Dict, rate_hz: float, seconds: float,
              rng: np.random.Generator) -> List[float]:
    per_s = arrival["per_rate"] * rate_hz
    if per_s <= 0:
        return []
    if arrival["kind"] == "periodic":
        phase = arrival.get("phase_s", 0.0)
        n = int(math.floor((seconds - phase) * per_s - 1e-9)) + 1
        return [phase + k / per_s for k in range(max(n, 0))]
    if arrival["kind"] == "poisson":
        n = max(int(round(seconds * per_s)), 1)
        gaps = [-math.log(1.0 - (i + 0.5) / n) / per_s for i in range(n)]
        gaps = [gaps[i] for i in rng.permutation(n)]
        # the first due at 0, then the permuted gaps: n arrivals for
        # every seed (the n - 1 gaps sum to less than ``seconds``)
        times = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
        return [float(t) for t in times if t < seconds]
    raise ValueError(f"unknown arrival kind {arrival['kind']!r}")


def _prompts(rng: np.random.Generator, lengths: List[int],
             vocab: int) -> List[np.ndarray]:
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]


def open_loop(mix: Dict, seed: int, seconds: float, vocab: int,
              rate_hz: Optional[float] = None) -> List[Item]:
    """Requests due in ``[0, seconds)``, sorted by due time."""
    rng = np.random.default_rng(seed)
    rate = mix["rate_hz"] if rate_hz is None else rate_hz
    items: List[Item] = []
    for s in mix["streams"]:
        due = _arrivals(s["arrival"], rate, seconds, rng)
        n = len(due)
        if n == 0:
            continue
        order = rng.permutation(n)
        plen = [quantile_sizes(s["prompt"], n)[i] for i in order]
        order = rng.permutation(n)
        nnew = [quantile_sizes(s["new_tokens"], n)[i] for i in order]
        for t, p, k in zip(due, _prompts(rng, plen, vocab), nnew):
            items.append(Item(uid=-1, stream=s["name"], prompt=p,
                              new_tokens=k, due_s=t))
    items.sort(key=lambda it: (it.due_s, it.stream))
    for uid, it in enumerate(items):
        it.uid = uid
    return items


def closed_loop(mix: Dict, seed: int, vocab: int) -> List[List[Item]]:
    """Each client's queue of requests, in the order it sends them."""
    rng = np.random.default_rng(seed)
    (s,) = mix["streams"]
    clients, per = mix["clients"], mix["requests_per_client"]
    n = clients * per
    plen = quantile_sizes(s["prompt"], n)
    nnew = quantile_sizes(s["new_tokens"], n)
    plen = [plen[i] for i in rng.permutation(n)]
    nnew = [nnew[i] for i in rng.permutation(n)]
    prompts = _prompts(rng, plen, vocab)
    queues: List[List[Item]] = [[] for _ in range(clients)]
    for uid in range(n):
        c = uid % clients
        queues[c].append(Item(uid=uid, stream=s["name"], prompt=prompts[uid],
                              new_tokens=nnew[uid], client=c))
    return queues


def length_range(mix: Dict, key: str) -> tuple:
    """(min, max) a stream's ``key`` distribution can give, over streams."""
    lo, hi = [], []
    for s in mix["streams"]:
        d = s[key]
        if d["dist"] == "fixed":
            lo.append(d["value"])
            hi.append(d["value"])
        else:
            lo.append(d["lo"])
            hi.append(d["hi"])
    return min(lo), max(hi)
