"""Whether what the window served is right: its greedy tokens against the
plain float32 reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed, is scored by the reference:
the longest request, every request that was preempted and restored (up to
half the sample), and others drawn with weight by their served tokens until
the sample holds ``correct.sample_tokens`` served tokens or
``correct.max_sequences`` requests.  For each served token the reference's
logits at its position give the gap ``best logit - logit of the served
token``; a greedy server that computes right serves tokens whose gap is
rounding.  The number compared is the mean gap over the sample's served
tokens, against the configuration's ``correct.mean_logit_gap``; the widest
gap and the share of tokens that differ from the reference's are read
beside it.

A control is the reference in a lower precision put in the program's
place: at each served position of the same sequences (the same prompts
and served tokens before it) it picks its own greedy token, and that token
is scored and judged exactly as a served token is.
"""

from __future__ import annotations

import importlib
from typing import Dict, List, Optional, Sequence

import numpy as np

from bench.model import Dims
from bench.weights import make_weights

ROW_BLOCK = 128
# the reference module's lower precisions: "int8" (int8 activations into
# the int8 weight matmuls) sits below the bfloat16 operands that the
# program's float32 matmuls take on the TPU; "bfloat16" is read beside it
CONTROLS = ("int8", "bfloat16")
NUMBER = "mean_logit_gap"


def sample(recs, conf: Dict, seed: int) -> List:
    c = conf["correct"]
    done = [r for r in recs if r.req.done and not r.req.truncated
            and len(r.req.generated) > 0]
    if not done:
        return []
    rng = np.random.default_rng(seed + 17)
    longest = max(done, key=lambda r: (len(r.req.generated), r.item.uid))
    picked = [longest]
    restored = [r for r in done if r.req.preemptions > 0 and r is not longest]
    order = rng.permutation(len(restored))
    picked += [restored[i] for i in order[:c["max_sequences"] // 2]]
    rest = [r for r in done if all(r is not p for p in picked)]
    # the others drawn with weight by served tokens, so that a sample of
    # few sequences still holds some hundreds of served tokens
    if rest:
        w = np.array([len(r.req.generated) for r in rest], float)
        order = rng.choice(len(rest), size=len(rest), replace=False,
                           p=w / w.sum())
        for i in order:
            if (len(picked) >= c["max_sequences"]
                    or sum(len(p.req.generated) for p in picked)
                    >= c["sample_tokens"]):
                break
            picked.append(rest[int(i)])
    return picked[:c["max_sequences"]]


def _batch(d: Dims, picked, n_rows: int):
    """Tokens (prompt + served tokens but the last) and targets (the
    served tokens at the positions that produced them), padded to
    ``max_len`` rounded up to a whole number of 128-row blocks."""
    s = -(-d.max_len // ROW_BLOCK) * ROW_BLOCK
    tokens = np.zeros((n_rows, s), np.int32)
    targets = np.zeros((n_rows, s), np.int32)
    mask = np.zeros((n_rows, s), bool)
    for i, r in enumerate(picked):
        p, g = np.asarray(r.req.prompt), list(r.req.generated)
        seq = np.concatenate([p, np.asarray(g[:-1], np.int32)])
        tokens[i, :len(seq)] = seq
        lo = len(p) - 1
        targets[i, lo:lo + len(g)] = g
        mask[i, lo:lo + len(g)] = True
    return tokens, targets, mask


def reference(conf: Dict):
    return importlib.import_module(f"bench.references.{conf['reference']}")


def _numbers(best, at, mask) -> Dict[str, float]:
    gap = (np.asarray(best) - np.asarray(at))[mask]
    return dict(mean_logit_gap=float(gap.mean()),
                max_logit_gap=float(gap.max()),
                disagree=float(np.mean(gap > 0)))


def check(d: Dims, conf: Dict, seed: int, recs,
          controls: Sequence[str] = ()) -> Dict:
    """The comparison's numbers for the served tokens (``program``) and
    for each control named, each a dict of ``mean_logit_gap`` (the number
    compared), ``max_logit_gap`` and ``disagree``; None with no sample."""
    picked = sample(recs, conf, seed)
    out = dict(sequences=len(picked),
               tokens=sum(len(r.req.generated) for r in picked),
               preempted=sum(1 for r in picked if r.req.preemptions),
               program=None)
    if not picked:
        return out
    ref = reference(conf)
    weights = make_weights(d, seed)
    tokens, targets, mask = _batch(d, picked,
                                   conf["correct"]["max_sequences"])
    best, _a, at = ref.score(d, weights, tokens, targets)
    out["program"] = _numbers(best, at, mask)
    for name in controls:
        _b, picks, _t = ref.score(d, weights, tokens, targets, dtype=name)
        ctl = np.where(mask, np.asarray(picks), targets)
        _b, _a, at_ctl = ref.score(d, weights, tokens, ctl)
        out[name] = _numbers(best, at_ctl, mask)
    return out


def decide(numbers: Optional[Dict], conf: Dict) -> bool:
    """The one decision, for served tokens and controls alike: the number
    compared at or under the configuration's limit.  No sample is not
    correct."""
    limit = conf["correct"][NUMBER]
    return bool(numbers is not None and numbers[NUMBER] <= limit)
