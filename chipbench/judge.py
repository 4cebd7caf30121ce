"""How ``correct`` is decided for a served model.

Once the window has closed, a sample of the requests the run finished is
drawn from the seed, the longest among them always in it, until it holds
``sample_tokens`` served tokens.  The float32 reference runs over each
sampled prompt with its served tokens; every served token is greedy, so its
logit should be the reference's best up to rounding.  Two numbers are read:

* ``gap_max``: the widest gap, over all sampled tokens, by which a served
  token's logit lies below the reference's best;
* ``gap_mean``: the mean of those gaps.

Only the numbers named under ``check.limits`` in the configuration file are
compared, each against its limit; the others are printed as readings.  On
the chip the widest gap of sound runs and of the bfloat16 control overlap
(both flip near-ties), while the mean separates them (PERF.md), so the
configuration compares ``gap_mean``.

Where the sample is empty, nothing was checked and the run is not correct.
"""
from __future__ import annotations

import numpy as np

from .traffic import seed_words


def pick_sample(finished: list, seed: int, sample_tokens: int,
                also=None) -> list:
    """The longest finished request, then one marked in ``also`` (such as
    one that was preempted mid-stream) if any, then others in a seeded
    order, until the served tokens reach ``sample_tokens``."""
    if not finished:
        return []
    size = [len(r.req.prompt) + r.req.output_len for r in finished]
    longest = int(np.argmax(size))
    rng = np.random.default_rng(seed_words(seed) + [7])
    perm = [int(i) for i in rng.permutation(len(finished))]
    marked = [i for i in perm if also is not None and also[i]
              and i != longest][:1]
    order = [longest] + marked + [i for i in perm
                                  if i != longest and i not in marked]
    out, n = [], 0
    for i in order:
        if n >= sample_tokens:
            break
        out.append(finished[i])
        n += finished[i].req.output_len
    return out


def numbers(gaps: list) -> dict:
    g = np.concatenate([np.asarray(x, float) for x in gaps]) if gaps \
        else np.zeros(0)
    if g.size == 0:
        return {}
    return {"gap_max": float(g.max()), "gap_mean": float(g.mean())}


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {value, limit}}): every number at or under its
    limit, and at least one token checked."""
    check = {k: {"value": nums.get(k, float("nan")), "limit": v}
             for k, v in limits.items()}
    ok = bool(nums) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in check.values())
    return ok, check
