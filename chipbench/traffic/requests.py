"""The one general generator of serving requests.

A traffic file (``traffic/<name>.json``) gives ``kind`` (when requests are
sent: ``backlog.py``, ``closed.py``, ``poisson.py``) and what they look
like::

    "classes": [{"share": 0.8,
                 "prompt": {"lo": 16, "hi": 128},        log-uniform
                 "new_tokens": {"lo": 16, "hi": 48}}],   uniform
    "pool": 512,              sizes in the fixed set
    "shared_prefix": {"tokens": 256, "groups": 4}        optional

Every seed gets the *same set* of ``pool`` (prompt length, new tokens)
pairs — the quantiles of each class's distributions, in the class's share
— in another order, and its own token ids: the seed may not change the
amount of work, only its order. With ``shared_prefix`` the requests of a
group begin with the group's ``tokens`` common ids.
"""
from __future__ import annotations

import math

import numpy as onp


def size_pool(params: dict) -> list:
    """The fixed set of (prompt length, new tokens, class index)."""
    classes = params["classes"]
    total = int(params["pool"])
    fixed = onp.random.RandomState(12345)     # the same for every seed
    out = []
    for ci, c in enumerate(classes):
        n = max(1, round(total * float(c["share"])))
        q = (onp.arange(n) + 0.5) / n
        lo, hi = c["prompt"]["lo"], c["prompt"]["hi"]
        plen = onp.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
        lo, hi = c["new_tokens"]["lo"], c["new_tokens"]["hi"]
        new = lo + fixed.permutation(q) * (hi + 1 - lo)
        out.extend((int(round(p)), int(min(hi, math.floor(t))), ci)
                   for p, t in zip(plen, new))
    return out


def draw(params: dict, vocab: int, seed: int):
    """An endless iterator of (prompt ids, new tokens): the pool in the
    seed's order, again and again."""
    rng = onp.random.RandomState(seed % 2**32)
    pool = size_pool(params)
    shared = params.get("shared_prefix")
    prefixes = None
    if shared:
        prefixes = rng.randint(0, vocab, (int(shared["groups"]),
                                          int(shared["tokens"])))
    while True:
        for i in rng.permutation(len(pool)):
            plen, new, _ = pool[i]
            prompt = rng.randint(0, vocab, (plen,)).astype(onp.int32)
            if prefixes is not None:
                pre = prefixes[rng.randint(len(prefixes))][:plen]
                prompt[:len(pre)] = pre
            yield prompt, new
