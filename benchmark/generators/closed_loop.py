"""Closed-loop request traffic: ``clients`` callers, each sending its
next request the moment its last one finished.

Parameters (the traffic file): ``clients``; ``prompt_tokens`` and
``output_tokens`` as {median, sigma, lo, hi} of a clipped lognormal;
``levels`` L: each length takes the L values at the (i + 0.5) / L
quantiles, and the pool is all L x L pairs of a prompt level with an
output level, sent in blocks of L requests in which every prompt level
and every output level occurs exactly once (block k pairs prompt level
a with output level (a + k) mod L).  Every block therefore carries the
same prompt tokens and the same output tokens.  ``order_seed`` fixes
the order of the blocks and the order inside each block: it is a
property of the mix, so every run sends the same sizes in the same
order and the run's seed draws only the token ids (uniform over the
vocabulary: no two prompts share a prefix).  With a seeded order the
same requests read up to 5 % apart in tokens per second from seed to
seed, by which long prompts happened to prefill together.
``ramp_requests``: how many requests the clients finish before the
window opens.
"""
from __future__ import annotations

import numpy as np

from .common import lognormal_pool


class ClosedLoop:
    def __init__(self, params: dict, vocab_size: int, seed: int):
        self.clients = int(params["clients"])
        self.ramp_requests = int(params["ramp_requests"])
        self.levels = n = int(params["levels"])
        self._prompts = lognormal_pool(params["prompt_tokens"], n).tolist()
        self._outputs = lognormal_pool(params["output_tokens"], n).tolist()
        self._rng = np.random.default_rng(int(seed))
        self._vocab = int(vocab_size)
        order = np.random.default_rng(int(params["order_seed"]))
        self._round = [(self._prompts[a], self._outputs[(a + k) % n])
                       for k in order.permutation(n)
                       for a in order.permutation(n)]
        self._i = 0

    def next_request(self):
        """(prompt ids int32, max_new_tokens): the round of L blocks,
        over and over.  Called under the caller's lock."""
        p, o = self._round[self._i % len(self._round)]
        self._i += 1
        ids = self._rng.integers(0, self._vocab, p, dtype=np.int64)
        return ids.astype(np.int32), int(o)


def build(params, vocab_size, seed):
    return ClosedLoop(params, vocab_size, seed)
