"""Pre-training batches: documents packed end to end into fixed-length
rows.

Parameters (the traffic file): ``batch`` and ``seq`` (a step consumes
batch x seq tokens; rows are seq + 1 long so inputs and labels are one
shift apart); ``doc_tokens`` as {median, sigma, lo, hi} of a clipped
lognormal and ``pool`` document lengths to cycle through in seeded
order; ``zipf_a`` (token ids are Zipf(a) ranks over the vocabulary,
rank r drawn with probability ~ r^-a, mapped through a seeded
permutation so frequent ids are not the small ones); ``fetch_every``
(the loss is fetched every that many steps) and ``fetch_lag`` (that
fetch waits until so many further steps are dispatched; 0 if left out);
no boundary mask.
"""
from __future__ import annotations

import numpy as np

from .common import lognormal_pool


class PackedDocs:
    def __init__(self, params: dict, vocab_size: int, seed: int):
        self.batch, self.seq = int(params["batch"]), int(params["seq"])
        self.fetch_every = int(params["fetch_every"])
        self.fetch_lag = int(params.get("fetch_lag", 0))
        self._lens = lognormal_pool(params["doc_tokens"], int(params["pool"]))
        self._rng = np.random.default_rng(int(seed))
        ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
        p = ranks ** -float(params["zipf_a"])
        self._cdf = np.cumsum(p / p.sum())
        self._ids = self._rng.permutation(vocab_size).astype(np.int32)
        self._order, self._i = [], 0
        self._left = np.zeros(0, np.int32)

    def _doc(self):
        if self._i == len(self._order):
            self._order = self._rng.permutation(len(self._lens)).tolist()
            self._i = 0
        n = int(self._lens[self._order[self._i]])
        self._i += 1
        r = np.searchsorted(self._cdf, self._rng.random(n), side="left")
        return self._ids[np.minimum(r, len(self._ids) - 1)]

    def next_batch(self):
        """(inputs, labels), int32 (batch, seq) each."""
        need = self.batch * (self.seq + 1)
        parts, have = [self._left], len(self._left)
        while have < need:
            d = self._doc()
            parts.append(d)
            have += len(d)
        flat = np.concatenate(parts)
        rows, self._left = flat[:need].reshape(self.batch, self.seq + 1), flat[need:]
        return np.ascontiguousarray(rows[:, :-1]), np.ascontiguousarray(rows[:, 1:])


def build(params, vocab_size, seed):
    return PackedDocs(params, vocab_size, seed)
