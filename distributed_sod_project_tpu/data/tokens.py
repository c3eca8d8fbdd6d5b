"""Synthetic packed-document token source for the token model.

What a pre-training loader hands the step, without a corpus: documents
of log-normal length (median ``doc_len_median``, sigma ``doc_len_sigma``
of the log) whose token ids follow a Zipf law over the vocabulary slice
(exponent ``zipf_exponent``; frequent ids are the low ones), joined by
an end-of-document id (0) and cut into sequences of ``seq_len``.
Attention is plain causal across the joins.  A sample is a pure
function of ``(seed, index)``, so the host loader's determinism
contract (``data/pipeline.py``) holds; it carries ``tokens`` and the
next-token ``targets``, both ``[seq_len]`` int32.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

EOD = 0


class PackedTokens:
    def __init__(self, size: int = 256, seq_len: int = 8192,
                 vocab: int = 16384, doc_len_median: float = 512.0,
                 doc_len_sigma: float = 1.2, zipf_exponent: float = 1.0,
                 seed: int = 0):
        if vocab < 2:
            raise ValueError("vocab must hold the end-of-document id and "
                             "at least one token")
        self.size, self.seq_len, self.vocab = size, seq_len, vocab
        self.mu, self.sigma = float(np.log(doc_len_median)), doc_len_sigma
        self.seed = seed
        p = np.arange(1, vocab, dtype=np.float64) ** -float(zipf_exponent)
        self._cdf = np.cumsum(p / p.sum())

    def __len__(self) -> int:
        return self.size

    def __getitem__(self, index: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, int(index)]))
        n = self.seq_len + 1
        ids = 1 + np.searchsorted(self._cdf, rng.random(n)).astype(np.int32)
        np.minimum(ids, self.vocab - 1, out=ids)
        # Document ends: enough log-normal lengths to cover the sequence,
        # the first document entered at a random offset (a packed stream
        # is cut anywhere).
        lens = np.maximum(rng.lognormal(self.mu, self.sigma,
                                        size=n // 8 + 8), 1.0)
        ends = np.cumsum(lens).astype(np.int64) - int(rng.integers(lens[0]))
        ids[ends[(ends >= 0) & (ends < n)]] = EOD
        return {"tokens": ids[:-1], "targets": ids[1:].copy()}
