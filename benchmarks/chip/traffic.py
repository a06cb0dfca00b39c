"""The one token generator every traffic mix uses.

A traffic file (``traffic/<name>.json``) gives the federation's shape (the
sites, local steps, batch and sequence length, the wire codec, the local
optimizer) and the token mix.  Each site draws its tokens i.i.d. from its
own unigram distribution over the configuration's vocabulary:
``non_iid_alpha`` of it is a Zipf law shared by every site, the rest a
Zipf law over a permutation of the vocabulary that is the site's own, so
the sites' data differ as federated silos' do and a model that learns
lowers the loss.  Every distribution and every row comes from ``--seed``:
the same seed gives the same batches in the same order at every site,
and every seed gives the same sizes.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def _zipf(vocab: int, exponent: float, perm: np.ndarray) -> np.ndarray:
    p = np.empty(vocab, np.float64)
    p[perm] = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    return p / p.sum()


class SiteTokens:
    """Per-site token batches ``{"tokens", "labels"}`` of shape
    ``(batch, seq_len)``, drawn in call order from per-site streams.

    ``next_batch(site)`` is what the program's client calls; each site's
    stream is used by that site alone, so concurrent sites stay
    reproducible."""

    def __init__(self, mix: Dict, vocab: int, seed: int):
        self.batch, self.seq_len = mix["batch"], mix["seq_len"]
        tok = mix["tokens"]
        root = np.random.SeedSequence([int(seed) & (2**63 - 1), 0x70C5])
        kids = root.spawn(2 * mix["sites"] + 1)
        shared = _zipf(vocab, tok["zipf_exponent"],
                       np.random.default_rng(kids[0]).permutation(vocab))
        a = float(tok["non_iid_alpha"])
        self._cdf: List[np.ndarray] = []
        self._rng: List[np.random.Generator] = []
        for s in range(mix["sites"]):
            own = _zipf(vocab, tok["zipf_exponent"],
                        np.random.default_rng(kids[1 + s]).permutation(vocab))
            cdf = np.cumsum(a * shared + (1.0 - a) * own)
            self._cdf.append(cdf / cdf[-1])
            self._rng.append(np.random.default_rng(kids[1 + mix["sites"]
                                                        + s]))
        self.vocab = vocab

    def next_batch(self, site: int) -> Dict[str, np.ndarray]:
        u = self._rng[site].random((self.batch, self.seq_len + 1))
        toks = np.minimum(np.searchsorted(self._cdf[site], u, side="right"),
                          self.vocab - 1).astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
